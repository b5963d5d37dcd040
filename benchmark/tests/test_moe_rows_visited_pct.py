"""``layer_metrics/lm_moe_rows_visited_pct.py`` on hand-made records: the
formula, and nothing where the program counts no row block (a parent of
PR 32) or the step wrote no pairs. Run by hand: ``python3 -m pytest
benchmark/tests -q`` (not part of tier-1)."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import scopes  # noqa: E402
from benchmark.layer_metrics import lm_moe_rows_visited_pct  # noqa: E402

ONE = types.SimpleNamespace(chips=1)
PLAN = {"model.moe_layers": 6, "model.moe_pair_capacity": 32768,
        "model.experts_held": 8, "model.experts_total": 64}


def _record(monkeypatch, counters):
    record = {"programs": {
        "train_step": {"dispatches": 31, "scopes": None,
                       "counters": counters},
        "warmup/1": {"dispatches": 1, "scopes": None,
                     "counters": {"model.moe_row_block": 8,
                                  "moe.local_pairs": 1, **PLAN}}}}
    monkeypatch.setattr(scopes, "record", lambda: record)


def test_the_formula_on_a_hand_made_record(monkeypatch):
    _record(monkeypatch, {**PLAN, "model.moe_row_block": 1024,
                          "moe.local_pairs": 31124})
    assert lm_moe_rows_visited_pct.read(ONE) == pytest.approx(
        100.0 * (31124 + 6 * 1024) / (6 * 32768))  # 18.96


def test_a_full_buffer_reads_a_block_over_the_whole(monkeypatch):
    _record(monkeypatch, {**PLAN, "model.moe_row_block": 1024,
                          "moe.local_pairs": 6 * 32768})
    assert lm_moe_rows_visited_pct.read(ONE) == pytest.approx(103.125)


@pytest.mark.parametrize("missing", ["model.moe_row_block",
                                     "moe.local_pairs"])
def test_a_program_without_a_counter_reads_nothing(monkeypatch, missing):
    counters = {**PLAN, "model.moe_row_block": 1024,
                "moe.local_pairs": 31124}
    del counters[missing]
    _record(monkeypatch, counters)
    assert lm_moe_rows_visited_pct.read(ONE) is None


def test_no_record_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "record", lambda: None)
    assert lm_moe_rows_visited_pct.read(ONE) is None
