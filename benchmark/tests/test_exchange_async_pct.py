"""``layer_metrics/lm_exchange_async_pct.py`` on records: the one recorded
on the chip beside this file (a step of PR 25, whose program counts
``exchange.wire_bytes`` alone: what a parent of PR 30 gives too), and that
record with the counter the ring lowering counts. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import scopes  # noqa: E402
from benchmark.layer_metrics import lm_exchange_async_pct  # noqa: E402

FOUR = types.SimpleNamespace(chips=4)


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(BENCH, "tests", "recorded_scoped",
                           "tiny_scoped.record.json")) as f:
        record = json.load(f)
    monkeypatch.setattr(scopes, "record", lambda: record)
    return record


def test_a_program_without_the_counter_reads_nothing(recorded):
    assert lm_exchange_async_pct.read(FOUR) is None


def test_no_record_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "record", lambda: None)
    assert lm_exchange_async_pct.read(FOUR) is None


@pytest.mark.parametrize("share", [1.0, 0.75, 0.0])
def test_the_step_programs_share_is_read(recorded, share):
    """The step is the program with most dispatches; a warm-up's counter
    is not the step's. One chip has no exchange to speak of."""
    step = scopes.step_program(recorded)
    wire = step["counters"]["exchange.wire_bytes"]
    step["counters"]["exchange.async_bytes"] = int(share * wire)
    recorded["programs"]["warmup/1"] = {
        "dispatches": 1, "scopes": None,
        "counters": {"exchange.wire_bytes": 8, "exchange.async_bytes": 8}}
    assert lm_exchange_async_pct.read(FOUR) == pytest.approx(100.0 * share)
    assert lm_exchange_async_pct.read(types.SimpleNamespace(chips=1)) is None
