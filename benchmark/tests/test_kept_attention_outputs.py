"""``layer_metrics/lm_kept_attention_outputs.py`` on records: the pair
recorded on the chip beside this file (a plain stack's step of PR 25,
whose program has no such counter: what a parent of PR 28 gives too), and
that record with the counters a looped step counts. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import scopes  # noqa: E402
from benchmark.layer_metrics import lm_kept_attention_outputs  # noqa: E402


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(BENCH, "tests", "recorded_scoped",
                           "tiny_scoped.record.json")) as f:
        record = json.load(f)
    monkeypatch.setattr(scopes, "record", lambda: record)
    return record


def test_a_program_without_the_counter_reads_nothing(recorded):
    assert lm_kept_attention_outputs.read(None) is None


def test_no_record_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "record", lambda: None)
    assert lm_kept_attention_outputs.read(None) is None


@pytest.mark.parametrize("kept", [32, 0])
def test_the_step_programs_counter_is_read(recorded, kept):
    """The step is the program with most dispatches; a warm-up's counter
    is not the step's."""
    step = scopes.step_program(recorded)
    step["counters"].update({"model.block_applications": 32,
                             "model.recomputed_blocks": 32,
                             "model.kept_attention_outputs": kept})
    recorded["programs"]["warmup/1"] = {
        "dispatches": 1, "scopes": None,
        "counters": {"model.kept_attention_outputs": 7}}
    assert lm_kept_attention_outputs.read(None) == kept
