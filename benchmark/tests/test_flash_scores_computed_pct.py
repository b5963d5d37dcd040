"""``layer_metrics/flash_scores_computed_pct.py`` on records: the pair
recorded on the chip beside this file (a plain stack's step of PR 25, whose
program counts no score: what a parent of PR 35 gives too), that record
with the two counters a step of PR 35 counts, and a rehearsal's own record.
Run by hand: ``python3 -m pytest benchmark/tests -q`` (not part of
tier-1)."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
from benchmark import scopes  # noqa: E402
from benchmark.layer_metrics import flash_scores_computed_pct  # noqa: E402


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(BENCH, "tests", "recorded_scoped",
                           "tiny_scoped.record.json")) as f:
        record = json.load(f)
    monkeypatch.setattr(scopes, "record", lambda: record)
    return record


def test_a_program_without_the_counters_reads_nothing(recorded):
    assert flash_scores_computed_pct.read(None) is None


def test_no_record_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "record", lambda: None)
    assert flash_scores_computed_pct.read(None) is None


@pytest.mark.parametrize("visible,computed,pct", [
    (67117056 * 512, 71303168 * 512, 106.237),  # 32 x 16: the looped cell
    (64, 72, 112.5), (0, 0, None)])
def test_the_step_programs_counters_are_read(recorded, visible, computed,
                                             pct):
    """The step is the program with most dispatches; a warm-up's counters
    are not the step's."""
    scopes.step_program(recorded)["counters"].update(
        {"flash.scores_visible": visible, "flash.scores_computed": computed})
    recorded["programs"]["warmup/1"] = {
        "dispatches": 1, "scopes": None,
        "counters": {"flash.scores_visible": 1, "flash.scores_computed": 9}}
    got = flash_scores_computed_pct.read(None)
    assert got == (None if pct is None else pytest.approx(pct, abs=1e-3))


def test_a_rehearsals_record_is_read(monkeypatch):
    """The looped cell's rehearsal step, its attention through the kernel
    (interpreted on the CPU): the record the program writes holds the two
    counters, and the reader their ratio — the rehearsal's T = 128 is one
    block computed whole, T x T scores a call for T (T + 1) / 2 visible."""
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from benchmark import run\n"
        "_, cell, config, traffic = run.load_cell("
        "'lm_ouro_2_6b_t8k_1chip', True)\n"
        "import jax, types\n"
        "from horovod_tpu.parallel import sequence\n"
        "sequence.local_attention_impl = lambda t: 'flash'\n"
        "run.configure_jax(True)\n"
        "runner = run.load_module('runners', config['runner'])\n"
        "ctx = types.SimpleNamespace(config=config, traffic=traffic, "
        "seed=7, chips=1, seeded=run.load_module('seeded'), "
        "reference=run.load_module('reference', config['runner']), "
        "readings=run.load_module('readings'), rehearse=True, "
        "say=lambda m: None, t0=0.0)\n"
        "runner.setup(ctx)\n"
        "from horovod_tpu.core import timeline\n"
        "print(json.dumps(timeline.record()))\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True).stdout
    record = json.loads(out.splitlines()[-1])
    monkeypatch.setattr(scopes, "record", lambda: record)
    counters = scopes.step_program(record)["counters"]
    with open(os.path.join(BENCH, "traffic", "t8k_b1.json")) as f:
        t = json.load(f)["rehearsal"]["seq_len"]
    assert counters["flash.scores_computed"] * (t + 1) \
        == counters["flash.scores_visible"] * 2 * t
    assert flash_scores_computed_pct.read(None) == pytest.approx(
        100 * 2 * t / (t + 1))
