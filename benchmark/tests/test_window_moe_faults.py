"""The faults an LM whose attention kind is chosen by layer invites — the
sliding layers attending full causal, the rotary embedding on the full
layers too, the output gate left out — must come out as not correct in its
cell: planted in the PROGRAM (``run.py --rehearse`` in a process of its
own) and as the reference's ``sliding_full`` / ``rotary_everywhere`` /
``no_gate`` variants put in the program's place. The cell's float8 control
and its other faults are ``test_control.py``'s and ``test_faults.py``'s,
which take every cell of the manifest. Run by hand: ``python3 -m pytest
benchmark/tests -q`` — not part of tier-1. The readings at the cell's own
size come from ``tests/calibrate.py`` on the chip (PERF.md).

As a script: ``python3 benchmark/tests/test_window_moe_faults.py <cell>
<fault>``.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lm_trinity_mini_t8k_1chip"
FAULTS = ("sliding_full", "rotary_everywhere", "no_gate")


@pytest.mark.parametrize("fault", FAULTS)
def test_the_fault_in_the_program_is_seen(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), CELL, fault],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_variant_is_not_correct(fault):
    sys.path.insert(0, BENCH)
    import run as harness

    _, _, config, traffic = harness.load_cell(CELL, rehearse=True)
    limits = harness.load_json("limits", CELL + ".json")["rehearsal"]
    seeded = harness.load_module("seeded")
    compare = harness.load_module("compare")
    reference = harness.load_module("reference", config["runner"])
    for seed in (5, 2147483653, 3000000001):
        expected = reference.run(config, traffic, seed, 1, seeded)
        got = reference.run(config, traffic, seed, 1, seeded, variant=fault)
        correct, rows = compare.decide(got, expected, limits)
        assert not correct, rows


def _plant(fault: str) -> None:
    from horovod_tpu.models import transformer

    kinds = transformer.ATTENTION_KINDS
    if fault == "sliding_full":
        import horovod_tpu as hvd

        attend = hvd.local_attention

        def full(*args, window=None, **kwargs):
            """Every layer's attention full causal, its window dropped."""
            return attend(*args, **kwargs)

        hvd.local_attention = full
    elif fault == "rotary_everywhere":
        kinds["full"] = kinds["full"]._replace(rotary=True)
    elif fault == "no_gate":
        import flax.linen as nn
        import jax.numpy as jnp

        dense = nn.DenseGeneral.__call__

        def left_open(self, x, *a, **kw):
            """The output gate's projection, made and never used: its
            sigmoid is 1 everywhere."""
            y = dense(self, x, *a, **kw)
            return jnp.full_like(y, 1e4) if self.name == "gate" else y

        nn.DenseGeneral.__call__ = left_open
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run

    run.load_cell(sys.argv[1], rehearse=True)
    _plant(sys.argv[2])
    sys.exit(run.main(["--workload", sys.argv[1], "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse"]))
