"""The two faults an expert-layer LM with a multi-token-prediction module
invites — dropping the tokens over an expert's capacity, and training
without the MTP term — must come out as not correct in its cell: planted
in the PROGRAM (``run.py --rehearse`` in a process of its own) and as the
reference's ``dropped_tokens`` / ``no_mtp`` variants put in the program's
place. The cell's other faults and its float8 control are
``test_faults.py``'s and ``test_control.py``'s, which take every cell of
the manifest. Run by hand: ``python3 -m pytest benchmark/tests -q`` — not
part of tier-1.

As a script: ``python3 benchmark/tests/test_moe_faults.py <cell> <fault>``.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lm_glm_4_7_flash_t8k_1chip"
FAULTS = ("dropped_tokens", "no_mtp")


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


def _plant(fault: str, total: int) -> None:
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import moe

    if fault == "no_mtp":
        make = transformer.make_loss_fn
        transformer.make_loss_fn = lambda cfg, **kw: make(
            cfg._replace(mtp=cfg.mtp._replace(weight=0.0)), **kw)
    elif fault == "dropped_tokens":
        routed = moe.routed_experts

        def capped(x, idx, gates, wg, wu, wd, first=0):
            """A capacity of 1.0 x the mean load: an expert's pairs past
            tokens x k / experts, in token order, lose their gate."""
            held = wg.shape[0]
            n, k = idx.shape
            local = idx - first
            here = (local >= 0) & (local < held)
            onehot = (jnp.where(here, local, held)[..., None]
                      == jnp.arange(held)).any(axis=1)     # (N, held)
            taken = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
            keep = jnp.take_along_axis(
                taken, jnp.clip(local, 0, held - 1), axis=1) <= n * k // total
            return routed(x, idx, jnp.where(keep, gates, 0.0), wg, wu, wd,
                          first=first)

        moe.routed_experts = capped
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run

    config = run.load_cell(sys.argv[1], rehearse=True)[2]
    _plant(sys.argv[2], config["published"]["n_routed_experts"])
    sys.exit(run.main(["--workload", sys.argv[1], "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse"]))
