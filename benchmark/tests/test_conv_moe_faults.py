"""The faults a convolution-and-attention expert-layer LM invites — a
short convolution that looks one position AHEAD, q/k norms left out,
tokens dropped over an expert's capacity — must come out as not correct
in its cell: planted in the PROGRAM (``run.py --rehearse`` in a process of
its own) and as the reference's ``conv_ahead`` / ``no_qk_norm`` /
``dropped_tokens`` variants put in the program's place. The cell's other
faults and its float8 control are ``test_faults.py``'s and
``test_control.py``'s, which take every cell of the manifest. Run by hand:
``python3 -m pytest benchmark/tests -q`` — not part of tier-1.

As a script: ``python3 benchmark/tests/test_conv_moe_faults.py <cell>
<fault>``.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lm_lfm2_24b_a2b_t8k_1chip"
FAULTS = ("conv_ahead", "no_qk_norm", "dropped_tokens")


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
    import flax.linen as nn

    from horovod_tpu.ops import short_conv

    if fault == "conv_ahead":
        taps = short_conv.causal_taps

        def ahead(z, w, segment_ids=None):
            """The window one position later: t - K + 2 .. t + 1."""
            later = lambda a: None if a is None else \
                short_conv._back(a[:, ::-1], 1)[:, ::-1]
            return taps(later(z), w, later(segment_ids))

        short_conv.causal_taps = ahead
    elif fault == "no_qk_norm":
        normed = nn.RMSNorm.__call__

        def left_out(self, x, *a, **kw):
            """A q/k norm that norms nothing (its scale is made, and
            never used)."""
            y = normed(self, x, *a, **kw)
            return x if self.name in ("q_norm", "k_norm") else y

        nn.RMSNorm.__call__ = left_out
    elif fault == "dropped_tokens":
        sys.path.insert(0, os.path.join(BENCH, "tests"))
        import test_moe_faults

        test_moe_faults._plant(fault, total)
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run

    config = run.load_cell(sys.argv[1], rehearse=True)[2]
    _plant(sys.argv[2], config["published"]["num_experts"])
    sys.exit(run.main(["--workload", sys.argv[1], "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse"]))
