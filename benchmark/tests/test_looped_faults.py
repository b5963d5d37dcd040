"""The fault a looped model invites — training its last pass alone — must
come out as not correct in the looped cell, planted in the PROGRAM
(``run.py --rehearse`` in a process of its own, ``exit_loss`` replaced by
the last pass's mean) and as the reference's ``last_pass_only`` variant
put in the program's place. The cell's other faults and its float8
control are ``test_faults.py``'s and ``test_control.py``'s, which take
every cell of the manifest. Run by hand: ``python3 -m pytest
benchmark/tests -q`` — not part of tier-1.

As a script: ``python3 benchmark/tests/test_looped_faults.py <cell>``.
"""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lm_ouro_2_6b_t8k_1chip"


def test_last_pass_only_in_the_program_is_seen():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), CELL],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["compared"]


def test_last_pass_only_variant_is_not_correct():
    sys.path.insert(0, BENCH)
    import run as harness

    _, _, config, traffic = harness.load_cell(CELL, rehearse=True)
    limits = harness.load_json("limits", CELL + ".json")["rehearsal"]
    seeded = harness.load_module("seeded")
    compare = harness.load_module("compare")
    reference = harness.load_module("reference", config["runner"])
    for seed in (5, 2147483653, 3000000001):
        expected = reference.run(config, traffic, seed, 1, seeded)
        fault = reference.run(config, traffic, seed, 1, seeded,
                              variant="last_pass_only")
        correct, rows = compare.decide(fault, expected, limits)
        assert not correct, rows


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from horovod_tpu.models import transformer

    transformer.exit_loss = lambda losses, gates, beta: losses[-1].mean()
    sys.path.insert(0, BENCH)
    import run

    sys.exit(run.main(["--workload", sys.argv[1], "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearse"]))
