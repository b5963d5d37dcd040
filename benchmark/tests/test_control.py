"""The control must come out as not correct: the plain reference put in the
program's place and computed in float8 (the nearest precision under the
configurations' bfloat16), at a size a test run can hold (each
configuration's ``rehearsal`` sizes), held to the cell's rehearsal limits.
The same reference against itself is correct. The readings at the cells'
own sizes are in PERF.md (``tests/calibrate.py`` on the chip). Run by hand:
``python3 -m pytest benchmark/tests -q`` — not part of tier-1."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import run as harness  # noqa: E402


def _cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_float8_control_is_not_correct(name):
    _, _, config, traffic = harness.load_cell(name, rehearse=True)
    limits = harness.load_json("limits", name + ".json")["rehearsal"]
    seeded = harness.load_module("seeded")
    compare = harness.load_module("compare")
    reference = harness.load_module("reference", config["runner"])
    chips = 1  # the control needs no exchange
    for seed in (5, 2147483653, 3000000001):
        expected = reference.run(config, traffic, seed, chips, seeded)
        same, _ = compare.decide(expected, expected, limits)
        assert same
        control = reference.run(config, traffic, seed, chips, seeded,
                                variant="fp8")
        correct, rows = compare.decide(control, expected, limits)
        assert not correct, rows
