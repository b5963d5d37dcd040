"""``flops_window_moe.py`` against the parameter tree's shapes and hand
counts of each kind of layer of the Trinity-Mini cell. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1; tier-1's
``tests/test_window_moe_lm.py`` holds the three parameter counts to each
other)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import flops  # noqa: E402
from benchmark import flops_window_moe as fw  # noqa: E402
from benchmark.reference import train_window_moe_lm as reference  # noqa


def _config():
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as f:
        return json.load(f)


def test_the_count_is_the_parameter_trees():
    cell = _config()
    for cfg in (cell, dict(cell, **cell["rehearsal"])):
        leaves = 0
        for _, shape, _ in reference.leaf_specs(cfg):
            n = 1
            for s in shape:
                n *= s
            leaves += n
        assert fw.params(cfg) == leaves
    assert fw.params(cell) == cell["parameters_per_chip"] == 705_473_792


def test_the_plan_reads_the_kinds():
    plan = fw.layer_plan(_config())
    assert [p["window"] for p in plan] == [2048, 2048, 2048, None, 2048]
    assert [p["rotary"] for p in plan] == [True, True, True, False, True]
    assert all(p["gate"] for p in plan)
    assert [p["ffn"] for p in plan] == ["dense"] + ["moe"] * 4


def test_trinity_mini_by_hand():
    cell = _config()
    e, t, d = 2048, 8192, 128
    attn = e * 4096 * 3 + 2 * e * 512 + 2 * d  # q, gate, o; k, v; 2 scales
    assert attn == 27_263_232 == fw.attention_params(cell, True)
    dense = 3 * e * 6144
    expert, router = 3 * e * 1024, e * 128
    assert fw.params(cell) == (2 * 25024 * e + e + 5 * (4 * e + attn)
                               + dense + 4 * (router + 17 * expert))
    windowed = 4 * flops.attention_pairs(t, 2048) * 32 * d
    full = 4 * (t * (t + 1) // 2) * 32 * d
    assert fw.attention_forward_flops(cell, t, 2048) == windowed
    assert fw.attention_forward_flops(cell, t, None) == full
    routed = 4 * t * 8 * 16 / 128
    assert routed == 32_768 == fw.expected_pairs(cell, 1, t)
    forward = (2 * t * (5 * (attn - 2 * d) + dense
                        + 4 * (router + expert))
               + 4 * windowed + full + 2 * (t - 1) * e * 25024
               + 2 * routed * expert)
    assert fw.step_flops(cell, 1, t) == 3 * forward
    assert 3 * forward == pytest.approx(18.14e12, rel=1e-3)
    assert fw.flash_step_flops(cell, 1, t) == 3 * (4 * windowed + full)
    assert (4 * windowed + full) / forward == pytest.approx(0.25, abs=0.01)
    q, kv = t * 32 * d * 2, t * 4 * d * 2
    assert fw.flash_step_bytes(cell, 1, t) == 5 * (6 * q + 6 * kv)
    weights = 4 * 16 * e * 1024 * 2
    assert fw.experts_step_bytes(cell, routed) \
        == 9 * (routed * (e + 1024) * 2 + weights)
