"""``flops_moe.py`` against hand counts of the GLM-4.7-Flash cell. Run by
hand: ``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import flops_moe  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_glm_4_7_flash_by_hand():
    cell = _config("glm_4_7_flash")
    assert cell["num_hidden_layers"] == 6 and cell["n_routed_experts"] == 8
    assert flops_moe.params(cell) == pytest.approx(813.3e6, rel=1e-4)
    assert flops_moe.blocks(cell) == 7 and flops_moe.moe_layers(cell) == 6
    assert flops_moe.step_flops(cell, 1, 8192) \
        == pytest.approx(33.53e12, rel=1e-3)
    cfg = dict(cell, num_hidden_layers=5)  # ISSUE 31's reckoning, by hand
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
           + 20 * 256 * 2048)
    assert mla == 21_757_952 == flops_moe.mla_params(cfg)
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184 == flops_moe.expert_params(cfg)
    dense = 3 * 2048 * 10240
    assert flops_moe.dense_params(cfg) == dense
    outside = mla + expert + 2048 * 64            # an expert layer less
    assert outside == pytest.approx(31.33e6, rel=1e-3)  # its routed experts
    norms = 2 * 2048 + 768 + 512
    assert flops_moe.params(cfg) == (
        2 * 19360 * 2048 + 2048 + 3 * 2048 + 2 * 2048 * 2048
        + 6 * (mla + norms) + dense + 5 * (outside - mla + 8 * expert))
    assert flops_moe.params(cfg) == pytest.approx(706.5e6, rel=1e-4)
    t = 8192
    pairs = t * (t + 1) // 2
    attn = 2 * pairs * 20 * (256 + 256)
    assert flops_moe.attention_forward_flops(cfg, t) == attn
    routed = 5 * t * 4 * 8 / 64
    assert routed == 5 * 4096 == flops_moe.expected_pairs(cfg, 1, t)
    forward = (2 * t * (6 * mla + dense + 5 * (expert + 2048 * 64)
                        + 2 * 2048 * 2048)
               + 6 * attn + 2 * (t - 1) * 2048 * 19360
               + 2 * (t - 2) * 2048 * 19360 + 2 * routed * expert)
    assert flops_moe.step_flops(cfg, 1, t) == 3 * forward
    assert 3 * forward == pytest.approx(29.7e12, rel=1e-3)
    # the measured pairs take the uniform router's place
    assert flops_moe.step_flops(cfg, 1, t, 2 * routed) \
        == 3 * (forward + 2 * routed * expert)
    assert flops_moe.flash_step_flops(cfg, 1, t) == 3 * 6 * attn
    assert flops_moe.flash_step_flops(cfg, 1, t) / (3 * forward) \
        == pytest.approx(0.4165, rel=1e-3)
    assert flops_moe.experts_step_flops(cfg, routed) / (3 * forward) \
        == pytest.approx(0.039, rel=1e-2)
    q = t * 20 * 256 * 2
    assert flops_moe.flash_step_bytes(cfg, 1, t) == 6 * 12 * q
    weights = 5 * 8 * 2048 * 1536 * 2
    assert flops_moe.experts_step_bytes(cfg, routed) \
        == 9 * (routed * (2048 + 1536) * 2 + weights)
