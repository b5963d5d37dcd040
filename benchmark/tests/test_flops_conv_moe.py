"""``flops_conv_moe.py`` against the parameter tree's shapes and hand
counts of one layer of each kind of the LFM2-24B-A2B cell. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import flops_conv_moe  # noqa: E402
from benchmark.reference import train_conv_moe_lm as reference  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _size(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_the_count_is_the_parameter_trees():
    """Every leaf the reference (and so the runner, which places the same
    specs into the program's tree) makes, at the cell's sizes and at the
    rehearsal's."""
    cell = _config("lfm2_24b_a2b")
    for cfg in (cell, dict(cell, **cell["rehearsal"])):
        leaves = sum(_size(shape)
                     for _, shape, _ in reference.leaf_specs(cfg))
        assert flops_conv_moe.params(cfg) == leaves
    assert flops_conv_moe.params(cell) == cell["parameters_per_chip"] \
        == 821_606_528


def test_lfm2_24b_a2b_by_hand():
    cell = _config("lfm2_24b_a2b")
    assert cell["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                   "conv"]
    assert flops_conv_moe.layers_of(cell, "conv") == 4
    assert flops_conv_moe.layers_of(cell, "full_attention") == 1
    assert flops_conv_moe.moe_layers(cell) == 4
    e, t = 2048, 8192
    conv = e * 3 * e + e * 3 + e * e          # W_in, the taps, W_out
    assert conv == 16_783_360 == flops_conv_moe.conv_params(cell)
    attn = e * e + 2 * e * 8 * 64 + e * e + 2 * 64  # q, k, v, o, two scales
    assert attn == 10_485_888 == flops_conv_moe.attention_params(cell)
    dense = 3 * e * 11776
    assert dense == 72_351_744 == flops_conv_moe.dense_params(cell)
    expert = 3 * e * 1536
    assert expert == 9_437_184 == flops_conv_moe.expert_params(cell)
    router = e * 64
    assert flops_conv_moe.params(cell) == (
        2 * 16384 * e + e + 5 * 2 * e + 4 * conv + attn + dense
        + 4 * (router + 16 * expert))
    pairs = t * (t + 1) // 2
    causal = 4 * pairs * 32 * 64
    assert flops_conv_moe.attention_forward_flops(cell, t) == causal
    routed = 4 * t * 4 * 16 / 64
    assert routed == 4 * 8192 == flops_conv_moe.expected_pairs(cell, 1, t)
    gate = t * e * (2 + 2 * 3)                # B u, three taps, C c
    assert flops_conv_moe.conv_gate_forward_flops(cell, t) == gate
    forward = (2 * t * (4 * (conv - 3 * e) + (attn - 128) + dense
                        + 4 * router)
               + 4 * gate + causal + 2 * (t - 1) * e * 16384
               + 2 * routed * expert)
    assert flops_conv_moe.step_flops(cell, 1, t) == 3 * forward
    assert 3 * forward == pytest.approx(11.73e12, rel=1e-3)
    # the measured pairs take the uniform router's place
    assert flops_conv_moe.step_flops(cell, 1, t, 2 * routed) \
        == 3 * (forward + 2 * routed * expert)
    assert flops_conv_moe.flash_step_flops(cell, 1, t) == 3 * causal
    q, kv = t * 32 * 64 * 2, t * 8 * 64 * 2
    assert flops_conv_moe.flash_step_bytes(cell, 1, t) == 6 * q + 6 * kv
    assert flops_conv_moe.experts_step_flops(cell, routed) / (3 * forward) \
        == pytest.approx(0.158, rel=1e-2)
    weights = 4 * 16 * e * 1536 * 2
    assert flops_conv_moe.experts_step_bytes(cell, routed) \
        == 9 * (routed * (e + 1536) * 2 + weights)
    stream = t * e * 2
    assert flops_conv_moe.conv_gate_step_bytes(cell, 1, t) == 4 * 11 * stream
    assert flops_conv_moe.conv_gate_step_flops(cell, 1, t) == 3 * 4 * gate
