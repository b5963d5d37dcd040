"""``flops_looped.py`` against ``flops.py`` and hand counts. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
from benchmark import flops, flops_looped  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_one_pass_two_matrices_is_the_plain_count():
    """At R = 1 with a two-matrix FFN every count is ``flops.py``'s, on
    the plain configuration's own sizes (window 4096, GQA 24 over 2)."""
    cfg = _config("starcoder2_3b")
    assert flops_looped.passes_of(cfg) == 1
    assert flops_looped.layer_matmul_params(cfg, 2) \
        == flops.lm_layer_matmul_params(cfg)
    for rows, t in ((1, 8192), (4, 2048)):
        assert flops_looped.step_flops(cfg, rows, t, mlp_matrices=2) \
            == flops.lm_step_flops(cfg, rows, t)
        assert flops_looped.flash_step_flops(cfg, rows, t) \
            == flops.flash_step_flops(cfg, rows, t)
        assert flops_looped.flash_step_bytes(cfg, rows, t) \
            == flops.flash_step_bytes(cfg, rows, t)


def test_ouro_2_6b_by_hand():
    cell = _config("ouro_2_6b")
    assert cell["num_hidden_layers"] == 8  # the depth the cell runs
    # q, k, v, out 2048 x 2048 each; gate, up, down 2048 x 5632 each
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert per_layer == 51_380_224
    assert flops_looped.layer_matmul_params(cell) == per_layer
    pairs = 8192 * 8193 // 2  # full causal
    attn = 4 * pairs * 2048
    head = 2 * 8191 * 2048 * 49152
    for depth, n_params, forward_tf, step_tf in (
            (6, 509.6e6, 33.4e12, 100.2e12),   # ISSUE 27's reckoning
            (8, 612.4e6, 42.3e12, 127.0e12)):  # the cell
        cfg = dict(cell, num_hidden_layers=depth)
        # embedding and head, the layers with four norms, the final
        # norm, the gate with its bias
        assert flops_looped.params(cfg) == (
            2 * 49152 * 2048 + 2048 + 2048 + 1
            + depth * (per_layer + 4 * 2048))
        assert flops_looped.params(cfg) == pytest.approx(n_params, rel=1e-3)
        forward = 4 * (2 * 8192 * depth * per_layer + depth * attn + head)
        assert flops_looped.forward_flops(cfg, 8192) == forward
        assert flops_looped.step_flops(cfg, 1, 8192) == 3 * forward
        assert forward == pytest.approx(forward_tf, rel=2e-3)
        assert 3 * forward == pytest.approx(step_tf, rel=1e-3)
        # the kernels: L x R calls each way
        assert flops_looped.flash_step_flops(cfg, 1, 8192) \
            == 3 * 4 * depth * attn
        assert flops_looped.flash_step_bytes(cfg, 1, 8192) \
            == 4 * depth * 12 * (8192 * 2048 * 2)
    # the four heads of a step, whatever the depth
    assert 3 * 4 * head == pytest.approx(19.8e12, rel=2e-3)
