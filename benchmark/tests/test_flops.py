"""``flops.py`` against hand counts. Run by hand:
``python3 -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    import importlib.util

    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location("m", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


flops = _load("flops.py")


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_starcoder2_3b_by_hand():
    cfg = _config("starcoder2_3b")
    # q and out 3072x3072, k and v 3072x256, MLP 2 x 3072x12288
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288
    assert per_layer == 95_944_704
    assert flops.lm_layer_matmul_params(cfg) == per_layer
    assert flops.lm_params(cfg) == (5 * (per_layer + 2 * 3072)
                                    + 2 * 49152 * 3072 + 3072)
    # a window of 4096 on 8192 positions: the first 4096 queries see
    # 1..4096 keys, the other 4096 see 4096 each
    pairs = 4096 * 4097 // 2 + 4096 * 4096
    assert flops.attention_pairs(8192, 4096) == pairs
    assert flops.attention_pairs(8192, None) == 8192 * 8193 // 2
    assert flops.attention_pairs(1024, 4096) == 1024 * 1025 // 2
    attn = 4 * pairs * 3072
    step = 3 * (2 * 8192 * 5 * per_layer + 2 * 8191 * 3072 * 49152
                + 5 * attn)
    assert flops.lm_step_flops(cfg, 1, 8192) == step
    assert step == pytest.approx(35.6e12, rel=0.01)  # ISSUE 24's reckoning
    assert flops.flash_step_flops(cfg, 1, 8192) == 3 * 5 * attn
    assert flops.flash_step_bytes(cfg, 1, 8192) == 5 * 6 * (
        8192 * 3072 * 2 + 8192 * 256 * 2)


def test_roofline_names_its_bound():
    peaks = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    assert flops.roofline_seconds(2e12, 8e9, peaks) == (0.01, "compute")
    assert flops.roofline_seconds(2e11, 8e9, peaks) == (0.01, "memory")


def test_resnet50_by_hand():
    path = os.path.join(HERE, "configs", "resnet50.json")
    if not os.path.exists(path):
        pytest.skip("no resnet50 configuration in this benchmark")
    cfg = _config("resnet50")
    convs = flops.resnet_convs(cfg)
    assert len(convs) == 53  # 1 stem + 16 blocks x 3 + 4 projections
    assert convs[0] == (112, 112, 7, 7, 3, 64)
    assert convs[1] == (56, 56, 1, 1, 64, 64)
    assert convs[-1] == (7, 7, 1, 1, 512, 2048)
    forward = flops.resnet_forward_flops(cfg)
    # 4.09 G multiply-adds an image is the figure every ResNet-50 v1.5
    # table gives; XLA's count of the repo's whole step is 24.49 GFLOP.
    assert forward == pytest.approx(2 * 4.09e9, rel=0.01)
    assert 3 * forward == pytest.approx(24.49e9, rel=0.02)
