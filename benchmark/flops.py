"""Operations and bytes from shapes: what the mathematics needs, with a
multiply-add as 2. Recomputed work (flash's and the fused head's second
forward) and the optimizer's update do not count.
"""

from __future__ import annotations


def lm_layer_matmul_params(cfg: dict) -> int:
    e, h, g = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, m = cfg["head_dim"], cfg["intermediate_size"]
    return e * h * d + 2 * e * g * d + h * d * e + 2 * e * m


def lm_params(cfg: dict) -> int:
    """Every parameter: untied embedding and head, two norm scales a layer
    and the final one."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return (2 * v * e + e
            + cfg["num_hidden_layers"] * (lm_layer_matmul_params(cfg)
                                          + 2 * e))


def attention_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal window lets see: query t sees
    min(t + 1, window) keys."""
    w = min(window or seq_len, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def lm_attention_forward_flops(cfg: dict, seq_len: int) -> int:
    """QK^T and PV of one layer for one sequence."""
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * attention_pairs(seq_len, cfg["sliding_window"]) * hd


def lm_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """Forward and backward (3 x forward) of ``rows`` sequences: the
    layers' matmuls on every token, the head on the seq_len - 1 positions
    that have a target, and attention over the visible pairs."""
    layers = cfg["num_hidden_layers"]
    forward = (2 * seq_len * layers * lm_layer_matmul_params(cfg)
               + 2 * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"]
               + layers * lm_attention_forward_flops(cfg, seq_len))
    return 3 * rows * forward


def flash_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """What the attention kernels of one step need: forward 2 matmuls over
    the visible pairs, backward 4 (dV, dP, dQ, dK); the backward's second
    QK^T is recomputation."""
    return (3 * rows * cfg["num_hidden_layers"]
            * lm_attention_forward_flops(cfg, seq_len))


def flash_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the kernels of one step cannot avoid, bfloat16: forward
    reads Q, K, V and writes O; backward reads Q, K, V, O, dO and writes
    dQ, dK, dV (the float32 log-sum-exp rows are left out: 1/64 of Q)."""
    q = seq_len * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = seq_len * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    forward = 2 * q + 2 * kv
    backward = 4 * q + 4 * kv
    return rows * cfg["num_hidden_layers"] * (forward + backward)


def resnet_convs(cfg: dict):
    """(out_h, out_w, kernel_h, kernel_w, in_ch, out_ch) of every
    convolution of a bottleneck ResNet v1.5 on a square image."""
    size = cfg["image_size"]
    f = cfg["num_filters"]
    convs = []
    size = -(-size // 2)
    convs.append((size, size, 7, 7, 3, f))
    size = -(-size // 2)  # 3x3 max pool, stride 2
    cin = f
    for i, blocks in enumerate(cfg["stage_sizes"]):
        width = f * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = -(-size // stride)
            convs.append((size, size, 1, 1, cin, width))
            convs.append((out, out, 3, 3, width, width))
            convs.append((out, out, 1, 1, width, 4 * width))
            if cin != 4 * width or stride != 1:
                convs.append((out, out, 1, 1, cin, 4 * width))
            cin, size = 4 * width, out
    return convs


def resnet_forward_flops(cfg: dict) -> int:
    """One image's forward: the convolutions and the dense head."""
    total = sum(2 * oh * ow * kh * kw * ci * co
                for oh, ow, kh, kw, ci, co in resnet_convs(cfg))
    width = cfg["num_filters"] * 2 ** (len(cfg["stage_sizes"]) - 1) * 4
    return total + 2 * width * cfg["num_classes"]


def resnet_step_flops(cfg: dict, images: int) -> int:
    return 3 * images * resnet_forward_flops(cfg)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which peak bounds it."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
