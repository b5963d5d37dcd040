"""Operations and bytes of a convolution-and-attention expert-layer LM's
step from shapes (``configs/lfm2_24b_a2b.json``'s keys): every layer's
mixer by ``layer_types`` — a ``conv`` layer's two projections and its
gate-and-tap pass, a ``full_attention`` layer's four projections and the
causal products at its head width —, the leading dense layers' SwiGLU, and
in every expert layer the router and the routed experts AT THE PAIRS THAT
WERE ROUTED HERE (no shared expert); one head over the vocabulary's slice.
A multiply-add is 2; forward x 3 for forward and backward; what the
backward computes again (flash's and the fused head's second forward, the
expert layer's and the gate-and-tap pass's recomputation) and the
optimizer's update do not count.
"""

from __future__ import annotations

from benchmark import flops


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layers_of(cfg: dict, kind: str) -> int:
    """Layers whose mixer is ``kind`` (``conv`` | ``full_attention``)."""
    return sum(1 for k in cfg["layer_types"] if k == kind)


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def experts_total(cfg: dict) -> int:
    return cfg["published"]["num_experts"]


def conv_params(cfg: dict) -> int:
    """A conv mixer's two projections and its taps."""
    e = cfg["hidden_size"]
    return e * 3 * e + e * cfg["conv_L_cache"] + e * e


def attention_params(cfg: dict) -> int:
    """A GQA mixer's four projections and the two q/k norm scales."""
    e, d = cfg["hidden_size"], head_dim(cfg)
    kv = cfg["num_key_value_heads"] * d
    return e * e + 2 * e * kv + e * e + 2 * d


def expert_params(cfg: dict) -> int:
    """One gated expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * experts_total(cfg)


def params(cfg: dict) -> int:
    """Every parameter held here: embedding and head over the slice, the
    mixers, two norms a block and the final one, the dense layers, the
    routers and the held experts."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return (2 * v * e + e + cfg["num_hidden_layers"] * 2 * e
            + layers_of(cfg, "conv") * conv_params(cfg)
            + layers_of(cfg, "full_attention") * attention_params(cfg)
            + cfg["num_dense_layers"] * dense_params(cfg)
            + moe_layers(cfg) * (router_params(cfg)
                                 + cfg["num_experts"] * expert_params(cfg)))


def expected_pairs(cfg: dict, rows: int, seq_len: int) -> float:
    """(token, choice) pairs a step that a uniform router would send to
    the experts held here, all expert layers together."""
    return (moe_layers(cfg) * rows * seq_len * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / experts_total(cfg))


def attention_forward_flops(cfg: dict, seq_len: int) -> int:
    """QK^T and PV of one attention layer, one sequence, over the causal
    pairs, every query head at the head width."""
    return (4 * flops.attention_pairs(seq_len, None)
            * cfg["num_attention_heads"] * head_dim(cfg))


def conv_gate_forward_flops(cfg: dict, seq_len: int) -> int:
    """One conv layer's gate-and-tap pass: B u, K taps as multiply-adds,
    C c — (2 + 2 K) operations a channel and position."""
    return seq_len * cfg["hidden_size"] * (2 + 2 * cfg["conv_L_cache"])


def experts_forward_flops(cfg: dict, pairs: float) -> float:
    return 2 * pairs * expert_params(cfg)


def forward_flops(cfg: dict, rows: int, seq_len: int, pairs: float) -> float:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    convs, attns = layers_of(cfg, "conv"), layers_of(cfg, "full_attention")
    matmul_params = (convs * (conv_params(cfg) - e * cfg["conv_L_cache"])
                     + attns * (attention_params(cfg) - 2 * head_dim(cfg))
                     + cfg["num_dense_layers"] * dense_params(cfg)
                     + moe_layers(cfg) * router_params(cfg))
    return (rows * (2 * seq_len * matmul_params
                    + convs * conv_gate_forward_flops(cfg, seq_len)
                    + attns * attention_forward_flops(cfg, seq_len)
                    + 2 * (seq_len - 1) * e * v)            # the head
            + experts_forward_flops(cfg, pairs))


def step_flops(cfg: dict, rows: int, seq_len: int, pairs=None) -> float:
    """Forward and backward (3 x forward) of ``rows`` sequences with
    ``pairs`` (token, choice) pairs routed here (a uniform router's where
    none is given)."""
    if pairs is None:
        pairs = expected_pairs(cfg, rows, seq_len)
    return 3 * forward_flops(cfg, rows, seq_len, pairs)


def flash_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """What the attention kernels of one step need: forward 2 matmuls over
    the causal pairs, backward 4; the backward's second QK^T is
    recomputation."""
    return (3 * rows * layers_of(cfg, "full_attention")
            * attention_forward_flops(cfg, seq_len))


def flash_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the kernels cannot avoid, bfloat16: forward reads Q, K,
    V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV
    (K and V at their own, fewer, heads)."""
    q = seq_len * cfg["num_attention_heads"] * head_dim(cfg) * 2
    kv = seq_len * cfg["num_key_value_heads"] * head_dim(cfg) * 2
    forward = 2 * q + 2 * kv
    backward = 4 * q + 4 * kv
    return rows * layers_of(cfg, "full_attention") * (forward + backward)


def experts_step_flops(cfg: dict, pairs: float) -> float:
    """The routed experts' grouped products, forward and both transposes,
    at ``pairs`` rows a step."""
    return 3 * experts_forward_flops(cfg, pairs)


def experts_step_bytes(cfg: dict, pairs: float) -> float:
    """HBM traffic the three grouped products of every expert layer cannot
    avoid, bfloat16, whatever implements them: each product reads its
    rows and the held experts' matrix and writes its rows; its two
    transposes read the rows' cotangent with the matrix, and the rows
    with their cotangent, and write a row cotangent and a matrix."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = moe_layers(cfg) * cfg["num_experts"] * e * f * 2
    rows_in_out = pairs * (e + f) * 2
    one_product = (rows_in_out + weights) * 3  # forward, dX, dW
    return 3 * one_product


def conv_gate_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """The gate-and-tap passes of one step, forward and backward."""
    return (3 * rows * layers_of(cfg, "conv")
            * conv_gate_forward_flops(cfg, seq_len))


def conv_gate_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the gate-and-tap passes cannot avoid, bfloat16, with
    the taps fused into one pass each way: forward reads the three streams
    [B | C | u] and writes one; backward reads the three streams and the
    output's cotangent and writes the three streams' cotangents (the taps
    and their gradient, K floats a channel, are left out)."""
    stream = seq_len * cfg["hidden_size"] * 2
    forward = 3 * stream + stream
    backward = 3 * stream + stream + 3 * stream
    return rows * layers_of(cfg, "conv") * (forward + backward)
