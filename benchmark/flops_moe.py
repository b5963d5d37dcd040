"""Operations and bytes of a latent-attention / expert-layer LM's step
from shapes (``configs/glm_4_7_flash.json``'s keys): latent attention's
five projections, attention over the causal pairs at its own query / key
and value widths, the leading dense layers, and in every expert layer the
shared expert, the router and the routed experts AT THE PAIRS THAT WERE
ROUTED HERE; the MTP module (one more expert-layer block and its
2E -> E projection) and two heads over the vocabulary's slice. A
multiply-add is 2; forward x 3 for forward and backward; what the backward
computes again (flash's and the fused head's second forward) and the
optimizer's update do not count.
"""

from __future__ import annotations

from benchmark import flops


def blocks(cfg: dict) -> int:
    """Block applications a step: the layers and the MTP module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def moe_layers(cfg: dict) -> int:
    return blocks(cfg) - cfg["first_k_dense_replace"]


def experts_total(cfg: dict) -> int:
    return cfg["published"]["n_routed_experts"]


def mla_params(cfg: dict) -> int:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return (e * qr + qr * h * (nope + rope) + e * (kvr + rope)
            + kvr * h * (nope + vd) + h * vd * e)


def expert_params(cfg: dict) -> int:
    """One gated expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_token_params(cfg: dict) -> int:
    """What every token meets in an expert layer whatever it is routed
    to: the shared expert and the router's full width."""
    return (cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * experts_total(cfg))


def params(cfg: dict) -> int:
    """Every parameter held here: embedding and head over the slice, the
    blocks (two norms and MLA's two each), the final norm, the MTP
    module's three norms and projection."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    norms = 2 * e + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return (2 * v * e + e + 3 * e + 2 * e * e
            + blocks(cfg) * (mla_params(cfg) + norms)
            + cfg["first_k_dense_replace"] * dense_params(cfg)
            + moe_layers(cfg) * (moe_token_params(cfg)
                                 + cfg["n_routed_experts"]
                                 * expert_params(cfg)))


def expected_pairs(cfg: dict, rows: int, seq_len: int) -> float:
    """(token, choice) pairs a step that a uniform router would send to
    the experts held here, all expert layers together."""
    return (moe_layers(cfg) * rows * seq_len * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / experts_total(cfg))


def attention_forward_flops(cfg: dict, seq_len: int) -> int:
    """QK^T at the query / key width and PV at the value width, one block,
    one sequence, over the causal pairs."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2 * flops.attention_pairs(seq_len, None)
            * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]))


def experts_forward_flops(cfg: dict, pairs: float) -> float:
    return 2 * pairs * expert_params(cfg)


def forward_flops(cfg: dict, rows: int, seq_len: int, pairs: float) -> float:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    per_token = (blocks(cfg) * mla_params(cfg)
                 + cfg["first_k_dense_replace"] * dense_params(cfg)
                 + moe_layers(cfg) * moe_token_params(cfg)
                 + 2 * e * e)                               # W_eh
    return (rows * (2 * seq_len * per_token
                    + blocks(cfg) * attention_forward_flops(cfg, seq_len)
                    + 2 * (seq_len - 1) * e * v             # the main head
                    + 2 * (seq_len - 2) * e * v)            # the MTP head
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
    return 3 * rows * blocks(cfg) * attention_forward_flops(cfg, seq_len)


def flash_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the kernels cannot avoid, bfloat16: forward reads Q, K,
    V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV
    (the kernel is given every head's own K: the shared rotary key is
    broadcast before it)."""
    h = cfg["num_attention_heads"]
    qk = seq_len * h * (cfg["qk_nope_head_dim"]
                        + cfg["qk_rope_head_dim"]) * 2
    v = seq_len * h * cfg["v_head_dim"] * 2
    forward = 2 * qk + 2 * v
    backward = 4 * qk + 4 * v
    return rows * blocks(cfg) * (forward + backward)


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
    weights = moe_layers(cfg) * cfg["n_routed_experts"] * e * f * 2
    rows_in_out = pairs * (e + f) * 2
    one_product = (rows_in_out + weights) * 3  # forward, dX, dW
    return 3 * one_product
