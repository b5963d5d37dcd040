"""Operations and bytes of an expert-layer LM's step whose attention kind
is chosen by layer (``configs/trinity_mini.json``'s keys), counted layer by
layer from a PLAN of fields that the configuration's file gives
(:func:`layer_plan`): each layer's window (None for full causal), whether
it takes the rotary embedding, whether its heads' output is gated, and
whether its feed-forward is the dense SwiGLU or an expert layer; beside
them the heads' own width (``head_dim``, free of ``hidden / heads``) and
the expert share (``num_experts`` held of ``published.num_experts``,
``num_shared_experts``). In every expert layer the router, the shared
expert and the routed experts AT THE PAIRS THAT WERE ROUTED HERE count;
one head over the vocabulary's slice. A multiply-add is 2; forward x 3
for forward and backward; what the backward computes again (flash's and
the fused head's second forward, the expert layer's gather and
activation) and the optimizer's update do not count; elementwise work
(norms, rotary, the gate's sigmoid and product) does not either.
"""

from __future__ import annotations

from benchmark import flops

# A kind of the published ``layer_types`` -> whether it takes the
# configuration's ``sliding_window`` and the rotary embedding, and the
# program's attention kind that runs it (``models/transformer.py``
# ``ATTENTION_KINDS``; the runner builds the model's ``layer_types`` from
# it, so the count and the model read one mapping).
KINDS = {"sliding_attention": {"windowed": True, "rotary": True,
                               "program": "sliding"},
         "full_attention": {"windowed": False, "rotary": False,
                            "program": "full"}}


def layer_plan(cfg: dict):
    """One ``{"window", "rotary", "gate", "ffn"}`` a layer, read from the
    configuration's file."""
    plan = []
    for i, kind in enumerate(cfg["layer_types"]):
        k = KINDS[kind]
        plan.append({
            "window": cfg["sliding_window"] if k["windowed"] else None,
            "rotary": k["rotary"], "gate": cfg["attention_output_gate"],
            "ffn": "dense" if i < cfg["num_dense_layers"] else "moe"})
    return plan


def experts_total(cfg: dict) -> int:
    return cfg["published"]["num_experts"]


def moe_layers(cfg: dict) -> int:
    return sum(1 for layer in layer_plan(cfg) if layer["ffn"] == "moe")


def attention_params(cfg: dict, gate: bool) -> int:
    """One attention mixer: q, k, v, the gate where there is one and the
    output projection at H x D wide heads, and the two q/k norm scales."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hd, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return e * hd * (2 + gate) + 2 * e * kv + 2 * d


def expert_params(cfg: dict) -> int:
    """One gated expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_params(cfg)


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * experts_total(cfg)


def params(cfg: dict) -> int:
    """Every parameter held here: embedding and head over the slice, the
    final norm, four norms a block (sandwich norms), each layer's mixer
    and its dense layer or its router, held experts and shared expert."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    total = 2 * v * e + e
    for layer in layer_plan(cfg):
        total += 4 * e + attention_params(cfg, layer["gate"])
        total += dense_params(cfg) if layer["ffn"] == "dense" else (
            router_params(cfg) + shared_params(cfg)
            + cfg["num_experts"] * expert_params(cfg))
    return total


def expected_pairs(cfg: dict, rows: int, seq_len: int) -> float:
    """(token, choice) pairs a step that a uniform router would send to
    the experts held here, all expert layers together."""
    return (moe_layers(cfg) * rows * seq_len * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / experts_total(cfg))


def attention_forward_flops(cfg: dict, seq_len: int, window) -> int:
    """QK^T and PV of one attention layer, one sequence, over the pairs
    its window leaves visible, every query head at the head width."""
    return (4 * flops.attention_pairs(seq_len, window)
            * cfg["num_attention_heads"] * cfg["head_dim"])


def experts_forward_flops(cfg: dict, pairs: float) -> float:
    return 2 * pairs * expert_params(cfg)


def forward_flops(cfg: dict, rows: int, seq_len: int, pairs: float) -> float:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    per_row = 2 * (seq_len - 1) * e * v                      # the head
    for layer in layer_plan(cfg):
        matmul_params = attention_params(cfg, layer["gate"]) \
            - 2 * cfg["head_dim"]
        matmul_params += dense_params(cfg) if layer["ffn"] == "dense" \
            else router_params(cfg) + shared_params(cfg)
        per_row += 2 * seq_len * matmul_params \
            + attention_forward_flops(cfg, seq_len, layer["window"])
    return rows * per_row + experts_forward_flops(cfg, pairs)


def step_flops(cfg: dict, rows: int, seq_len: int, pairs=None) -> float:
    """Forward and backward (3 x forward) of ``rows`` sequences with
    ``pairs`` (token, choice) pairs routed here (a uniform router's where
    none is given)."""
    if pairs is None:
        pairs = expected_pairs(cfg, rows, seq_len)
    return 3 * forward_flops(cfg, rows, seq_len, pairs)


def flash_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """What the attention kernels of one step need: forward 2 matmuls over
    each layer's visible pairs, backward 4; the backward's second QK^T is
    recomputation."""
    return 3 * rows * sum(attention_forward_flops(cfg, seq_len,
                                                  layer["window"])
                          for layer in layer_plan(cfg))


def flash_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the kernels cannot avoid, bfloat16: forward reads Q, K,
    V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV
    (K and V at their own, fewer, heads), every attention layer."""
    q = seq_len * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    kv = seq_len * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    forward = 2 * q + 2 * kv
    backward = 4 * q + 4 * kv
    return rows * len(layer_plan(cfg)) * (forward + backward)


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
