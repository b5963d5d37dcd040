"""Operations and bytes of a looped LM's step from shapes: the stack of
``num_hidden_layers`` blocks applied ``total_ut_steps`` (R) times over the
same parameters, a gated (three-matrix) MLP, one head application a pass.
A multiply-add is 2; what the backward computes again (the blocks under
the program's recomputation rule, flash's and the fused head's second
forward) and the optimizer's update do not count, nor do the exit gates
(hidden_size -> 1: a ten-thousandth of a block).

The attention's counts are ``flops.py``'s, a pass; for a configuration of
one pass, with ``mlp_matrices=2``, every count equals ``flops.py``'s
(``benchmark/tests/test_flops_looped.py``).
"""

from __future__ import annotations

from benchmark import flops


def passes_of(cfg: dict) -> int:
    return cfg.get("total_ut_steps", 1)


def layer_matmul_params(cfg: dict, mlp_matrices: int = 3) -> int:
    e, h, g = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, m = cfg["head_dim"], cfg["intermediate_size"]
    return e * h * d + 2 * e * g * d + h * d * e + mlp_matrices * e * m


def params(cfg: dict) -> int:
    """Every parameter, each counted once however often it is applied:
    untied embedding and head, four norm scales a layer, the final norm,
    the exit gate with its bias."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return (2 * v * e + e + e + 1
            + cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 4 * e))


def forward_flops(cfg: dict, seq_len: int, mlp_matrices: int = 3) -> int:
    """One sequence's forward: R passes of the layers' matmuls on every
    token and of attention over the visible pairs, and R heads on the
    seq_len - 1 positions that have a target."""
    layers = cfg["num_hidden_layers"]
    return passes_of(cfg) * (
        2 * seq_len * layers * layer_matmul_params(cfg, mlp_matrices)
        + layers * flops.lm_attention_forward_flops(cfg, seq_len)
        + 2 * (seq_len - 1) * cfg["hidden_size"] * cfg["vocab_size"])


def step_flops(cfg: dict, rows: int, seq_len: int,
               mlp_matrices: int = 3) -> int:
    """Forward and backward (3 x forward) of ``rows`` sequences."""
    return 3 * rows * forward_flops(cfg, seq_len, mlp_matrices)


def flash_step_flops(cfg: dict, rows: int, seq_len: int) -> int:
    """What the attention kernels of one step need, L x R calls each way
    (``flops.flash_step_flops`` a pass); the recomputed forward calls are
    recomputation."""
    return passes_of(cfg) * flops.flash_step_flops(cfg, rows, seq_len)


def flash_step_bytes(cfg: dict, rows: int, seq_len: int) -> int:
    """HBM traffic the L x R kernel calls each way cannot avoid
    (``flops.flash_step_bytes`` a pass)."""
    return passes_of(cfg) * flops.flash_step_bytes(cfg, rows, seq_len)
