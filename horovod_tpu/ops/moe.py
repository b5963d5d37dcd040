"""The expert layer as ONE CHIP of an expert-parallel deployment holds it.

A DeepSeek-V3-style routed layer (sigmoid scores, a bias-corrected top-k,
normalised and scaled gates) whose ``total`` experts are spread over
chips: this chip is told which it holds (``first``, ``held``), scores all
``total``, takes the top-k, and computes ITS OWN experts' part of the
result for the tokens routed to them. What the absent experts would add is
left out — the all-to-all that would bring it (``parallel/expert.py``'s
transport) is another chip's traffic, and this module has no exchange.
Summed over the shares, with whatever every chip computes alike (a shared
expert) counted once, the parts give the uncut layer
(``tests/test_moe_lm.py``: the share test).

**No token is dropped, whatever the imbalance.** There is no capacity
factor: the (token, choice) pairs whose expert is held here are gathered
in expert order into a buffer whose static size covers the worst case —
every pair of the batch, ``tokens x top_k`` rows — and a grouped matrix
product runs over the groups' real sizes (:func:`grouped_matmul`), so its
time follows the rows that were routed here and not the buffer
(:func:`routed_experts`). Dispatch and combine are row gathers in both
directions (a row's transpose is a gather by its token: no scatter-add on
the device).

All functions are plain traced code: they run inside or outside
``hvd.spmd``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.core import state as _state
from horovod_tpu.core.state import HorovodError


def route(x, router_w, bias, top_k: int, scale: float):
    """Each token's ``top_k`` experts and their gates.

    ``x``: (N, E) tokens; ``router_w``: (E, total); ``bias``: (total,) the
    score correction that only the CHOICE sees. Scores are
    ``sigmoid(x @ router_w)`` in float32 at full matmul precision (a
    top-k is discontinuous: the router is the one product of the layer
    whose rounding can change which experts run); the choice is the top-k
    of ``scores + bias``; the gates are the chosen scores, normalised over
    the ``top_k`` choices — held here or not — and scaled:
    ``scale * s_k / (sum_j s_j + 1e-20)``. Returns ``(idx, gates)``, both
    (N, top_k), int32 and float32."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    gates = scale * picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates


# The grouped product's tiles, (rows, contraction, columns): the best of
# a sweep on a v5e chip at 8 groups x (2048, 1536), bfloat16, 4,096 real
# rows in a 32,768-row buffer (tools/moe_sweep.py; PERF.md, PR 31).
_TILES = (512, 2048, 512)


def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for consecutive row groups: ``x``
    (M, K), ``w`` (G, K, N), ``group_sizes`` (G,) int32 whose sum may be
    less than M. Rows past the last group belong to nobody: whatever they
    hold on return, the caller masks (:func:`routed_experts` does).

    On a TPU the Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox``: its grid is sized on the
    device from the groups' real sizes, so its time, forward and both
    transposes, follows the rows that were routed here. Elsewhere, and for
    operands that are not bfloat16, ``jax.lax.ragged_dot`` (as attention
    is the kernel on a TPU and blockwise elsewhere: the Pallas interpreter
    is no CPU path, and the tiles below fill the VMEM at two bytes an
    element). Measured
    beside ``jax.lax.ragged_dot``, which XLA lowers to a grouped kernel of
    its own, at 8 groups x (2048, 1536) with 4,096 real rows of 32,768:
    forward 0.675 ms for 0.687, forward and backward 1.453 for 1.534; and
    XLA's kernel runs as a custom call named ``ragged-dot-none`` that
    carries none of the program's scopes and wants its weights in a
    layout of its own (15.3 + 2.4 ms a step outside every phase in the
    benchmark's cell: PERF.md, PR 31)."""
    sizes = group_sizes.astype(jnp.int32)
    if _state.target_platform() != "tpu" or x.dtype != jnp.bfloat16:
        return lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

    tiles = (math.gcd(x.shape[0], _TILES[0]), min(x.shape[1], _TILES[1]),
             min(w.shape[2], _TILES[2]))
    return _megablox.gmm(x, w, sizes, x.dtype, tiles)


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


def _fold(rows, slot, weight=None):
    """``out[t] = sum_j weight[t, j] * rows[slot[t, j]]`` in float32: each
    token's ``k`` buffer rows (``slot`` (N, k); ``weight`` ones where
    None), a row gather a choice — never an (N, k, E) array."""
    out = 0.0
    for j in range(slot.shape[1]):
        part = rows[slot[:, j]].astype(jnp.float32)
        out = out + (part if weight is None else weight[:, j, None] * part)
    return out


@jax.custom_vjp
def _take(x, token, slot, here):
    """Buffer rows ``x[token]`` (``token`` (C,): the token of each row of
    the expert-ordered buffer). Its transpose is a gather too: ``slot``
    (N, k) names each token's rows, ``here`` which of them are its own."""
    return x[token]


def _take_fwd(x, token, slot, here):
    return x[token], (token, slot, here)


def _take_bwd(res, g):
    token, slot, here = res
    dx = _fold(g, slot, here.astype(jnp.float32)).astype(g.dtype)
    return dx, _int_zero(token), _int_zero(slot), _int_zero(here)


_take.defvjp(_take_fwd, _take_bwd)


@jax.custom_vjp
def _put(ys, weight, pair, slot):
    """``out[t] = sum_j weight[t, j] * ys[slot[t, j]]`` (float32), the
    buffer's rows back at their tokens; ``pair`` (C,) says whose each row
    is, for the transpose: a row's cotangent is its own weight x its
    token's."""
    return _fold(ys, slot, weight)


def _put_fwd(ys, weight, pair, slot):
    return _fold(ys, slot, weight), (ys, weight, pair, slot)


def _put_bwd(res, g):
    ys, weight, pair, slot = res
    k = slot.shape[1]
    d_ys = (weight.reshape(-1)[pair][:, None] * g[pair // k]).astype(ys.dtype)
    d_weight = jnp.stack(
        [jnp.sum(g * ys[slot[:, j]].astype(jnp.float32), axis=-1)
         for j in range(k)], axis=1).astype(weight.dtype)
    return d_ys, d_weight, _int_zero(pair), _int_zero(slot)


_put.defvjp(_put_fwd, _put_bwd)


@jax.checkpoint
def _part(x, weight, wg, wu, wd, order, inverse, here, pairs):
    """The held experts' part over the expert-ordered buffer of all
    ``N k`` pairs, the routed ones first: ``order`` (N k,) the pairs in
    expert order, ``inverse`` (N, k) each pair's row, ``here`` (N, k)
    whether its expert is held here, ``weight`` (N, k) the gates, zero
    where it is not. Nothing of the buffer is kept for the backward: it is
    gathered and computed again there (``jax.checkpoint``)."""
    with jax.named_scope("dispatch"):
        routed = (jnp.arange(order.shape[0]) < jnp.sum(pairs))[:, None]
        xs = jnp.where(routed, _take(x, order // inverse.shape[1], inverse,
                                     here), 0)
    with jax.named_scope("experts"):
        h = jax.nn.silu(grouped_matmul(xs, wg, pairs)) \
            * grouped_matmul(xs, wu, pairs)
        ys = grouped_matmul(h, wd, pairs)
    with jax.named_scope("combine"):
        # The rows nobody was routed to hold whatever the kernel left:
        # masked here on the way out and, by xs's mask, on the way back.
        # (A pair held elsewhere reads such a row and weighs it with
        # zero, forward by its gate and back by ``here``.)
        return _put(jnp.where(routed, ys, 0), weight, order,
                    inverse).astype(x.dtype)


def routed_experts(x, idx, gates, wg, wu, wd, first: int = 0):
    """This chip's experts' part of the layer, no token dropped.

    ``x``: (N, E); ``idx``, ``gates``: (N, top_k) from :func:`route`;
    ``wg``, ``wu``: (held, E, F), ``wd``: (held, F, E) — gated experts
    ``(silu(x wg) * (x wu)) wd``, experts ``first .. first + held`` of the
    router's. Returns ``(out, pairs)``: (N, E) the sum over each token's
    choices HELD HERE of gate x expert output (zero rows for a token none
    of whose choices is here), and (held,) int32 how many (token, choice)
    pairs each held expert took.

    The pairs are sorted by expert, the ones held elsewhere last: that
    order is the buffer, ``N top_k`` rows, the worst case — an imbalance
    costs time, never a token. The grouped products run over the routed
    rows alone; the gathers and the elementwise passes between them run
    over the whole buffer (PERF.md, PR 31, has what that costs)."""
    n, k = idx.shape
    held = wg.shape[0]
    local = idx - first
    here = (local >= 0) & (local < held)
    with jax.named_scope("dispatch"):
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
        pairs = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
    out = _part(x, jnp.where(here, gates, 0.0), wg.astype(x.dtype),
                wu.astype(x.dtype), wd.astype(x.dtype), order, inverse, here,
                pairs)
    return out, pairs


def check_share(total: int, held: int, first: int, top_k: int) -> None:
    """Raise unless experts ``first .. first + held`` are a share of
    ``total`` the router can choose ``top_k`` of."""
    if not (0 < held <= total and 0 <= first and first + held <= total):
        raise HorovodError(
            f"experts {first}..{first + held} are not a share of the "
            f"router's {total}.")
    if not 0 < top_k <= total:
        raise HorovodError(
            f"top_k ({top_k}) must be between 1 and the router's width "
            f"({total}).")
