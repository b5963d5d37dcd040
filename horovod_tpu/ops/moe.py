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
every pair of the batch, ``tokens x top_k`` rows — and every pass of the
layer gets its EXTENT ON THE DEVICE from the routed-row count ``R``, the
sum of the held experts' pairs. The grouped matrix products run over the
groups' real sizes (:func:`grouped_matmul`: a kernel whose grid the
device sizes); every pass between them — the rows' gather into expert
order, the gated activation, the weighing with the gates and the way back
to the tokens, and the transposes of the three — is a loop over blocks of
:data:`ROW_BLOCK` rows with ``ceil(R / ROW_BLOCK)`` rounds
(:func:`_rounds`). The layer's time follows the rows that were routed
here, from none to the whole buffer, in steps of one block; nothing but
arithmetic on ``(tokens x top_k,)`` scalars (the pairs' sort by expert,
the counts, the gates and their cotangents brought into the buffer's
order and back by a sort each) follows the buffer's static size, and a
row nobody was routed to is never read, nor is a buffer cleared
(``tests/test_moe_lm.py``: the poison test).

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


# The grouped product's tiles, (rows, contraction, columns), at most: the
# best of a sweep on a v5e chip at 8 groups x (2048, 1536), bfloat16, 4,096
# real rows in a 32,768-row buffer (tools/moe_sweep.py; PERF.md, PR 31).
_TILES = (512, 2048, 512)


def _fit(size: int, most: int) -> int:
    """The largest tile of at most ``most`` that divides ``size`` in whole
    lanes (a multiple of 128), so that no tile is computed to be masked:
    1536 of a 3072-wide contraction, not 2048 and a half-empty second."""
    whole = [t for t in range(128, min(size, most) + 1, 128)
             if size % t == 0]
    return whole[-1] if whole else min(size, most)


def _tiles(m: int, k: int, n: int):
    """The tiles of an (m, k) x (k, n) grouped product, its transposes'
    too (``megablox`` asks once a call, with that call's sizes)."""
    return (math.gcd(m, _TILES[0]), _fit(k, _TILES[1]), _fit(n, _TILES[2]))


def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for consecutive row groups: ``x``
    (M, K), ``w`` (G, K, N), ``group_sizes`` (G,) int32 whose sum may be
    less than M. Rows past the last group belong to nobody: whatever they
    hold, going in or on return, no caller reads (:func:`routed_experts`'s
    passes stop at the routed rows; a product's transposes mask them).

    On a TPU the Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox``: its grid is sized on the
    device from the groups' real sizes, so its time, forward and both
    transposes, follows the rows that were routed here. Elsewhere, and for
    operands that are not bfloat16, ``jax.lax.ragged_dot`` (as attention
    is the kernel on a TPU and blockwise elsewhere: the Pallas interpreter
    is no CPU path, and the tiles above fill the VMEM at two bytes an
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

    return _megablox.gmm(x, w, sizes, x.dtype, _tiles)


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


# Rows a round: every pass between the grouped products is a loop over row
# blocks of the buffer whose trip count the DEVICE computes from the
# routed-row count, as the grouped kernel sizes its grid (the best of a
# sweep on a v5e chip, by a hair over 256 and 1024, and the grouped
# kernel's row tile: tools/moe_sweep.py --layer block; PERF.md, PR 32).
ROW_BLOCK = 512


def row_block(buffer_rows: int) -> int:
    """The rows a round takes of a buffer of ``buffer_rows``."""
    return math.gcd(buffer_rows, ROW_BLOCK)


def _rounds(routed, buffer_rows, body, init):
    """``carry = body(at, block, carry)`` for ``at`` = 0, B, 2B, ... below
    ``routed`` (a traced int32, at most ``buffer_rows``): ``ceil(routed /
    B)`` rounds, none where nothing was routed. The last round's block
    runs past ``routed`` by less than B rows, never past the buffer."""
    block = row_block(buffer_rows)
    return lax.fori_loop(
        0, (routed + block - 1) // block,
        lambda i, carry: body(i * block, block, carry), init)


def _buffer(rows: int, width: int, dtype):
    """A (rows, width) buffer that a pass's rounds land their blocks in.
    No pass reads a row above the routed ones, so nothing has to clear
    it: on a TPU it is the memory as it was (a Pallas call that writes
    nothing, for no pass over ``rows`` rows), elsewhere zeros."""
    if _state.target_platform() != "tpu":
        return jnp.zeros((rows, width), dtype)
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="hvd_moe_buffer")()


def _rows(a, at, block):
    return lax.dynamic_slice_in_dim(a, at, block)


def _land(buf, rows, at):
    return lax.dynamic_update_slice_in_dim(buf, rows, at, 0)


def _to_tokens(rows_of, width, token, routed, n):
    """``out[t]`` = the sum of ``rows_of(at, block)[i]`` (float32,
    (block, width)) over the buffer rows ``r = at + i`` below ``routed``
    with ``token[r] == t``: the buffer's rows added back at their tokens,
    round by round, (n, width) float32. A pair held elsewhere has no row
    below ``routed`` and reads nothing."""
    def body(at, block, out):
        live = (at + jnp.arange(block) < routed)[:, None]  # the last round
        return out.at[_rows(token, at, block)].add(
            jnp.where(live, rows_of(at, block), 0.0))
    return _rounds(routed, token.shape[0], body,
                   jnp.zeros((n, width), jnp.float32))


@jax.custom_vjp
def _take(x, token, routed):
    """Buffer rows ``x[token]`` below ``routed`` (``token`` (C,): the
    token of each row of the expert-ordered buffer); the rows above the
    last round's are nobody's."""
    def body(at, block, buf):
        return _land(buf, x[_rows(token, at, block)], at)
    return _rounds(routed, token.shape[0], body,
                   _buffer(token.shape[0], x.shape[1], x.dtype))


def _take_fwd(x, token, routed):
    return _take(x, token, routed), (token, routed, x.shape[0])


def _take_bwd(res, g):
    token, routed, n = res
    dx = _to_tokens(lambda at, block: _rows(g, at, block).astype(jnp.float32),
                    g.shape[1], token, routed, n).astype(g.dtype)
    return dx, _int_zero(token), _int_zero(routed)


_take.defvjp(_take_fwd, _take_bwd)


def _silu_mul(gu):
    f = gu.shape[1] // 2
    return jax.nn.silu(gu[:, :f]) * gu[:, f:]


@jax.custom_vjp
def _gated(gu, routed):
    """``silu(g) * u`` of the routed rows of ``gu`` = ``[g | u]`` (C, 2F):
    (C, F), nobody's above the last round."""
    def body(at, block, h):
        return _land(h, _silu_mul(_rows(gu, at, block)), at)
    return _rounds(routed, gu.shape[0], body,
                   _buffer(gu.shape[0], gu.shape[1] // 2, gu.dtype))


def _gated_fwd(gu, routed):
    return _gated(gu, routed), (gu, routed)


def _gated_bwd(res, dh):
    gu, routed = res

    def body(at, block, dgu):
        # ``dgu`` starts as ``gu`` and each round turns one block of it
        # into its cotangent, in place: no second buffer, none to clear.
        _, back = jax.vjp(_silu_mul, _rows(dgu, at, block))
        return _land(dgu, back(_rows(dh, at, block))[0], at)
    return _rounds(routed, gu.shape[0], body, gu), _int_zero(routed)


_gated.defvjp(_gated_fwd, _gated_bwd)


def _in_order(values, key):
    """``values`` (C,) in the buffer's order: sorted by ``key`` as the
    pairs were (the same stable sort; a sort of C scalars takes 20 us on
    a v5e chip where a gather of them by ``order`` takes 230)."""
    return lax.sort((key, values), num_keys=1, is_stable=True)[1]


@jax.custom_vjp
def _put(ys, weight, key, order, here, routed):
    """``out[t]`` = the sum over token ``t``'s choices held ``here`` of
    their weight x their row of ``ys`` (float32, (N, E)), taken from the
    buffer's side: row ``r`` below ``routed`` adds its own pair's weight x
    ``ys[r]`` at its token (``order`` (C,) names each row's pair, ``key``
    (C,) is what sorted them). A weight's cotangent is its row's."""
    return _put_fwd(ys, weight, key, order, here, routed)[0]


def _put_fwd(ys, weight, key, order, here, routed):
    gate = _in_order(weight.reshape(-1), key)
    token = order // weight.shape[1]
    out = _to_tokens(
        lambda at, block: _rows(gate, at, block)[:, None]
        * _rows(ys, at, block).astype(jnp.float32),
        ys.shape[1], token, routed, weight.shape[0])
    return out, (ys, gate, token, key, order, here, routed)


def _put_bwd(res, g):
    ys, gate, token, key, order, here, routed = res

    def body(at, block, carry):
        # (``d_ys`` lands in a buffer of its own, not over ``ys``: a round
        # that reads a carry in one fusion and writes it in another makes
        # XLA copy the whole carry, twice a round. PERF.md, PR 32.)
        d_ys, d_gate = carry
        mine = g[_rows(token, at, block)]  # each row's token's
        d_ys = _land(d_ys, (_rows(gate, at, block)[:, None]
                            * mine).astype(ys.dtype), at)
        d_gate = _land(d_gate, jnp.sum(
            mine * _rows(ys, at, block).astype(jnp.float32), axis=-1), at)
        return d_ys, d_gate
    d_ys, d_gate = _rounds(
        routed, order.shape[0], body,
        (_buffer(*ys.shape, ys.dtype), jnp.zeros(order.shape, jnp.float32)))
    # Row r's cotangent is pair order[r]'s: back in the pairs' own order
    # (a sort by ``order``, which is a permutation), the held ones kept.
    d_weight = jnp.where(here, _in_order(d_gate, order).reshape(here.shape),
                         0.0).astype(gate.dtype)
    return (d_ys, d_weight, _int_zero(key), _int_zero(order),
            _int_zero(here), _int_zero(routed))


_put.defvjp(_put_fwd, _put_bwd)


@jax.checkpoint
def _part(x, weight, wgu, wd, key, order, here, pairs):
    """The held experts' part over the expert-ordered buffer of all
    ``N k`` pairs, the routed ones first: ``key`` (N k,) each pair's held
    expert (``held`` for one held elsewhere), ``order`` (N k,) the pairs
    sorted by it, ``here`` (N, k) whether a pair's expert is held here,
    ``weight`` (N, k) the gates, zero where it is not, ``wgu`` (held, E,
    2F) the gate and up matrices side by side. Nothing of the buffer is
    kept for the backward: it is gathered and computed again there
    (``jax.checkpoint``)."""
    routed = jnp.sum(pairs)
    with jax.named_scope("dispatch"):
        xs = _take(x, order // here.shape[1], routed)
    with jax.named_scope("experts"):
        ys = grouped_matmul(_gated(grouped_matmul(xs, wgu, pairs), routed),
                            wd, pairs)
    with jax.named_scope("combine"):
        # A pair held elsewhere has a row above ``routed``: no round
        # reads it, forward or back, whatever the kernels left there.
        return _put(ys, weight, key, order, here, routed).astype(x.dtype)


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
    costs time, never a token. The grouped products (gate and up side by
    side as one, then down) and every pass between them run over the
    routed rows alone, in rounds of :data:`ROW_BLOCK` rows whose number
    the device computes (PERF.md, PR 32, has the layer's time over R)."""
    held = wg.shape[0]
    local = idx - first
    here = (local >= 0) & (local < held)
    with jax.named_scope("dispatch"):
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
    wgu = jnp.concatenate([wg, wu], axis=2).astype(x.dtype)
    out = _part(x, jnp.where(here, gates, 0.0), wgu, wd.astype(x.dtype),
                key, order, here, pairs)
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
