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
order, the gated activation weighed with the gates, the way back to the
tokens, and the transposes of the three — is a loop over blocks of
:data:`ROW_BLOCK` rows with ``ceil(R / ROW_BLOCK)`` rounds
(:func:`_rounds`). The layer's time follows the rows that were routed
here, from none to the whole buffer, in steps of one block; nothing but
arithmetic on ``(tokens x top_k,)`` scalars (the pairs' sort by expert,
the counts, the gates and their cotangents brought into the buffer's
order and back by a sort each) follows the buffer's static size, and a
row nobody was routed to is never read, nor is a buffer cleared
(``tests/test_moe_lm.py``: the poison test).

**The backward runs no grouped product twice.** Each row is weighed with
its gate BEFORE the down product (``(gate h) wd``, the same sum as
``gate (h wd)``), so a gate's cotangent is ``<d(gate h), h>``, which the
activation's backward has in hand, and nothing reads the down product's
result back. The gate-and-up product is kept for the backward (one
(tokens x top_k, 2F) buffer a layer: the result of :func:`_gate_up`,
which :func:`_down` takes); the backward computes again only the rows'
gather (what the gate-and-up matrices' gradient reads) and the gated
activation (what the down matrices' reads), both passes over the routed
rows (``tests/test_moe_lm.py``: the residuals and product-count tests).

All functions are plain traced code: they run inside or outside
``hvd.spmd``.
"""

from __future__ import annotations

import functools
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


def _gather(x, token, routed, dtype):
    """Buffer rows ``x[token]`` below ``routed``, in ``dtype`` (``token``
    (C,): the token of each row of the expert-ordered buffer); the rows
    above the last round's are nobody's."""
    def body(at, block, buf):
        return _land(buf, x[_rows(token, at, block)].astype(dtype), at)
    return _rounds(routed, token.shape[0], body,
                   _buffer(token.shape[0], x.shape[1], dtype))


def _fold(rows, token, routed, n):
    """``out[t]`` = the sum of the rows of ``rows`` (C, width) below
    ``routed`` whose token is ``t``: the buffer's rows added back at
    their tokens in float32, round by round, (n, width). A pair held
    elsewhere has no row below ``routed`` and adds nothing."""
    def body(at, block, out):
        live = (at + jnp.arange(block) < routed)[:, None]  # the last round
        return out.at[_rows(token, at, block)].add(jnp.where(
            live, _rows(rows, at, block).astype(jnp.float32), 0.0))
    return _rounds(routed, token.shape[0], body,
                   jnp.zeros((n, rows.shape[1]), jnp.float32))


@jax.custom_vjp
def _take(x, token, routed):
    """The routed rows gathered into the buffer (:func:`_gather`); a
    token's cotangent is the sum of its rows'."""
    return _gather(x, token, routed, x.dtype)


def _take_fwd(x, token, routed):
    return _take(x, token, routed), (token, routed, x.shape[0])


def _take_bwd(res, g):
    token, routed, n = res
    return (_fold(g, token, routed, n).astype(g.dtype), _int_zero(token),
            _int_zero(routed))


_take.defvjp(_take_fwd, _take_bwd)


def _silu_mul(gu):
    f = gu.shape[1] // 2
    return jax.nn.silu(gu[:, :f]) * gu[:, f:]


def _in_order(values, key):
    """``values`` (C,) in the buffer's order: sorted by ``key`` as the
    pairs were (the same stable sort; a sort of C scalars takes 20 us on
    a v5e chip where a gather of them by ``order`` takes 230)."""
    return lax.sort((key, values), num_keys=1, is_stable=True)[1]


@jax.custom_vjp
def _gated(gu, weight, key, order, here, routed):
    """``gate x silu(g) * u`` of the routed rows of ``gu`` = ``[g | u]``
    (C, 2F): (C, F) in ``gu``'s dtype, computed in float32 and rounded
    once, nobody's above the last round. Each row is weighed with its own
    pair's gate HERE, before the down product (``(gate h) wd`` is the
    same sum as ``gate (h wd)``): ``weight`` (N, k) in the pairs' order,
    brought into the buffer's by ``key`` (C,) as the pairs were sorted;
    ``order`` (C,) names each row's pair, ``here`` (N, k) says which pairs
    are held. A weight's cotangent is its row's ``<d(gate h), h>``, zero
    for a pair held elsewhere."""
    return _gated_fwd(gu, weight, key, order, here, routed)[0]


def _gated_fwd(gu, weight, key, order, here, routed):
    gate = _in_order(weight.reshape(-1), key)

    def body(at, block, h):
        return _land(h, (_rows(gate, at, block)[:, None] * _silu_mul(
            _rows(gu, at, block).astype(jnp.float32))).astype(gu.dtype), at)
    h = _rounds(routed, gu.shape[0], body,
                _buffer(gu.shape[0], gu.shape[1] // 2, gu.dtype))
    return h, (gu, gate, key, order, here, routed)


def _gated_bwd(res, dh):
    gu, gate, key, order, here, routed = res

    def body(at, block, carry):
        # (``dgu`` lands in a buffer of its own, not over ``gu``: the
        # forward's product is read again by the activation's second
        # forward, and XLA would copy it whole to free it for a pass
        # that writes over it.) The round reads ``h`` = silu(g) * u off
        # the same rows for the gates' cotangents.
        dgu, d_gate = carry
        h, back = jax.vjp(_silu_mul,
                          _rows(gu, at, block).astype(jnp.float32))
        d = _rows(dh, at, block).astype(jnp.float32)
        dgu = _land(dgu, back(_rows(gate, at, block)[:, None] * d)[0]
                    .astype(gu.dtype), at)
        return dgu, _land(d_gate, jnp.sum(d * h, axis=-1), at)
    dgu, d_gate = _rounds(
        routed, gu.shape[0], body,
        (_buffer(*gu.shape, gu.dtype), jnp.zeros(order.shape, jnp.float32)))
    # Row r's cotangent is pair order[r]'s: back in the pairs' own order
    # (a sort by ``order``, which is a permutation), the held ones kept.
    d_weight = jnp.where(here, _in_order(d_gate, order).reshape(here.shape),
                         0.0).astype(gate.dtype)
    return (dgu, d_weight, _int_zero(key), _int_zero(order),
            _int_zero(here), _int_zero(routed))


_gated.defvjp(_gated_fwd, _gated_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _put(ys, token, routed, n):
    """The buffer's rows folded back at their ``n`` tokens (:func:`_fold`,
    summed in float32): (n, E) in ``ys``'s dtype. The gates are in the
    rows already (:func:`_gated`). A row's cotangent is its token's,
    gathered: no row of ``ys`` is read."""
    return _fold(ys, token, routed, n).astype(ys.dtype)


def _put_fwd(ys, token, routed, n):
    return _put(ys, token, routed, n), (token, routed)


def _put_bwd(n, res, g):
    token, routed = res
    return (_gather(g, token, routed, g.dtype), _int_zero(token),
            _int_zero(routed))


_put.defvjp(_put_fwd, _put_bwd)


@jax.checkpoint
def _gate_up(x, wgu, order, here, pairs):
    """The routed rows gathered into the expert-ordered buffer of all
    ``N k`` pairs, the routed ones first (``order`` (N k,) the pairs
    sorted by their held expert, ``here`` (N, k) whether a pair's expert
    is held here), and their gate-and-up product ``[g | u]`` (N k, 2F)
    (``wgu`` (held, E, 2F) the gate and up matrices side by side).

    That product is the one value of the routed part that the backward
    keeps: it is this function's result, so nothing computes it again.
    Inside, ``jax.checkpoint`` keeps nothing: the backward gathers the
    rows again for ``wgu``'s gradient, cheaper than keeping them."""
    routed = jnp.sum(pairs)
    with jax.named_scope("dispatch"):
        xs = _take(x, order // here.shape[1], routed)
    with jax.named_scope("experts"):
        return grouped_matmul(xs, wgu, pairs)


@jax.checkpoint
def _down(gu, weight, wd, key, order, here, pairs):
    """The gated activation of the kept product ``gu``, weighed with the
    gates (``weight`` (N, k), zero where a pair is held elsewhere; ``key``
    (N k,) each pair's held expert, ``held`` for one held elsewhere), its
    down product and the fold back to the tokens: (N, E) in ``gu``'s
    dtype.

    The backward computes the activation again from ``gu`` (what ``wd``'s
    gradient reads) and no product: nothing reads the down product's
    result back, since the gates weigh the rows before it and their
    cotangents come from the activation's backward (:func:`_gated`)."""
    routed = jnp.sum(pairs)
    with jax.named_scope("experts"):
        ys = grouped_matmul(_gated(gu, weight, key, order, here, routed),
                            wd, pairs)
    with jax.named_scope("combine"):
        # A pair held elsewhere has a row above ``routed``: no round
        # reads it, forward or back, whatever the kernels left there.
        return _put(ys, order // here.shape[1], routed, here.shape[0])


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
    the device computes (PERF.md, PR 32, has the layer's time over R).
    The backward keeps the gate-and-up product and runs neither product
    forward again (module docstring)."""
    held = wg.shape[0]
    local = idx - first
    here = (local >= 0) & (local < held)
    with jax.named_scope("dispatch"):
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
    wgu = jnp.concatenate([wg, wu], axis=2).astype(x.dtype)
    out = _down(_gate_up(x, wgu, order, here, pairs),
                jnp.where(here, gates, 0.0), wd.astype(x.dtype), key, order,
                here, pairs)
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
