"""The gated short convolution: the mixer of an LFM2-style ``conv`` layer
between its two projections.

A ``conv`` layer mixes positions with no attention at all: its input
projection gives three streams ``[B | C | u]`` of the model's width each,
and what stands between that projection and the output one is

    z_t = B_t * u_t
    c_t = sum_{j < K} w[:, j] * z_{t - (K - 1) + j}      (z = 0 before 0)
    out_t = C_t * c_t

— a depthwise causal convolution of ``K`` taps (``conv_L_cache``; 3 in the
published models) over every channel alone, gated on both sides, with no
bias and no activation. Position ``t`` sees ``t - K + 1 .. t`` and nothing
later; with ``segment_ids`` (packed documents) a tap that would reach into
another document contributes nothing, as if each document began after
``K - 1`` zeros.

The whole pass is elementwise but for the shifts, so its time is its HBM
traffic: the least is one read of ``[B | C | u]`` and one write of ``out``
forward, and a read of the streams and of ``out``'s cotangent and one write
of ``d[B | C | u]`` backward, all bfloat16.

**On a TPU, for bfloat16 streams, a Pallas kernel pair behind a
``jax.custom_vjp``** (:func:`runs_kernels`: a TPU, bfloat16, and a width in
whole lanes — the rule of ``ops/moe.grouped_matmul``, read off the input,
no option). The forward kernel (``hvd_conv_fwd``) reads the three streams
as three column-block views of the ONE (B, T, 3E) array, computes ``z``,
the taps and the gate in float32 over a (T, E) tile, 16 rows at a time
(the shifts are sublane rotations), and writes ``out`` alone; the grid
walks T innermost and carries the last rows of ``z`` into the next tile in
VMEM. The backward kernel (``hvd_conv_bwd``) reads the streams and
``out``'s cotangent once, computes ``z`` and ``c`` again from the tile (no
``jax.checkpoint``: nothing is marked recomputed), reads the first rows of
the NEXT tile's ``C`` and cotangent for the taps' transpose, and writes
``d[B | C | u]`` in place as one (B, T, 3E) array: three DMAs a tile into
the output's column slabs, waited two grid steps later. The taps'
gradient is summed in float32 across T. The residuals are ``bcu`` and the
taps alone. Each geometry's two ``pallas_call``s are
built once a process (:func:`_fwd_call`, :func:`_bwd_call`:
``functools.lru_cache``, ``ops/flash_attention.py``'s memo and its reason,
PERF.md PR 35).

**Everywhere else** — another platform (the Pallas interpreter is no CPU
path), float32, a width that is not whole lanes — the plain form: ``K``
shifted products (:func:`causal_taps`) for XLA to fuse, in float32, inside
a ``jax.checkpoint`` so that the backward keeps the streams alone.
:func:`causal_taps` is that path's seam, and the one the benchmark's
``conv_ahead`` plant replaces in its CPU rehearsal
(``benchmark/tests/test_conv_moe_faults.py``); on the chip the kernels are
held to the float32 reference by the cell's ``correct``.

Plain traced code around the kernels: it runs inside or outside
``hvd.spmd``. Under sequence parallelism a shard's first ``K - 1``
positions would need the previous shard's last ones (a halo exchange):
``models/transformer.py`` raises there, and for ``decode=True``, whose
state would be the last ``K - 1`` values of ``z`` and not a KV cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.core import state as _state


def _back(a, shift: int, fill=0):
    """``a`` (B, T, ...) moved ``shift`` positions later along T: row ``t``
    holds ``a[t - shift]``, and ``fill`` where that is before 0."""
    if shift == 0:
        return a
    pad = [(0, 0), (shift, 0)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad, constant_values=fill)[:, :a.shape[1]]


def causal_taps(z, w, segment_ids=None):
    """``c_t = sum_j w[:, j] * z_{t - (K - 1) + j}``: ``z`` (B, T, E),
    ``w`` (E, K) — tap ``K - 1`` weighs the position itself, tap 0 the one
    ``K - 1`` before. ``segment_ids`` (B, T): a tap from another segment
    contributes nothing."""
    taps = w.shape[1]
    out = z * w[:, taps - 1]
    for j in range(taps - 1):
        shift = taps - 1 - j
        earlier = _back(z, shift)
        if segment_ids is not None:
            same = segment_ids == _back(segment_ids, shift, fill=-1)
            earlier = jnp.where(same[..., None], earlier, 0)
        out = out + earlier * w[:, j]
    return out


@jax.checkpoint
def _plain(bcu, w, segment_ids):
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    conv = causal_taps(b * u, w.astype(jnp.float32), segment_ids)
    return (c * conv).astype(bcu.dtype)


# ---------------------------------------------------------------------------
# The kernel pair
# ---------------------------------------------------------------------------

# A tile's (rows of T, lanes of E) at most: the best of a sweep on a v5e
# chip at T = 8192, E = 2048, K = 3 (tools/conv_cells.py; PERF.md, PR 37).
_BLOCKS = (512, 512)
# Rows a step of a tile's inner loop, and beside a tile: the last of the
# previous tile's ``z`` (forward and backward, carried in VMEM) and the
# first of the next tile's ``C`` and cotangent (backward, read as a block
# of their own) — a bfloat16 tile's sublanes, so at most 17 taps. A strip
# of a tile at a time keeps the arithmetic in registers: a whole tile's
# float32 intermediates would go through VMEM op by op.
_STRIP = 16
_F32 = jnp.float32


def _tiles(t: int, e: int, taps: int, most=_BLOCKS):
    """``(rows, lanes)`` of a tile of a (T, E) stream, or None where the
    kernels cannot take the shape: lanes a multiple of 128 dividing ``E``
    (the three streams are column blocks of one array), rows a multiple
    of :data:`_STRIP` dividing ``T``."""
    if e % 128 or taps - 1 > _STRIP:
        return None
    lanes = max(b for b in range(128, min(e, most[1]) + 1, 128)
                if e % b == 0)
    rows = [b for b in range(_STRIP, min(t, most[0]) + 1, _STRIP)
            if t % b == 0]
    return (rows[-1], lanes) if rows else None


def runs_kernels(t: int, e: int, taps: int, dtype) -> bool:
    """Whether :func:`gated_short_conv` of (·, ``t``, 3 ``e``) streams of
    ``dtype`` with ``taps`` taps runs the kernel pair: on a TPU, for
    bfloat16, at a width in whole lanes and a length in whole strips
    (``model.conv_kernel_layers`` counts by it)."""
    return (_state.target_platform() == "tpu" and dtype == jnp.bfloat16
            and _tiles(t, e, taps) is not None)


def _keep(segment_ids, taps: int):
    """(B, T, 2 (K - 1)) int32: column ``s - 1`` whether position ``t - s``
    is in ``t``'s segment (the forward's taps), column ``K - 2 + s``
    whether ``t + s`` is (the backward's); 0 off either end."""
    ahead = lambda a, s: jnp.pad(a, [(0, 0), (0, s)],
                                 constant_values=-1)[:, s:]
    seg = segment_ids.astype(jnp.int32)
    cols = ([seg == _back(seg, s, fill=-1) for s in range(1, taps)]
            + [seg == ahead(seg, s) for s in range(1, taps)])
    return jnp.stack(cols, axis=-1).astype(jnp.int32)


def _row():
    return jax.lax.broadcasted_iota(jnp.int32, (_STRIP, 1), 0)


def _before(cur, prev, shift):
    """Row ``r`` of a strip holds ``cur[r - shift]``, the previous strip's
    last rows before it: one sublane rotation of each."""
    return jnp.where(_row() < shift, pltpu.roll(prev, shift, 0),
                     pltpu.roll(cur, shift, 0))


def _after(cur, nxt, shift):
    """Row ``r`` holds ``cur[r + shift]``, the next strip's first rows
    after it."""
    back = _STRIP - shift
    return jnp.where(_row() < back, pltpu.roll(cur, back, 0),
                     pltpu.roll(nxt, back, 0))


def _masked(a, keep_ref, at, col):
    if keep_ref is None:
        return a
    return jnp.where(keep_ref[pl.ds(at, _STRIP), col:col + 1] != 0, a, 0.)


def _front(refs, at, z_prev, w, taps):
    """A strip's ``z``, ``c`` and the shifted ``z`` each tap weighs (tap
    ``K - 1``, ``z`` itself, last), from its rows of the streams."""
    b_ref, u_ref, keep_ref = refs
    z = b_ref[pl.ds(at, _STRIP)].astype(_F32) \
        * u_ref[pl.ds(at, _STRIP)].astype(_F32)
    conv, weighed = z * w[taps - 1:taps], []
    for j in range(taps - 1):
        shift = taps - 1 - j
        zj = _masked(_before(z, z_prev, shift), keep_ref, at, shift - 1)
        conv = conv + zj * w[j:j + 1]
        weighed.append(zj)
    return z, conv, weighed + [z]


def _fwd_kernel(b_ref, c_ref, u_ref, w_ref, *refs, taps, has_segs):
    keep_ref = refs[0] if has_segs else None
    out_ref, z_scr = refs[has_segs:]
    rows = out_ref.shape[0]
    w = w_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _():
        z_scr[...] = jnp.zeros(z_scr.shape, _F32)

    def strip(i, z_prev):
        at = pl.multiple_of(i * _STRIP, _STRIP)
        z, conv, _ = _front((b_ref, u_ref, keep_ref), at, z_prev, w, taps)
        out_ref[pl.ds(at, _STRIP)] = (
            c_ref[pl.ds(at, _STRIP)].astype(_F32) * conv
        ).astype(out_ref.dtype)
        return z

    z_scr[...] = jax.lax.fori_loop(0, rows // _STRIP, strip, z_scr[...])


def _bwd_kernel(b_ref, c_ref, u_ref, g_ref, *refs, taps, has_segs, nt, e,
                steps):
    """Grid (B, E tiles, T tiles): a tile's arithmetic strip by strip into
    VMEM, ``d[B]``, ``d[C]``, ``d[u]`` side by side, then three DMAs of
    them into their column slabs of the one (B, T, 3E) output, waited two
    steps later (two slots: the next tile's inputs and arithmetic go on
    meanwhile). A strip's ``d z`` needs the next strip's first rows of
    ``d c``, so the loop finishes strip ``i - 1`` at strip ``i``, and the
    tile's last strip with the next tile's first rows."""
    refs = list(refs)
    cn_ref, gn_ref = (refs.pop(0), refs.pop(0)) if nt > 1 else (None, None)
    keep_ref = refs.pop(0) if has_segs else None
    w_ref, d_hbm, dw_ref, z_scr, out, sems = refs
    rows, lanes = out.shape[2:]
    bi, ei, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    step = (bi * pl.num_programs(1) + ei) * nt + t
    slot = step % 2
    load = lambda ref, at: ref[pl.ds(at, _STRIP)].astype(_F32)
    fold = lambda a: a[:8] + a[8:]  # (16, lanes) -> (8, lanes)
    w = w_ref[...]

    def copies(slot):  # a wait needs the sizes and the semaphore alone
        return [pltpu.make_async_copy(
            out.at[slot, k],
            d_hbm.at[bi, pl.ds(t * rows, rows), pl.ds(k * e + ei * lanes,
                                                      lanes)],
            sems.at[slot, k]) for k in range(3)]

    @pl.when(step >= 2)
    def _():
        for copy in copies(slot):
            copy.wait()

    @pl.when(t == 0)
    def _():
        z_scr[...] = jnp.zeros(z_scr.shape, _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    def front(i, z_prev):
        at = pl.multiple_of(i * _STRIP, _STRIP)
        z, conv, weighed = _front((b_ref, u_ref, keep_ref), at, z_prev, w,
                                  taps)
        g = load(g_ref, at)
        out[slot, 1, pl.ds(at, _STRIP)] = (g * conv).astype(out.dtype)
        dc = g * load(c_ref, at)
        return z, dc, [fold(dc * zj) for zj in weighed]

    def back(i, dc, dc_next):
        # d z_t = sum_s w[K-1-s] * dc_{t+s}
        at = pl.multiple_of(i * _STRIP, _STRIP)
        dz = dc * w[taps - 1:taps]
        for shift in range(1, taps):
            later = _masked(_after(dc, dc_next, shift), keep_ref, at,
                            taps - 2 + shift)
            dz = dz + later * w[taps - 1 - shift:taps - shift]
        out[slot, 0, pl.ds(at, _STRIP)] = (dz * load(u_ref, at)).astype(
            out.dtype)
        out[slot, 2, pl.ds(at, _STRIP)] = (dz * load(b_ref, at)).astype(
            out.dtype)

    def strip(i, carry):
        z_prev, dc_prev, acc = carry
        z, dc, parts = front(i, z_prev)
        back(i - 1, dc_prev, dc)
        return z, dc, [a + p for a, p in zip(acc, parts)]

    z_last, dc_last, acc = jax.lax.fori_loop(
        1, rows // _STRIP, strip, front(0, z_scr[...]))
    z_scr[...] = z_last
    if nt > 1:
        nxt = gn_ref[...].astype(_F32) * cn_ref[...].astype(_F32)
        nxt = jnp.where(t == nt - 1, 0., nxt)
    else:
        nxt = jnp.zeros((_STRIP, lanes), _F32)
    back(rows // _STRIP - 1, dc_last, nxt)
    for j, a in enumerate(acc):
        dw_ref[j:j + 1] += jnp.sum(a, axis=0, keepdims=True)
    for copy in copies(slot):
        copy.start()

    @pl.when(step == steps - 1)
    def _():
        for copy in copies(slot) + (copies(1 - slot) if steps > 1 else []):
            copy.wait()


def _stream(k, ne):
    """Index map of stream ``k``'s column block of a (B, T, 3E) array."""
    return lambda b, e, t: (b, t, k * ne + e)


_VMEM = 64 * 1024 * 1024  # room for the sweep's larger tiles


@functools.lru_cache(maxsize=None)
def _fwd_call(b, t, e, taps, dtype, has_segs, tiles, interpret):
    """The forward ``pallas_call`` of one geometry, built once a process."""
    rows, lanes = tiles
    ne, nt = e // lanes, t // rows
    tile = lambda k: pl.BlockSpec((None, rows, lanes), _stream(k, ne))
    in_specs = [tile(0), tile(1), tile(2),
                pl.BlockSpec((taps, lanes), lambda b_, e_, t_: (0, e_))]
    if has_segs:
        in_specs.append(pl.BlockSpec((None, rows, 2 * (taps - 1)),
                                     lambda b_, e_, t_: (b_, t_, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, has_segs=has_segs),
        name="hvd_conv_fwd",
        grid=(b, ne, nt),
        in_specs=in_specs,
        out_specs=tile(0),
        scratch_shapes=[pltpu.VMEM((_STRIP, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        out_shape=jax.ShapeDtypeStruct((b, t, e), dtype),
        interpret=interpret)


@functools.lru_cache(maxsize=None)
def _bwd_call(b, t, e, taps, dtype, has_segs, tiles, interpret):
    """The backward ``pallas_call`` of one geometry, built once a process:
    ``d[B | C | u]`` (B, T, 3E) in ``dtype`` and the taps' gradient a batch
    row, (B, K, E) float32."""
    rows, lanes = tiles
    ne, nt = e // lanes, t // rows
    tile = lambda k: pl.BlockSpec((None, rows, lanes), _stream(k, ne))
    in_specs = [tile(0), tile(1), tile(2), tile(0)]  # B, C, u; cotangent
    if nt > 1:  # the next tile's first rows of C and of the cotangent
        last, per = t // _STRIP - 1, rows // _STRIP
        nxt = lambda k: pl.BlockSpec(
            (None, _STRIP, lanes), lambda b_, e_, t_: (
                b_, jnp.minimum((t_ + 1) * per, last), k * ne + e_))
        in_specs += [nxt(1), nxt(0)]
    if has_segs:
        in_specs.append(pl.BlockSpec((None, rows, 2 * (taps - 1)),
                                     lambda b_, e_, t_: (b_, t_, 0)))
    in_specs.append(pl.BlockSpec((taps, lanes), lambda b_, e_, t_: (0, e_)))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, has_segs=has_segs, nt=nt,
                          e=e, steps=b * ne * nt),
        name="hvd_conv_bwd",
        grid=(b, ne, nt),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # written by DMA
            pl.BlockSpec((None, taps, lanes), lambda b_, e_, t_: (b_, 0, e_)),
        ],
        scratch_shapes=[pltpu.VMEM((_STRIP, lanes), _F32),   # z carried
                        pltpu.VMEM((2, 3, rows, lanes), dtype),  # 2 slots
                        pltpu.SemaphoreType.DMA((2, 3))],
        compiler_params=pltpu.CompilerParams(
            # (a DMA is waited two steps after it starts: one core walks
            # the whole grid)
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM),
        out_shape=[jax.ShapeDtypeStruct((b, t, 3 * e), dtype),
                   jax.ShapeDtypeStruct((b, taps, e), _F32)],
        interpret=interpret)


def _kernel_args(bcu, w, keep):
    b, t, e3 = bcu.shape
    return (b, t, e3 // 3, w.shape[1]), [] if keep is None else [keep]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernels(bcu, w, keep, tiles, interpret):
    (b, t, e, taps), segs = _kernel_args(bcu, w, keep)
    call = _fwd_call(b, t, e, taps, bcu.dtype, bool(segs), tiles, interpret)
    return call(bcu, bcu, bcu, w.astype(_F32).T, *segs)


def _kernels_fwd(bcu, w, keep, tiles, interpret):
    return _kernels(bcu, w, keep, tiles, interpret), (bcu, w, keep)


def _kernels_bwd(tiles, interpret, residuals, g):
    bcu, w, keep = residuals
    (b, t, e, taps), segs = _kernel_args(bcu, w, keep)
    call = _bwd_call(b, t, e, taps, bcu.dtype, bool(segs), tiles, interpret)
    nxt = [bcu, g] if t // tiles[0] > 1 else []
    d_bcu, dw = call(bcu, bcu, bcu, g, *nxt, *segs, w.astype(_F32).T)
    return d_bcu, jnp.sum(dw, axis=0).T.astype(w.dtype), None


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_short_conv(bcu, w, segment_ids=None):
    """``C * taps(B * u)`` of ``bcu`` = ``[B | C | u]`` (B, T, 3E) with the
    taps ``w`` (E, K); (B, T, E) in ``bcu``'s dtype, float32 inside: the
    kernel pair where :func:`runs_kernels`, the plain form elsewhere."""
    _, t, e3 = bcu.shape
    taps = w.shape[1]
    if not runs_kernels(t, e3 // 3, taps, bcu.dtype):
        return _plain(bcu, w, segment_ids)
    keep = None if segment_ids is None or taps == 1 \
        else _keep(segment_ids, taps)
    # (compiled wherever the rule holds; interpreted only where a test
    # grants the rule off the chip)
    return _kernels(bcu, w, keep, _tiles(t, e3 // 3, taps),
                    _state.target_platform() != "tpu")
