"""Flash attention — the framework's hot-op pallas kernel.

Within-device attention is the FLOPs hot spot of the Transformer family and
of every sequence-parallel strategy's local block. Naive attention
materializes the (Tq, Tk) score matrix in HBM — a 16k-token context costs
16 GB at fp32 and OOMs a v5e chip. This module provides:

* :func:`blockwise_attention` — an O(Tq·block_k) memory online-softmax
  attention as a ``lax.scan`` over K/V blocks. Pure JAX: runs anywhere,
  differentiates through the scan, and is the reference/recompute path.
* :func:`flash_attention` — a pallas TPU kernel of the same math: grid over
  (batch, heads, q-blocks, k-blocks), running max/normalizer/accumulator in
  VMEM scratch, MXU matmuls in bf16 with fp32 accumulation. Backward is a
  single fused FlashAttention-2-style pallas kernel producing dq, dk and dv
  in one sweep (5 matmuls per block pair — the score/dp recompute is shared
  instead of being done once per output as in the classic two-pass dq +
  dk/dv decomposition).

**The kernels follow the mask** (:func:`_block_visibility`): a (q block, k
block) pair the causal line, the sliding window or the padding leaves
nothing of is neither computed NOR FETCHED — the index maps of K and V
(forward) and of the q-side operands (backward) name, for such a pair, the
block the nearest visible pair names (:func:`_block_range`), and Pallas
moves nothing for an index that did not change; a pair the mask leaves
whole is computed with no mask at all; a pair the mask's line crosses — an
EDGE: the causal diagonal, the window's far edge — is walked by sub-tiles
(:func:`_sub_tile`: halves of the block's sides) in a rolled loop, the
wholly masked sub-tiles left out: the backward computes a wholly visible
sub-tile with no mask and a crossed one masked (one body each in its
text); the forward computes the sub-tiles a row of them does not skip as
one masked rectangle, so that the running softmax's per-row work is paid
once a row. At T = 8192 full
causal with the 1024-wide blocks 106 % of the visible scores are computed
(112.5 % when an edge pair was computed whole), and at most 36 of the 64
pairs fetch a K/V block where all 64 did. :func:`score_counts` gives both
numbers for any call, from the same classification.

Both support **grouped-query attention** (fewer K/V heads than Q heads —
``H % Hkv == 0``, each K/V head serves a contiguous group of Q heads) and
**packed-sequence segment masking** (``q_segment_ids``/``kv_segment_ids``:
positions attend only within their own segment).

Layout everywhere: ``(B, T, H, D)`` (as in :mod:`horovod_tpu.parallel.sequence`),
with global position offsets so sequence-parallel shards mask causally
against their true positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.core import state as _state

_NEG_INF = -1e30
# lse padding for query rows beyond Tq: exp(s - 1e30) == 0, so padded rows
# contribute nothing to dk/dv and their (sliced-away) dq rows stay finite.
_POS_BIG = 1e30
# The kernels run their softmax in base 2: the TPU transcendental unit
# computes 2^x natively, so exp(x) = 2^(x·log2e) costs an extra full-block
# VPU multiply — folded into the √scale operand pre-scaling instead. lse
# crosses the kernel boundary in natural-log units (converted on the tiny
# per-row arrays).
_LOG2E = math.log2(math.e)
_LN2 = math.log(2.0)

# Grid layout for the kernels: only dimensions carrying a running
# accumulation are 'arbitrary' — telling Mosaic the rest are parallel lets
# it pipeline/partition freely. The forward holds two (bq, bk) fp32 score
# intermediates; the 48 MB budget admits the 2048×2048 default blocks
# (32 MB of score tiles — the r4 device-timed optimum on v5e), where the
# 16 MB default scoped budget stopped at 1024×1024.
_FWD_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024)


def _small_vmem_chip() -> bool:
    """TPU v2/v3 cores have 16 MB VMEM — the 2048×2048 forward default
    (32 MB of fp32 score tiles) cannot allocate there; v4+ carry 128 MB."""
    dev = _state.target_device()
    kind = dev.device_kind.lower()
    return dev.platform == "tpu" and ("v2" in kind or "v3" in kind)


# bwd grid (b, kv-mem-block, q-head, q-block): dk/dv accumulate across
# (q-head-in-group, q-block); the kv dimension reuses the scratch buffers.
# The fused kernel's resident K/V block + two kv-sized fp32 accumulators
# need more than the conservative 16 MB default scoped-vmem budget; v5e
# has 128 MB physical VMEM.
_BWD_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=100 * 1024 * 1024)


def _check_gqa(h: int, hkv: int) -> int:
    if h % hkv != 0:
        raise ValueError(
            f"GQA needs q heads ({h}) divisible by kv heads ({hkv}).")
    return h // hkv


# ---------------------------------------------------------------------------
# Blockwise (lax.scan) attention — pure JAX, O(block) memory
# ---------------------------------------------------------------------------


def blockwise_attention(q, k, v, causal: bool = True,
                        sm_scale: float | None = None,
                        q_offset=0, kv_offset=0, block_k: int = 512,
                        q_segment_ids=None, kv_segment_ids=None,
                        window: int | None = None):
    """Online-softmax attention scanning over K/V blocks.

    q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D) with H % Hkv == 0 (GQA: each KV
    head serves H/Hkv consecutive Q heads). ``q_offset``/``kv_offset`` are
    the global positions of q[.,0] and k[.,0] (traced scalars allowed) for
    causal masking across sequence shards. ``q_segment_ids``/
    ``kv_segment_ids``: optional (B, Tq)/(B, Tk) int32 — attention is
    masked to equal segment ids (packed sequences). Returns (B, Tq, H, D)
    in q's dtype.
    """
    _check_seg_pair(q_segment_ids, kv_segment_ids)
    _check_window(window, causal)
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = _check_gqa(h, hkv)
    if g > 1:
        # Reference path: expand KV heads locally (the kernels below do
        # grouped indexing instead; this path optimizes for clarity).
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_k = min(block_k, tk)
    nk = -(-tk // block_k)
    pad = nk * block_k - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qT = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.bfloat16)   # (B,H,Tq,D)
    kT = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.bfloat16)
    vT = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.bfloat16)
    k_blocks = kT.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vT.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    if kv_segment_ids is not None:
        kvseg_pad = jnp.pad(kv_segment_ids, ((0, 0), (0, pad)),
                            constant_values=-2)
        kvseg_blocks = kvseg_pad.reshape(b, nk, block_k).transpose(1, 0, 2)
    else:
        kvseg_blocks = jnp.zeros((nk, b, 1), jnp.int32)        # unused

    qpos = q_offset + jnp.arange(tq)[:, None]                  # (Tq, 1)

    # checkpoint: without it, scan's VJP stores every step's (Tq, block_k)
    # score/probability matrices — the full T² in HBM, defeating the point.
    # With it, backward recomputes each block's scores from (q, k-block).
    @jax.checkpoint
    def step(carry, xs):
        m, l, acc = carry
        kb, vb, kvseg_b, jb = xs                               # block j
        s = jnp.einsum("bhqd,bhkd->bhqk", qT, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        kpos = kv_offset + jb * block_k + jnp.arange(block_k)[None, :]
        valid = kpos < (kv_offset + tk)                        # strip padding
        if causal:
            valid = valid & (qpos >= kpos)
            if window is not None:
                valid = valid & (kpos > qpos - window)
        valid = jnp.broadcast_to(valid[None, None],
                                 (b, h, tq, block_k))
        if q_segment_ids is not None:
            seg_ok = (q_segment_ids[:, :, None]
                      == kvseg_b[:, None, :])                  # (B, Tq, bk)
            valid = valid & seg_ok[:, None]
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        # Fully-masked-so-far guard: when m_new is still the -inf init,
        # exp(s - m_new) would be exp(0); zero those probabilities.
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0),
                              (k_blocks, v_blocks, kvseg_blocks,
                               jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------


def _block_visibility(q_first, q_len, k_first, k_len, k_end, causal,
                      window=None, has_segs=False):
    """Classify the rectangle of scores between ``q_len`` query rows from
    global position ``q_first`` and ``k_len`` keys from ``k_first``:
    ``(skip, interior)``. ONE function for a (q block, k block) pair of a
    kernel's grid, for a sub-tile of such a pair, and for the counts of
    :func:`score_counts`; its arguments may be Python ints, numpy arrays
    or traced scalars (``&`` and ``|`` only).

    ``skip`` — nothing in the rectangle is visible: every key is in every
    row's future (causal), or beyond every row's past horizon (``window``:
    query p sees keys [p-window+1, p]), or padding (``k_end`` is the
    global position one past the last real key). A skipped PAIR is not
    computed and not fetched: the K/V (forward) and q-side (backward)
    index maps name the block the nearest visible pair names
    (:func:`_block_range`), and Pallas fetches nothing for an index that
    did not change. ``interior`` — every score in it is visible and
    unpadded: computed with no position mask at all (the iota-and-select
    VPU passes are where causal flash attention wins its time back). A
    pair that is neither is an EDGE (the causal diagonal, the window's far
    edge, the sequence's end): the kernels walk its sub-tiles
    (:func:`_sub_tile`) and classify each with this same function — the
    wholly masked ones are left out; of the rest the backward computes the
    wholly visible ones unmasked and only the ones the mask's line crosses
    masked, the forward a row's as one masked rectangle (``_fwd_kernel``
    says why). With segment ids there is no interior (any rectangle may
    straddle a segment boundary); the skipping still applies.
    """
    q_last = q_first + q_len - 1
    k_last = k_first + k_len - 1
    skip = k_first >= k_end                    # entirely padding
    interior = k_last < k_end
    if causal:
        skip = skip | (q_last < k_first)
        interior = interior & (q_first >= k_last)
    if window is not None:
        # The FIRST (smallest) query row sees the oldest keys: skippable
        # only when the newest key is older than even that row's horizon;
        # interior only when the LAST row still sees the oldest key.
        skip = skip | (k_last < q_first - (window - 1))
        interior = interior & (k_first >= q_last - (window - 1))
    if has_segs:
        interior = interior & False
    return skip, interior


def _block_range(i, x_lo, x_hi, off, block, n):
    """Block index ``i`` (of ``n`` blocks of ``block`` positions from
    global position ``off``) moved into the blocks that reach position
    ``x_lo`` or later and start at ``x_hi`` or before — what
    :func:`_block_visibility`'s two ``skip`` inequalities leave, solved
    for the index: ``(i+1)*block - 1 + off >= x_lo`` and
    ``i*block + off <= x_hi``. ``None`` leaves a side open. An index map
    built on it names, for a skipped pair, a block a visible pair names;
    where nothing is visible it names some one block, all the same."""
    lo, hi = 0, n - 1
    if x_lo is not None:
        lo = jnp.minimum(jnp.maximum(x_lo - off, 0) // block, hi)
    if x_hi is not None:
        hi = jnp.minimum(jnp.maximum(x_hi - off, 0) // block, hi)
        lo = jnp.minimum(lo, hi)
    return jnp.clip(i, lo, hi)


def _sub_tile(block: int, q_lanes: int | None = None) -> int:
    """The side of an edge pair's sub-tiles along a block side: half the
    block where the half is still a whole number of 128-lane tiles, else
    the block (a small block is its own sub-tile: computed whole and
    masked, as every edge pair was before). ``q_lanes``: the backward's q
    block, the lanes of its score tile, which stays whole — a sub-tile of
    keys taller than that is wide holds masked scores wherever the
    diagonal crosses it, so the keys' side is cut down to it (D > 128: 256
    q lanes, sub-tiles of 256 keys; 12.02 ms a backward call for 12.17 at
    halves, my chip run, PR 35). At the 1024-wide defaults an edge pair is
    2 x 2 (forward) or 2 x 1 (backward) sub-tiles of 512, one of four —
    one of two — left out on the diagonal: 106 % of the visible scores are
    computed at T = 8192 full causal where whole edge blocks made it
    112.5 %."""
    sub = block // 2 if block % 256 == 0 else block
    if q_lanes is not None and q_lanes % 128 == 0 and sub % q_lanes == 0:
        sub = q_lanes
    return sub


def _fwd_blocks(tq, tk, block_q, block_k):
    """The forward grid of a call: ``(block_q, block_k, nq, nk)``."""
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    return block_q, block_k, -(-tq // block_q), -(-tk // block_k)


def _bwd_blocks(tq, tk, block_q, block_kc, block_kv_mem):
    """The backward grid of a call: ``(block_q, block_kc, bkv_mem, nq,
    nkm)``. kv memory block: how much K/V sits VMEM-resident per grid
    step. The dq partial-sum dimension is ceil(Tk / block_kv_mem) — one
    memory block (a no-op reduction) whenever Tk fits."""
    block_q, block_kc = min(block_q, tq), min(block_kc, tk)
    bkv_mem = block_kc * max(1, min(block_kv_mem, tk) // block_kc)
    return block_q, block_kc, bkv_mem, -(-tq // block_q), -(-tk // bkv_mem)


def _fwd_kernel(qoff_ref, kvoff_ref, *refs, causal, sm_scale, block_q,
                block_k, sub_q, sub_k, nk, tk, has_segs, window,
                compact_lse):
    if has_segs:
        (q_ref, k_ref, v_ref, qseg_ref, kvseg_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        qseg_ref = kvseg_ref = None
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_off = kvoff_ref[0]
    q_first = qoff_ref[0] + iq * block_q
    k_first = kv_off + ik * block_k
    k_end = kv_off + tk
    classify = functools.partial(_block_visibility, k_end=k_end,
                                 causal=causal, window=window,
                                 has_segs=has_segs)
    skip, interior = classify(q_first, block_q, k_first, block_k)

    def _accumulate(r0, nr, c0, nc, masked, kv_segs=None):
        """Rows [r0, r0+nr) x keys [c0, c0+nc) of the pair."""
        rows, cols = pl.ds(r0, nr), pl.ds(c0, nc)
        s = jax.lax.dot_general(
            q_ref[rows, :], k_ref[cols, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (nr, nc)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            kpos = k_first + c0 + jax.lax.broadcasted_iota(
                jnp.int32, (nr, nc), 1)
            valid = kpos < k_end                              # strip padding
            if causal:
                qpos = q_first + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (nr, nc), 0)
                valid = jnp.logical_and(valid, qpos >= kpos)
                if window is not None:
                    valid = jnp.logical_and(
                        valid, kpos > qpos - window)
            if has_segs:
                valid = jnp.logical_and(valid,
                                        qseg_ref[rows, :1] == kv_segs)
            s = jnp.where(valid, s, _NEG_INF)
        # Running softmax in base 2 (operands carry the log2e factor).
        m_prev = m_scr[rows, :1]                              # (nr, 1)
        l_prev = l_scr[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        if masked:
            p = jnp.where(valid, p, 0.0)
        l_scr[rows, :1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[rows, :1] = m_new
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[cols, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(interior)
    def _fast():
        _accumulate(0, block_q, 0, block_k, masked=False)

    # An edge pair by sub-tiles, one row of them a round of a rolled loop.
    # A row sees an interval of keys, so the sub-tiles a row of them does
    # not skip are neighbours: they are computed as ONE masked rectangle
    # (the running softmax's per-row work — the maximum, the rescaling of
    # the accumulator — is paid once a row, as in a whole block, whatever
    # the number of sub-tiles; paid a sub-tile it cost more than the
    # masked quarter saved). The kernel's text holds one masked body an
    # extent (one and two sub-tiles at the defaults), whatever the mask.
    n_sub_k = block_k // sub_k

    def _sub_row(r, carry):
        r0 = pl.multiple_of(r * sub_q, sub_q)
        seen = [~classify(q_first + r0, sub_q, k_first + c * sub_k, sub_k)[0]
                for c in range(n_sub_k)]
        extent = sum(s.astype(jnp.int32) for s in seen)
        first, none_yet = 0, True       # the sub-tiles skipped before them
        for s in seen[:-1]:
            none_yet = none_yet & ~s
            first = first + none_yet.astype(jnp.int32)
        for n in range(1, n_sub_k + 1):
            @pl.when(extent == n)
            def _rectangle(n=n):
                start = first if n < n_sub_k else 0
                _accumulate(
                    r0, sub_q, pl.multiple_of(start * sub_k, sub_k),
                    n * sub_k, masked=True,
                    kv_segs=jnp.concatenate(
                        [kvseg_ref[pl.ds(start + i, 1), :]
                         for i in range(n)], axis=1) if has_segs else None)

        return carry

    @pl.when(jnp.logical_and(~skip, ~interior))
    def _edge():
        lax.fori_loop(0, block_q // sub_q, _sub_row, 0)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        o_ref[...] = (acc_scr[:] / l).astype(o_ref.dtype)
        # Log-sum-exp residual for the backward kernel, converted from the
        # base-2 running values to natural log. Written COMPACT when the
        # block admits it — each (block_q,) row stored as a
        # (block_q//128, 128) tile: r4 emitted a lane-broadcast
        # (block_q, 128) buffer whose lane 0 was sliced outside — 128x
        # the information's bytes of HBM write + relayout (64 MB/layer at
        # B=2/T=8k; compacting measured -1.45 ms/step over the bench LM's
        # 8 layers, ~0.18 ms/layer — tools/lm_copies.py, r5). The
        # column -> tile reshape is an in-VMEM relayout of a few vregs.
        # Small blocks (block_q//128 not a multiple of 8 — pallas's
        # second-to-last-dim rule) keep the legacy broadcast layout.
        lse_col = (m_scr[:, :1]
                   + jnp.log2(jnp.maximum(l_scr[:, :1], 1e-20))) * _LN2
        if compact_lse:
            lse_ref[...] = lse_col.reshape(block_q // 128, 128)
        else:
            lse_ref[...] = jnp.broadcast_to(lse_col, (block_q, 128))


@functools.lru_cache(maxsize=None)
def _fwd_call(b, h, hkv, tq, tk, d, dtype, causal, offsets, block_q,
              block_k, has_segs, window, interpret):
    """The forward ``pallas_call`` of one geometry, built ONCE a process.
    ``pl.pallas_call`` returns a fresh ``jax.jit`` every time, whose trace
    cache a second building cannot hit: built a call site, the kernel's
    body is traced again wherever the model is — 24 times for the looped
    cell's 8 layers (flax's ``nn.scan`` traces its body four times and
    ``nn.remat`` each block inside it), seconds of every start (PERF.md,
    PR 35). ``offsets``: the two static offsets, or None where they are
    traced (the index maps then read the prefetched scalars)."""
    g = h // hkv
    block_q, block_k, nq, nk = _fwd_blocks(tq, tk, block_q, block_k)
    sub_q, sub_k = _sub_tile(block_q), _sub_tile(block_k)
    # Compact lse tiles need block_q//128 to satisfy pallas's
    # divisible-by-8 second-to-last-dim rule (see _finalize).
    compact_lse = block_q % (8 * 128) == 0

    def k_block(iq, ik, qoff_ref, kvoff_ref):
        """The K/V block pair (iq, ik) reads: ik, or for a skipped pair
        the nearest block a visible pair of this q block names."""
        q_off, kv_off = offsets or (qoff_ref[0], kvoff_ref[0])
        q_first = q_off + iq * block_q
        return _block_range(
            ik, None if window is None else q_first - (window - 1),
            q_first + block_q - 1 if causal else None, kv_off, block_k, nk)

    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda b_, h_, iq, ik, *_: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, d),
        lambda b_, h_, iq, ik, *offs: (b_, h_ // g, k_block(iq, ik, *offs),
                                       0))
    in_specs = [q_spec, kv_spec, kv_spec]
    if has_segs:
        # q segs lane-broadcast (B, L, 128): a per-row column; kv segs a
        # row a column sub-tile, (B, nk, block_k / sub_k, sub_k).
        in_specs += [
            pl.BlockSpec((None, block_q, 128),
                         lambda b_, h_, iq, ik, *_: (b_, iq, 0)),
            pl.BlockSpec((None, None, block_k // sub_k, sub_k),
                         lambda b_, h_, iq, ik, *offs:
                         (b_, k_block(iq, ik, *offs), 0, 0)),
        ]
    # One derived row count keeps the lse spec/shape/kernel in sync.
    lse_rows = block_q // 128 if compact_lse else block_q
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, sm_scale=1.0, block_q=block_q,
            block_k=block_k, sub_q=sub_q, sub_k=sub_k, nk=nk, tk=tk,
            has_segs=has_segs, window=window, compact_lse=compact_lse),
        name="hvd_flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # The two offsets, in SMEM before the pipeline starts: the
            # index maps read them where they are traced (ring attention).
            num_scalar_prefetch=2,
            grid=(b, h, nq, nk),
            in_specs=in_specs,
            out_specs=[
                q_spec,
                pl.BlockSpec((None, None, lse_rows, 128),
                             lambda b_, h_, iq, ik, *_: (b_, h_, iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),      # running max
                pltpu.VMEM((block_q, 128), jnp.float32),      # normalizer
                pltpu.VMEM((block_q, d), jnp.float32),        # accumulator
            ]),
        compiler_params=_FWD_SEMANTICS,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nq * block_q, d), dtype),
            # Log-sum-exp: compact (block_q//128, 128) tiles per q-block
            # (see _finalize), reshaped to (B, H, L) by the caller; legacy
            # lane-broadcast rows when the block is too small for
            # pallas's divisible-by-8 rule.
            jax.ShapeDtypeStruct((b, h, nq * lse_rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )


def _static_offsets(q_offset, kv_offset):
    """``(q_offset, kv_offset)`` where both are Python ints, else None."""
    if isinstance(q_offset, int) and isinstance(kv_offset, int):
        return q_offset, kv_offset
    return None


def _flash_fwd(q, k, v, qseg, kvseg, causal, sm_scale, q_offset, kv_offset,
               block_q, block_k, interpret, window=None):
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    _check_gqa(h, hkv)
    has_segs = qseg is not None
    call = _fwd_call(b, h, hkv, tq, tk, d, q.dtype, causal,
                     _static_offsets(q_offset, kv_offset), block_q, block_k,
                     has_segs, window, interpret)
    block_q, block_k, nq, nk = _fwd_blocks(tq, tk, block_q, block_k)
    pad_q = nq * block_q - tq
    pad_k = nk * block_k - tk

    # Fold the softmax scale AND the exp→exp2 conversion factor into the
    # operands (√(scale·log2e) each side): the kernel then skips both the
    # per-score-block scale multiply and the exp's internal log2e multiply
    # — two full VPU passes over every (bq, bk) tile.
    rs = math.sqrt(sm_scale * _LOG2E)
    qT = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32) * rs
    kT = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32) * rs
    vT = jnp.transpose(v, (0, 2, 1, 3))
    if pad_q:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    args = [jnp.asarray([q_offset], jnp.int32),
            jnp.asarray([kv_offset], jnp.int32),
            qT.astype(jnp.bfloat16), kT.astype(jnp.bfloat16),
            vT.astype(jnp.bfloat16)]
    if has_segs:
        sub_k = _sub_tile(block_k)
        qseg_b = jnp.pad(qseg, ((0, 0), (0, pad_q)), constant_values=-1)
        args += [jnp.broadcast_to(qseg_b[..., None], qseg_b.shape + (128,)),
                 jnp.pad(kvseg, ((0, 0), (0, pad_k)),
                         constant_values=-2).reshape(
                             b, nk, block_k // sub_k, sub_k)]
    out, lse = call(*args)
    if pad_q:
        out = out[:, :, :tq]
    if lse.shape[2] * 128 == nq * block_q:
        # The residual arrives compact: (B, H, nq·bq/128, 128) tiles
        # reshape contiguously to (B, H, L).
        lse_c = lse.reshape(b, h, nq * block_q)
    else:
        lse_c = lse[..., 0]  # legacy lane-broadcast: slice lane 0
    return jnp.transpose(out, (0, 2, 1, 3)), lse_c


# ---------------------------------------------------------------------------
# Fused pallas backward kernel (FlashAttention-2 math, single sweep)
#
# Classic FA2 runs two passes (dq over k-blocks; dk/dv over q-blocks),
# recomputing the probabilities and dP in each — 7 matmuls per block pair.
# This kernel shares the recompute: one sweep produces dq, dk AND dv in
# 5 matmuls per block pair (s, dv, dp, dk, dq). Grid is
# (batch, kv-mem-block, q-head, q-block) with the kv memory block resident
# in VMEM; dk/dv accumulate in scratch across q-blocks (and across the
# q heads of a GQA group), while dq is written per kv-mem-block as partial
# sums reduced by one XLA add afterwards (a no-op when the whole K/V
# sequence fits one memory block).
#
# Layout: scores are (block_k, block_q) — k in sublanes, q in lanes — so
# the per-query lse/di rows broadcast along sublanes for free, with no
# lane-broadcast buffers (reference timeline of the classic decomposition:
# /root/reference has no attention at all; this is TPU-native ground).
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(qoff_ref, kvoff_ref, *refs, causal, sm_scale,
                      block_q, block_kc, sub_k, bkv_mem, nq, tk,
                      heads_per_kv, has_segs, may_have_dead, window):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qseg_ref, kvseg_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = refs
        qseg_ref = kvseg_ref = None
    ikm = pl.program_id(1)
    hq = pl.program_id(2)
    iq = pl.program_id(3)
    hq_in_group = lax.rem(hq, jnp.int32(heads_per_kv))

    @pl.when(jnp.logical_and(hq_in_group == 0, iq == 0))
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_scr[:] = jnp.zeros_like(dq_scr)

    kv_off = kvoff_ref[0]
    q_first = qoff_ref[0] + iq * block_q
    k_mem_first = kv_off + ikm * bkv_mem          # global position
    k_end = kv_off + tk
    nkc = bkv_mem // block_kc
    classify = functools.partial(_block_visibility, q_first, block_q,
                                 k_end=k_end, causal=causal, window=window,
                                 has_segs=has_segs)

    q = q_ref[...]                                            # (bq, D)
    do = do_ref[...]                                          # (bq, D)
    lse_row = lse_ref[...]                                    # (1, bq)
    di_row = di_ref[...]                                      # (1, bq)
    # Rows whose lse kept the -inf init never attended to anything;
    # exp(s - lse) would overflow — route them through exp(-inf) = 0.
    # Dead rows can only exist with segment masking or when the K/V shard
    # can sit entirely in a row's causal future (ring attention); the
    # common same-shard call skips the guard (two VPU passes per block).
    dead_row = (lse_row <= _NEG_INF * 0.5) if may_have_dead else None

    def _compute(k0, nkr, masked):
        """Keys [k0, k0+nkr) of the resident memory block x the q block."""
        sl = pl.ds(k0, nkr)
        k_c = k_ref[sl, :]                                    # (nkr, D)
        v_c = v_ref[sl, :]
        s = lax.dot_general(k_c, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            kpos = k_mem_first + k0 + lax.broadcasted_iota(
                jnp.int32, (nkr, block_q), 0)
            valid = kpos < k_end                              # strip padding
            if causal:
                qpos = q_first + lax.broadcasted_iota(
                    jnp.int32, (nkr, block_q), 1)
                valid = jnp.logical_and(valid, qpos >= kpos)
                if window is not None:
                    valid = jnp.logical_and(
                        valid, kpos > qpos - window)
            if has_segs:
                valid = jnp.logical_and(
                    valid, kvseg_ref[sl, :1] == qseg_ref[:1, :])
            if may_have_dead:
                valid = jnp.logical_and(valid, ~dead_row)
            p = jnp.exp2(jnp.where(valid, s - lse_row, _NEG_INF))
        else:
            if may_have_dead:
                p = jnp.exp2(jnp.where(dead_row, _NEG_INF, s - lse_row))
            else:
                p = jnp.exp2(s - lse_row)
        p_lo = p.astype(do.dtype)
        dv_new = lax.dot_general(                             # Pᵀ·dO
            p_lo, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_scr[sl, :] += dv_new
        dp = lax.dot_general(v_c, do, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_row)
        if sm_scale != 1.0:
            ds = ds * sm_scale
        ds = ds.astype(q.dtype)
        dk_scr[sl, :] += lax.dot_general(                     # dSᵀ·Q
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[:] += lax.dot_general(                         # dS·K
            ds, k_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _sub(i):
        """The edge compute block ``i`` by its sub-tiles of ``sub_k`` keys
        (the q block whole), a rolled loop: one unmasked and one masked
        sub-tile body in the kernel's text."""
        def body(j, carry):
            k0 = pl.multiple_of(i * block_kc + j * sub_k, sub_k)
            sub_skip, sub_interior = classify(k_mem_first + k0, sub_k)

            @pl.when(sub_interior)
            def _visible():
                _compute(k0, sub_k, masked=False)

            @pl.when(jnp.logical_and(~sub_skip, ~sub_interior))
            def _crossed():
                _compute(k0, sub_k, masked=True)

            return carry

        lax.fori_loop(0, block_kc // sub_k, body, 0)

    def _step(i, carry):
        # Same classification as the forward, at the compute block's
        # global position within the full (padded) K sequence.
        k0 = pl.multiple_of(i * block_kc, block_kc)
        skip, interior = classify(k_mem_first + k0, block_kc)

        @pl.when(interior)
        def _fast():
            _compute(k0, block_kc, masked=False)

        @pl.when(jnp.logical_and(~skip, ~interior))
        def _edge():
            _sub(i)

        return carry

    # Whole-step skip: the entire kv memory block is in this q block's
    # future, or beyond its past horizon (its q-side operands were not
    # fetched for it either: _flash_bwd's index maps). dq still gets a
    # (zero) write — the partial-sum reduction reads every slot.
    step_skip, _ = classify(k_mem_first, bkv_mem)

    @pl.when(~step_skip)
    def _run():
        lax.fori_loop(0, nkc, _step, 0)

    dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(hq_in_group == heads_per_kv - 1, iq == nq - 1))
    def _write_kv():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _bwd_call(b, h, hkv, tq, tk, d, dtypes, causal, offsets, block_q,
              block_kc, block_kv_mem, has_segs, window, interpret):
    """The backward ``pallas_call`` of one geometry, built once a process
    (as :func:`_fwd_call`, for its reason). ``dtypes``: q's, k's, v's."""
    g_heads = h // hkv
    block_q, block_kc, bkv_mem, nq, nkm = _bwd_blocks(
        tq, tk, block_q, block_kc, block_kv_mem)

    def q_block(ikm, iq, qoff_ref, kvoff_ref):
        """The q-side block step (ikm, iq) reads: iq, or for a skipped
        step the nearest q block an active step of this memory block
        names."""
        q_off, kv_off = offsets or (qoff_ref[0], kvoff_ref[0])
        k_mem_first = kv_off + ikm * bkv_mem
        return _block_range(
            iq, k_mem_first if causal else None,
            None if window is None else k_mem_first + bkv_mem - 1
            + (window - 1), q_off, block_q, nq)

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda b_, ikm, hq, iq, *offs:
                         (b_, hq, q_block(ikm, iq, *offs), 0))
    kspec = pl.BlockSpec((None, None, bkv_mem, d),
                         lambda b_, ikm, hq, iq, *_: (b_, hq // g_heads,
                                                      ikm, 0))
    rowspec = pl.BlockSpec((None, None, 1, block_q),
                           lambda b_, ikm, hq, iq, *offs:
                           (b_, hq, 0, q_block(ikm, iq, *offs)))
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    if has_segs:
        # bwd layout: q segs as a lane row (B, 1, L); kv segs
        # sublane-broadcast (B, Lk, 128).
        in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         lambda b_, ikm, hq, iq, *offs:
                         (b_, 0, q_block(ikm, iq, *offs))),
            pl.BlockSpec((None, bkv_mem, 128),
                         lambda b_, ikm, hq, iq, *_: (b_, ikm, 0)),
        ]
    # Static elision of the dead-row guard: with concrete offsets where the
    # K/V shard starts at or before the q shard (the plain same-sequence
    # call), every causal row sees at least one key. Traced offsets (ring
    # attention) keep the guard.
    may_have_dead = has_segs or not (
        offsets is not None and (not causal or offsets[1] <= offsets[0]))
    q_dtype, k_dtype, v_dtype = dtypes
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, causal=causal, sm_scale=1.0,
            block_q=block_q, block_kc=block_kc,
            sub_k=_sub_tile(block_kc, block_q),
            bkv_mem=bkv_mem, nq=nq, tk=tk, heads_per_kv=g_heads,
            has_segs=has_segs, may_have_dead=may_have_dead, window=window),
        name="hvd_flash_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                # the offsets, as forward
            grid=(b, nkm, h, nq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, None, None, block_q, d),
                             lambda b_, ikm, hq, iq, *_:
                             (ikm, b_, hq, iq, 0)),
                kspec,
                kspec,
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),        # dq acc
                pltpu.VMEM((bkv_mem, d), jnp.float32),        # dk acc
                pltpu.VMEM((bkv_mem, d), jnp.float32),        # dv acc
            ]),
        compiler_params=_BWD_SEMANTICS,
        out_shape=[
            # One memory block: the partial IS the result — emit in q's
            # dtype. Several: keep partials fp32 so the cross-block sum
            # rounds once, like the single-scratch accumulation it replaces.
            jax.ShapeDtypeStruct((nkm, b, h, nq * block_q, d),
                                 q_dtype if nkm == 1 else jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, nkm * bkv_mem, d), k_dtype),
            jax.ShapeDtypeStruct((b, hkv, nkm * bkv_mem, d), v_dtype),
        ],
        interpret=interpret,
    )


def _flash_bwd(q, k, v, out, lse_c, g_out, qseg, kvseg, causal, sm_scale,
               q_offset, kv_offset, block_q, block_kc, block_kv_mem,
               interpret, g_lse=None, window=None):
    """Fused backward. ``lse_c``: compact (B, H, Tq) fp32 from the forward."""
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    _check_gqa(h, hkv)
    has_segs = qseg is not None
    call = _bwd_call(b, h, hkv, tq, tk, d, (q.dtype, k.dtype, v.dtype),
                     causal, _static_offsets(q_offset, kv_offset), block_q,
                     block_kc, block_kv_mem, has_segs, window, interpret)
    block_q, block_kc, bkv_mem, nq, nkm = _bwd_blocks(
        tq, tk, block_q, block_kc, block_kv_mem)
    pad_q = nq * block_q - tq
    pad_k = nkm * bkv_mem - tk

    to_bhtd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    # √(scale·log2e) folded into q and k (matching the forward's
    # pre-scaling, so the recomputed base-2 scores line up with the saved
    # lse); dq/dk then carry a residual √(scale·ln2), applied once on the
    # small (…, D) outputs below.
    rs = math.sqrt(sm_scale * _LOG2E)
    rs_out = math.sqrt(sm_scale * _LN2)
    qT = to_bhtd(q).astype(jnp.float32) * rs
    kT = to_bhtd(k).astype(jnp.float32) * rs
    vT = to_bhtd(v)
    doT, outT = to_bhtd(g_out), to_bhtd(out)
    # delta_i = rowsum(dO ⊙ O): the softmax-jacobian correction term,
    # cheap elementwise work — computed in plain XLA, compact (B, H, Tq).
    di = jnp.sum(doT.astype(jnp.float32) * outT.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # lse cotangent (b, h, tq): d lse/d s = softmax(s) = p, so it enters
        # the shared ds = p * (dp - di') term as di' = di - g_lse.
        di = di - g_lse.astype(jnp.float32)
    lse_p, di_p = lse_c * _LOG2E, di      # lse to the kernel's base-2 units
    if pad_q:
        pads = ((0, 0), (0, 0), (0, pad_q), (0, 0))
        qT, doT = jnp.pad(qT, pads), jnp.pad(doT, pads)
        lse_p = jnp.pad(lse_p, ((0, 0), (0, 0), (0, pad_q)),
                        constant_values=_POS_BIG)
        di_p = jnp.pad(di_p, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        pads = ((0, 0), (0, 0), (0, pad_k), (0, 0))
        kT, vT = jnp.pad(kT, pads), jnp.pad(vT, pads)
    args = [jnp.asarray([q_offset], jnp.int32),
            jnp.asarray([kv_offset], jnp.int32),
            qT.astype(jnp.bfloat16), kT.astype(jnp.bfloat16),
            vT.astype(jnp.bfloat16), doT.astype(jnp.bfloat16),
            lse_p[:, :, None, :], di_p[:, :, None, :]]    # (B, H, 1, L)
    if has_segs:
        qseg_b = jnp.pad(qseg, ((0, 0), (0, pad_q)),
                         constant_values=-1)[:, None, :]
        kvseg_b = jnp.pad(kvseg, ((0, 0), (0, pad_k)), constant_values=-2)
        args += [qseg_b, jnp.broadcast_to(kvseg_b[..., None],
                                          kvseg_b.shape + (128,))]
    dq_part, dk, dv = call(*args)

    dq_sum = dq_part[0] if nkm == 1 else jnp.sum(dq_part, axis=0)
    # Residual √(scale·ln2) from the operand folding (the base-2 softmax
    # jacobian contributes ln2; dq = dS·(√(scale·log2e)·k) etc.).
    dq = (dq_sum.astype(jnp.float32) * rs_out).astype(q.dtype)
    dk = (dk.astype(jnp.float32) * rs_out).astype(k.dtype)
    from_bhtd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    if pad_q:
        dq = dq[:, :, :tq]
    if pad_k:
        dk, dv = dk[:, :, :tk], dv[:, :, :tk]
    return from_bhtd(dq), from_bhtd(dk), from_bhtd(dv)


# ---------------------------------------------------------------------------
# custom-VJP plumbing. The public wrappers normalize optional arguments and
# call inner custom_vjp functions (segment ids travel as differentiable
# array args with float0 cotangents; a (0,)-shaped sentinel means "none").
# ---------------------------------------------------------------------------

def _check_window(window, causal):
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True.")
        if window < 1:
            raise ValueError(f"window must be >= 1 (got {window}).")


def _check_seg_pair(qseg, kvseg):
    if (qseg is None) != (kvseg is None):
        raise ValueError(
            "q_segment_ids and kv_segment_ids must be given together.")


def _seg_or_sentinel(seg):
    if seg is None:
        return jnp.zeros((0,), jnp.int32)
    return jnp.asarray(seg, jnp.int32)


def _unwrap_seg(seg):
    return None if seg.shape[0] == 0 else seg


def _resolve(sm_scale, interpret, d):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _state.target_platform() != "tpu"
    return sm_scale, interpret


# Measured throughput-optimal on v5e (D=128, T=16k): tall score blocks
# (1024 k-rows × 512 q-lanes) with 4096 K/V rows VMEM-resident per step.
_BWD_BLOCK_Q = 512         # bwd q block (lanes of the score layout)
_BWD_BLOCK_KC = 1024       # bwd kv compute block (sublanes); doubled for
                           # T >= 32k in _default_blocks (device-timed r4:
                           # 2048 is reproducibly 1.3% faster there, a
                           # tie at 16k, and ~1% slower at 8k)
_BWD_BLOCK_KV_MEM = 4096   # kv rows resident in VMEM per grid step


def _default_blocks(d, t, block_q, block_k, bwd_q, bwd_k, bwd_mem):
    """Resolve unset block sizes. Explicit arguments always win.

    The D <= 128 defaults are tuned on v5e at D=128. For D > 128 the
    d-proportional part of the kernels' VMEM footprint (operand blocks,
    the backward's K/V residency and dk/dv accumulators) doubles; those
    defaults are a sweep's on a v5e chip at D=256, 20 heads, T=8192, full
    causal (tools/moe_sweep.py; PERF.md, PR 31): forward 1024x1024 (6.32
    ms a call for 7.88 at the 512x512 this function gave before it was
    timed, 6.60 at 1024x512, 6.91 at 1024x2048); backward 256 q lanes x
    1024 k sublanes with 4096 K/V rows resident (13.46 ms for 14.37 at
    512 x 512 x 2048; 512 q lanes with 4096 resident rows is the worst
    measured, 19.5).
    """
    big = d > 128
    # fwd 2048x2048: device-timeline-measured best at D=128, T=16k on v5e
    # (4.84 ms vs 5.01 at 1024x1024 — tools/fa_sweep.py, r4); at T=8k the
    # same sweep puts 1024x1024 7% ahead, so the bump applies from 16k.
    # The 2048 tiles need the raised _FWD_SEMANTICS vmem budget (two
    # 16 MB fp32 score tiles), which v2/v3's 16 MB physical VMEM cannot
    # hold — those keep 1024 everywhere.
    if t >= 16384 and not big and not _small_vmem_chip():
        fwd_default = 2048
    else:
        fwd_default = 1024
    bwd_k_default = (
        2 * _BWD_BLOCK_KC
        if t >= 32768 and not big and not _small_vmem_chip()
        else _BWD_BLOCK_KC)
    return ((block_q or fwd_default),
            (block_k or fwd_default),
            (bwd_q or (256 if big else _BWD_BLOCK_Q)),
            (bwd_k or bwd_k_default),
            (bwd_mem or _BWD_BLOCK_KV_MEM))


def score_counts(tq: int, tk: int, d: int, causal: bool = True,
                 window: int | None = None, q_offset: int = 0,
                 kv_offset: int = 0, block_q=None, block_k=None,
                 block_q_bwd=None, block_k_bwd=None, block_kv_mem=None):
    """``(visible, computed)``: the scores the mask leaves visible, and the
    scores the kernels compute, over ONE forward and ONE backward call of
    :func:`flash_attention` at these lengths and this head width, a
    (batch, head) — static arithmetic (numpy), from the blocks
    :func:`_default_blocks` resolves and the classification the kernels
    themselves run (:func:`_block_visibility`, pair by pair and, in an
    edge pair, sub-tile by sub-tile). ``visible`` counts each call's
    mask once: ``computed / visible`` is 1 for a kernel that computes
    nothing masked, 1.0624 at T = 8192 full causal with the 1024-wide
    defaults (1.125 when edge pairs were computed whole). Segment ids are
    data and are not counted (they mask more, and skip nothing)."""
    bq, bk, bq_b, bk_b, bm = _default_blocks(
        d, max(tq, tk), block_q, block_k, block_q_bwd, block_k_bwd,
        block_kv_mem)
    rows = q_offset + np.arange(tq, dtype=np.int64) - kv_offset
    newest = np.minimum(rows, tk - 1) if causal else np.full(tq, tk - 1)
    oldest = np.maximum(rows - (window - 1), 0) if window is not None else 0
    visible = int(np.maximum(newest - oldest + 1, 0).sum())

    def walked(block_q, sub_q, nq, block_k, sub_k, nk):
        def classified(len_q, n_q, len_k, n_k):
            return _block_visibility(
                q_offset + np.arange(n_q)[:, None] * len_q, len_q,
                kv_offset + np.arange(n_k)[None, :] * len_k, len_k,
                kv_offset + tk, causal, window)
        skip, interior = classified(block_q, nq, block_k, nk)
        edge = ~(skip | interior)
        rq, rk = block_q // sub_q, block_k // sub_k
        sub_skip, _ = classified(sub_q, nq * rq, sub_k, nk * rk)
        in_edge = np.repeat(np.repeat(edge, rq, axis=0), rk, axis=1)
        return int(interior.sum()) * block_q * block_k \
            + int((in_edge & ~sub_skip).sum()) * sub_q * sub_k

    fq, fk, nq, nk = _fwd_blocks(tq, tk, bq, bk)
    computed = walked(fq, _sub_tile(fq), nq, fk, _sub_tile(fk), nk)
    gq, gk, mem, nq, nkm = _bwd_blocks(tq, tk, bq_b, bk_b, bm)
    computed += walked(gq, gq, nq, gk, _sub_tile(gk, gq), nkm * mem // gk)
    return 2 * visible, computed


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 9, 10, 11, 12, 13))
def _flash(q, k, v, qseg, kvseg, causal, sm_scale, q_offset, kv_offset,
           block_q, block_k, bwd_blocks, interpret, window):
    sm_scale, interpret = _resolve(sm_scale, interpret, q.shape[-1])
    out, _ = _flash_fwd(q, k, v, _unwrap_seg(qseg), _unwrap_seg(kvseg),
                        causal, sm_scale, q_offset, kv_offset,
                        block_q, block_k, interpret, window)
    return out


# The two residuals the backward kernel reads that only the forward KERNEL
# can make, by the names ``jax.ad_checkpoint.checkpoint_name`` gives them.
# A ``jax.checkpoint`` whose policy saves these two
# (``save_only_these_names(OUT_RESIDUAL, LSE_RESIDUAL)``) reads them back
# in its backward and does not run the forward kernel a second time; with
# no policy, or another's names, they lower to nothing.
OUT_RESIDUAL = "hvd_flash_out"
LSE_RESIDUAL = "hvd_flash_lse"


def _fwd_and_residuals(q, k, v, qseg, kvseg, causal, sm_scale, q_offset,
                       kv_offset, block_q, block_k, interpret, window):
    """The forward kernel and the residuals of both VJP rules: ``(out,
    lse_c, residuals)``, ``out`` and ``lse_c`` NAMED. The names have to be
    given here, inside the rule: the residual is this variable, and a name
    on the attention's result outside the ``custom_vjp`` is another."""
    sm_scale, interpret = _resolve(sm_scale, interpret, q.shape[-1])
    out, lse_c = _flash_fwd(q, k, v, _unwrap_seg(qseg), _unwrap_seg(kvseg),
                            causal, sm_scale, q_offset, kv_offset,
                            block_q, block_k, interpret, window)
    out = checkpoint_name(out, OUT_RESIDUAL)
    lse_c = checkpoint_name(lse_c, LSE_RESIDUAL)
    return out, lse_c, (q, k, v, qseg, kvseg, out, lse_c, q_offset,
                        kv_offset)


def _flash_fwd_rule(q, k, v, qseg, kvseg, causal, sm_scale, q_offset,
                    kv_offset, block_q, block_k, bwd_blocks, interpret,
                    window):
    out, _, residuals = _fwd_and_residuals(
        q, k, v, qseg, kvseg, causal, sm_scale, q_offset, kv_offset,
        block_q, block_k, interpret, window)
    return out, residuals


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, bwd_blocks,
                    interpret, window, residuals, g):
    q, k, v, qseg, kvseg, out, lse_c, q_offset, kv_offset = residuals
    sm_scale, interpret = _resolve(sm_scale, interpret, q.shape[-1])
    bq, bkc, bkv_mem = bwd_blocks
    dq, dk, dv = _flash_bwd(q, k, v, out, lse_c[:, :, :q.shape[1]], g,
                            _unwrap_seg(qseg), _unwrap_seg(kvseg),
                            causal, sm_scale, q_offset, kv_offset,
                            bq, bkc, bkv_mem, interpret, window=window)
    # Offsets and segment ids are integers: cotangent space is float0.
    zero = lambda x: np.zeros(jnp.shape(x), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero(qseg), zero(kvseg), zero(q_offset), zero(kv_offset))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: float | None = None,
                    q_offset=0, kv_offset=0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None, *,
                    q_segment_ids=None, kv_segment_ids=None,
                    block_q_bwd: int | None = None,
                    block_k_bwd: int | None = None,
                    block_kv_mem: int | None = None,
                    window: int | None = None):
    """Pallas flash attention, (B, T, H, D) layout.

    ``q``: (B, Tq, H, D); ``k``/``v``: (B, Tk, Hkv, D) with H a multiple of
    Hkv (GQA/MQA — each KV head serves H/Hkv consecutive Q heads).
    ``q_segment_ids``/``kv_segment_ids``: optional (B, Tq)/(B, Tk) int32
    packed-sequence segment ids; attention is masked to equal ids.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (so the same code path is testable on the simulated CPU pod). Backward
    is a single fused FlashAttention-2 pallas kernel (5 matmuls per block
    pair instead of the classic two-pass 7), recomputing block
    probabilities from the saved log-sum-exp — no (Tq, Tk) matrix is ever
    materialized in either direction. The backward's one super-linear HBM
    term: when Tk exceeds ``block_kv_mem``, dq is produced as
    ``ceil(Tk/block_kv_mem)`` fp32 partial sums — an
    ``O(B·H·Tq·D·Tk/block_kv_mem)`` buffer reduced by a single XLA add
    (≈1 GB at T=32k, B=1, H=8, D=128 with the 4k default). Long-context
    runs that are HBM-tight should raise ``block_kv_mem`` (fewer, larger
    partials) before shrinking the score tiles.

    Forward blocks default to 2048×2048 for T ≥ 16k and 1024×1024 below
    — device-timeline-measured optima on a v5e chip at D=128 (the kernel
    holds two (bq, bk) fp32 intermediates in VMEM; the 48 MB scoped
    budget admits the 2048 tiles; v2/v3 chips stay at 1024). Backward
    blocks default to ``block_q_bwd=512``
    q lanes × ``block_k_bwd=1024`` k sublanes per score tile, with
    ``block_kv_mem=4096`` K/V rows VMEM-resident per grid step. Head dims
    above 128 have unset defaults of their own, swept at D=256 (see
    ``_default_blocks``); explicit arguments always win.

    **Residual names.** The backward kernel reads q, k, v, the output and
    the log-sum-exp. The last two only the forward kernel can make, and
    the VJP rule names them (``jax.ad_checkpoint.checkpoint_name``):
    :data:`OUT_RESIDUAL` — the (B, Tq, H, D) output, in q's dtype as the
    kernel wrote it — and :data:`LSE_RESIDUAL` — the float32 log-sum-exp.
    A name alone lowers to nothing, and a bare ``jax.checkpoint`` around
    this call ignores it: its backward runs the forward kernel again. To
    keep what the kernel wrote and recompute the rest, give the
    checkpoint the policy
    ``jax.checkpoint_policies.save_only_these_names(OUT_RESIDUAL,
    LSE_RESIDUAL)`` (both: with the output alone the kernel still runs
    again for the log-sum-exp), or list the two among your own policy's
    names. A looped ``models.transformer`` stack does.
    """
    _check_seg_pair(q_segment_ids, kv_segment_ids)
    _check_window(window, causal)
    block_q, block_k, bq_b, bk_b, bm = _default_blocks(
        q.shape[-1], max(q.shape[1], k.shape[1]), block_q, block_k,
        block_q_bwd, block_k_bwd, block_kv_mem)
    return _flash(q, k, v, _seg_or_sentinel(q_segment_ids),
                  _seg_or_sentinel(kv_segment_ids), causal, sm_scale,
                  q_offset, kv_offset, block_q, block_k,
                  (bq_b, bk_b, bm), interpret, window)


# ---------------------------------------------------------------------------
# flash_attention_lse — out AND per-row log-sum-exp, both differentiable.
# The building block for ring attention's flash path: per-shard partial
# results merge exactly via their lse (softmax-weighted average), so each
# ring step runs the full pallas kernel instead of pure-JAX blockwise math.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 9, 10, 11, 12, 13))
def _flash_lse(q, k, v, qseg, kvseg, causal, sm_scale, q_offset, kv_offset,
               block_q, block_k, bwd_blocks, interpret, window):
    sm_scale, interpret = _resolve(sm_scale, interpret, q.shape[-1])
    out, lse_c = _flash_fwd(q, k, v, _unwrap_seg(qseg), _unwrap_seg(kvseg),
                            causal, sm_scale, q_offset, kv_offset,
                            block_q, block_k, interpret, window)
    return out, jnp.transpose(lse_c[:, :, :q.shape[1]], (0, 2, 1))


def _flash_lse_fwd_rule(q, k, v, qseg, kvseg, causal, sm_scale, q_offset,
                        kv_offset, block_q, block_k, bwd_blocks, interpret,
                        window):
    out, lse_c, residuals = _fwd_and_residuals(
        q, k, v, qseg, kvseg, causal, sm_scale, q_offset, kv_offset,
        block_q, block_k, interpret, window)
    lse_rows = jnp.transpose(lse_c[:, :, :q.shape[1]], (0, 2, 1))
    return (out, lse_rows), residuals


def _flash_lse_bwd_rule(causal, sm_scale, block_q, block_k, bwd_blocks,
                        interpret, window, residuals, cotangents):
    q, k, v, qseg, kvseg, out, lse_c, q_offset, kv_offset = residuals
    g_out, g_lse = cotangents                       # (B,Tq,H,D), (B,Tq,H)
    sm_scale, interpret = _resolve(sm_scale, interpret, q.shape[-1])
    bq, bkc, bkv_mem = bwd_blocks
    g_lse_bht = jnp.transpose(g_lse, (0, 2, 1))     # (B, H, Tq)
    dq, dk, dv = _flash_bwd(q, k, v, out, lse_c[:, :, :q.shape[1]], g_out,
                            _unwrap_seg(qseg), _unwrap_seg(kvseg),
                            causal, sm_scale, q_offset, kv_offset,
                            bq, bkc, bkv_mem, interpret, g_lse=g_lse_bht,
                            window=window)
    zero = lambda x: np.zeros(jnp.shape(x), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero(qseg), zero(kvseg), zero(q_offset), zero(kv_offset))


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_lse(q, k, v, causal: bool = True,
                        sm_scale: float | None = None,
                        q_offset=0, kv_offset=0,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool | None = None, *,
                        q_segment_ids=None, kv_segment_ids=None,
                        block_q_bwd: int | None = None,
                        block_k_bwd: int | None = None,
                        block_kv_mem: int | None = None,
                        window: int | None = None):
    """Like :func:`flash_attention` but returns ``(out, lse)``.

    ``lse``: (B, Tq, H) float32 log-sum-exp of the scaled scores per query
    row. Rows that attend to nothing (everything masked) get a very
    negative finite value (exp(lse - anything) == 0 in a merge). Both
    outputs are differentiable — the lse cotangent folds into the
    FlashAttention-2 backward's correction term (di' = di - g_lse), so
    partial-attention merges (ring attention) backprop exactly. Supports
    GQA and segment ids like :func:`flash_attention`, including its
    head-dim-aware default block sizes.

    **Residual names**: as :func:`flash_attention`'s — the same two, the
    same way (one helper makes both rules' residuals). The ``lse``
    returned is a transposed slice of the named one, so it is not the
    residual: name nothing outside. Ring attention wraps each step in a
    bare ``jax.checkpoint``, which saves nothing, named or not.
    """
    _check_seg_pair(q_segment_ids, kv_segment_ids)
    _check_window(window, causal)
    block_q, block_k, bq_b, bk_b, bm = _default_blocks(
        q.shape[-1], max(q.shape[1], k.shape[1]), block_q, block_k,
        block_q_bwd, block_k_bwd, block_kv_mem)
    return _flash_lse(q, k, v, _seg_or_sentinel(q_segment_ids),
                      _seg_or_sentinel(kv_segment_ids), causal, sm_scale,
                      q_offset, kv_offset, block_q, block_k,
                      (bq_b, bk_b, bm), interpret, window)
