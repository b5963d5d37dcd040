"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference has no attention code at all (SURVEY §5.7) — its scale story
stops at data parallelism. On TPU, long-context training is a first-class
capability of this framework, built from the same group machinery the fork
introduced for MPI sub-communicators: a *context-parallel group* is just an
``hvd`` group whose ranks hold consecutive shards of the sequence axis, and
the two standard strategies ride the group's ICI links:

* :func:`ring_attention` — blockwise attention with the K/V shards rotating
  around the group ring (``lax.ppermute``), accumulating with an online
  (flash-style) softmax. Memory per chip is O(T_local²-ish blockwise), so
  context length scales linearly with group size. (Liu et al., "Ring
  Attention with Blockwise Transformers", 2023.)
* :func:`ulysses_attention` — all-to-all the sequence axis against the head
  axis (``hvd.alltoall``): each rank ends up with the FULL sequence for
  H/g of the heads, runs ordinary attention locally, and all-to-alls back.
  (Jacobs et al., "DeepSpeed Ulysses", 2023.)

Both compose with data parallelism through groups: e.g. 8 chips as 2 DP × 4 SP
is ``hvd.init([[0,1,2,3],[4,5,6,7]])`` with gradient allreduce on group 0 and
sequence parallelism within group 1 or 2 — the TPU realisation of the fork's
overlapping-communicator design (README.md:8-13).

All functions run inside ``hvd.spmd`` traced code. Tensors are the local
sequence shard, layout ``(batch, seq_local, heads, head_dim)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.core import context as _ctx
from horovod_tpu.core import state as _state
from horovod_tpu.core.state import AXIS_NAME, HorovodError

_NEG_INF = -1e30  # large-negative mask (not -inf: keeps exp/max NaN-free)


def _require_traced(fn_name: str) -> _ctx.TraceContext:
    tctx = _ctx.current()
    if tctx is None:
        raise HorovodError(
            f"{fn_name} must be called inside an hvd.spmd-wrapped step "
            f"function (it lowers to mesh collectives).")
    return tctx


def _group_ring(tctx: _ctx.TraceContext, group):
    """(rings, group size, traced group rank) for a group or group family.

    ``rings``: one member-position list per group — a family (tuple of
    pairwise-disjoint, equal-size groups) turns into PARALLEL rings
    rotating in a single ppermute (disjoint cycles in one perm), the
    DP×SP composition: every data-parallel replica runs its own sequence
    ring simultaneously. ``grank`` is each rank's position within its own
    ring (−1 outside all of them).
    """
    if isinstance(group, (tuple, list)):
        fam = tuple(group)
        if not fam:
            raise HorovodError("ring_attention family must be non-empty.")
        sizes = {_state.get_group(g).size for g in fam}
        if len(sizes) != 1:
            raise HorovodError(
                f"ring_attention family groups must have equal sizes; got "
                f"{sorted(_state.get_group(g).size for g in fam)}.")
        all_pos = [tctx.member_positions(g) for g in fam]
        flat = [p for ring in all_pos for p in ring]
        if len(set(flat)) != len(flat):
            raise HorovodError(
                "ring_attention family groups must be pairwise disjoint.")
        grank = None
        for g in fam:
            r = tctx.rank(g)
            grank = r if grank is None else jnp.maximum(grank, r)
        return all_pos, sizes.pop(), grank
    g = _state.get_group(group)
    return [tctx.member_positions(group)], g.size, tctx.rank(group)


def _ppermute_ring(x, rings, shift: int = 1):
    """Rotate x one hop around each ring: member m -> member (m+shift),
    all rings' disjoint cycles in ONE collective-permute."""
    perm = [(ring[m], ring[(m + shift) % len(ring)])
            for ring in rings for m in range(len(ring))]
    return lax.ppermute(x, AXIS_NAME, perm)


def _lse_merge(m, l, acc, o_s, lse_s):
    """Merge a partial attention result into the running (m, l, acc) by its
    log-sum-exp — the exact softmax-weighted average both ring layouts use.
    Fully-masked partials arrive with lse ≈ -inf and contribute nothing."""
    m_new = jnp.maximum(m, lse_s)
    alpha = jnp.exp(m - m_new)
    w = jnp.exp(lse_s - m_new)
    return (m_new, l * alpha + w,
            acc * alpha[..., None] + w[..., None] * o_s.astype(jnp.float32))


def _rotate_kv(kv_k, kv_v, kvseg, has_segs, member, positions, gsize):
    """One forward ring hop for K/V (and their segment ids). Non-members
    aren't in the perm (they'd receive zeros): they keep their own shard so
    their local attention is unaffected."""
    kv_k2 = _ppermute_ring(kv_k, positions)
    kv_v2 = _ppermute_ring(kv_v, positions)
    kvseg2 = _ppermute_ring(kvseg, positions) if has_segs else kvseg
    if gsize > 1:
        kv_k2 = jnp.where(member, kv_k2, kv_k)
        kv_v2 = jnp.where(member, kv_v2, kv_v)
        if has_segs:
            kvseg2 = jnp.where(member, kvseg2, kvseg)
    return kv_k2, kv_v2, kvseg2


def _block_attend(q, k, v, m, l, acc, q_off, kv_off, causal, sm_scale,
                  qseg=None, kvseg=None, window=None):
    """One blockwise-softmax accumulation step (the flash-attention update).

    q: (B, H, Tq, D); k/v: (B, Hkv, Tk, D) with H % Hkv == 0 (GQA heads
    are expanded locally, so the ring only ever carries Hkv heads);
    m/l: (B, H, Tq) running max / normalizer; acc: (B, H, Tq, D) running
    numerator. Offsets are global sequence positions of the blocks (for
    causal masking across shards). ``qseg``/``kvseg``: optional (B, Tq)/
    (B, Tk) int32 packed-sequence segment ids.
    """
    if k.shape[1] != q.shape[1]:
        reps = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    tq, tk = q.shape[2], k.shape[2]
    if causal:
        qpos = q_off + jnp.arange(tq)[:, None]
        kpos = kv_off + jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
        if window is not None:
            s = jnp.where(kpos > qpos - window, s, _NEG_INF)
    if qseg is not None:
        seg_ok = qseg[:, None, :, None] == kvseg[:, None, None, :]
        s = jnp.where(seg_ok, s, _NEG_INF)
    m_blk = jnp.max(s, axis=-1)                      # (B, H, Tq)
    m_new = jnp.maximum(m, m_blk)
    # Rescale previous accumulator; masked-out-everything rows stay finite
    # because m stays at its init (_NEG_INF) and alpha = exp(0) = 1.
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                # (B, H, Tq, Tk)
    # Rows with every position masked so far have m_new == _NEG_INF and
    # s - m_new == 0, i.e. p == 1 on masked positions: zero them so
    # correctness never depends on which shard the ring delivers first.
    p = jnp.where((m_new <= _NEG_INF * 0.5)[..., None],
                  jnp.zeros_like(p), p)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, group=0, causal: bool = True,
                   sm_scale: float | None = None,
                   block_k: int | None = None, impl: str = "auto",
                   q_segment_ids=None, kv_segment_ids=None,
                   layout: str = "contiguous", window: int | None = None):
    """Exact attention over a sequence sharded across the group's ranks.

    ``group`` may also be a *family* (tuple of pairwise-disjoint,
    equal-size group indices): every group runs its own ring
    simultaneously — disjoint cycles in one collective-permute per hop —
    which is the DP×SP (and DP×TP×SP) composition: each data-parallel
    replica sequence-shards its own batch. Ranks outside every family
    group compute plain local attention on their shard.

    ``q``: local shard, ``(B, T_local, H, D)``; ``k``/``v``:
    ``(B, T_local, Hkv, D)`` with H a multiple of Hkv (GQA/MQA — the ring
    only ever carries the Hkv K/V heads, so grouped heads cut ring traffic
    too); rank i of the group holds global positions
    ``[i*T_local, (i+1)*T_local)``. Returns the local shard of the
    attention output, same shape as ``q``. K/V rotate around the ring so
    every rank sees every key/value block once; the online softmax makes
    the result exactly full attention over ``T_local * g``.

    ``q_segment_ids``/``kv_segment_ids``: optional (B, T_local) int32
    packed-sequence segment ids for the local shard; the kv ids rotate
    around the ring with their K/V shard, and attention is masked to
    equal ids (Horovod-group analog of the reference's — absent — packing
    support; the segment mask composes with the causal mask).

    ``layout``: ``'contiguous'`` — rank i holds global positions
    ``[i*T_local, (i+1)*T_local)``; ``'zigzag'`` — rank i holds chunks
    ``i`` and ``2g-1-i`` of a 2g-way split (build shards with
    :func:`zigzag_shard` / undo with :func:`zigzag_unshard`). Zigzag
    balances the causal mask's work across ranks: under the contiguous
    layout the lockstep ring waits on the last rank (it owns the whole
    causal triangle's densest rows) while rank 0 idles — zigzag gives
    every rank one early and one late chunk, equalising per-step work
    (the Striped/zigzag Ring Attention recipe). Each ring step processes
    the four (q-chunk, kv-chunk) pairs — via the flash kernel on TPU, the
    pure-JAX blockwise update elsewhere (``impl`` chooses, as usual);
    ``block_k`` sub-blocking does not apply.

    ``impl``: ``'flash'`` runs each ring step through the pallas kernel
    (:func:`~horovod_tpu.ops.flash_attention.flash_attention_lse`) and
    merges the per-shard partials by their log-sum-exp — exact, and the
    per-step math runs at kernel speed instead of pure-JAX blockwise;
    ``'blockwise'`` is the pure-JAX path (any backend, and the one
    ``block_k`` sub-blocking applies to); ``'auto'`` picks 'flash' on TPU.
    NOTE: the flash impl (and the blockwise one) computes the QK/PV matmuls
    in bfloat16 (fp32 accumulation) — fp32 inputs lose mantissa bits on the
    MXU path by design; pass ``impl='blockwise'`` off-TPU for an fp32-input
    check.

    ``block_k`` (blockwise impl) bounds per-step score memory: each received
    shard is consumed in K/V sub-blocks of that size (must divide T_local),
    so peak score memory is (B, H, T_local, block_k) instead of
    (…, T_local)². Default: T_local (one block) up to 2048, else 1024.
    Passing ``block_k`` under ``impl='auto'`` selects the blockwise path
    (it is a blockwise-tuning request); combining it with an explicit
    ``impl='flash'`` is an error — the flash kernel blocks internally in
    VMEM.

    Non-members of ``group`` (when the program's mesh is larger) compute
    plain local attention over their own shard.
    """
    tctx = _require_traced("ring_attention")
    positions, gsize, grank = _group_ring(tctx, group)
    if q.ndim != 4:
        raise HorovodError(
            f"ring_attention expects (batch, seq, heads, head_dim); got "
            f"shape {list(q.shape)}.")
    b, t_local, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv != 0:
        raise HorovodError(
            f"ring_attention needs q heads ({h}) divisible by kv heads "
            f"({hkv}).")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise HorovodError(
            "ring_attention needs q_segment_ids and kv_segment_ids "
            "together.")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if layout not in ("contiguous", "zigzag"):
        raise HorovodError(f"Unknown ring_attention layout {layout!r}.")
    if layout == "zigzag":
        if impl == "auto":
            impl = "flash" if _state.target_platform() == "tpu" else "blockwise"
        if impl not in ("flash", "blockwise"):
            raise HorovodError(f"Unknown ring_attention impl {impl!r}.")
        if block_k is not None:
            raise HorovodError(
                "ring_attention layout='zigzag' consumes whole chunks per "
                "step; block_k sub-blocking does not apply.")
        if t_local % 2 != 0:
            raise HorovodError(
                f"zigzag layout needs an even local sequence length "
                f"(got {t_local}: two chunks per rank).")
        return _ring_attention_zigzag(q, k, v, positions, gsize, grank,
                                      causal, sm_scale, impl,
                                      q_segment_ids, kv_segment_ids,
                                      window)
    if impl == "auto":
        # An explicit block_k is a blockwise-tuning request; otherwise the
        # pallas kernel wins on TPU.
        if block_k is not None or _state.target_platform() != "tpu":
            impl = "blockwise"
        else:
            impl = "flash"
    if impl == "flash":
        if block_k is not None:
            raise HorovodError(
                "ring_attention block_k only applies to impl='blockwise'; "
                "the flash kernel blocks internally in VMEM. Pass "
                "impl='blockwise' to use block_k, or drop it.")
        return _ring_attention_flash(q, k, v, positions, gsize, grank,
                                     causal, sm_scale,
                                     q_segment_ids, kv_segment_ids, window)
    if impl != "blockwise":
        raise HorovodError(f"Unknown ring_attention impl {impl!r}.")
    if block_k is None:
        if t_local <= 2048:
            block_k = t_local
        else:
            # Largest divisor of t_local not exceeding 1024 (always exists:
            # 1 divides everything), so untuned calls never hit the
            # divisibility error below.
            block_k = max(d for d in range(1, min(1024, t_local) + 1)
                          if t_local % d == 0)
    block_k = min(block_k, t_local)
    if t_local % block_k != 0:
        raise HorovodError(
            f"ring_attention block_k ({block_k}) must divide the local "
            f"sequence length ({t_local}).")
    n_sub = t_local // block_k

    # (B, H, T, D) compute layout.
    qT = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.bfloat16)
    kT = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.bfloat16)
    vT = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.bfloat16)

    member = grank >= 0
    grank_c = jnp.maximum(grank, 0)
    q_off = grank_c * t_local

    m0 = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    acc0 = jnp.zeros((b, h, t_local, d), jnp.float32)

    # One compiled ring step, scanned gsize times: program size is O(1) in
    # the group size (a pod-axis SP group can be 64-256 wide — BASELINE.md's
    # v5e-256 north star — so a Python unroll is not an option), and the ring
    # uses one fixed symmetric ppermute (shift-by-1 neighbor hop on ICI).
    # jax.checkpoint makes reverse-mode recompute each step's block scores
    # from (q, k-shard) instead of storing the (B,H,T_local,block_k)
    # probability residuals — without it backward memory is the full
    # attention matrix, defeating ring attention's purpose.
    has_segs = q_segment_ids is not None
    kvseg0 = (jnp.asarray(kv_segment_ids, jnp.int32) if has_segs
              else jnp.zeros((b, 1), jnp.int32))     # placeholder carry

    @jax.checkpoint
    def step(carry, s):
        kv_k, kv_v, kvseg, m, l, acc = carry
        # At step s this rank holds the K/V shard of member (grank - s) % g.
        src = (grank_c - s) % gsize
        kv_off = src * t_local
        qseg_a = q_segment_ids if has_segs else None
        kvseg_a = kvseg if has_segs else None
        if n_sub == 1:
            m2, l2, acc2 = _block_attend(qT, kv_k, kv_v, m, l, acc,
                                         q_off, kv_off, causal, sm_scale,
                                         qseg_a, kvseg_a, window)
        else:
            # Consume the shard in sub-blocks: bounded score memory.
            def sub_step(j, mla):
                ms, ls, accs = mla
                kb = lax.dynamic_slice_in_dim(kv_k, j * block_k, block_k, 2)
                vb = lax.dynamic_slice_in_dim(kv_v, j * block_k, block_k, 2)
                sb = (lax.dynamic_slice_in_dim(kvseg_a, j * block_k,
                                               block_k, 1)
                      if has_segs else None)
                return _block_attend(qT, kb, vb, ms, ls, accs,
                                     q_off, kv_off + j * block_k,
                                     causal, sm_scale, qseg_a, sb, window)

            m2, l2, acc2 = lax.fori_loop(0, n_sub, sub_step, (m, l, acc))
        # Non-members never rotate K/V; only their s=0 (pure local
        # attention) step may contribute, or they'd re-accumulate their
        # own block every round.
        keep = member | (s == 0)
        m2 = jnp.where(keep, m2, m)
        l2 = jnp.where(keep, l2, l)
        acc2 = jnp.where(keep, acc2, acc)
        # Rotate K/V (and their segment ids) forward one hop for the next
        # step (one extra rotation on the last step is harmless: shards
        # return to their owners).
        kv_k2 = _ppermute_ring(kv_k, positions)
        kv_v2 = _ppermute_ring(kv_v, positions)
        kvseg2 = _ppermute_ring(kvseg, positions) if has_segs else kvseg
        if gsize > 1:
            # Non-members aren't in the perm: they'd receive zeros. Keep
            # their own K/V so their local attention is unaffected.
            kv_k2 = jnp.where(member, kv_k2, kv_k)
            kv_v2 = jnp.where(member, kv_v2, kv_v)
            if has_segs:
                kvseg2 = jnp.where(member, kvseg2, kvseg)
        return (kv_k2, kv_v2, kvseg2, m2, l2, acc2), None

    carry = (kT, vT, kvseg0, m0, l0, acc0)
    if gsize == 1:
        carry, _ = step(carry, 0)
    else:
        carry, _ = lax.scan(step, carry, jnp.arange(gsize))
    _, _, _, m, l, acc = carry

    out = acc / jnp.maximum(l, 1e-20)[..., None]     # (B, H, T, D) fp32
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_attention_flash(q, k, v, positions, gsize, grank, causal, sm_scale,
                          q_segment_ids=None, kv_segment_ids=None,
                          window=None):
    """Ring attention where each step is the pallas flash kernel.

    Per step the kernel returns the shard-partial output and its per-row
    log-sum-exp; partials merge exactly as a running softmax-weighted
    average (acc = Σ exp(lse_i - m)·o_i, l = Σ exp(lse_i - m)). Shards
    entirely in a row's causal future come back with lse ≈ -inf and o = 0,
    so they contribute nothing regardless of ring arrival order. Gradients
    flow through the kernel's lse-aware VJP; jax.checkpoint keeps backward
    memory at O(T_local) per step (the Ring Attention blockwise-remat
    recipe), recomputing each step's kernel forward during the replay.
    """
    from horovod_tpu.ops.flash_attention import flash_attention_lse

    b, t_local, h, d = q.shape
    member = grank >= 0
    grank_c = jnp.maximum(grank, 0)
    q_off = grank_c * t_local

    qb = q.astype(jnp.bfloat16)
    m0 = jnp.full((b, t_local, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, t_local, h), jnp.float32)
    acc0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    has_segs = q_segment_ids is not None
    kvseg0 = (jnp.asarray(kv_segment_ids, jnp.int32) if has_segs
              else jnp.zeros((b, 1), jnp.int32))     # placeholder carry

    @jax.checkpoint
    def step(carry, s):
        kv_k, kv_v, kvseg, m, l, acc = carry
        src = (grank_c - s) % gsize
        kv_off = src * t_local
        seg_kw = (dict(q_segment_ids=q_segment_ids, kv_segment_ids=kvseg)
                  if has_segs else {})
        o_s, lse_s = flash_attention_lse(qb, kv_k, kv_v, causal, sm_scale,
                                         q_off, kv_off, window=window,
                                         **seg_kw)
        m_new, l_new, acc_new = _lse_merge(m, l, acc, o_s, lse_s)
        keep = member | (s == 0)
        m2 = jnp.where(keep, m_new, m)
        l2 = jnp.where(keep, l_new, l)
        acc2 = jnp.where(keep, acc_new, acc)
        kv_k2, kv_v2, kvseg2 = _rotate_kv(kv_k, kv_v, kvseg, has_segs,
                                          member, positions, gsize)
        return (kv_k2, kv_v2, kvseg2, m2, l2, acc2), None

    carry = (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), kvseg0,
             m0, l0, acc0)
    if gsize == 1:
        carry, _ = step(carry, 0)
    else:
        carry, _ = lax.scan(step, carry, jnp.arange(gsize))
    _, _, _, m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-20)[..., None]     # (B, T, H, D) fp32
    return out.astype(q.dtype)


def zigzag_shard(x, group_size: int, axis: int = 1):
    """Shard a sequence axis in the zigzag (load-balanced causal) layout.

    The sequence splits into ``2g`` chunks; rank i holds chunks ``i`` and
    ``2g-1-i`` concatenated — one early chunk and one late chunk, so the
    causal triangle's work is the same on every rank (contiguous sharding
    gives rank 0 almost nothing to do and rank g-1 everything; the
    lockstep ring then waits on the busiest rank every step). Returns the
    rank-stacked layout (leading axis = group size). See
    ``ring_attention(layout='zigzag')``.
    """
    g = group_size
    chunks = jnp.split(jnp.asarray(x), 2 * g, axis=axis)
    rows = [jnp.concatenate([chunks[i], chunks[2 * g - 1 - i]], axis=axis)
            for i in range(g)]
    return jnp.stack(rows, axis=0)


def zigzag_unshard(stacked, axis: int = 1):
    """Inverse of :func:`zigzag_shard` (input: rank-stacked)."""
    g = stacked.shape[0]
    out = [None] * (2 * g)
    for i in range(g):
        lo, hi = jnp.split(stacked[i], 2, axis=axis)
        out[i], out[2 * g - 1 - i] = lo, hi
    return jnp.concatenate(out, axis=axis)


def zigzag_positions(group_rank, t_local: int, group_size: int):
    """Global token positions of a rank's zigzag shard, ``(t_local,)``.

    Chunk ``rank`` then chunk ``2g-1-rank`` (each ``t_local//2`` long) —
    what rotary embeddings and loss masking need in place of the
    contiguous layout's ``shard_offset + arange`` (``group_rank`` may be
    traced). Non-members (rank −1) get the rank-0 positions.
    """
    c = t_local // 2
    r = jnp.maximum(group_rank, 0)
    lo = r * c + jnp.arange(c)
    hi = (2 * group_size - 1 - r) * c + jnp.arange(c)
    return jnp.concatenate([lo, hi])


def _ring_attention_zigzag(q, k, v, positions, gsize, grank, causal,
                           sm_scale, impl, q_segment_ids=None,
                           kv_segment_ids=None, window=None):
    """Ring attention over zigzag-sharded sequences (Striped/zigzag
    load balancing for the causal mask).

    The local shard is two contiguous chunks at non-adjacent global
    positions, so each ring step processes the four (q-chunk, kv-chunk)
    pairs — each on a contiguous position range — and merges them into
    the running softmax. Per-pair causal skipping plus the balanced
    layout makes every rank's per-step work equal, removing the
    contiguous layout's straggler (rank g-1 owns the whole causal
    triangle's densest rows while rank 0 idles). ``impl='flash'`` runs
    each pair through the pallas kernel and merges by log-sum-exp;
    ``'blockwise'`` (the non-TPU path) accumulates each pair with the
    pure-JAX online-softmax update.
    """
    from horovod_tpu.ops.flash_attention import flash_attention_lse

    b, t_local, h, d = q.shape
    c = t_local // 2
    member = grank >= 0
    grank_c = jnp.maximum(grank, 0)
    use_flash = impl == "flash"
    # Global start positions of this rank's two chunks.
    q_offs = (grank_c * c, (2 * gsize - 1 - grank_c) * c)

    qb = q.astype(jnp.bfloat16)
    if use_flash:
        q_chunks = (qb[:, :c], qb[:, c:])                 # (B, c, H, D)
    else:
        qT = jnp.transpose(qb, (0, 2, 1, 3))              # (B, H, T, D)
        q_chunks = (qT[:, :, :c], qT[:, :, c:])
    has_segs = q_segment_ids is not None
    qseg_chunks = ((q_segment_ids[:, :c], q_segment_ids[:, c:])
                   if has_segs else (None, None))
    kvseg0 = (jnp.asarray(kv_segment_ids, jnp.int32) if has_segs
              else jnp.zeros((b, 1), jnp.int32))     # placeholder carry

    def fresh():
        rows = (b, c, h) if use_flash else (b, h, c)
        return (jnp.full(rows, _NEG_INF, jnp.float32),
                jnp.zeros(rows, jnp.float32),
                jnp.zeros(rows + (d,), jnp.float32))

    @jax.checkpoint
    def step(carry, s):
        kv_k, kv_v, kvseg, accs = carry
        src = (grank_c - s) % gsize
        kv_offs = (src * c, (2 * gsize - 1 - src) * c)
        kv_chunks = ((kv_k[:, :c], kv_v[:, :c]),
                     (kv_k[:, c:], kv_v[:, c:]))
        kvseg_chunks = ((kvseg[:, :c], kvseg[:, c:]) if has_segs
                        else (None, None))
        keep = member | (s == 0)
        new_accs = []
        for qi in range(2):
            m, l, acc = accs[qi]
            for ki in range(2):
                kc, vc = kv_chunks[ki]
                if use_flash:
                    seg_kw = (dict(q_segment_ids=qseg_chunks[qi],
                                   kv_segment_ids=kvseg_chunks[ki])
                              if has_segs else {})
                    o_s, lse_s = flash_attention_lse(
                        q_chunks[qi], kc, vc, causal, sm_scale,
                        q_offs[qi], kv_offs[ki], window=window, **seg_kw)
                    m_n, l_n, acc_n = _lse_merge(m, l, acc, o_s, lse_s)
                else:
                    kT = jnp.transpose(kc, (0, 2, 1, 3))
                    vT = jnp.transpose(vc, (0, 2, 1, 3))
                    m_n, l_n, acc_n = _block_attend(
                        q_chunks[qi], kT, vT, m, l, acc,
                        q_offs[qi], kv_offs[ki], causal, sm_scale,
                        qseg_chunks[qi], kvseg_chunks[ki], window)
                m = jnp.where(keep, m_n, m)
                l = jnp.where(keep, l_n, l)
                acc = jnp.where(keep, acc_n, acc)
            new_accs.append((m, l, acc))
        kv_k2, kv_v2, kvseg2 = _rotate_kv(kv_k, kv_v, kvseg, has_segs,
                                          member, positions, gsize)
        return (kv_k2, kv_v2, kvseg2, tuple(new_accs)), None

    carry = (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), kvseg0,
             (fresh(), fresh()))
    if gsize == 1:
        carry, _ = step(carry, 0)
    else:
        carry, _ = lax.scan(step, carry, jnp.arange(gsize))
    _, _, _, accs = carry
    outs = []
    for _m, l, acc in accs:
        out_c = acc / jnp.maximum(l, 1e-20)[..., None]
        if not use_flash:
            out_c = jnp.transpose(out_c, (0, 2, 1, 3))    # back to (B,c,H,D)
        outs.append(out_c)
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def ulysses_attention(q, k, v, group: int = 0, causal: bool = True,
                      sm_scale: float | None = None,
                      attn_fn=None, q_segment_ids=None,
                      kv_segment_ids=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses layout swap).

    Input: local sequence shard ``(B, T_local, H, D)`` with H divisible by
    the group size. ``hvd.alltoall`` swaps sharding seq→heads so each rank
    holds the FULL sequence for ``H/g`` heads, runs ordinary (or custom via
    ``attn_fn(q, k, v)``) attention, and swaps back. Two all-to-alls of the
    activations per call; attention math is entirely local — the better
    trade when heads are plentiful and T_local is moderate.

    ``q_segment_ids``/``kv_segment_ids``: optional (B, T_local) int32
    packed-sequence ids for the LOCAL shard; they are allgathered to the
    full sequence (tiny int arrays) for the local attention. Ignored when
    ``attn_fn`` is given (pass your own masking inside it).

    ``group`` may be a *family* (tuple of equal-size groups covering the
    mesh, like :func:`ring_attention`'s): every group runs its own
    sequence↔heads exchange in ONE XLA AllToAll — the DP×SP composition
    for the Ulysses layout (each data-parallel replica swaps within its
    own group).
    """
    tctx = _require_traced("ulysses_attention")
    _, gsize, grank = _group_ring(tctx, group)
    from horovod_tpu.ops import collectives as _coll

    b, t_local, h, d = q.shape
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise HorovodError(
            "ulysses_attention needs q_segment_ids and kv_segment_ids "
            "together.")
    if k.shape[2] != h:
        raise HorovodError(
            f"ulysses_attention needs equal q/kv head counts (got {h} vs "
            f"{k.shape[2]}): the all-to-all swaps the head axis against "
            f"the sequence axis. Expand GQA KV heads first (jnp.repeat), "
            f"or use ring_attention, which carries Hkv heads natively.")
    if h % gsize != 0:
        raise HorovodError(
            f"ulysses_attention needs heads ({h}) divisible by the group "
            f"size ({gsize}).")

    def seq_to_heads(x):
        # (B, T, H, D) -> all-to-all so heads are sharded, sequence whole.
        # Layout for alltoall: dim 0 must be the exchanged axis.
        xs = jnp.transpose(x, (2, 1, 0, 3))            # (H, T, B, D)
        xs = _coll.alltoall(xs, group=group)            # heads swap shards
        # Received g blocks of H/g heads, each for a different seq shard:
        hs = h // gsize
        xs = xs.reshape((gsize, hs, t_local, b, d))     # (g, H/g, T, B, D)
        xs = jnp.transpose(xs, (3, 0, 2, 1, 4))         # (B, g, T, H/g, D)
        return xs.reshape((b, gsize * t_local, hs, d))  # full seq, H/g heads

    def heads_to_seq(x):
        hs = h // gsize
        xs = x.reshape((b, gsize, t_local, hs, d))
        xs = jnp.transpose(xs, (1, 3, 2, 0, 4))         # (g, H/g, T, B, D)
        xs = xs.reshape((h, t_local, b, d))
        xs = _coll.alltoall(xs, group=group)
        return jnp.transpose(xs, (2, 1, 0, 3))          # (B, T, H, D)

    def full_segs(segs):
        # (B, T_local) -> (B, T): allgather concatenates dim 0, so swap
        # the sequence axis in and back out. Tiny int arrays.
        s = jnp.transpose(segs, (1, 0))
        s = _coll.allgather(s, group=group)
        return jnp.transpose(s, (1, 0))

    # Static membership: a family that covers the program's mesh (the
    # DP×SP composition) has no non-members, so the local-attention
    # fallback below would be dead compute XLA still executes into a
    # select — skip building it.
    program_size = _state.get_group(tctx.group_index).size
    if isinstance(group, (tuple, list)):
        members = sum(_state.get_group(g).size for g in group)
    else:
        members = gsize
    full_cover = (members == program_size) or group == tctx.group_index

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if attn_fn is None:
        seg_kw = {}
        if q_segment_ids is not None:
            qs_full = full_segs(q_segment_ids)
            # Self-attention passes one id array for both sides: gather it
            # once (half the registered collectives per packed layer).
            kvs_full = (qs_full if kv_segment_ids is q_segment_ids
                        else full_segs(kv_segment_ids))
            seg_kw = dict(q_segment_ids=qs_full, kv_segment_ids=kvs_full)
        attn_out = local_attention(qf, kf, vf, causal=causal,
                                   sm_scale=sm_scale, **seg_kw)
    else:
        attn_out = attn_fn(qf, kf, vf)
    out = heads_to_seq(attn_out)
    if not full_cover:
        # Non-members of a subset group: the layout swap was identity for
        # them, so `out` is meaningless — give them plain local attention
        # over their own shard (the non-participant convention).
        nm_kw = {}
        if q_segment_ids is not None:
            nm_kw = dict(q_segment_ids=q_segment_ids,
                         kv_segment_ids=kv_segment_ids)
        out = jnp.where(grank >= 0, out,
                        local_attention(q, k, v, causal=causal,
                                        sm_scale=sm_scale, **nm_kw))
    return out


def local_attention_impl(t: int) -> str:
    """What :func:`local_attention`'s ``impl='auto'`` means for ``t``
    tokens on the devices ``hvd.init`` was given."""
    if t <= 2048:
        return "xla"
    return "flash" if _state.target_platform() == "tpu" else "blockwise"


def local_attention(q, k, v, causal: bool = True,
                    sm_scale: float | None = None, impl: str = "auto",
                    q_segment_ids=None, kv_segment_ids=None,
                    window: int | None = None):
    """Single-device attention, (B, T, H, D) layout; GQA (``k``/``v`` with
    fewer heads) and packed-sequence segment masking supported on every
    impl.

    ``impl``:
    * ``'xla'`` — materialize the (T, T) scores; fastest for short T.
    * ``'flash'`` — the pallas kernel (ops/flash_attention.py); O(block)
      memory, fused FlashAttention-2 backward kernel.
    * ``'blockwise'`` — the lax.scan online softmax; O(block) memory on any
      backend.
    * ``'auto'`` — 'xla' for T ≤ 2048, else 'flash' on TPU / 'blockwise'
      elsewhere (the pallas interpreter is too slow for real sizes).
    """
    b, t, h, d = q.shape
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise HorovodError(
            "local_attention needs q_segment_ids and kv_segment_ids "
            "together.")
    from horovod_tpu.ops import flash_attention as _fa

    # One behavior for `window` on every impl: causal-only, >= 1 (the same
    # check the flash kernel applies — so 'xla'/'blockwise' can't silently
    # accept argument combinations 'flash' rejects).
    _fa._check_window(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if impl == "auto":
        impl = local_attention_impl(t)

    if impl == "flash":
        return _fa.flash_attention(q, k, v, causal, sm_scale,
                                   q_segment_ids=q_segment_ids,
                                   kv_segment_ids=kv_segment_ids,
                                   window=window)
    if impl == "blockwise":
        return _fa.blockwise_attention(q, k, v, causal=causal,
                                       sm_scale=sm_scale,
                                       q_segment_ids=q_segment_ids,
                                       kv_segment_ids=kv_segment_ids,
                                       window=window)
    if impl != "xla":
        raise HorovodError(f"Unknown attention impl {impl!r}.")
    if k.shape[2] != h:
        reps = h // k.shape[2]
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.bfloat16),
                   k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if q_segment_ids is not None:
        seg_ok = (q_segment_ids[:, None, :, None]
                  == kv_segment_ids[:, None, None, :])
        s = jnp.where(seg_ok, s, _NEG_INF)
    if window is not None:
        pos = jnp.arange(t)
        in_window = pos[None, :] > pos[:, None] - window
        s = jnp.where(in_window[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
