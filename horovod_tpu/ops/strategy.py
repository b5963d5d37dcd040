"""Allreduce decomposition strategies: how a fusion bucket becomes wire ops.

The pre-strategy gradient path lowered every bucket to ONE flat full-axis
``psum`` — the same program shape for 8 chips on one ICI slice and 256
chips across DCN-connected slices. This module makes the decomposition a
per-bucket decision among three lowerings. All compute the same group sum
and keep replicas exactly in lockstep; like any change of collective
implementation, a decomposition may re-associate the floating-point
reduction, so cross-algorithm results can differ in the last ulp on data
where addition order matters (bit-exact on integer-valued data — the
tests/test_strategy.py contract):

``flat``
    The plain sum. ``lax.psum``, one XLA AllReduce, for small leaves (one
    α) and the only lowering for subset groups (whose masked-psum scheme,
    ops/collectives.py ``_traced_groups_arg``, has no uniform partition for
    the phased variants to ride). A LARGE leaf summed over the whole axis
    of 2 to 8 ranks in its own dtype goes round a ring of
    ``lax.ppermute`` instead (:func:`_ring_allreduce`): this TPU compiler
    runs every all-reduce, reduce-scatter and all-gather synchronously,
    alone on the core's timeline, and issues a collective-permute
    asynchronously, with the backward's fusions between its start and its
    done — where it finds any: left to itself it packs every ring behind
    the backward's end, so the rings of one exchange are chained, one on
    the links at a time, the last leaf's first
    (:func:`one_ring_at_a_time`). Which leaf is decided by what the
    lowering sees of it (:func:`ring_eligible`), never by an option.

``rs_ag``
    ``lax.psum_scatter`` + ``lax.all_gather`` (tiled) — the two halves of a
    ring allreduce as separate XLA ops. Same bytes on the wire, one extra
    α; in exchange XLA's latency-hiding scheduler can interleave bucket
    *i*'s all-gather with neighbouring buckets' compute, and the full-size
    fused buffer is live for one phase instead of two (each phase's working
    set is the 1/n shard). Buckets whose element count is not divisible by
    the group size are padded with explicit zeros and sliced back — never
    silently truncated.

``hierarchical``
    The classic two-level scheme for multi-slice jobs: intra-slice
    reduce-scatter over ICI → cross-slice allreduce over DCN on the
    1/local_size shard → intra-slice all-gather over ICI. DCN, the
    bottleneck link, carries ``2(M-1)/M · S/L`` bytes instead of
    ``2(n-1)/n · S`` — the busbw factor the MLPerf pod submissions
    (arXiv:1909.09756) are built on. Requires a multi-slice topology with
    equal slice sizes (XLA replica_groups must be uniform); refused
    otherwise.

Selection: explicit ``algo="flat"|"rs_ag"|"hierarchical"`` (infeasible
choices raise), or ``"auto"`` — the α–β cost model (utils/costs.py, seeded
analytically, refreshed by ``tools/allreduce_bench.py --calibrate``) picks
per bucket from its wire bytes and the discovered topology
(ops/topology.py). Wire compression composes: the caller quantizes ONCE,
every phase moves the wire dtype, dequantize happens once at the end
(ops/collectives.py ``_compressed_psum``).

Each phase is visible as a ``REDUCE_SCATTER`` / ``CROSS_SLICE`` /
``ALL_GATHER`` named scope in the HLO and stamped on the collective's
timeline row (trace-time host stamps, the QUANTIZE precedent —
device-fidelity mode recovers the real spans from the xplane).

**Multi-channel lowerings** (``channels=C > 1``): the bucket is split
into ``C`` shards, each lowered as an INDEPENDENT channel instance of
the same decomposition — C concurrent collectives instead of one
serialized one, so XLA's latency-hiding scheduler can run shard k+1's
intra-slice reduce-scatter while shard k's cross-slice DCN hop is in
flight (arXiv:2508.13397's concurrent-stream decomposition; the
multi-ring pod allreduce of arXiv:1909.09756). The split is
numerics-invisible by construction: channelization happens strictly
BELOW quantization — compression compresses the whole bucket exactly as
the single-channel path does (same block grid, same scales, same
stochastic-rounding keys) and only the already-quantized wire is split
across channel instances; phased lowerings split shard-major (each
rank's reassembled shard is the same element run the single-channel
lowering produces), with the same explicit zero padding. Channelized
results are therefore bit-exact vs ``channels=1`` for every
algorithm × wire format, including non-divisible bucket sizes
(tests/test_channels.py pins the full matrix). Each channel instance is
wrapped in a ``CH<c>`` named scope (inside it, the usual phase scopes).
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp
from jax import lax

from horovod_tpu.core.state import AXIS_NAME, HorovodError
from horovod_tpu.ops import topology as _topology
from horovod_tpu.utils import costs as _costs
from horovod_tpu.utils import env as _env

ALGORITHMS = _costs.ALGORITHMS  # ("flat", "rs_ag", "hierarchical")


def resolve_spec(spec) -> str:
    """Normalize an ``algo=`` argument: ``None`` → ``"flat"`` (the exact
    pre-strategy lowering; the GRADIENT path resolves None against
    ``HOROVOD_ALLREDUCE_ALGO`` before it gets here — parallel/optimizer.py
    — so raw value collectives never change shape behind the user's
    back); strings are validated."""
    if spec is None:
        return "flat"
    if not isinstance(spec, str):
        raise HorovodError(
            f"algo= must be None or a string, got {type(spec).__name__}.")
    value = spec.strip().lower()
    if value not in (*ALGORITHMS, "auto"):
        raise HorovodError(
            f"Unknown allreduce algorithm {spec!r}; choose one of "
            f"{list(ALGORITHMS)} or 'auto' "
            f"(HOROVOD_ALLREDUCE_ALGO / algo=).")
    return value


def resolve_channels(spec) -> int:
    """Normalize a ``channels=`` argument: ``None`` → 1 (the exact
    single-channel lowering — the GRADIENT path resolves None against
    ``HOROVOD_EXCHANGE_CHANNELS`` / the planner before it gets here,
    ops/exchange.py); integers are validated."""
    if spec is None:
        return 1
    if isinstance(spec, bool) or not isinstance(spec, int):
        raise HorovodError(
            f"channels= must be None or a positive integer, got "
            f"{spec!r}.")
    if spec < 1:
        raise HorovodError(
            f"channels= must be >= 1 (1 = the single-channel lowering), "
            f"got {spec}.")
    return int(spec)


def select(spec: str, *, nbytes: int, group, restricted: bool = False,
           name: str = "", topo: "_topology.Topology | None" = None,
           phase_nbytes: tuple[int, int] | None = None,
           gather: bool = False
           ) -> tuple[str, "_topology.Topology | None"]:
    """Concrete algorithm for one collective: resolves ``auto`` through
    the cost model and enforces feasibility.

    ``restricted``: the collective cannot take a phased lowering — subset
    groups (masked full-axis psum has no uniform partition) and group
    families (their slot-stacked lowering is its own scheme). Explicit
    ``rs_ag``/``hierarchical`` then raise; ``auto`` falls back to
    ``flat``. ``topo``: pass an already-discovered topology to skip
    re-discovery (the per-bucket gradient path discovers once per trace).
    ``phase_nbytes``/``gather``: the phase-asymmetric compression view of
    the bucket for ``auto`` pricing (utils/costs.py
    :meth:`~horovod_tpu.utils.costs.CostModel.choose`). Returns
    ``(algo, topology)`` — topology is None when it was not needed (flat
    and rs_ag need only the group size, which the lowering takes from the
    collective's own ``gsize``)."""
    if restricted:
        if spec in ("rs_ag", "hierarchical"):
            raise HorovodError(
                f"allreduce algo={spec!r} (tensor {name}) requires a "
                f"full-axis single group: subset groups and group "
                f"families only support the flat masked-psum lowering. "
                f"Use algo='flat'/'auto' or reduce on the full group.")
        return "flat", None
    if spec == "flat":
        return "flat", None
    if spec == "rs_ag":
        return "rs_ag", topo
    if topo is None:
        topo = _topology.discover(group)
    if spec == "auto":
        if topo.group_size <= 1:
            return "flat", topo
        model = _costs.model_for(topo)
        return model.choose(nbytes, topo, phase_nbytes=phase_nbytes,
                            gather=gather), topo
    if spec == "hierarchical":
        if not topo.multi_slice:
            raise HorovodError(
                f"allreduce algo='hierarchical' (tensor {name}) needs a "
                f"multi-slice topology; this group's {topo.group_size} "
                f"rank(s) live on one slice. Use 'flat'/'rs_ag'/'auto', "
                f"or HOROVOD_TOPOLOGY_SLICES=N to simulate slices in "
                f"tests.")
        if topo.local_size is None or topo.local_size < 2:
            raise HorovodError(
                f"allreduce algo='hierarchical' (tensor {name}) needs "
                f"equal-sized slices with >=2 ranks each (XLA "
                f"replica_groups must be uniform); got per-slice sizes "
                f"{[len(m) for m in topo.slice_members()]}.")
    return spec, topo


# ---------------------------------------------------------------------------
# Lowerings (traced, full-axis group). Input: any-shape array already
# member-masked/quantized by the caller; output: the exact group sum,
# same shape and dtype.
# ---------------------------------------------------------------------------


def _phase(tl, name: str, activity: str):
    """Trace-time timeline stamp + HLO named scope for one phase."""
    import jax

    if tl.active:
        tl.start_activity(name, activity)
    return jax.named_scope(activity)


def _end(tl, name: str, activity: str) -> None:
    if tl.active:
        tl.end_activity(name, activity)


def _ch_scope(c: int):
    """HLO named scope labelling one channel instance's wire ops."""
    import jax

    return jax.named_scope(f"CH{c}")


def _channel_sizes(total: int, channels: int) -> list[int]:
    """Near-equal contiguous split of ``total`` units over ``channels``
    (leading channels take the remainder; zero-size tails are dropped, so
    a channel count above the unit count degrades to one unit per
    channel). The split is a pure function of (total, channels) — every
    rank derives the identical partition, the HVD103 requirement."""
    channels = max(1, int(channels))
    base, rem = divmod(total, channels)
    return [base + (1 if c < rem else 0)
            for c in range(channels) if base or c < rem]


def lower_allreduce(x, algo: str, name: str,
                    topo: "_topology.Topology | None", gsize: int,
                    channels: int = 1, compressed: bool = False):
    """Emit ``algo``'s wire ops for a full-axis-group sum of ``x``.
    ``gsize`` is the group size (rs_ag needs nothing else — it may run
    with ``topo=None``); hierarchical needs the discovered topology.
    ``channels``: concurrent channel instances (module docstring);
    1 = the exact classic lowering. ``flat`` with one channel is the
    plain sum (:func:`_plain_sum`): ``lax.psum`` of ``x`` — one array or a
    bucket's tuple of leaves — but for the large leaves of a whole-axis
    group, which go round a ring of collective-permutes, one ring after
    another where the caller chains them (:func:`one_ring_at_a_time`);
    ``compressed`` says ``x`` is a quantized wire and not the leaves
    themselves, and keeps the ``psum``."""
    if gsize <= 1:
        return lax.psum(x, AXIS_NAME) if algo == "flat" else x
    if algo == "flat":
        if channels <= 1:
            return _plain_sum(x, gsize, compressed)
        return _flat_channels(x, name, channels)
    if algo == "rs_ag":
        return _rs_ag(x, gsize, name, channels)
    if algo == "hierarchical":
        assert topo is not None, "hierarchical needs a discovered topology"
        return _hierarchical(x, topo, name, channels)
    raise HorovodError(f"unknown allreduce algorithm {algo!r}")


# ---------------------------------------------------------------------------
# The plain sum: ``flat``, one channel, the leaves' own dtype on the wire.
# ---------------------------------------------------------------------------

# The least slab (a leaf's bytes over the group's size) that goes round the
# ring. A round costs the two chips' handshake whatever it carries, and
# short rounds find no cover in the chain (:func:`one_ring_at_a_time`): on
# four v5e chips, with starcoder2_3b's 37.7 MB leaves (slabs of 9.4 MB) in
# the chain, the permutes waited 11.3 ms a step (3.6 on those leaves' own
# rounds, 7.0 on the 151 MB leaves' behind them) and the all-reduces took
# 0.6; with them left as all-reduces, 1.9 and 8.3, and half the
# instructions (PERF.md section 6, PR 30).
RING_MIN_SLAB_BYTES = 16 << 20
RING_MAX_RANKS = 8  # 2(n-1) rounds a leaf: past one host the rounds are too many


def ring_eligible(v, n: int) -> bool:
    """Whether the plain sum of leaf ``v`` over a group of ``n`` ranks is
    lowered as :func:`_ring_allreduce`: the group is the whole axis, the
    leading dimension cuts into two halves of ``n`` slabs each, a slab of
    a matrix keeps the tiled layout's sublanes whole (8 rows of 32 bits),
    and a slab is worth its rounds (:data:`RING_MIN_SLAB_BYTES`). Asked
    by the lowering (:func:`_plain_sum`) and by the order the exchange
    traces its buckets in (ops/fusion.py ``trace_order``)."""
    if not 2 <= n <= RING_MAX_RANKS or v.ndim < 2:
        return False
    try:
        if n != lax.axis_size(AXIS_NAME):
            return False
    except NameError:  # no axis bound: not inside a compiled step
        return False
    slabs, ragged = divmod(v.shape[0], 2 * n)
    if ragged or (v.ndim == 2 and slabs % (8 * max(1, 4 // v.dtype.itemsize))):
        return False
    return v.size // n * v.dtype.itemsize >= RING_MIN_SLAB_BYTES


# The open chain (:func:`one_ring_at_a_time`): ``None`` outside one, else
# the last slabs the newest ring received, which the next ring waits for.
_chain: list | None = None


@contextlib.contextmanager
def one_ring_at_a_time():
    """Inside, every :func:`_ring_allreduce` starts only when the one
    traced before it has received its last slabs: ONE ring on the links at
    a time, in the order of tracing — which the caller makes the order the
    gradients come to exist in (ops/fusion.py ``trace_order``).

    Why: XLA's latency-hiding scheduler lays a program out from its end
    and gives every collective-permute enough compute to cover what ONE
    permute takes alone. Independent rings are each other's cover in its
    eyes, so it packs all of them behind the backward's last
    fusion, where they share two links and wait for one another (PERF.md
    section 6, PR 30). A chain it cannot pack: each ring's cover has to be
    found further up the backward, and one ring at a time is the load its
    estimate is right for. The dependency is an ``optimization_barrier``
    on the next leaf and the last ring's slabs: nothing moves for it."""
    global _chain
    outer, _chain = _chain, []
    try:
        yield
    finally:
        _chain = outer


def _ring_order(devices) -> list[int]:
    """The ranks in the order of a cycle whose neighbours are one ICI hop
    apart where the devices say where they lie — a 2 x k slice: up one
    column, down the other; rank order would cross a 2 x 2 slice's
    diagonal twice — else rank order."""
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return list(range(len(devices)))
    a, b = (0, 1) if len({c[0] for c in coords}) <= 2 else (1, 0)
    return sorted(range(len(devices)), key=lambda r: (
        coords[r][a], -coords[r][b] if coords[r][a] % 2 else coords[r][b]))


def _plain_sum(x, n: int, compressed: bool):
    """``lax.psum`` of one array or of a bucket's tuple of leaves, but for
    the leaves :func:`ring_eligible` picks when ``x`` is the leaves
    themselves (not a quantized wire): those go through
    :func:`_ring_allreduce`, the last first, each after the ring before
    it where a chain is open (:func:`one_ring_at_a_time`). The rest stay
    ONE ``psum`` of a tuple, as before; with no leaf picked the text is
    today's."""
    import jax

    from horovod_tpu.core import context as _ctx
    from horovod_tpu.core import state as _state
    from horovod_tpu.core import timeline as _tl

    leaves, treedef = jax.tree.flatten(x)
    ring = [not compressed and ring_eligible(v, n) for v in leaves]
    _tl.session().count_plan(
        "exchange.async_bytes",
        sum(v.size * v.dtype.itemsize for v, r in zip(leaves, ring) if r))
    if not any(ring):
        return lax.psum(x, AXIS_NAME)
    order = _ring_order(
        _state.get_group(_ctx.current().group_index).devices)
    rest = iter(lax.psum(tuple(v for v, r in zip(leaves, ring) if not r),
                         AXIS_NAME))
    out = [None if r else next(rest) for r in ring]
    for i in reversed([i for i, r in enumerate(ring) if r]):
        v = leaves[i]
        if _chain:  # not before the ring before has its last slabs
            v, _ = lax.optimization_barrier((v, tuple(_chain)))
        out[i], last = _ring_allreduce(v, order)
        if _chain is not None:
            _chain[:] = last
    return jax.tree.unflatten(treedef, out)


def _ring_allreduce(x, order: list[int]):
    """The sum of ``x`` over the whole axis as a ring reduce-scatter then a
    ring all-gather made of ``lax.ppermute``: the one collective this TPU
    compiler issues asynchronously (``collective-permute-start`` /
    ``-done``, the backward's fusions between them), where an
    ``all-reduce``, a ``reduce-scatter`` and an ``all-gather`` hold the
    core for their whole length.

    ``x`` is cut along axis 0 into two halves of ``n`` slabs; one half
    goes round the ring ``order`` one way, the other the other way (a
    chip has two neighbours on it; one stream alone moves 41 GB/s, the two
    68). Each slab is summed on one chip after another in one fixed order
    of ranks and then handed round as it is, so every rank receives the
    same bits — never "rotate and accumulate", which lets replicas drift.
    The order of a leaf's float sum differs from XLA's all-reduce.

    The slab is an index on an axis of its own (``x`` viewed as
    ``[2, n, rows, ...]``): a dynamic offset inside the tiled dimension
    reads as unaligned to the compiler and the sums run at a quarter of
    the memory's rate. The gathered slabs land in ``x``'s own buffer
    (in-place ``dynamic_update_slice``); the barrier orders every read of
    ``x`` before the first of them, or XLA copies the leaf.

    Returns the sum and the last slab each half received, for the next
    ring of a chain to wait on (:func:`one_ring_at_a_time`)."""
    import jax.numpy as jnp

    n = len(order)
    rank = lax.axis_index(AXIS_NAME)
    ring_pos = [0] * n
    for p, r in enumerate(order):
        ring_pos[r] = p
    pos = rank if order == sorted(order) else jnp.asarray(
        ring_pos, jnp.int32)[rank]
    rows = x.shape[0] // (2 * n)
    xr = x.reshape((2, n, rows) + x.shape[1:])
    zero = jnp.int32(0)

    def at(lane, slab):  # indices of one slab of one half
        return (zero + lane, slab % n) + (zero,) * x.ndim

    steps = (1, -1)  # lane 0 goes up the ring, lane 1 down
    perms = [[(order[p], order[(p + step) % n]) for p in range(n)]
             for step in steps]
    sums = []
    for lane, step in enumerate(steps):
        carry = None
        for k in range(n):
            mine = lax.dynamic_slice(
                xr, at(lane, pos - step * k), (1, 1) + xr.shape[2:])
            carry = mine if carry is None else lax.ppermute(
                carry, AXIS_NAME, perms[lane]) + mine
        sums.append(carry)
    out, last = xr, []
    for lane, carry in enumerate(lax.optimization_barrier(sums)):
        for k in range(n):  # the slab summed here, then its n - 1 peers'
            if k:
                carry = lax.ppermute(carry, AXIS_NAME, perms[lane])
            out = lax.dynamic_update_slice(
                out, carry, at(lane, pos - steps[lane] * (n - 1 + k)))
        last.append(carry)
    return lax.optimization_barrier(out.reshape(x.shape)), last


def _flat_channels(x, name: str, channels: int):
    """Channelized flat: C concurrent full-axis psums over contiguous
    chunks. psum is elementwise over the same rank set, so any split is
    exactly the single-channel sum."""
    flat = x.reshape(-1)
    parts, o = [], 0
    for c, q in enumerate(_channel_sizes(flat.shape[0], channels)):
        with _ch_scope(c):
            parts.append(lax.psum(flat[o:o + q], AXIS_NAME))
        o += q
    if len(parts) == 1:
        return parts[0].reshape(x.shape)
    return jnp.concatenate(parts).reshape(x.shape)


def _flatten_pad(x, multiple: int):
    """(flat_padded, orig_size) — explicit zero pad to a multiple, so the
    scatter phase always divides evenly (never silent truncation)."""
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % multiple
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, size


def _shard_parts(flat, n: int, sizes):
    """Per-channel flattened column blocks of ``flat`` viewed as
    ``(n, per)``: channel c carries every rank's shard slice
    ``[o_c, o_c + q_c)`` — the shard-major split, chosen so the
    concatenation of a rank's per-channel shards IS the single-channel
    lowering's shard, element for element (what keeps the mid-pipeline
    quantization of the phase-asymmetric path bit-identical)."""
    per = flat.shape[0] // n
    cols = flat.reshape(n, per)
    parts, o = [], 0
    for q in sizes:
        parts.append(cols[:, o:o + q].reshape(-1))
        o += q
    return parts


def _merge_gathered(parts, n: int, sizes):
    """Reassemble per-channel all-gather results (channel c: ``(n*q_c,)``)
    into the flat single-channel order."""
    cols = [p.reshape(n, q) for p, q in zip(parts, sizes)]
    merged = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return merged.reshape(-1)


def _rs_ag(x, n: int, name: str, channels: int = 1):
    from horovod_tpu.core import timeline as _tl

    tl = _tl.session()
    flat, size = _flatten_pad(x, n)
    if channels <= 1:
        with _phase(tl, name, "REDUCE_SCATTER"):
            shard = lax.psum_scatter(flat, AXIS_NAME, scatter_dimension=0,
                                     tiled=True)
        _end(tl, name, "REDUCE_SCATTER")
        with _phase(tl, name, "ALL_GATHER"):
            full = lax.all_gather(shard, AXIS_NAME, tiled=True)
        _end(tl, name, "ALL_GATHER")
        return full[:size].reshape(x.shape)
    sizes = _channel_sizes(flat.shape[0] // n, channels)
    outs = []
    for c, part in enumerate(_shard_parts(flat, n, sizes)):
        with _ch_scope(c):
            with _phase(tl, name, "REDUCE_SCATTER"):
                shard = lax.psum_scatter(part, AXIS_NAME,
                                         scatter_dimension=0, tiled=True)
            _end(tl, name, "REDUCE_SCATTER")
            with _phase(tl, name, "ALL_GATHER"):
                outs.append(lax.all_gather(shard, AXIS_NAME, tiled=True))
            _end(tl, name, "ALL_GATHER")
    return _merge_gathered(outs, n, sizes)[:size].reshape(x.shape)


def _two_level_groups(topo: "_topology.Topology"):
    """(intra, cross) axis_index_groups for the two-level scheme — both
    uniform covering partitions of the full axis, so they lower on TPU
    (unlike subset replica_groups, ops/collectives.py)."""
    intra = topo.slice_members()
    L = topo.local_size
    cross = [[intra[s][j] for s in range(topo.num_slices)]
             for j in range(L)]
    return intra, cross


def _hierarchical(x, topo: "_topology.Topology", name: str,
                  channels: int = 1):
    from horovod_tpu.core import timeline as _tl

    tl = _tl.session()
    intra, cross = _two_level_groups(topo)
    L = topo.local_size
    flat, size = _flatten_pad(x, L)
    if channels <= 1:
        with _phase(tl, name, "REDUCE_SCATTER"):
            shard = lax.psum_scatter(flat, AXIS_NAME, scatter_dimension=0,
                                     axis_index_groups=intra, tiled=True)
        _end(tl, name, "REDUCE_SCATTER")
        with _phase(tl, name, "CROSS_SLICE"):
            shard = lax.psum(shard, AXIS_NAME, axis_index_groups=cross)
        _end(tl, name, "CROSS_SLICE")
        with _phase(tl, name, "ALL_GATHER"):
            full = lax.all_gather(shard, AXIS_NAME,
                                  axis_index_groups=intra, tiled=True)
        _end(tl, name, "ALL_GATHER")
        return full[:size].reshape(x.shape)
    # Channelized: each shard-major channel runs the full RS -> AR -> AG
    # chain independently, so shard k+1's ICI phases can overlap shard
    # k's DCN hop in the compiled schedule.
    sizes = _channel_sizes(flat.shape[0] // L, channels)
    outs = []
    for c, part in enumerate(_shard_parts(flat, L, sizes)):
        with _ch_scope(c):
            with _phase(tl, name, "REDUCE_SCATTER"):
                shard = lax.psum_scatter(part, AXIS_NAME,
                                         scatter_dimension=0,
                                         axis_index_groups=intra,
                                         tiled=True)
            _end(tl, name, "REDUCE_SCATTER")
            with _phase(tl, name, "CROSS_SLICE"):
                shard = lax.psum(shard, AXIS_NAME,
                                 axis_index_groups=cross)
            _end(tl, name, "CROSS_SLICE")
            with _phase(tl, name, "ALL_GATHER"):
                outs.append(lax.all_gather(shard, AXIS_NAME,
                                           axis_index_groups=intra,
                                           tiled=True))
            _end(tl, name, "ALL_GATHER")
    return _merge_gathered(outs, L, sizes)[:size].reshape(x.shape)


def gradient_algo_default() -> str:
    """The gradient path's ``algo=None`` resolution:
    ``HOROVOD_ALLREDUCE_ALGO`` (utils/env.py; typos raise there)."""
    return _env.allreduce_algo_default()


# ---------------------------------------------------------------------------
# Compressed lowerings beyond compress-once/psum/decompress: the
# phase-asymmetric hierarchical path (per-phase wire formats) and the
# gather-based exchanges for unsummable wire formats (int4). Called from
# ops/collectives.py ``_compressed_psum``; full-axis single groups only
# (the same restriction as every phased decomposition).
# ---------------------------------------------------------------------------


def _quantize_scoped(tl, name, comp, value, wctx):
    """compress under the QUANTIZE timeline stamp + HLO named scope (the
    _compressed_psum convention — the per-block scale exchange rides
    inside this scope)."""
    import jax

    if tl.active:
        tl.start_activity(name, "QUANTIZE")
    with jax.named_scope("QUANTIZE"):
        wire, meta = comp.compress(value, wctx)
    if tl.active:
        tl.end_activity(name, "QUANTIZE")
    return wire, meta


def _dequantize_scoped(tl, name, fn):
    import jax

    if tl.active:
        tl.start_activity(name, "DEQUANTIZE")
    with jax.named_scope("DEQUANTIZE"):
        out = fn()
    if tl.active:
        tl.end_activity(name, "DEQUANTIZE")
    return out


def lower_hierarchical_asym(x, topo: "_topology.Topology", name: str,
                            intra_comp, cross_comp, key,
                            channels: int = 1):
    """Phase-asymmetric two-level allreduce: intra-slice reduce-scatter
    over ICI in ``intra_comp``'s wire (None = the logical full-precision
    dtype), cross-slice exchange over DCN in ``cross_comp``'s wire with
    the integer budget scoped to the SLICE count (the wider-accumulator
    scheme: the inter-phase accumulator is full precision, the cross hop
    re-quantizes just the 1/L shard), intra-slice all-gather back over
    ICI in ``intra_comp``'s wire. ``cross_comp`` summable (int8_block):
    the hop is a psum of integer wire values over the cross partition;
    unsummable (int4): the hop is an all-gather of packed payloads +
    per-rank scales over the cross partition, summed in fp32 after
    dequantization. Exactly the α–β-motivated policy: bytes are only
    worth shaving where they cross DCN.

    ``channels > 1``: the RS and AG phases split shard-major into C
    channel instances; the cross hop quantizes the REASSEMBLED per-rank
    shard exactly once (identical block grid / scales / rounding keys to
    the single-channel path — the bit-exactness contract) and splits the
    resulting WIRE block rows across C concurrent DCN instances. The
    mid-pipeline quantize is a cross-channel barrier by design: the
    alternative (per-channel scales) would change numerics with the
    channel count."""
    from horovod_tpu.core import timeline as _tl
    from horovod_tpu.ops import compression as _compression

    tl = _tl.session()
    intra, cross = _two_level_groups(topo)
    L, M = topo.local_size, topo.num_slices
    flat, size = _flatten_pad(x, L)
    orig_dtype = x.dtype
    sizes = (_channel_sizes(flat.shape[0] // L, channels)
             if channels > 1 else [flat.shape[0] // L])
    C = len(sizes)

    def to_intra(v):
        return (v if intra_comp is None
                else v.astype(intra_comp.wire_dtype(orig_dtype)))

    def from_intra(v):
        return v if intra_comp is None else v.astype(flat.dtype)

    if C <= 1:
        with _phase(tl, name, "REDUCE_SCATTER"):
            shard = lax.psum_scatter(to_intra(flat), AXIS_NAME,
                                     scatter_dimension=0,
                                     axis_index_groups=intra, tiled=True)
            shard = from_intra(shard)
        _end(tl, name, "REDUCE_SCATTER")
    else:
        shard_parts = []
        for c, part in enumerate(_shard_parts(flat, L, sizes)):
            with _ch_scope(c):
                with _phase(tl, name, "REDUCE_SCATTER"):
                    sp = lax.psum_scatter(to_intra(part), AXIS_NAME,
                                          scatter_dimension=0,
                                          axis_index_groups=intra,
                                          tiled=True)
                    shard_parts.append(from_intra(sp))
                _end(tl, name, "REDUCE_SCATTER")
        # Reassembled per-rank shard == the single-channel shard, element
        # for element (the shard-major split contract): the quantize
        # below sees the exact same tensor.
        shard = (shard_parts[0] if C == 1
                 else jnp.concatenate(shard_parts))
    if cross_comp is None or not cross_comp.applies_to(shard.dtype):
        red = _cross_psum_channels(tl, name, shard, cross, C)
    else:
        wctx = _compression.WireContext(
            group_size=topo.group_size,
            sum_width=M if cross_comp.summable else 1,
            pmax=lambda v: lax.pmax(v, AXIS_NAME,
                                    axis_index_groups=cross),
            rank_data=lax.axis_index(AXIS_NAME),
            # Association-proof default key (see _bitsum_key): the
            # channelized path reassembles `shard` from channel parts,
            # and the float-sum key fallback would flip with the
            # reassociated reduction.
            key=key if key is not None else _bitsum_key(shard, 0x5319))
        wire, meta = _quantize_scoped(tl, name, cross_comp, shard, wctx)
        if cross_comp.summable:
            summed = _cross_psum_channels(tl, name, wire, cross, C)
            red = _dequantize_scoped(
                tl, name, lambda: cross_comp.decompress(
                    summed, meta, shard.dtype, wctx))
        elif C <= 1:
            with _phase(tl, name, "CROSS_SLICE"):
                red = cross_comp.gathered_sum(
                    lambda a: lax.all_gather(a, AXIS_NAME,
                                             axis_index_groups=cross),
                    wire, meta, shard.dtype, wctx)
            _end(tl, name, "CROSS_SLICE")
        else:
            # Unsummable cross wire (int4): split the packed BLOCK rows
            # over C concurrent cross-partition gathers; each channel
            # dequantize-sums its rows (per-block local, so the row
            # split is exact), then the fp32 partials reassemble into
            # the single-channel accumulator.
            unit, orig_shape = meta
            totals, o = [], 0
            for c, q in enumerate(_channel_sizes(wire.shape[0], C)):
                with _ch_scope(c):
                    with _phase(tl, name, "CROSS_SLICE"):
                        gw = lax.all_gather(wire[o:o + q], AXIS_NAME,
                                            axis_index_groups=cross)
                        gu = lax.all_gather(unit[o:o + q], AXIS_NAME,
                                            axis_index_groups=cross)
                        totals.append(cross_comp.stacked_sum(gw, gu))
                    _end(tl, name, "CROSS_SLICE")
                o += q
            total = (totals[0] if len(totals) == 1
                     else jnp.concatenate(totals, axis=0))
            red = cross_comp._restore(total, orig_shape, shard.dtype)
    if C <= 1:
        with _phase(tl, name, "ALL_GATHER"):
            full = lax.all_gather(to_intra(red), AXIS_NAME,
                                  axis_index_groups=intra, tiled=True)
            full = from_intra(full)
        _end(tl, name, "ALL_GATHER")
        return full[:size].reshape(x.shape)
    outs, o = [], 0
    for c, q in enumerate(sizes):
        with _ch_scope(c):
            with _phase(tl, name, "ALL_GATHER"):
                fc = lax.all_gather(to_intra(red[o:o + q]), AXIS_NAME,
                                    axis_index_groups=intra, tiled=True)
                outs.append(from_intra(fc))
            _end(tl, name, "ALL_GATHER")
        o += q
    return _merge_gathered(outs, L, sizes)[:size].reshape(x.shape)


# ---------------------------------------------------------------------------
# FSDP lowerings (ops/mesh.py data × fsdp factorization): the ZeRO-2/3
# gradient exchange — the reduce-scatter PREFIX of the replicated
# decompositions, with the trailing all-gather omitted — and the ZeRO-3
# gather-on-use parameter all-gather. Bit-identity contract
# (tests/test_fsdp.py): each case below runs byte-for-byte the same
# collectives on the same tensors as the matching replicated lowering
# (single slice: the `rs_ag` prefix; multi-slice: the `hierarchical` /
# `lower_hierarchical_asym` prefix), so the reduced shard IS that
# lowering's pre-all-gather shard, element for element.
# ---------------------------------------------------------------------------


def fsdp_exchange_groups(fmesh, topo: "_topology.Topology | None"):
    """``(fsdp_groups, data_groups)`` axis_index_groups for one FSDP
    exchange. In the default multi-slice layout (fsdp == one slice) the
    partitions are taken from the TOPOLOGY (``_two_level_groups``) so
    they are identical — as lists, not just as sets — to the ones the
    hierarchical lowerings emit; HVD101 then sees the already-admitted
    intra/cross shapes."""
    if topo is not None and fmesh.multi_slice and fmesh.matches_slices():
        return _two_level_groups(topo)
    return fmesh.fsdp_groups(), fmesh.data_groups()


def lower_fsdp_grad_exchange(x, fmesh, name: str, comp, key,
                             topo: "_topology.Topology | None" = None):
    """Reduce one gradient leaf to this rank's flat shard: quantize (per
    the compression case below) → reduce-scatter over the ``fsdp``
    partition → psum over the ``data`` partition → dequantize the
    SHARD. Returns ``(shard, orig_size)``: the group-SUMMED shard (the
    caller divides for the average, mirroring ``_divide_avg``) of the
    zero-padded flat layout ``fmesh.padded_numel(orig_size, block)``.

    Cases (each the exact prefix of a replicated lowering):

    * ``comp`` None / elementwise / scalar-scale summable (none, bf16,
      int8): quantize ONCE on the full leaf — meta is shape-agnostic, so
      the shard dequantizes directly. RS+AR on the wire dtype.
    * blocked summable (int8_block), single ``data`` group: the ``rs_ag``
      summable path on the flattened block wire; the shard dequantizes
      through the per-ELEMENT scale vector sliced at this rank's offset
      (block boundaries need not align with shard boundaries).
    * blocked summable, multi-slice with fsdp == slice: the
      ``lower_hierarchical_asym`` mirror — full-precision RS over ICI,
      quantize the SHARD (scales live on the shard; nothing to slice),
      integer psum over DCN, dequantize. Requires the default layout;
      other fsdp sizes refuse rather than invent a fourth scheme.

    Unsummable wires (int4) are refused by the caller
    (parallel/optimizer.py) — their gather-based exchange has no
    shard-keeping prefix."""
    from horovod_tpu.core import timeline as _tl
    from horovod_tpu.ops import compression as _compression

    tl = _tl.session()
    F, D, W = fmesh.fsdp_size, fmesh.data_size, fmesh.group_size
    fgroups, dgroups = fsdp_exchange_groups(fmesh, topo)
    block = getattr(comp, "block", None) if comp is not None else None
    orig_dtype = x.dtype
    if comp is not None and not comp.summable:
        raise HorovodError(
            f"compression {comp.name!r} (tensor {name}) has an "
            f"unsummable wire format: its gather-based exchange has no "
            f"reduce-scatter prefix for the sharded modes to keep. Use "
            f"none/bf16/int8/int8_block with sharding, or sharding='off'.")

    if comp is None or block is None:
        # Elementwise / scalar-scale case: quantize once, full leaf.
        if comp is not None:
            wctx = _compression.WireContext(
                group_size=W, sum_width=W,
                pmax=lambda v: lax.pmax(v, AXIS_NAME),
                rank_data=lax.axis_index(AXIS_NAME), key=key)
            wire, meta = _quantize_scoped(tl, name, comp, x, wctx)
        else:
            wire, meta, wctx = x, None, None
        flat, size = _flatten_pad(wire, F)
        with _phase(tl, name, "REDUCE_SCATTER"):
            shard = lax.psum_scatter(flat, AXIS_NAME, scatter_dimension=0,
                                     axis_index_groups=fgroups, tiled=True)
        _end(tl, name, "REDUCE_SCATTER")
        if D > 1:
            with _phase(tl, name, "CROSS_SLICE"):
                shard = lax.psum(shard, AXIS_NAME,
                                 axis_index_groups=dgroups)
            _end(tl, name, "CROSS_SLICE")
        if comp is not None:
            shard = _dequantize_scoped(
                tl, name,
                lambda: comp.decompress(shard, meta, orig_dtype, wctx))
        return shard, size

    if D == 1:
        # Blocked summable, one data group: the rs_ag summable prefix.
        wctx = _compression.WireContext(
            group_size=W, sum_width=W,
            pmax=lambda v: lax.pmax(v, AXIS_NAME),
            rank_data=lax.axis_index(AXIS_NAME), key=key)
        wire, meta = _quantize_scoped(tl, name, comp, x, wctx)
        unit, _orig_shape = meta
        wflat, wsize = _flatten_pad(wire, F)
        with _phase(tl, name, "REDUCE_SCATTER"):
            shard = lax.psum_scatter(wflat, AXIS_NAME, scatter_dimension=0,
                                     axis_index_groups=fgroups, tiled=True)
        _end(tl, name, "REDUCE_SCATTER")
        shard_len = wflat.shape[0] // F
        # Per-element scales in the wire-flat layout: a shard boundary
        # may cut a block, so the scalar-per-block vector is expanded
        # and sliced at this rank's element offset.
        unit_flat = jnp.repeat(unit, block)
        if wflat.shape[0] > wsize:
            unit_flat = jnp.pad(unit_flat, (0, wflat.shape[0] - wsize))

        def _deq():
            r = lax.axis_index(AXIS_NAME)
            local = r if fgroups is None else r % F
            u = lax.dynamic_slice(unit_flat, (local * shard_len,),
                                  (shard_len,))
            return (shard * u).astype(orig_dtype)

        shard = _dequantize_scoped(tl, name, _deq)
        return shard, wsize

    # Blocked summable across slices: the lower_hierarchical_asym
    # mirror. Only defined on the default layout (fsdp == slice) — the
    # quantize-the-shard scheme is pinned to the intra/cross partition.
    if not (fmesh.multi_slice and fmesh.matches_slices()):
        raise HorovodError(
            f"compression {comp.name!r} (tensor {name}) with sharding "
            f"requires the fsdp axis to be exactly one ICI slice "
            f"(fsdp_size={F}, data_size={D}, num_slices="
            f"{fmesh.num_slices}): the phase-asymmetric cross-slice "
            f"scheme quantizes the per-slice shard. Drop "
            f"HOROVOD_FSDP_AXIS_SIZE or use none/bf16 compression.")
    flat, size = _flatten_pad(x, F)
    with _phase(tl, name, "REDUCE_SCATTER"):
        shard = lax.psum_scatter(flat, AXIS_NAME, scatter_dimension=0,
                                 axis_index_groups=fgroups, tiled=True)
    _end(tl, name, "REDUCE_SCATTER")
    wctx = _compression.WireContext(
        group_size=W, sum_width=D,
        pmax=lambda v: lax.pmax(v, AXIS_NAME, axis_index_groups=dgroups),
        rank_data=lax.axis_index(AXIS_NAME),
        key=key if key is not None else _bitsum_key(shard, 0x5319))
    wire, meta = _quantize_scoped(tl, name, comp, shard, wctx)
    summed = _cross_psum_channels(tl, name, wire, dgroups, 1)
    shard = _dequantize_scoped(
        tl, name,
        lambda: comp.decompress(summed, meta, orig_dtype, wctx))
    return shard.reshape(-1), size


def lower_fsdp_param_gather(shard, fmesh, name: str,
                            topo: "_topology.Topology | None" = None):
    """The ZeRO-3 gather-on-use: all-gather one layer's flat parameter
    shard over the ``fsdp`` partition, at the parameter dtype (gathering
    a quantized wire would change FORWARD numerics — the exchange only
    compresses gradients). Emitted under its own ``FSDP_GATHER`` named
    scope so hvd-lint HVD105 can tell gather-on-use from a reduce
    lowering's trailing all-gather, and XLA's latency-hiding scheduler
    can be audited for overlap (``fsdp_gather_exposed_ms`` in bench)."""
    from horovod_tpu.core import timeline as _tl

    tl = _tl.session()
    fgroups, _ = fsdp_exchange_groups(fmesh, topo)
    if fmesh.fsdp_size <= 1:
        return shard
    with _phase(tl, name, "FSDP_GATHER"):
        full = lax.all_gather(shard, AXIS_NAME,
                              axis_index_groups=fgroups, tiled=True)
    _end(tl, name, "FSDP_GATHER")
    return full


def _bitsum_key(value, salt: int):
    """A PRNG key from ``value``'s raw bits via a WRAPPING int32 sum.

    Mid-pipeline stochastic requantizations (the rs_ag int4 stage-2, the
    hierarchical-asym cross hop) need a per-step key when the caller
    threads none. Deriving it from a FLOAT ``jnp.sum`` of the tensor —
    the Int8Compressor fallback — is association-fragile: the
    channelized lowering builds the same tensor through a different
    program shape, XLA reassociates the reduction, the sum moves one
    ulp, and the derived key (hence every stochastic draw) flips,
    breaking the channels-vs-single bit-exactness contract. Integer
    addition is exact and associative (wrapping two's complement), so
    this key is identical under ANY program restructuring of a
    bit-identical tensor."""
    import jax

    bits = lax.bitcast_convert_type(
        value.reshape(-1).astype(jnp.float32), jnp.int32)
    return jax.random.fold_in(jax.random.PRNGKey(salt), jnp.sum(bits))


def _cross_psum_channels(tl, name: str, value, cross, channels: int):
    """The hierarchical cross-slice psum, split over ``channels``
    concurrent DCN instances along the leading axis (elementwise-exact
    for any split). ``channels <= 1`` emits the classic single psum."""
    if channels <= 1:
        with _phase(tl, name, "CROSS_SLICE"):
            out = lax.psum(value, AXIS_NAME, axis_index_groups=cross)
        _end(tl, name, "CROSS_SLICE")
        return out
    parts, o = [], 0
    for c, q in enumerate(_channel_sizes(value.shape[0], channels)):
        with _ch_scope(c):
            with _phase(tl, name, "CROSS_SLICE"):
                parts.append(lax.psum(value[o:o + q], AXIS_NAME,
                                      axis_index_groups=cross))
            _end(tl, name, "CROSS_SLICE")
        o += q
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def lower_gathered(x, comp, algo: str, name: str, gsize: int, key,
                   rank_data, channels: int = 1):
    """Unsummable-wire (int4) reduction for the single-level algorithms.

    ``flat``: quantize with per-rank local block scales (full ±QCAP range
    — nothing sums on the wire, so no budget division at ANY group size),
    all-gather wire + scales, dequantize-and-sum in fp32. ``rs_ag``: the
    bandwidth-optimal two-phase version — the block grid is split
    shard-wise and exchanged with one all-to-all (rank j dequantize-sums
    every rank's j-th shard: the reduce-scatter), then the reduced shard
    is RE-quantized with fresh local scales and all-gathered packed (no
    sum in a gather, so full range again). Ring-equivalent int4 bytes:
    ``~2(n-1)/n · S/8`` vs the flat gather's ``(n-1) · S/8``.

    Records the rank's local stage-1 contribution for error feedback
    (the stage-2 requantization error applies to the already-reduced
    shard, not this rank's own gradient — see the residual collector
    contract in ops/compression.py).

    ``channels > 1``: both quantizations run ONCE on exactly the
    single-channel path's tensors (bit-exactness contract); only the
    wire's packed block rows split across C concurrent gather/exchange
    instances (per-block dequantization makes any row split exact)."""
    import jax

    from horovod_tpu.core import timeline as _tl
    from horovod_tpu.ops import compression as _compression

    tl = _tl.session()
    wctx = _compression.WireContext(
        group_size=gsize, sum_width=1, rank_data=rank_data, key=key)
    wire, meta = _quantize_scoped(tl, name, comp, x, wctx)
    if _compression.collecting():
        with jax.named_scope("EF_LOCAL"):
            _compression.record_local(
                comp.decompress(wire, meta, x.dtype, wctx))
    if algo == "flat" or gsize <= 1:
        if channels <= 1 or gsize <= 1:
            with _phase(tl, name, "ALL_GATHER"):
                out = comp.gathered_sum(
                    lambda a: lax.all_gather(a, AXIS_NAME),
                    wire, meta, x.dtype, wctx)
            _end(tl, name, "ALL_GATHER")
            return out
        unit, orig_shape = meta
        totals, o = [], 0
        for c, q in enumerate(_channel_sizes(wire.shape[0], channels)):
            with _ch_scope(c):
                with _phase(tl, name, "ALL_GATHER"):
                    gw = lax.all_gather(wire[o:o + q], AXIS_NAME)
                    gu = lax.all_gather(unit[o:o + q], AXIS_NAME)
                    totals.append(comp.stacked_sum(gw, gu))
                _end(tl, name, "ALL_GATHER")
            o += q
        total = (totals[0] if len(totals) == 1
                 else jnp.concatenate(totals, axis=0))
        return comp._restore(total, orig_shape, x.dtype)
    assert algo == "rs_ag", algo
    unit, orig_shape = meta
    nb = wire.shape[0]
    pad_b = (-nb) % gsize
    if pad_b:  # zero blocks quantize to zero: explicit pad, never trunc
        wire = jnp.pad(wire, ((0, pad_b), (0, 0)))
        unit = jnp.pad(unit, (0, pad_b))
    chunk = (nb + pad_b) // gsize
    csizes = (_channel_sizes(chunk, channels)
              if channels > 1 else [chunk])
    if len(csizes) <= 1:
        with _phase(tl, name, "REDUCE_SCATTER"):
            w_recv = lax.all_to_all(wire, AXIS_NAME, split_axis=0,
                                    concat_axis=0, tiled=True)
            u_recv = lax.all_to_all(unit, AXIS_NAME, split_axis=0,
                                    concat_axis=0, tiled=True)
            shard = comp.stacked_sum(
                w_recv.reshape(gsize, chunk, -1),
                u_recv.reshape(gsize, chunk))  # (chunk, B) fp32
        _end(tl, name, "REDUCE_SCATTER")
    else:
        # Shard-major channel split of the block grid: channel c carries
        # every destination rank's rows [o_c, o_c + q_c) of its chunk,
        # so the concatenated per-rank reduced shard is row-for-row the
        # single-channel one — the stage-2 requantization below then
        # sees the identical tensor.
        w3 = wire.reshape(gsize, chunk, -1)
        u2 = unit.reshape(gsize, chunk)
        shard_parts, o = [], 0
        for c, q in enumerate(csizes):
            with _ch_scope(c):
                with _phase(tl, name, "REDUCE_SCATTER"):
                    wc = w3[:, o:o + q, :].reshape(gsize * q, -1)
                    uc = u2[:, o:o + q].reshape(-1)
                    w_recv = lax.all_to_all(wc, AXIS_NAME, split_axis=0,
                                            concat_axis=0, tiled=True)
                    u_recv = lax.all_to_all(uc, AXIS_NAME, split_axis=0,
                                            concat_axis=0, tiled=True)
                    shard_parts.append(comp.stacked_sum(
                        w_recv.reshape(gsize, q, -1),
                        u_recv.reshape(gsize, q)))
                _end(tl, name, "REDUCE_SCATTER")
            o += q
        shard = jnp.concatenate(shard_parts, axis=0)  # (chunk, B) fp32
    # Stage-2 rounding key: association-proof when the caller threads
    # none (see _bitsum_key — the float-sum fallback would diverge
    # between the channelized and single-channel programs).
    key2 = (_bitsum_key(shard, 0x5318) if key is None
            else jax.random.fold_in(key, 1))
    wctx2 = _compression.WireContext(
        group_size=gsize, sum_width=1, rank_data=rank_data, key=key2)
    wire2, meta2 = _quantize_scoped(tl, name, comp,
                                    shard.reshape(-1), wctx2)
    if channels <= 1:
        with _phase(tl, name, "ALL_GATHER"):
            full = comp.gathered_concat(
                lambda a: lax.all_gather(a, AXIS_NAME),
                wire2, (meta2[0], (chunk * comp.block * gsize,)),
                jnp.float32, wctx2)
        _end(tl, name, "ALL_GATHER")
    else:
        unit2 = meta2[0]
        parts, o = [], 0
        for c, q in enumerate(_channel_sizes(wire2.shape[0], channels)):
            with _ch_scope(c):
                with _phase(tl, name, "ALL_GATHER"):
                    gw = lax.all_gather(wire2[o:o + q], AXIS_NAME)
                    gu = lax.all_gather(unit2[o:o + q], AXIS_NAME)
                    # (g, q, B) fp32 dequantized rows, rank-major.
                    parts.append(comp._unpack(gw) * gu[..., None])
                _end(tl, name, "ALL_GATHER")
            o += q
        full3 = (parts[0] if len(parts) == 1
                 else jnp.concatenate(parts, axis=1))
        full = full3.reshape(-1)
    size = 1
    for d in orig_shape:
        size *= d
    return full.reshape(-1)[:size].reshape(orig_shape).astype(x.dtype)
