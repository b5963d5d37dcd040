"""Tensor fusion: batch many small gradients into few large collectives.

The reference fuses consecutive ALLREDUCE responses with matching device set
and dtype into one flat 64 MB buffer before a single ``MPI_Allreduce``
(planner at mpi_ops.cc:1604-1637, execution memcpy-in / reduce / memcpy-out at
:1229-1310), tunable via ``HOROVOD_FUSION_THRESHOLD`` (0 disables). On TPU the
motivation shifts — XLA already fuses elementwise work — but collective *count*
still matters: each psum has fixed launch/latency cost on ICI, so grouping a
pytree of N gradients into ≲threshold-sized buckets turns N collectives into
ceil(total_bytes/threshold) and keeps each transfer large enough to hit peak
ICI bandwidth.

The plan is computed host-side at trace time (shapes are static under jit).
The flat buffer itself is built only for a bucket whose wire needs one
(:attr:`Bucket.packed`), inside the compiled program; a plain-sum bucket is
reduced over its leaves where they lie.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused collective: a set of same-dtype leaves ≤ threshold bytes.

    The analog of one fused ``MPIResponse`` (mpi_ops.cc:1604-1637): the
    reference merges only *consecutive* same-dtype responses and deliberately
    does not reorder past a non-fusable tensor (:1629-1634); we keep the same
    rule — buckets are contiguous runs in submission order — so fusion
    behavior is predictable and matches the reference's observable semantics.

    ``wire_dtype``: the dtype the bucket's collective actually moves — the
    compressed representation when gradient compression is on
    (ops/compression.py), else ``dtype``. Bucket BOUNDARIES are always
    planned on the logical (``dtype``) bytes, so the fusion structure is
    compression-invariant: turning compression on/off changes bytes per
    collective, never the collective count or membership (which keeps
    bench comparisons and the multi-host trace-time schedule stable).

    ``wire_bits``: bits per logical element on the wire when that differs
    from ``wire_dtype``'s width (int4 packs two elements per int8 carrier
    byte); 0 = derive from the dtype.

    ``channels``: concurrent channel instances this bucket's collective
    lowers to (ops/strategy.py channelized lowerings; 1 = the classic
    single instance). A planned, tuned decision — the exchange planner
    (ops/exchange.py) chooses it per bucket from the per-channel α–β
    model the way ``auto`` chooses algorithms — never a numerics change:
    channelization splits the wire below quantization, so results stay
    bit-exact at any channel count.

    Phase-asymmetric hierarchical buckets (ops/compression.py
    ``resolve_phase_formats``) carry per-PHASE wire formats instead of one
    ``wire_dtype``: ``intra_wire_dtype`` is what the intra-slice ICI
    reduce-scatter/all-gather move (None = the logical dtype, full
    precision), ``cross_wire_dtype``/``cross_wire_bits`` what the
    cross-slice DCN hop moves. These feed the cost model's per-phase byte
    pricing, the plan artifact, and the hvd-lint HVD102 contract.
    """

    indices: tuple[int, ...]
    dtype: jnp.dtype
    total_bytes: int
    wire_dtype: object = None  # None = uncompressed (dtype on the wire)
    algo: str = "flat"  # decomposition tag (ops/strategy.py)
    # Issue-order position under the whole-step exchange scheduler
    # (ops/exchange.py): 0 = first collective of the step. Enumeration
    # order (the pre-scheduler default) leaves priority == plan position.
    priority: int = 0
    wire_bits: int = 0
    intra_wire_dtype: object = None
    cross_wire_dtype: object = None
    cross_wire_bits: int = 0
    channels: int = 1

    @property
    def elems(self) -> int:
        """Logical element count of the bucket (of its flat buffer, where
        one is built)."""
        return self.total_bytes // jnp.dtype(self.dtype).itemsize

    @property
    def packed(self) -> bool:
        """Whether the bucket's collective needs the leaves in one flat
        buffer: a compressed wire scales the bucket as one vector, and
        ``rs_ag`` / ``hierarchical`` / ``channels > 1`` cut one buffer
        into shards. A plain sum (``flat``, the leaves' dtype on the
        wire, one channel) adds elementwise whatever the shape, so it is
        reduced in the leaves' own shapes (:func:`fused_apply`): one
        ``lax.psum`` of the small leaves, a ring of collective-permutes a
        large one, one ring after another (ops/strategy.py
        ``_plain_sum``)."""
        return (self.algo != "flat" or self.wire_dtype is not None
                or self.channels != 1)

    @property
    def bytes_on_wire(self) -> int:
        """Bytes this bucket's (single-phase) collective moves per
        direction."""
        if self.wire_bits:
            return self.elems * self.wire_bits // 8
        if self.wire_dtype is None:
            return self.total_bytes
        return self.elems * np.dtype(self.wire_dtype).itemsize

    @property
    def cross_bytes_on_wire(self) -> int:
        """Full-bucket-equivalent bytes of the hierarchical cross-slice
        DCN hop (the hop physically moves the 1/local_size shard; the
        fp32 baseline shrinks by the same factor, so the RATIO is what
        this property exists to pin — the acceptance gate's
        'int4 cross-slice wire bytes <= 12.5% of fp32')."""
        if self.cross_wire_dtype is None:
            return self.bytes_on_wire
        if self.cross_wire_bits:
            return self.elems * self.cross_wire_bits // 8
        return self.elems * np.dtype(self.cross_wire_dtype).itemsize

    @property
    def intra_bytes_on_wire(self) -> int:
        """Full-bucket-equivalent bytes of one intra-slice ICI phase."""
        if self.cross_wire_dtype is None:
            return self.bytes_on_wire
        if self.intra_wire_dtype is None:
            return self.total_bytes  # phase-asymmetric: logical precision
        return self.elems * np.dtype(self.intra_wire_dtype).itemsize

    def describe(self) -> str:
        """One-line human/report form — the single place elems/bytes/wire
        are derived, consumed by the timeline and the bench instead of
        each re-deriving them."""
        if self.cross_wire_dtype is not None:
            intra = ("f" + str(np.dtype(self.dtype).itemsize * 8)
                     if self.intra_wire_dtype is None
                     else np.dtype(self.intra_wire_dtype).name)
            wire = (f" wire=intra:{intra}"
                    f"/cross:{np.dtype(self.cross_wire_dtype).name}"
                    f":{self.cross_bytes_on_wire}B")
        elif self.wire_dtype is not None:
            wire = (f" wire={np.dtype(self.wire_dtype).name}"
                    f":{self.bytes_on_wire}B")
        else:
            wire = ""
        return (f"bucket[{len(self.indices)} tensors, {self.elems} "
                f"{np.dtype(self.dtype).name}, {self.total_bytes}B, "
                f"algo={self.algo}{wire}, ch={self.channels}, "
                f"prio={self.priority}]")


@dataclasses.dataclass(frozen=True)
class SparseBucket:
    """One sparse (IndexedSlices) gradient exchange in the whole-step plan
    (ops/sparse.py; ops/exchange.py serializes these rows into the
    ``.exchange.json`` artifact ONLY when present, so dense-only plans
    keep byte-identical JSON and stable hashes).

    ``index`` is the leaf's position in the FULL gradient-pytree
    enumeration (dense ``Bucket.indices`` count dense leaves only — the
    two index spaces are distinct by design). ``rows`` is the padded
    per-rank row capacity of the sparse wire format, ``row_elems`` the
    elements per slice row, ``dense_rows`` the embedding table's row
    count (``dense_shape[0]``). ``algo`` is the RESOLVED lowering —
    ``gather`` (padded allgather + dedup-and-merge) or ``dense``
    (densify + allreduce); ``auto`` never reaches a plan row.
    ``wire_dtype``/``wire_bits`` describe the gather-form value-payload
    wire (per-rank scales, nothing summed — ops/compression.py
    ``gathered_rows``); None = the logical dtype. Indices always move
    uncompressed at ``index_itemsize`` bytes each.
    """

    index: int
    dtype: jnp.dtype
    rows: int
    row_elems: int
    dense_rows: int
    algo: str = "gather"
    wire_dtype: object = None
    wire_bits: int = 0
    index_itemsize: int = 4
    label: str = ""

    @property
    def values_bytes(self) -> int:
        """Logical bytes of one rank's padded value block."""
        return self.rows * self.row_elems * jnp.dtype(self.dtype).itemsize

    @property
    def payload_wire_bytes(self) -> int:
        """Wire bytes of one rank's gather payload: value block (in its
        wire format) + uncompressed index block."""
        if self.wire_bits:
            vals = self.rows * self.row_elems * self.wire_bits // 8
        elif self.wire_dtype is not None:
            vals = (self.rows * self.row_elems
                    * np.dtype(self.wire_dtype).itemsize)
        else:
            vals = self.values_bytes
        return vals + self.rows * self.index_itemsize

    @property
    def dense_bytes(self) -> int:
        """Logical bytes of the densified table (the dense candidate)."""
        return (self.dense_rows * self.row_elems
                * jnp.dtype(self.dtype).itemsize)

    def describe(self) -> str:
        wire = ""
        if self.wire_dtype is not None:
            wire = f" wire={np.dtype(self.wire_dtype).name}"
        return (f"sparse[leaf {self.index}"
                f"{' ' + self.label if self.label else ''}, "
                f"{self.rows}x{self.row_elems} "
                f"{np.dtype(self.dtype).name} of {self.dense_rows} rows, "
                f"algo={self.algo}{wire}, "
                f"payload={self.payload_wire_bytes}B]")


def plan_buckets(leaves: Sequence[jax.Array], threshold_bytes: int,
                 compression=None, algo=None, group_size: int | None = None,
                 cross_compression=None) -> list[Bucket]:
    """Partition leaves (in order) into fusion buckets.

    threshold 0 disables fusion — every leaf is its own bucket
    (mpi_ops.cc:1492-1495 semantics). ``compression`` (a resolved
    :class:`~horovod_tpu.ops.compression.Compressor` or None) annotates
    each bucket with its wire dtype; bucket boundaries stay planned on
    logical bytes (see :class:`Bucket`). ``algo`` (a concrete
    decomposition name or a ``bucket -> name`` selector, ops/strategy.py)
    stamps each bucket's ``algo`` tag — selectors see the wire-annotated
    bucket, so cost-model choices run on the bytes the wire actually
    moves. ``group_size`` feeds the block compressor's in-wire sum-width
    budget (>127-rank worlds annotate the widened int16 wire);
    ``cross_compression`` the per-phase annotation of hierarchical
    buckets (:func:`_annotate_phase_wire`).
    """
    buckets = _partition(leaves, threshold_bytes)
    buckets = _annotate_algo(_annotate_wire(buckets, compression,
                                            group_size), algo)
    buckets = _annotate_phase_wire(buckets, compression, cross_compression)
    # Enumeration-order priorities: plan position == issue position (the
    # ops/exchange.py priority planner overrides these).
    return [dataclasses.replace(b, priority=i)
            for i, b in enumerate(buckets)]


def _annotate_wire(buckets: list[Bucket], compression,
                   group_size: int | None = None) -> list[Bucket]:
    """Stamp each bucket's wire dtype (and packed bit width) from the
    active compressor. ``group_size`` is the in-wire sum width for the
    block compressor's budget-driven dtype (int16 past 127 ranks)."""
    if compression is None:
        return buckets
    from horovod_tpu.ops import compression as _comp

    out = []
    for b in buckets:
        wire = _comp.wire_dtype_of(compression, b.dtype, group_size)
        if wire == jnp.dtype(b.dtype):
            out.append(b)
            continue
        bits = compression.WIRE_BITS
        out.append(dataclasses.replace(
            b, wire_dtype=wire,
            wire_bits=(bits if bits
                       and bits != np.dtype(wire).itemsize * 8 else 0)))
    return out


def _annotate_phase_wire(buckets: list[Bucket], compression,
                         cross_compression=None) -> list[Bucket]:
    """Per-phase wire formats for phase-asymmetric HIERARCHICAL buckets:
    the intra-slice ICI phases move ``intra``'s wire (None = the logical
    dtype at full precision), the cross-slice DCN hop ``cross``'s — the
    ops/strategy.py ``lower_hierarchical_asym`` contract mirrored onto
    the plan so cost-model pricing, the exchange artifact, and hvd-lint
    HVD102 all see the same per-phase truth. The single-phase
    ``wire_dtype`` is cleared on such buckets (there is no one wire)."""
    if compression is None and cross_compression is None:
        return buckets
    from horovod_tpu.ops import compression as _comp

    intra, cross, asym = _comp.resolve_phase_formats(compression,
                                                     cross_compression)
    if not asym:
        return buckets
    out = []
    for b in buckets:
        if b.algo != "hierarchical" \
                or not jnp.issubdtype(jnp.dtype(b.dtype), jnp.floating):
            out.append(b)
            continue
        cross_applies = cross is not None and cross.applies_to(b.dtype)
        intra_dt = (None if intra is None
                    else _comp.wire_dtype_of(intra, b.dtype, None))
        if intra_dt is not None and intra_dt == jnp.dtype(b.dtype):
            intra_dt = None
        if not cross_applies and intra_dt is None:
            # Every phase moves the logical dtype (e.g. an explicit
            # uncompressed cross override with no intra cast): drop any
            # single-phase annotation — the bucket has no wire format.
            out.append(dataclasses.replace(b, wire_dtype=None,
                                           wire_bits=0))
            continue
        # cross_wire_dtype is the logical dtype when the cross hop is
        # explicitly uncompressed but the intra phases still cast (bf16
        # ICI + f32 DCN) — the plan must mirror what the lowering moves,
        # not collapse to "uncompressed everywhere".
        cross_dt = (_comp.wire_dtype_of(cross, b.dtype, None)
                    if cross_applies else jnp.dtype(b.dtype))
        cross_bits = cross.WIRE_BITS if cross_applies else 0
        out.append(dataclasses.replace(
            b, wire_dtype=None, wire_bits=0,
            intra_wire_dtype=intra_dt, cross_wire_dtype=cross_dt,
            cross_wire_bits=(cross_bits if cross_bits
                             and cross_bits != np.dtype(cross_dt).itemsize
                             * 8 else 0)))
    return out


def _annotate_algo(buckets: list[Bucket], algo) -> list[Bucket]:
    """Stamp each bucket's decomposition tag (string or per-bucket
    selector); ``None`` keeps the ``flat`` default."""
    if algo is None:
        return buckets
    pick = algo if callable(algo) else (lambda b: algo)
    return [dataclasses.replace(b, algo=pick(b)) for b in buckets]


def _partition(leaves: Sequence[jax.Array],
               threshold_bytes: int) -> list[Bucket]:
    """Contiguous same-dtype runs of at most ``threshold_bytes`` (reference
    semantics, mpi_ops.cc:1604-1637)."""
    buckets: list[Bucket] = []
    cur: list[int] = []
    cur_dtype = None
    cur_bytes = 0

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            buckets.append(Bucket(tuple(cur), cur_dtype, cur_bytes))
            cur, cur_bytes = [], 0

    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize
        if threshold_bytes <= 0:
            buckets.append(Bucket((i,), leaf.dtype, nbytes))
            continue
        if cur and (leaf.dtype != cur_dtype
                    or cur_bytes + nbytes > threshold_bytes):
            flush()
        cur_dtype = leaf.dtype
        cur.append(i)
        cur_bytes += nbytes
    flush()
    return buckets


def trace_order(buckets: Sequence[Bucket], leaves: Sequence[jax.Array],
                group_size: int | None) -> list[Bucket]:
    """The order :func:`fused_apply` traces ``buckets`` in. The plan's —
    but the plain-sum buckets with a leaf that goes round the ring
    (ops/strategy.py ``ring_eligible``) come after the others, the one
    with the last leaf first: a backward pass brings the gradients into
    being from the last leaf to the first (ops/exchange.py, priority
    ordering), and the rings are chained in the order of tracing
    (``one_ring_at_a_time``). What a program computes does not depend on
    the order it is traced in; with no such bucket the order, and so the
    text, is the plan's."""
    from horovod_tpu.ops import strategy as _strategy

    def after(b):  # a stable sort: the others keep the plan's order
        ring = not b.packed and group_size and any(
            _strategy.ring_eligible(leaves[i], group_size)
            for i in b.indices)
        return (1, -b.indices[-1]) if ring else (0, 0)

    return sorted(buckets, key=after)


def fused_apply(leaves: Sequence[jax.Array], collective, threshold_bytes: int,
                labels: Sequence[str] | None = None, compression=None,
                algo=None, schedule=None, group_size: int | None = None,
                cross_compression=None):
    """Apply ``collective`` bucket-wise: one call a bucket.

    A bucket that needs the fusion buffer (:attr:`Bucket.packed`: a
    compressed wire, ``rs_ag`` / ``hierarchical``, ``channels > 1``) is
    packed into one flat buffer (MEMCPY_IN_FUSION_BUFFER,
    mpi_ops.cc:1240-1259), ``collective(flat_1d_array) -> flat_1d_array``
    runs once (mpi_ops.cc:1274), and the result is unpacked
    (MEMCPY_OUT_FUSION_BUFFER, :1281-1302). A plain-sum bucket is handed
    over as the tuple of its leaves in their own shapes,
    ``collective(leaves) -> leaves``: still one call and one row of the
    schedule, but no buffer is built — on a TPU a tiled gradient is not
    a flat vector, and each ``reshape(-1)`` is a pass over the leaf.
    What the tuple becomes is the lowering's to say (ops/strategy.py
    ``_plain_sum``): its small leaves one ``lax.psum`` (one all-reduce a
    leaf in the lowered text, adjacent and in the plan's order; XLA's
    combiner merges them into variadic all-reduces without a copy, and
    runs each alone on the core's timeline: nothing hides it), each large
    leaf of a whole-axis group a ring reduce-scatter and all-gather of
    collective-permutes, which the compiler issues asynchronously, the
    backward's fusions between a start and its done. XLA's scheduler
    does not spread those rings over the backward by itself: it packs
    them all behind its end. So the buckets are traced in
    :func:`trace_order` and their rings chained in that order, one on the
    links at a time (ops/strategy.py ``one_ring_at_a_time``). The plan
    (``threshold_bytes`` or ``schedule``) decides the buckets either way.

    ``labels``: one display name per leaf (gradient pytree paths). When
    given, the collective is invoked as ``collective(x, members)`` with
    the bucket's member labels so the schedule (and from it the device
    timeline) records which tensors each bucket carries — the analog of
    the reference timeline showing every fused tensor's own row.

    ``compression``: resolved compressor (or None) — annotates the plan's
    buckets with their wire dtype. The quantize/psum/dequantize itself is
    enacted by the ``collective`` callback (the allreduce lowering), so
    pack → quantize → collective → dequantize → unpack stays one compiled
    region per bucket.

    ``algo``: decomposition for the plan's buckets (a concrete name or a
    per-bucket selector, see :func:`plan_buckets`). When given, the
    collective is additionally invoked with ``algo=<bucket's tag>`` so
    the lowering enacts exactly the tagged decomposition.

    ``schedule``: a precomputed
    :class:`~horovod_tpu.ops.exchange.ExchangeSchedule` — its buckets
    (already wire/algo-annotated, in issue order) are enacted verbatim
    instead of planning here, and the timeline SCHEDULE row logs the plan
    hash alongside each bucket's priority. ``None`` keeps the classic
    single-threshold enumeration-order plan.
    """
    from horovod_tpu.core import timeline as _timeline
    from horovod_tpu.ops import strategy as _strategy

    leaves = list(leaves)
    if labels is not None and len(labels) != len(leaves):
        raise ValueError(
            f"fused_apply: {len(labels)} labels for {len(leaves)} leaves.")

    def run(x, bucket):
        kwargs = {}
        if labels is not None:
            kwargs["members"] = tuple(labels[i] for i in bucket.indices)
        if algo is not None:
            kwargs["algo"] = bucket.algo
        if bucket.channels != 1:
            # Channelized plans only come from the exchange planner /
            # explicit knobs; the classic plan_buckets path always
            # leaves channels=1, so plain collectives keep their
            # signature.
            kwargs["channels"] = bucket.channels
        return collective(x, **kwargs)

    out: list[jax.Array | None] = [None] * len(leaves)
    tl = _timeline.session()
    # SCHEDULE is genuine host work (the fusion plan is computed at trace
    # time, like the reference's coordinator-side planning at
    # mpi_ops.cc:1604-1637) — stamp it on the host clock. The per-step
    # MEMCPY_IN/OUT_FUSION_BUFFER activities execute inside the compiled
    # program; the device-fidelity timeline mode recovers them from the
    # xplane (core/xprof.py). The named_scopes below label the packing ops
    # in dumped HLO for humans.
    if tl.active:
        tl.start_activity("_fusion_buffer", "SCHEDULE")
    if schedule is not None:
        buckets = list(schedule.buckets)
        if tl.active:
            tl.event("_fusion_buffer",
                     f"plan={schedule.plan_hash()} mode={schedule.mode}",
                     "X")
    else:
        buckets = plan_buckets(leaves, threshold_bytes,
                               compression=compression, algo=algo,
                               group_size=group_size,
                               cross_compression=cross_compression)
    if tl.active:
        for bucket in buckets:
            tl.event("_fusion_buffer", bucket.describe(), "X")
        tl.end_activity("_fusion_buffer", "SCHEDULE")
    # The plan is final here: the bytes it will put on the wire a step, a
    # rank, go into the record of the program being traced
    # (core/timeline.py; the collectives it becomes are the capture's to
    # count: XLA's combiner merges them).
    tl.count_plan("exchange.wire_bytes",
                  sum(b.bytes_on_wire for b in buckets))
    tl.count_plan("exchange.unpacked_bytes",
                  sum(b.bytes_on_wire for b in buckets if not b.packed))
    # One ring on the links at a time, in this order (ops/strategy.py).
    with _strategy.one_ring_at_a_time():
        for bucket in trace_order(
                buckets, leaves,
                group_size if schedule is None else schedule.world_size):
            parts = [leaves[i] for i in bucket.indices]
            if not bucket.packed:
                for i, r in zip(bucket.indices, run(tuple(parts), bucket)):
                    out[i] = r
                continue
            if len(parts) == 1:
                [i], [leaf] = bucket.indices, parts
                out[i] = run(leaf.reshape(-1), bucket).reshape(leaf.shape)
                continue
            with jax.named_scope("MEMCPY_IN_FUSION_BUFFER"):
                flat = jnp.concatenate([p.reshape(-1) for p in parts], axis=0)
            reduced = run(flat, bucket)
            offset = 0
            with jax.named_scope("MEMCPY_OUT_FUSION_BUFFER"):
                for i, p in zip(bucket.indices, parts):
                    out[i] = reduced[offset: offset + p.size].reshape(p.shape)
                    offset += p.size
    return out


def fused_tree_apply(tree, collective, threshold_bytes: int):
    """Pytree wrapper around :func:`fused_apply` (its plan's buckets are
    plain sums: ``collective`` gets each as the tuple of its leaves)."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(
        treedef, fused_apply(leaves, collective, threshold_bytes))
