"""The four Horovod collectives, TPU-native.

Reference surface: ``HorovodAllreduce/Allgather/Broadcast/Gather`` TF ops
(/root/reference/horovod/tensorflow/mpi_ops.cc:2279-2504) executed by
``PerformOperation`` (mpi_ops.cc:757-1365) over MPI/NCCL. Here the data plane
is XLA collectives over ICI: allreduce → ``lax.psum`` (CrossReplicaSum),
allgather/gather → ``lax.all_gather``, broadcast → masked ``lax.psum``;
groups map onto sub-meshes (eager) or ``axis_index_groups`` (traced), exactly
the replica_groups correspondence called out in the north-star.

Two execution modes share one API:

* **Traced / SPMD** (the hot path): inside an ``hvd.spmd``-wrapped step
  function the collectives emit XLA ops on the mesh axis — compiled once,
  fused by XLA, riding ICI. This replaces the reference's entire background
  thread + coordinator + MPI machinery (mpi_ops.cc:1464-1733): SPMD program
  order is already globally consistent, so no negotiation is needed at
  runtime.
* **Eager** (host-driven, the analog of the reference's op-by-op dispatch and
  of Keras value-level collectives, keras/__init__.py:101-144): per-rank
  values are validated against each other exactly as the reference coordinator
  validates ``MPIRequest``s — mismatched dtype / shape / root raises
  ``HorovodError`` with reference-format messages — then dispatched as one
  ``shard_map`` program on the group's mesh.

Eager input/output convention (single controller holds every rank's value):

* list input = one array per rank, as if each rank passed its own tensor;
* single-array input = every rank passes the same value.
* ``allreduce``/``broadcast`` return the same container shape they were given;
  ``allgather`` returns the gathered array (identical on every rank);
  ``gather`` returns a per-rank list: the concatenation at ``root_rank``, each
  other rank's own input unchanged (mpi_ops.cc:2444-2447, design note
  :2472-2479).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import zlib
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.core import context as _ctx
from horovod_tpu.core import multihost as _mh
from horovod_tpu.core import negotiate as _neg
from horovod_tpu.core import state as _state
from horovod_tpu.core.state import AXIS_NAME, HorovodError
from horovod_tpu.ops import compression as _compression
from horovod_tpu.ops import strategy as _strategy

_name_counters: dict[str, int] = {}  # next index per op-type prefix
_name_lock = threading.Lock()


def _auto_name(prefix: str, name: str | None) -> str:
    """Auto-name collectives the way mpi_ops.py:191-209 derives op names from
    tensor names — the name is the cross-rank correlation key.

    One counter PER OP TYPE: in multi-host eager mode an extra unnamed
    collective on one process then shifts only that op type's subsequent
    names, and the index-keyed negotiation (core/multihost.py) turns any
    residual drift into a crisp schedule-divergence error instead of a
    stall.

    DETERMINISM CONTRACT (hvd-lint rule HVD003 enforces the user side):
    the counter is process-local state, so auto names stay in cross-process
    lockstep **iff every process issues the same sequence of auto-named
    collectives** — an auto-named collective under a branch only some
    processes take permanently shifts that op type's later names on those
    processes, and every subsequent auto-named collective then pairs with
    the wrong peer op. Collectives issued from conditional code paths must
    pass an explicit ``name=``. The counters reset on ``hvd.shutdown()``
    (:func:`reset_auto_names`), so a shutdown/re-init cycle — which every
    process performs together — restarts the sequence deterministically at
    ``<prefix>_0`` instead of carrying over whatever count the previous
    generation reached."""
    if name is not None:
        return name
    with _name_lock:
        n = _name_counters.get(prefix, 0)
        _name_counters[prefix] = n + 1
        return f"{prefix}_{n}"


def reset_auto_names() -> None:
    """Restart every per-op-type auto-name counter at 0 (see the
    determinism contract in :func:`_auto_name`); called on shutdown so
    each init generation's auto-name sequence is reproducible."""
    with _name_lock:
        _name_counters.clear()


@contextlib.contextmanager
def preserve_auto_names():
    """Run a block without permanently advancing the auto-name counters.

    The static verifier (horovod_tpu/analysis) lowers real step functions
    for inspection; those traces draw auto names from the SAME per-process
    counters live collectives use, so an un-restored analysis pass on one
    process of a multi-host job would shift that process's subsequent name
    sequence — precisely the divergence hazard the verifier exists to
    catch. Snapshot on entry, restore on exit."""
    with _name_lock:
        snap = dict(_name_counters)
    try:
        yield
    finally:
        with _name_lock:
            _name_counters.clear()
            _name_counters.update(snap)


# ---------------------------------------------------------------------------
# Eager dispatch machinery
# ---------------------------------------------------------------------------


def _as_rank_list(x, group_size: int):
    """Normalize eager input to (list_of_per_rank_arrays, was_list)."""
    if isinstance(x, (list, tuple)):
        if len(x) != group_size:
            raise HorovodError(
                f"Per-rank value list has length {len(x)} but the group has "
                f"{group_size} rank(s).")
        return [jnp.asarray(v) for v in x], True
    v = jnp.asarray(x)
    return [v] * group_size, False


def _eager_inputs(x, g: _state.Group):
    """Normalize eager input to (per-rank list, submitting ranks, was_list).

    Single-controller: the controller holds every rank's value (list of
    ``g.size``). Multi-host: each process passes values only for the ranks it
    drives (``local_member_ranks`` order) — one entry per local rank, or a
    single array meaning 'same value on each of my ranks'; the rest arrive
    from the other processes, exactly as each MPI process submits only its
    own tensor in the reference.
    """
    if not _mh.active():
        xs, was_list = _as_rank_list(x, g.size)
        return xs, list(range(g.size)), was_list
    lranks = list(g.local_member_ranks())
    if isinstance(x, (list, tuple)):
        if len(x) != len(lranks):
            raise HorovodError(
                f"Per-rank value list has length {len(x)} but this process "
                f"drives {len(lranks)} rank(s) of the group.")
        return [jnp.asarray(v) for v in x], lranks, True
    v = jnp.asarray(x)
    return [v] * len(lranks), lranks, False


def _validate(xs, op: _neg.CollectiveOp, name: str, g: _state.Group,
              ranks: Sequence[int], root_rank: int = -1,
              group: int = 0) -> _neg.Response:
    """Validate the submitting ranks' requests. Single-controller: all ranks
    are local, validation is immediate. Multi-host: this process's requests
    go through the cross-process negotiator (core/multihost.py) — the analog
    of MPI_Send to the coordinator + response broadcast."""
    requests = [
        _neg.Request(rank=ranks[j], name=name, op=op, dtype=str(v.dtype),
                     shape=tuple(v.shape), root_rank=root_rank, group=group)
        for j, v in enumerate(xs)
    ]
    if _mh.active():
        return _mh.negotiator().negotiate(name, requests, g.size, op=op)
    return _neg.validate(requests, g.size)


@functools.lru_cache(maxsize=None)
def _psum_fn(mesh_key, ndim: int):
    group = _state.get_group(mesh_key)
    spec = P(AXIS_NAME, *([None] * ndim))
    f = jax.shard_map(
        lambda x: lax.psum(x, AXIS_NAME),
        mesh=group.mesh, in_specs=spec, out_specs=spec)
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _alltoall_device_fn(mesh_key, ndim: int):
    """Device all-to-all over the group mesh — the eager exchange in BOTH
    controller modes (multi-host: each controller holds only its ranks'
    blocks, so a real collective is mandatory; single-controller uses the
    same program so the default test world exercises the device path)."""
    group = _state.get_group(mesh_key)
    spec = P(AXIS_NAME, *([None] * ndim))

    def f(x):  # x: (1, d0, *s) local shard
        y = lax.all_to_all(x[0], AXIS_NAME, split_axis=0, concat_axis=0,
                           tiled=True)
        return y[None]

    return jax.jit(jax.shard_map(f, mesh=group.mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


@functools.lru_cache(maxsize=None)
def _allgather_fn(mesh_key, ndim: int):
    group = _state.get_group(mesh_key)
    in_spec = P(AXIS_NAME, *([None] * ndim))
    out_spec = P(*([None] * (ndim + 1)))

    def f(x):  # x: (1, *shape) local shard
        g = lax.all_gather(x, AXIS_NAME)  # (size, 1, *shape)
        return jnp.squeeze(g, axis=1)

    return jax.jit(jax.shard_map(f, mesh=group.mesh, in_specs=in_spec,
                                 out_specs=out_spec, check_vma=False))


def clear_caches() -> None:
    """Drop compiled collective programs (called on shutdown/re-init)."""
    _psum_fn.cache_clear()
    _allgather_fn.cache_clear()
    _alltoall_device_fn.cache_clear()
    reset_auto_names()


class _activity:
    """Timeline activity scope around an eager dispatch — the analog of the
    ACTIVITY_START_ALL/END_ALL hooks in PerformOperation (mpi_ops.cc:741-753)."""

    def __init__(self, tensor: str, activity: str) -> None:
        from horovod_tpu.core import timeline as _tl

        self._tl = _tl.session()
        self._tensor = tensor
        self._activity = activity

    def __enter__(self):
        if self._tl.active:
            self._tl.start_activity(self._tensor, self._activity)
        return self

    def __exit__(self, *exc):
        if self._tl.active:
            self._tl.end_activity(self._tensor, self._activity)


def _stack_ranked(g: _state.Group, xs):
    """Rank-stack eager values: host stack single-controller, global-array
    assembly (rows on their owning devices across processes) multi-host."""
    if _mh.active():
        from horovod_tpu.parallel import spmd as _spmd

        return _spmd._global_from_local_rows(g, xs)
    return jnp.stack(xs, axis=0)


def _unstack_ranked(g: _state.Group, out, ranks):
    """Per-submitting-rank rows of a rank-stacked result."""
    if not _mh.active():
        return [out[i] for i in ranks]
    by_row = {}
    for s in out.addressable_shards:
        row = s.index[0].start or 0
        by_row[row] = s.data[0]
    return [by_row[i] for i in ranks]


def _eager_psum(group: _state.Group, xs, ranks):
    """Sum per-rank values across the group's mesh; returns per-submitting-
    rank results."""
    orig_dtype = xs[0].dtype
    vals = xs
    if orig_dtype == jnp.bool_:
        vals = [v.astype(jnp.int32) for v in vals]
    out = _psum_fn(group.index, vals[0].ndim)(_stack_ranked(group, vals))
    outs = _unstack_ranked(group, out, ranks)
    if orig_dtype == jnp.bool_:
        outs = [o.astype(jnp.bool_) for o in outs]
    return outs


def _eager_allgather_padded(group: _state.Group, xs, ranks, sizes):
    """Device all-gather with first-dim padding, then host-side trim+concat —
    the static-shape realisation of MPI_Allgatherv (mpi_ops.cc:908-928): the
    size exchange is the validated response's tensor_sizes (negotiated across
    processes in multi-host mode)."""
    dmax = max(sizes)
    padded = []
    for v, r in zip(xs, ranks):
        d0 = sizes[r]
        if d0 < dmax:
            pad = [(0, dmax - d0)] + [(0, 0)] * (v.ndim - 1)
            v = jnp.pad(v, pad)
        padded.append(v)
    gathered = _allgather_fn(group.index, padded[0].ndim)(
        _stack_ranked(group, padded))
    # out_specs is fully replicated, so every process holds the whole result.
    parts = [gathered[i, : sizes[i]] for i in range(group.size)]
    return jnp.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# Traced (in-SPMD) lowerings
# ---------------------------------------------------------------------------


def _traced_groups_arg(tctx: _ctx.TraceContext, group: int):
    """(member mesh-positions or None, group size) for running group
    ``group``'s collective inside a program traced on group
    ``tctx.group_index``'s mesh. None positions mean the whole axis.

    Subset psum-family collectives do NOT use XLA ``replica_groups``
    (``axis_index_groups``): a members+singletons cover is non-uniform,
    which the TPU backend rejects outright ("axis_index_groups must all
    be the same size for TPU lowering" — discovered AOT-compiling for
    real v5e slices, tools/pod_compile.py r5; the CPU test backend
    accepts it). Instead they run a MASKED full-axis psum — non-members
    contribute zeros and restore their input afterwards — which lowers
    everywhere and rides the full ICI torus. Uniform covering partitions
    (group families) still take the replica_groups fast path
    (:func:`_family_partition`)."""
    if group == tctx.group_index:
        return None, _state.get_group(group).size
    positions = tctx.member_positions(group)
    return positions, _state.get_group(group).size


def _traced_member_mask(tctx: _ctx.TraceContext, group: int):
    """Traced boolean: is the executing device a member of `group`?"""
    if group == tctx.group_index:
        return None  # everyone is a member
    return tctx.rank(group) >= 0


def _is_group_index(group) -> bool:
    """True for a single group index (int or numpy integer scalar)."""
    return isinstance(group, (int, np.integer))


def _bucket_key(key, members, name):
    """Fold the per-bucket salt into a user-threaded per-step key, which
    is shared by every bucket of the step: same-shaped buckets must draw
    independent rounding noise. A fusion bucket's member-label tuple is
    stable across retraces (auto-generated collective names are NOT — a
    global counter); crc32, not hash(), so the fold matches across
    processes."""
    if key is None:
        return None
    salt = "/".join(members) if members else name
    return jax.random.fold_in(
        key, zlib.crc32(salt.encode("utf-8")) & 0x7FFFFFFF)


def _compressed_psum(x, comp, key, gsize, member, name, members=None,
                     algo="flat", topo=None, cross_spec=None,
                     channels=1):
    """Full-axis group sum with an optional wire compressor around it:
    quantize → wire collective(s) in the wire dtype → dequantize, each
    phase visible as a ``QUANTIZE``/``DEQUANTIZE`` named scope in the HLO
    and stamped on the collective's timeline row (trace-time host stamps,
    the SCHEDULE precedent — device-fidelity mode recovers the real spans
    from the xplane via the named scopes). ``member`` masks subset groups:
    non-members contribute zeros (which quantize to exactly zero, so they
    do not disturb the int8 budget or the group abs-max scale).

    ``algo`` selects the wire decomposition (ops/strategy.py): ``flat``
    is one psum; ``rs_ag``/``hierarchical`` are phase-structured
    (REDUCE_SCATTER/CROSS_SLICE/ALL_GATHER scopes) and COMPOSE with
    compression. Three compression shapes (ops/compression.py decides
    which applies):

    * summable wire (bf16/int8/int8_block on flat/rs_ag): compress ONCE,
      every phase moves the wire dtype, one dequantize at the end — the
      pre-existing structure, now with ``sum_width`` = the group size so
      the block compressor budgets (and >127-rank widens) correctly.
    * unsummable wire (int4 on flat/rs_ag): gather-based exchange,
      full-precision accumulator (``strategy.lower_gathered``).
    * phase-asymmetric hierarchical (int8_block/int4, or a
      ``cross_compression`` override): per-phase wire formats — ICI
      phases full-precision/bf16, the DCN hop compressed with the
      cross-slice format (``strategy.lower_hierarchical_asym``).

    Phased algorithms are only selected for full-axis groups (``member
    is None``; ops/strategy.py ``select`` enforces it). While an
    error-feedback collection is active (ops/compression.py), records
    this rank's local dequantized contribution per bucket.

    ``channels``: concurrent channel instances of the wire collective(s)
    (ops/strategy.py channelized lowerings; 1 = the classic single
    instance). Channelization composes with every compression shape —
    quantization always runs once, bucket-level, exactly as at
    ``channels=1``; only the wire movement splits."""
    contrib = x if member is None else jax.tree.map(
        lambda v: jnp.where(member, v, jnp.zeros_like(v)), x)
    intra_comp, cross_comp, asym = _compression.resolve_phase_formats(
        comp, cross_spec)
    if algo == "hierarchical" and asym:
        # The cross hop quantizes the intra-slice SUM's shard, not this
        # rank's own gradient: no attributable local residual.
        _compression.record_local(None)
        return _strategy.lower_hierarchical_asym(
            contrib, topo, name, intra_comp, cross_comp,
            _bucket_key(key, members, name), channels=channels)
    if comp is None or not comp.applies_to(_leaf_dtype(x)):
        _compression.record_local(None)  # exact contribution
        return _strategy.lower_allreduce(contrib, algo, name, topo, gsize,
                                         channels=channels)
    from horovod_tpu.core import timeline as _tl

    key = _bucket_key(key, members, name)
    if not comp.summable:
        return _strategy.lower_gathered(contrib, comp, algo, name, gsize,
                                        key, lax.axis_index(AXIS_NAME),
                                        channels=channels)
    tl = _tl.session()
    wctx = _compression.WireContext(
        group_size=gsize,
        sum_width=gsize,
        pmax=lambda v: lax.pmax(v, AXIS_NAME),
        rank_data=lax.axis_index(AXIS_NAME),
        key=key)
    if tl.active:
        tl.start_activity(name, "QUANTIZE")
    with jax.named_scope("QUANTIZE"):
        wire, meta = comp.compress(contrib, wctx)
    if tl.active:
        tl.end_activity(name, "QUANTIZE")
    if _compression.collecting():
        # The unsummed wire dequantizes to this rank's own effective
        # contribution (decompress is linear in the wire values).
        with jax.named_scope("EF_LOCAL"):
            _compression.record_local(
                comp.decompress(wire, meta, x.dtype, wctx))
    summed = _strategy.lower_allreduce(wire, algo, name, topo, gsize,
                                       channels=channels, compressed=True)
    if tl.active:
        tl.start_activity(name, "DEQUANTIZE")
    with jax.named_scope("DEQUANTIZE"):
        out = comp.decompress(summed, meta, x.dtype, wctx)
    if tl.active:
        tl.end_activity(name, "DEQUANTIZE")
    return out


def _leaf_dtype(x):
    """The dtype of one array, or of a bucket's leaves (one dtype a
    bucket: ops/fusion.py)."""
    return jax.tree.leaves(x)[0].dtype


def _traced_allreduce(tctx, x, group, average, name, comp=None, key=None,
                      members=None, algo="flat", cross_spec=None,
                      channels=1):
    """``x``: one array, or the tuple of a plain-sum fusion bucket's
    leaves in their own shapes (ops/fusion.py ``fused_apply``): a sum
    adds elementwise whatever the shape, so the tuple goes through the
    same masks and divides leaf by leaf and ONE plain sum
    (ops/strategy.py ``_plain_sum``: a ``lax.psum`` of the tuple — this
    JAX binds one a leaf, adjacent; XLA's combiner merges them — but for
    the large leaves of a whole-axis group, each a ring of
    collective-permutes). A wire that cuts or scales one flat buffer has
    no such form and refuses a tuple."""
    leaves = jax.tree.leaves(x)
    dtype, size = leaves[0].dtype, sum(v.size for v in leaves)
    applies = comp is not None and comp.applies_to(dtype)
    if isinstance(x, tuple) and (applies or algo != "flat" or channels != 1):
        raise HorovodError(
            f"allreduce of a tuple of leaves (tensor {name}) is the plain "
            f"sum only: compression, algo={algo!r} and channels={channels} "
            f"work on one flat buffer (ops/fusion.py packs it).")
    if not _is_group_index(group):
        if applies:
            raise HorovodError(
                f"Gradient compression ({comp.name}) does not support "
                f"group-family allreduce (tensor {name}): the slot-stacked "
                f"family lowering shares one wire buffer across groups with "
                f"different scales. Issue per-group compressed allreduces "
                f"or drop compression=.")
        # Families only take the slot-stacked/replica_groups lowering:
        # explicit phased algos raise, auto degrades to flat.
        _strategy.select(algo, nbytes=0, group=None, restricted=True,
                         name=name)
        _check_restricted_channels(channels, name)
        return _traced_allreduce_family(tctx, x, tuple(group), average, name)
    positions, gsize = _traced_groups_arg(tctx, group)
    wire_nbytes = _compression.wire_bytes(
        size, dtype, comp if applies else None, sum_width=gsize)
    if positions is None:
        # Price `auto` on what each candidate would actually move: the
        # gather-form flat for unsummable wire (int4), per-phase bytes
        # for phase-asymmetric formats (the optimizer's bucket selector
        # applies the same view — utils/costs.py choose()).
        select_kw = {}
        if applies or cross_spec is not None:
            intra_c, cross_c, asym = _compression.resolve_phase_formats(
                comp, cross_spec)
            if asym and jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
                select_kw["phase_nbytes"] = (
                    _compression.wire_bytes(size, dtype, intra_c),
                    _compression.wire_bytes(size, dtype, cross_c))
            if applies and not comp.summable:
                select_kw["gather"] = True
        concrete, topo = _strategy.select(
            algo, nbytes=wire_nbytes,
            group=_state.get_group(group), name=name, **select_kw)
        summed = _compressed_psum(x, comp, key, gsize, None, name, members,
                                  algo=concrete, topo=topo,
                                  cross_spec=cross_spec,
                                  channels=channels)
        return _divide_avg(summed, gsize, dtype) if average else summed
    # Subset group: masked full-axis psum (see _traced_groups_arg for why
    # not replica_groups; phased algos have no uniform partition here, so
    # explicit rs_ag/hierarchical raise and auto degrades to flat).
    # Members contribute x, everyone receives the member sum, non-members
    # restore their input.
    _strategy.select(algo, nbytes=0, group=None, restricted=True, name=name)
    _check_restricted_channels(channels, name)
    member = _traced_member_mask(tctx, group)
    summed = _compressed_psum(x, comp, key, gsize, member, name, members)
    if average:
        summed = _divide_avg(summed, gsize, dtype)
    return jax.tree.map(lambda s, v: jnp.where(member, s, v), summed, x)


def _check_restricted_channels(channels: int, name: str) -> None:
    """Subset groups and group families run the masked/slot-stacked flat
    lowering, which has no shard partition for channel instances to
    split; an explicit multi-channel request there raises rather than
    silently running one channel (the explicit-phased-algo precedent in
    ops/strategy.py ``select``)."""
    if channels > 1:
        raise HorovodError(
            f"channels={channels} (tensor {name}) requires a full-axis "
            f"single group: subset groups and group families only "
            f"support the single-instance masked-psum lowering. Drop "
            f"channels= or reduce on the full group.")


def _traced_allreduce_family(tctx, x, family, average, name):
    """One collective over a FAMILY of pairwise-disjoint groups: each group
    sums (averages) within itself, ranks in no listed group keep their value.

    This is the partitioned-communicator pattern the reference would need N
    sequential per-group collectives for: with tensor parallelism, gradients
    of TP-sharded parameters sync across *data-parallel families* — e.g.
    mesh {0..7} as 4 TP pairs has DP families [0,2,4,6] and [1,3,5,7] — and
    XLA runs the whole partition as a single AllReduce with replica_groups.
    """
    if not family:
        raise HorovodError(
            "allreduce group family is empty; pass at least one group "
            "index (or a plain int group).")
    prog = _state.get_group(tctx.group_index)
    seen: set[int] = set()
    groups, sizes = [], []
    for gi in family:
        pos = tctx.member_positions(gi)
        overlap = seen & set(pos)
        if overlap:
            raise HorovodError(
                f"allreduce group family {list(family)} is not pairwise "
                f"disjoint (mesh positions {sorted(overlap)} appear twice); "
                f"run overlapping groups as separate collectives.")
        seen |= set(pos)
        groups.append(pos)
        sizes.append(len(pos))
    # Membership / slot / divisor tables are known at trace time: one
    # table per quantity, indexed by the device's mesh position.
    div_np = np.ones((prog.size,), np.int32)
    member_np = np.zeros((prog.size,), bool)
    slot_np = np.zeros((prog.size,), np.int32)
    for si, (pos, sz) in enumerate(zip(groups, sizes)):
        for p in pos:
            div_np[p] = sz
            member_np[p] = True
            slot_np[p] = si
    idx = lax.axis_index(AXIS_NAME)
    dtype = _leaf_dtype(x)
    uniform_cover = len(set(sizes)) == 1 and len(seen) == prog.size
    if uniform_cover:
        # XLA replica_groups fast path: uniform covering partition, ONE
        # AllReduce, no extra traffic.
        summed = lax.psum(x, AXIS_NAME, axis_index_groups=groups)
        return _divide_avg(summed, sizes[0], dtype) if average else summed
    # Non-uniform or non-covering family: replica_groups would not lower
    # on TPU (see _traced_groups_arg). Slot-stacked masked psum — each
    # rank contributes x into its group's slot of an (n_groups, *shape)
    # buffer, one full-axis psum delivers every group's sum everywhere,
    # each rank reads its slot back. Wire bytes scale with len(family):
    # the price of odd-shaped families in one collective; equal-sized
    # covering families (the common TP/DP layout) never pay it.
    member = jnp.asarray(member_np)[idx]
    slot = jnp.asarray(slot_np)[idx]
    zero = jnp.zeros((), jnp.int32)

    def stack(v):
        buf = jnp.zeros((len(groups),) + v.shape, v.dtype)
        contrib = jnp.where(member, v, jnp.zeros_like(v))
        return lax.dynamic_update_slice(buf, contrib[None],
                                        (slot,) + (zero,) * v.ndim)

    def unstack(sums, v):
        summed = lax.dynamic_slice(sums, (slot,) + (zero,) * v.ndim,
                                   (1,) + tuple(v.shape))[0]
        if average:
            if len(set(sizes)) == 1:
                summed = _divide_avg(summed, sizes[0], dtype)
            else:
                div = jnp.asarray(div_np)[idx]
                summed = (summed // div
                          if jnp.issubdtype(dtype, jnp.integer)
                          else summed / div)
        return jnp.where(member, summed, v)

    all_sums = lax.psum(jax.tree.map(stack, x), AXIS_NAME)
    return jax.tree.map(unstack, all_sums, x)


def _family_partition(tctx, family, opname):
    """axis_index_groups for a family collective requiring a UNIFORM
    partition (XLA AllGather/ReduceScatter reject mixed group sizes, so —
    unlike the allreduce family, which pads with singletons — these
    families must cover the program's whole mesh)."""
    prog = _state.get_group(tctx.group_index)
    seen: set[int] = set()
    groups, sizes = [], set()
    for gi in family:
        pos = tctx.member_positions(gi)
        if seen & set(pos):
            raise HorovodError(
                f"{opname} group family {list(family)} is not pairwise "
                f"disjoint.")
        seen |= set(pos)
        groups.append(pos)
        sizes.add(len(pos))
    if len(sizes) != 1:
        raise HorovodError(
            f"{opname} group family {list(family)} has unequal group sizes "
            f"{sorted(sizes)}; XLA requires a uniform partition.")
    if len(seen) != prog.size:
        raise HorovodError(
            f"{opname} group family {list(family)} must cover the "
            f"program's whole mesh ({len(seen)} of {prog.size} positions).")
    return groups, sizes.pop()


def _traced_allgather(tctx, x, group, name):
    if not _is_group_index(group):
        groups, gsize = _family_partition(tctx, tuple(group), "allgather")
        g = lax.all_gather(x, AXIS_NAME, axis_index_groups=groups)
        return g.reshape((-1,) + tuple(x.shape[1:])) if x.ndim >= 1 else g
    positions, gsize = _traced_groups_arg(tctx, group)
    if positions is None:
        g = lax.all_gather(x, AXIS_NAME)  # (size, *shape)
        return g.reshape((-1,) + tuple(x.shape[1:])) if x.ndim >= 1 else g
    if x.ndim == 0:
        raise HorovodError(
            f"Rank zero tried to allgather a rank-zero tensor {name}, which "
            f"is not allowed.")
    # Subset allgather via scatter + masked full-axis psum (XLA AllGather
    # requires uniform group sizes, and subset replica_groups don't lower
    # on TPU at all — see _traced_groups_arg). Members place their block
    # at (group_rank * d0) and contribute; the psum assembles the
    # concatenation everywhere; non-members restore their own block at
    # slot 0 with zeros elsewhere — the SPMD analog of the
    # 'non-participants keep their input' convention.
    grank = tctx.rank(group)  # -1 for non-members
    member = grank >= 0
    d0 = x.shape[0]
    out_shape = (gsize * d0,) + tuple(x.shape[1:])
    buf = jnp.zeros(out_shape, dtype=x.dtype)
    start = (jnp.maximum(grank, 0) * d0).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    buf = lax.dynamic_update_slice(
        buf, x, (start,) + (zero,) * (x.ndim - 1))
    gathered = lax.psum(jnp.where(member, buf, jnp.zeros_like(buf)),
                        AXIS_NAME)
    return jnp.where(member, gathered, buf)


def _traced_broadcast(tctx, x, group, root_rank, name):
    positions, gsize = _traced_groups_arg(tctx, group)
    if not 0 <= root_rank < gsize:
        raise HorovodError(
            f"Invalid root rank {root_rank} for tensor {name} in a group "
            f"of size {gsize}.")
    subset = positions is not None
    grank = tctx.rank(group) if subset else lax.axis_index(AXIS_NAME)
    orig_dtype = x.dtype
    xv = x.astype(jnp.int32) if orig_dtype == jnp.bool_ else x
    # Only the root contributes, so the full-axis psum IS the broadcast —
    # no replica_groups needed for subsets (see _traced_groups_arg).
    masked = jnp.where(grank == root_rank, xv, jnp.zeros_like(xv))
    out = lax.psum(masked, AXIS_NAME)
    if orig_dtype == jnp.bool_:
        out = out.astype(jnp.bool_)
    if subset:
        out = jnp.where(grank >= 0, out, x)  # non-members keep their input
    return out


def _divide_avg(x, n: int, dtype):
    """``x`` (one array, or a bucket's leaves: each where it lies) / n."""
    if jnp.issubdtype(dtype, jnp.integer):
        # reference averages via tf.div → integer division
        return jax.tree.map(lambda v: v // n, x)
    return jax.tree.map(lambda v: v / n, x)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def allreduce(x, group: int = 0, average: bool = True, name: str | None = None,
              members: tuple[str, ...] | None = None,
              compression=None, compression_key=None, algo=None,
              cross_compression=None, channels=None):
    """Sum (optionally average) across the group.

    Reference: ``hvd.allreduce`` (tensorflow/__init__.py:47-83) →
    ``HorovodAllreduceOp`` (mpi_ops.cc:2245-2299) → ``MPI_Allreduce``/NCCL
    (mpi_ops.cc:1274, :1121). Sum happens in the collective; averaging is a
    local divide, as in the reference (division in Python, :80-82).

    ``group`` may also be a sequence of group indices — a *family* of
    pairwise-disjoint groups reduced in ONE collective (each group within
    itself; see :func:`_traced_allreduce_family`). Traced-only: the family
    form exists for sharded-parameter gradient sync inside compiled steps.

    ``members``: labels of the tensors of this call when it is a fusion
    bucket (set by :func:`horovod_tpu.ops.fusion.fused_apply`) — carried
    on the trace-time schedule so the device timeline can map a bucket's
    span back onto its member tensor rows. A packed bucket arrives as one
    flat buffer; a plain-sum bucket as the TUPLE of its leaves in their
    own shapes (traced-only, same dtype, no compression / phased algo /
    channels): one plain sum over the tuple, a tuple returned.

    ``compression``: a wire format name (``"bf16"``/``"int8"``) or
    :class:`~horovod_tpu.ops.compression.Compressor` — the collective then
    moves the compressed representation (ops/compression.py). Traced-only;
    ``None`` here means OFF (the ``HOROVOD_COMPRESSION`` environment
    default applies to the gradient path — ``allreduce_gradients`` /
    ``DistributedOptimizer`` — not to raw value collectives, so eager
    metric/batchnorm reductions never quantize by accident).
    ``compression_key``: optional PRNG key for stochastic-rounding
    compressors, threaded per step.

    ``cross_compression``: per-phase wire-format override for the
    hierarchical decomposition's cross-slice DCN hop (a compressor name
    or instance; ops/compression.py ``resolve_phase_formats``) — the
    intra-slice ICI phases then move full-precision (or bf16, when
    ``compression="bf16"``) payloads while only the DCN hop quantizes.
    Inert for ``flat``/``rs_ag`` (no cross-slice phase). ``None`` here
    means no override; the ``HOROVOD_COMPRESSION_CROSS_SLICE``
    environment default applies to the gradient path only.

    ``algo``: allreduce decomposition (ops/strategy.py) —
    ``"flat"`` (one psum, the default), ``"rs_ag"`` (reduce-scatter +
    all-gather phases), ``"hierarchical"`` (intra-slice RS → cross-slice
    AR → intra-slice AG on multi-slice topologies), or ``"auto"``
    (α–β cost-model choice per call, utils/costs.py). A *lowering*
    decision only: every algorithm computes the same group sum with
    replicas in exact lockstep (reduction order may re-associate, as
    with any collective-implementation change — ops/strategy.py).
    Traced-only, full-axis single groups only (subset groups and
    families refuse explicit phased algos and run flat under auto).
    ``None`` here means flat; the ``HOROVOD_ALLREDUCE_ALGO`` environment
    default applies to the gradient path (``allreduce_gradients`` /
    ``DistributedOptimizer``), not to raw value collectives.

    ``channels``: concurrent channel instances of the wire collective(s)
    (ops/strategy.py channelized lowerings) — the bucket splits into
    that many shards, each lowered as its own collective so XLA can
    overlap their phases; bit-exact vs the single instance for every
    algorithm × compression. Traced-only, full-axis single groups only
    (subset groups and families raise on channels > 1). ``None`` here
    means 1; the ``HOROVOD_EXCHANGE_CHANNELS`` / ``HOROVOD_MAX_CHANNELS``
    planner machinery applies to the gradient path only.
    """
    name = _auto_name("HorovodAllreduce", name)
    ch = _strategy.resolve_channels(channels)
    comp = (None if compression is None
            else _compression.resolve(compression))
    if isinstance(comp, _compression.NoneCompressor):
        comp = None  # explicit "none": the exact uncompressed path
    algo_spec = _strategy.resolve_spec(algo)
    tctx = _ctx.current()
    if tctx is not None:
        reg_group = (int(group) if _is_group_index(group)
                     else tuple(group))
        # A bucket reduced in its leaves' own shapes is still one row:
        # its shapes stand where a packed bucket's flat length does.
        shape = (tuple(v.shape for v in x) if isinstance(x, tuple)
                 else x.shape)
        tctx.register(name, "ALLREDUCE", _leaf_dtype(x), shape, reg_group,
                      members=members)
        return _traced_allreduce(tctx, x, group, average, name,
                                 comp, compression_key, members,
                                 algo=algo_spec,
                                 cross_spec=cross_compression,
                                 channels=ch)
    if comp is not None:
        raise HorovodError(
            f"compression={comp.name!r} is only supported inside hvd.spmd "
            f"traced programs (the compiled gradient path); eager value "
            f"collectives always run uncompressed. Drop compression= or "
            f"move the call inside hvd.spmd.")
    if cross_compression is not None:
        raise HorovodError(
            f"cross_compression={cross_compression!r} is only supported "
            f"inside hvd.spmd traced programs: the per-phase wire format "
            f"is a property of the compiled hierarchical lowering. Drop "
            f"it or move the call inside hvd.spmd.")
    if algo_spec != "flat":
        raise HorovodError(
            f"algo={algo_spec!r} is only supported inside hvd.spmd traced "
            f"programs: the decomposition is a property of the compiled "
            f"lowering. Eager collectives always run the flat psum; drop "
            f"algo= or move the call inside hvd.spmd.")
    if ch != 1:
        raise HorovodError(
            f"channels={ch} is only supported inside hvd.spmd traced "
            f"programs: the channel split is a property of the compiled "
            f"lowering. Eager collectives always run one instance; drop "
            f"channels= or move the call inside hvd.spmd.")
    if not _is_group_index(group):
        raise HorovodError(
            "Group-family allreduce is only available inside hvd.spmd traced "
            "code; eagerly, issue one allreduce per group.")
    g = _state.get_group(group)
    xs, ranks, was_list = _eager_inputs(x, g)
    _validate(xs, _neg.CollectiveOp.ALLREDUCE, name, g, ranks, group=group)
    if _mh.active() and not ranks:
        return [] if was_list else None  # no local members of the group
    with _activity(name, "XLA_ALLREDUCE"):
        outs = _eager_psum(g, xs, ranks)
    if average:
        outs = [_divide_avg(o, g.size, o.dtype) for o in outs]
    return list(outs) if was_list else outs[0]


def allgather(x, group: int = 0, name: str | None = None):
    """Concatenate every rank's tensor along dim 0; first dims may differ.

    Reference: ``HorovodAllgatherOp`` (mpi_ops.cc:2301-2356) →
    ``MPI_Allgatherv`` (mpi_ops.cc:911-928). The variable first dimension is
    negotiated via per-rank sizes in the response (mpi_message.h:124-129);
    eagerly we realise it as pad-to-max + AllGather + trim, traced it requires
    uniform shapes (static SPMD shapes).
    """
    name = _auto_name("HorovodAllgather", name)
    tctx = _ctx.current()
    if tctx is not None:
        reg_group = (int(group) if _is_group_index(group)
                     else tuple(group))
        tctx.register(name, "ALLGATHER", x.dtype, x.shape, reg_group)
        return _traced_allgather(tctx, x, group, name)
    if not _is_group_index(group):
        raise HorovodError(
            "Group-family allgather is only available inside hvd.spmd "
            "traced code; eagerly, issue one allgather per group.")
    g = _state.get_group(group)
    xs, ranks, _ = _eager_inputs(x, g)
    resp = _validate(xs, _neg.CollectiveOp.ALLGATHER, name, g, ranks,
                     group=group)
    if _mh.active() and not ranks:
        return None  # no local members: gathered result lives elsewhere
    with _activity(name, "XLA_ALLGATHER"):
        return _eager_allgather_padded(g, xs, ranks,
                                       list(resp.tensor_sizes))


def broadcast(x, root_rank: int, group: int = 0, name: str | None = None):
    """Every rank receives the root's tensor.

    Reference: ``HorovodBroadcastOp`` (mpi_ops.cc:2358-2421) → ``MPI_Ibcast``
    (mpi_ops.cc:1347-1351). Lowered as a masked CrossReplicaSum (one psum),
    the standard XLA broadcast idiom over ICI.
    """
    name = _auto_name("HorovodBroadcast", name)
    tctx = _ctx.current()
    if tctx is not None:
        tctx.register(name, "BROADCAST", x.dtype, x.shape, group, root_rank)
        return _traced_broadcast(tctx, x, group, root_rank, name)
    g = _state.get_group(group)
    xs, ranks, was_list = _eager_inputs(x, g)
    _validate(xs, _neg.CollectiveOp.BROADCAST, name, g, ranks, root_rank,
              group=group)
    if _mh.active() and not ranks:
        return [] if was_list else None
    orig_dtype = xs[0].dtype
    vals = xs
    if orig_dtype == jnp.bool_:
        vals = [v.astype(jnp.int32) for v in vals]
    masked = [v if r == root_rank else jnp.zeros_like(v)
              for r, v in zip(ranks, vals)]
    with _activity(name, "XLA_BCAST"):
        outs = _eager_psum(g, masked, ranks)
    if orig_dtype == jnp.bool_:
        outs = [o.astype(jnp.bool_) for o in outs]
    return list(outs) if was_list else outs[0]


def gather(x, root_rank: int, group: int = 0, name: str | None = None):
    """Rooted gather — the fork's novel op (mpi_ops.cc:2425-2504).

    Eager: returns a per-rank list; the root's entry is the concatenation of
    every rank's tensor along dim 0 (``MPI_Gatherv``, mpi_ops.cc:1013-1015),
    every other rank's entry is its own input unchanged (the kernel sets
    non-root output = input, mpi_ops.cc:2444-2447). Traced/SPMD: static shapes
    force a uniform output, so every member receives the gathered tensor
    (lowering = allgather); non-roots should ignore it — same data movement,
    same result at the root.
    """
    name = _auto_name("HorovodGather", name)
    tctx = _ctx.current()
    if tctx is not None:
        tctx.register(name, "GATHER", x.dtype, x.shape, group, root_rank)
        return _traced_allgather(tctx, x, group, name)
    g = _state.get_group(group)
    xs, ranks, _ = _eager_inputs(x, g)
    resp = _validate(xs, _neg.CollectiveOp.GATHER, name, g, ranks, root_rank,
                     group=group)
    if _mh.active() and not ranks:
        return []
    with _activity(name, "XLA_GATHER"):
        gathered = _eager_allgather_padded(g, xs, ranks,
                                           list(resp.tensor_sizes))
    return [gathered if r == root_rank else xs[j]
            for j, r in enumerate(ranks)]


# ---------------------------------------------------------------------------
# Alltoall (extension beyond the fork: upstream Horovod grew hvd.alltoall in
# 0.19; it is required here as the transport for all-to-all sequence
# parallelism — Ulysses-style attention in horovod_tpu.parallel.sequence).
# ---------------------------------------------------------------------------


def _traced_alltoall(tctx, x, group, name):
    if not _is_group_index(group):
        # Family form: each group exchanges within itself, one XLA AllToAll
        # over the uniform partition (DP x EP's transport).
        groups, gsize = _family_partition(tctx, tuple(group), "alltoall")
        if x.ndim == 0 or x.shape[0] % gsize != 0:
            raise HorovodError(
                f"Invalid alltoall tensor shape: first dimension of tensor "
                f"{name} ({list(x.shape)}) must be divisible by the group "
                f"size {gsize}.")
        return lax.all_to_all(x, AXIS_NAME, split_axis=0, concat_axis=0,
                              tiled=True, axis_index_groups=groups)
    positions, gsize = _traced_groups_arg(tctx, group)
    if x.ndim == 0 or x.shape[0] % gsize != 0:
        raise HorovodError(
            f"Invalid alltoall tensor shape: first dimension of tensor "
            f"{name} ({list(x.shape)}) must be divisible by the group size "
            f"{gsize}.")
    if positions is None:
        return lax.all_to_all(x, AXIS_NAME, split_axis=0, concat_axis=0,
                              tiled=True)
    # Subset group inside a bigger program: XLA AllToAll requires a uniform
    # partition, which the members+singletons cover can't provide. Use the
    # Bruck algorithm over ppermute instead: ceil(log2 g) rounds, round k
    # shifting the slots whose index has bit k set by +2^k around the group
    # ring. Every perm is STATIC (the round's shift), so program size is
    # O(log g) — a pod-wide subset group (64-256 ranks, BASELINE.md's v5e-256
    # north star) compiles in 6-8 rounds instead of g-1 unrolled ppermutes.
    # Bandwidth is (g/2)·log2(g) blocks vs the optimal g-1 — the classic
    # latency/program-size trade, right for a compiled SPMD program.
    #
    # Invariant: after the initial rotation, slot j at group rank r holds the
    # block (src=r, dst=r+j). A block at slot j moves in exactly the rounds
    # where bit k of j is set, always staying at slot j, so its total
    # displacement is j and it ends at its destination.
    member_positions = positions  # this group's mesh positions, group order
    grank = tctx.rank(group)  # -1 for non-members
    grank_c = jnp.maximum(grank, 0)
    member = grank >= 0
    block = x.shape[0] // gsize
    blocks = x.reshape((gsize, block) + tuple(x.shape[1:]))
    if gsize == 1:
        return x
    # Phase 1: local rotation so slot j holds the block destined for r+j.
    data = jnp.roll(blocks, -grank_c, axis=0)
    # Phase 2: log-rounds of static-shift exchanges.
    for k in range((gsize - 1).bit_length()):
        shift = 1 << k
        idx = [j for j in range(gsize) if j & shift]  # static slot list
        perm = [(member_positions[m],
                 member_positions[(m + shift) % gsize])
                for m in range(gsize)]
        sent = data[jnp.asarray(idx)]  # (|idx|, block, ...) static gather
        received = lax.ppermute(sent, AXIS_NAME, perm)
        updated = data.at[jnp.asarray(idx)].set(received)
        # Non-members aren't in the perm (they'd receive zeros): identity.
        data = jnp.where(member, updated, data)
    # Phase 3: slot j now holds the block from src = r - j; reorder so
    # out[src] = that block (reverse + rotate by r+1).
    out = jnp.roll(data[::-1], grank_c + 1, axis=0)
    out = jnp.where(member, out, blocks)  # non-members: keep own tensor
    return out.reshape(x.shape)


def _traced_reducescatter(tctx, x, group, name):
    if not _is_group_index(group):
        groups, gsize = _family_partition(tctx, tuple(group),
                                          "reducescatter")
        if x.ndim == 0 or x.shape[0] % gsize != 0:
            raise HorovodError(
                f"Invalid reducescatter tensor shape: first dimension of "
                f"tensor {name} ({list(x.shape)}) must be divisible by the "
                f"group size {gsize}.")
        return lax.psum_scatter(x, AXIS_NAME, scatter_dimension=0,
                                axis_index_groups=groups, tiled=True)
    positions, gsize = _traced_groups_arg(tctx, group)
    if x.ndim == 0 or x.shape[0] % gsize != 0:
        raise HorovodError(
            f"Invalid reducescatter tensor shape: first dimension of tensor "
            f"{name} ({list(x.shape)}) must be divisible by the group size "
            f"{gsize}.")
    block = x.shape[0] // gsize
    if positions is None:
        return lax.psum_scatter(x, AXIS_NAME, scatter_dimension=0,
                                tiled=True)
    # Subset group inside a bigger program: XLA ReduceScatter needs a
    # uniform partition, which members+singletons can't provide — but a
    # psum+slice moves ~2x the optimal bytes (every rank materializes the
    # full sum it keeps 1/g of). Build the reduce-scatter from static
    # ppermutes instead, like the Bruck subset alltoall above:
    #
    # * power-of-two g — recursive halving: log2(g) rounds, round k
    #   exchanging half the live working set with the partner at group
    #   distance g/2^(k+1) and summing. Bytes on the wire:
    #   n/2 + n/4 + ... = n·(1-1/g), the reduce-scatter optimum, with an
    #   O(log g) program (pod-scale subset groups compile in 6-8 rounds).
    # * other g — ring: g-1 rounds each moving one accumulated block to
    #   the right neighbour. Same optimal n·(g-1)/g bytes, O(g) program —
    #   acceptable for the odd-sized groups it serves.
    #
    # Non-members sit outside every perm (ppermute hands them zeros); the
    # final where() restores their 'keep your input' convention.
    member_positions = positions  # this group's mesh positions, group order
    grank = tctx.rank(group)
    grank_c = jnp.maximum(grank, 0)
    member = _traced_member_mask(tctx, group)
    if gsize == 1:
        return x[:block]
    blocks = x.reshape((gsize, block) + tuple(x.shape[1:]))
    if gsize & (gsize - 1) == 0:
        # Recursive halving. Invariant: entering round k the working set W
        # holds the 2^k-subcube partial sums of the g>>k consecutive blocks
        # selected by grank's top k bits; W[0] after the last round is this
        # rank's fully-reduced block.
        w = blocks
        half = gsize // 2
        while half >= 1:
            lo, hi = w[:half], w[half:]
            bit = (grank_c & half) != 0
            send = jnp.where(bit, lo, hi)   # the half the partner keeps
            keep = jnp.where(bit, hi, lo)
            perm = [(member_positions[m], member_positions[m ^ half])
                    for m in range(gsize)]
            recv = lax.ppermute(send, AXIS_NAME, perm)
            w = keep + recv
            half //= 2
        out = w[0]
    else:
        # Ring. At step s every member sends accumulated block
        # (r-s-1) mod g to its right neighbour and folds the received
        # block (r-s-2) mod g into its own contribution; after g-1 steps
        # rank r holds the complete block r.
        perm = [(member_positions[m], member_positions[(m + 1) % gsize])
                for m in range(gsize)]
        acc = blocks
        for s in range(gsize - 1):
            send_idx = (grank_c - s - 1) % gsize
            recv_idx = (grank_c - s - 2) % gsize
            sent = lax.dynamic_slice_in_dim(acc, send_idx, 1, axis=0)
            recv = lax.ppermute(sent, AXIS_NAME, perm)
            own = lax.dynamic_slice_in_dim(acc, recv_idx, 1, axis=0)
            acc = lax.dynamic_update_slice_in_dim(acc, own + recv,
                                                  recv_idx, axis=0)
        out = lax.dynamic_slice_in_dim(acc, grank_c, 1, axis=0)[0]
    if member is None:
        return out
    # Non-members: their own first block, unreduced (the non-participant
    # 'keep your input' convention, sliced to the uniform output shape).
    return jnp.where(member, out, blocks[0])


def reducescatter(x, group: int = 0, name: str | None = None):
    """Sum across the group, then scatter: rank i receives the i-th of
    ``size`` equal dim-0 blocks of the elementwise sum.

    Extension beyond the fork (upstream Horovod grew ``hvd.reducescatter``
    in 0.27); on TPU it lowers to XLA ReduceScatter — the bandwidth-optimal
    half of an allreduce, and the building block for sequence-sharded
    tensor-parallel activations. Dim 0 must be divisible by the group size.
    Eagerly: per-rank value lists in, per-rank output slices back.
    """
    name = _auto_name("HorovodReducescatter", name)
    tctx = _ctx.current()
    if tctx is not None:
        reg_group = (int(group) if _is_group_index(group)
                     else tuple(group))
        tctx.register(name, "REDUCESCATTER", x.dtype, x.shape, reg_group)
        return _traced_reducescatter(tctx, x, group, name)
    if not _is_group_index(group):
        raise HorovodError(
            "Group-family reducescatter is only available inside hvd.spmd "
            "traced code; eagerly, issue one reducescatter per group.")
    g = _state.get_group(group)
    xs, ranks, _ = _eager_inputs(x, g)
    _validate(xs, _neg.CollectiveOp.REDUCESCATTER, name, g, ranks,
              group=group)
    if _mh.active() and not ranks:
        return []
    block = xs[0].shape[0] // g.size
    with _activity(name, "XLA_REDUCESCATTER"):
        summed = _eager_psum(g, xs, ranks)
    return [summed[j][r * block:(r + 1) * block]
            for j, r in enumerate(ranks)]


def alltoall(x, group: int = 0, name: str | None = None):
    """Distribute equal splits of dim 0 to every rank and concatenate what is
    received: rank m's j-th block lands in rank j's output at slot m.

    Eager: always returns a per-rank list (outputs differ per rank even for
    identical inputs, like ``gather``); the exchange is one device
    ``all_to_all`` over the group mesh in both controller modes — like
    every other eager collective. Traced: ``lax.all_to_all`` on the mesh
    axis (Bruck ppermute rounds for subset groups). Dim 0 must be
    divisible by group size on every rank (uniform splits).
    """
    name = _auto_name("HorovodAlltoall", name)
    tctx = _ctx.current()
    if tctx is not None:
        reg_group = (int(group) if _is_group_index(group)
                     else tuple(group))
        tctx.register(name, "ALLTOALL", x.dtype, x.shape, reg_group)
        return _traced_alltoall(tctx, x, group, name)
    if not _is_group_index(group):
        raise HorovodError(
            "Group-family alltoall is only available inside hvd.spmd "
            "traced code; eagerly, issue one alltoall per group.")
    g = _state.get_group(group)
    xs, ranks, _ = _eager_inputs(x, g)
    _validate(xs, _neg.CollectiveOp.ALLTOALL, name, g, ranks, group=group)
    if _mh.active() and not ranks:
        return []
    # One real device collective in BOTH controller modes (r3 review: the
    # single-controller path used host-side slice/concat, so the default
    # test world never exercised the device exchange the multihost path
    # runs).
    with _activity(name, "XLA_ALLTOALL"):
        out = _alltoall_device_fn(g.index, xs[0].ndim)(
            _stack_ranked(g, xs))
    return _unstack_ranked(g, out, ranks)
