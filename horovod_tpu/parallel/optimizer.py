"""DistributedOptimizer and variable broadcast — the training-loop API.

Reference: ``hvd.DistributedOptimizer`` wraps any ``tf.train.Optimizer`` and
allreduce-averages every gradient inside ``compute_gradients``
(tensorflow/__init__.py:132-232); ``broadcast_global_variables`` syncs initial
weights from a root rank (:86-94). TPU-native equivalents target optax: the
wrapper is an ``optax.GradientTransformation`` that averages gradients across
the group *before* the inner transformation sees them (so Adam/momentum
statistics match single-process semantics, exactly as in the reference where
the allreduce happens in compute_gradients, before apply), with the
reference's tensor-fusion behavior (64 MB buckets, ``HOROVOD_FUSION_THRESHOLD``)
applied to the gradient pytree.
"""

from __future__ import annotations

import os
import typing

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.core import context as _ctx
from horovod_tpu.core import state as _state
from horovod_tpu.core.state import HorovodError
from horovod_tpu.ops import collectives as _coll
from horovod_tpu.ops import compression as _compression
from horovod_tpu.ops import exchange as _exchange
from horovod_tpu.ops import fusion as _fusion
from horovod_tpu.ops import mesh as _mesh
from horovod_tpu.ops import sparse as _sparse
from horovod_tpu.ops import strategy as _strategy
from horovod_tpu.ops import topology as _topology
from horovod_tpu.tune import apply as _tune_apply
from horovod_tpu.utils import costs as _costs
from horovod_tpu.utils import env as _env


# The named scopes every optimizer here traces its two halves under: the
# leading components of each instruction's op_name in the compiled step
# (pack, convert, collective, unpack | the inner transformation's pass),
# by which a profiler capture's device time is told apart.
EXCHANGE_SCOPE = "hvd.exchange"
UPDATE_SCOPE = "hvd.update"


class ErrorFeedbackState(typing.NamedTuple):
    """Optimizer-state wrapper carrying the error-feedback residual
    pytree alongside the inner optimizer's state. A plain pytree, so the
    PR 4 checkpoint layer persists and restores the residuals with the
    rest of the optimizer state — resumed training continues the exact
    compensation sequence (tests/test_block_compression.py pins the
    round-trip)."""

    inner: object
    residual: object


def allreduce_gradients(grads, group: int = 0, average: bool = True,
                        fusion_threshold: int | None = None,
                        compression=None, compression_key=None,
                        algo=None, schedule=None, priority_fn=None,
                        cross_compression=None, error_residual=None,
                        channels=None, sparse_algo=None):
    """Allreduce-average a gradient pytree with tensor fusion.

    Must run inside an ``hvd.spmd`` program (the analog of being inside the
    graph the reference builds). Leaves that are :class:`IndexedSlices` take
    the sparse exchange family (ops/sparse.py: padded allgather +
    dedup-and-merge, densify + allreduce, or the ``auto`` density
    switch — tensorflow/__init__.py:65-76 is the reference semantics).
    ``group`` may be a group family (tuple of disjoint group indices) —
    the DP-family sync for tensor-parallel shards; fusion applies as
    usual. Sparse leaves do not support families.

    ``fusion_threshold``: bytes a fusion bucket may hold (``None``: the
    ``HOROVOD_FUSION_THRESHOLD`` default, 64 MB; 0 = a bucket a leaf).
    It decides the buckets, one collective each, whatever their wire. The
    flat fusion buffer is built only for a bucket that needs one (a
    compressed wire, ``rs_ag`` / ``hierarchical``, ``channels > 1``); a
    plain-sum bucket is reduced in its leaves' own shapes, one
    ``lax.psum`` over the tuple (ops/fusion.py ``Bucket.packed``).

    ``compression``: wire compression for the dense buckets
    (``"bf16"``/``"int8"``/a :class:`~horovod_tpu.ops.compression.
    Compressor`; ops/compression.py). ``None`` defers to the
    ``HOROVOD_COMPRESSION`` environment default (unset = off, bit-identical
    to the uncompressed path). Sparse leaves apply the same knob to their
    VALUE payload in gather form (per-rank scales, nothing summed on the
    wire, fp32 accumulation on arrival — ops/sparse.py); indices never
    compress, and subset-group sparse exchanges stay uncompressed (the
    refusal paths in ops/sparse.py).
    ``compression_key``: optional per-step PRNG key for stochastic-rounding
    compressors (int8); without it the key is derived from the gradient
    bits, re-rolling every step inside the fixed compiled program.

    ``sparse_algo``: lowering for the sparse leaves — ``"gather"``
    (default: the reference's allgather path, upgraded with the padded
    wire format and dedup-and-merge), ``"dense"`` (densify + allreduce),
    or ``"auto"`` (density-based switch priced by the α–β cost model;
    ``HOROVOD_SPARSE_DENSITY_THRESHOLD`` overrides the crossover —
    ops/sparse.py). Full-axis single groups only; subset groups run the
    plain gather and refuse the rest. The resolved sparse rows are
    recorded on the committed exchange plan (``.exchange.json`` —
    serialized only when sparse leaves exist, so dense-only plan hashes
    are unchanged).

    ``algo``: allreduce decomposition per fusion bucket
    (``"flat"``/``"rs_ag"``/``"hierarchical"``/``"auto"``;
    ops/strategy.py). ``None`` defers to the ``HOROVOD_ALLREDUCE_ALGO``
    environment default (unset = ``flat``, the exact pre-strategy
    lowering). Under ``auto`` the α–β cost model (utils/costs.py) picks
    per bucket from its wire bytes and the discovered topology
    (ops/topology.py) — a lowering decision only, numerics unchanged.
    With ``HOROVOD_AUTOTUNE=1`` (and no explicit ``fusion_threshold=`` /
    ``HOROVOD_FUSION_THRESHOLD``) the cost model also retunes the fusion
    threshold — from the tuning cache when ``tools/allreduce_bench.py
    --calibrate`` has written one, analytically otherwise.

    ``schedule``: the whole-step exchange schedule (ops/exchange.py) —
    ``"enum"`` (buckets issued in pytree-enumeration order under the one
    global threshold, the pre-scheduler behavior) or ``"priority"``
    (reverse-layer first-needed-first issue order with per-region
    overlap-aware bucket sizing; bit-exact — same summands, only
    ordering/sizing change). ``None`` defers to
    ``HOROVOD_EXCHANGE_SCHEDULE`` (unset = ``enum``); typos raise.
    ``priority_fn(label, index) -> key`` optionally re-ranks leaves
    under ``"priority"`` (lower key = issued earlier; default is
    reverse enumeration). The committed plan is registered for the
    timeline (SCHEDULE row logs plan hash + per-bucket priority) and
    retrievable via :func:`horovod_tpu.ops.exchange.last_plan`.

    ``cross_compression``: per-phase wire-format override for
    hierarchical buckets' cross-slice DCN hop (ops/compression.py
    ``resolve_phase_formats``; inert for flat/rs_ag buckets). ``None``
    defers to ``HOROVOD_COMPRESSION_CROSS_SLICE`` (validated at
    ``hvd.init``; unset = the bucket compressor's own policy — the
    block/int4 formats are phase-asymmetric by default).

    ``channels``: channel count for the channelized bucket lowerings
    (ops/strategy.py) — each bucket splits into that many concurrent
    channel instances, bit-exact vs the single instance at any count.
    ``None`` defers to ``HOROVOD_EXCHANGE_CHANNELS`` when set, else the
    exchange planner chooses per bucket from the per-channel α–β model,
    capped by ``HOROVOD_MAX_CHANNELS`` (default 1 = channelization off —
    plans keep their pre-channel hashes). Requires the full-axis single
    group, like every phased lowering; subset groups and families run
    single-channel (an explicit count there raises).

    ``error_residual``: a pytree congruent with ``grads`` holding each
    rank's error-feedback residuals. When given, each dense float leaf
    contributes ``grad + residual`` to the exchange and the function
    returns ``(reduced, new_residual)`` where the new residual is the
    leaf's local quantization error (``contributed − dequantized own
    wire``; exactly zero for uncompressed buckets and for buckets whose
    quantization error is not attributable to this rank's own gradient —
    the phase-asymmetric hierarchical cross hop). Requires the full-axis
    single group (a subset/family exchange masks contributions, which
    would corrupt the residual algebra).
    """
    tctx = _ctx.current()
    if tctx is None:
        raise HorovodError(
            "allreduce_gradients must be called inside an hvd.spmd-wrapped "
            "step function (the SPMD analog of the reference's graph).")
    # Phased decompositions need the full-axis single-group lowering;
    # families and subset groups run the flat masked/slot-stacked scheme
    # (explicit rs_ag/hierarchical raise in strategy.select below).
    g_obj = (_state.get_group(group) if isinstance(group, (int, np.integer))
             else None)
    restricted = g_obj is None or int(group) != tctx.group_index

    def _tuned(name):
        # Applied TunedConfig value for an env knob (tune/apply.py):
        # None unless a config is active AND the env doesn't set the
        # knob (explicit env always beats tuned). Restricted groups
        # keep their defaults — the artifact was tuned for the
        # full-axis exchange, and e.g. a tuned hierarchical algo has no
        # subset-group lowering to fall back on.
        return None if restricted else _tune_apply.override(name)

    if algo is None:
        tuned_algo = _tuned("HOROVOD_ALLREDUCE_ALGO")
        algo_spec = (_strategy.resolve_spec(tuned_algo)
                     if tuned_algo is not None
                     else _strategy.gradient_algo_default())
    else:
        algo_spec = _strategy.resolve_spec(algo)
    exchange_mode = _exchange.resolve_mode(
        schedule if schedule is not None
        else _tuned("HOROVOD_EXCHANGE_SCHEDULE"))
    if fusion_threshold is None:
        tuned_threshold = _tuned("HOROVOD_FUSION_THRESHOLD")
        if tuned_threshold is not None:
            fusion_threshold = int(tuned_threshold)
        else:
            fusion_threshold = _state.fusion_threshold()
            if (_env.autotune_enabled()
                    and os.environ.get("HOROVOD_FUSION_THRESHOLD") is None):
                tune_group = g_obj if g_obj is not None \
                    else _state.get_group(tctx.group_index)
                fusion_threshold = _costs.tuned_fusion_threshold(
                    _topology.discover(tune_group))
    comp = _compression.resolve(
        compression if compression is not None
        else _tuned("HOROVOD_COMPRESSION"))
    if isinstance(comp, _compression.NoneCompressor):
        comp = None
    cross_spec = cross_compression
    if cross_spec is None:
        cross_spec = _tuned("HOROVOD_COMPRESSION_CROSS_SLICE")
    if cross_spec is None:
        cross_spec = _env.compression_cross_slice_default()
    # Channel resolution: explicit channels= > HOROVOD_EXCHANGE_CHANNELS
    # > the planner's per-bucket cost-model choice under
    # HOROVOD_MAX_CHANNELS (default 1 — channelization off). Restricted
    # groups have no shard partition for channels to split: an explicit
    # multi-channel request raises (ops/collectives.py), the planner
    # simply never assigns one.
    explicit_channels = (_strategy.resolve_channels(channels)
                         if channels is not None
                         else _env.exchange_channels_default())
    channel_cap = _env.max_channels()
    tuned_cap = _tuned("HOROVOD_MAX_CHANNELS")
    if tuned_cap is not None:
        channel_cap = int(tuned_cap)
    if restricted:
        if explicit_channels is not None and explicit_channels > 1:
            raise HorovodError(
                f"channels={explicit_channels} requires the full-axis "
                f"single group: subset groups and group families only "
                f"support the single-instance masked-psum lowering. "
                f"Use group=0 (the global group) or drop channels=.")
        explicit_channels, channel_cap = None, 1
    if error_residual is not None and restricted:
        raise HorovodError(
            "error_residual requires the full-axis single group: a "
            "subset-group or group-family exchange masks non-member "
            "contributions, which would corrupt the residual algebra. "
            "Use group=0 (the global group) or drop error feedback.")

    # Discover the topology ONCE per trace, not once per bucket — a model
    # has hundreds of buckets and discovery walks every group device.
    # The priority scheduler also wants it (sizing floor + the artifact's
    # declared partition shape).
    bucket_topo = (_topology.discover(g_obj)
                   if not restricted
                   and (algo_spec in ("auto", "hierarchical")
                        or exchange_mode == "priority"
                        or channel_cap > 1)
                   else None)
    gsize = g_obj.size if g_obj is not None else None

    def bucket_algo(bucket):
        kwargs = {}
        if not restricted and (comp is not None or cross_spec is not None):
            # The phase-asymmetric view of this bucket, so `auto` prices
            # the hierarchical candidate on what each phase would
            # actually move (int4 DCN hop = 1/8th of fp32) and the
            # gather-based flat lowering on its (n-1)-factor bytes.
            intra_c, cross_c, asym = _compression.resolve_phase_formats(
                comp, cross_spec)
            if asym and jnp.issubdtype(jnp.dtype(bucket.dtype),
                                       jnp.floating):
                elems = bucket.elems
                intra_b = _compression.wire_bytes(elems, bucket.dtype,
                                                  intra_c)
                cross_b = _compression.wire_bytes(elems, bucket.dtype,
                                                  cross_c)
                kwargs["phase_nbytes"] = (intra_b, cross_b)
            if comp is not None and not comp.summable:
                kwargs["gather"] = True
        concrete, _ = _strategy.select(
            algo_spec, nbytes=bucket.bytes_on_wire, group=g_obj,
            restricted=restricted, name="gradient bucket", topo=bucket_topo,
            **kwargs)
        return concrete

    is_sparse = lambda leaf: isinstance(leaf, _sparse.IndexedSlices)
    leaves, treedef = jax.tree.flatten(grads, is_leaf=is_sparse)
    paths = [jax.tree_util.keystr(p, simple=True, separator="/")
             for p, _ in jax.tree_util.tree_flatten_with_path(
                 grads, is_leaf=is_sparse)[0]]
    dense_idx = [i for i, l in enumerate(leaves) if not is_sparse(l)]
    out = list(leaves)

    sparse_rows = []
    for i, leaf in enumerate(leaves):
        if not is_sparse(leaf):
            continue
        if restricted:
            # Subset groups / families: the plain reference gather with
            # the pre-existing semantics — sparse leaves stay
            # UNCOMPRESSED there (compression= keeps applying to the
            # dense buckets only, as before this exchange family
            # existed); an explicit sparse_algo beyond 'gather' still
            # hits sparse.py's refusal path.
            out[i] = _sparse.allreduce_indexed_slices(
                leaf, group=group, average=average, algo=sparse_algo)
            continue
        # Plan ONCE (the single decision source — ops/sparse.py) and
        # hand the committed row to the lowering, so the artifact
        # records exactly what the compiled program runs by
        # construction, not by two plan calls happening to agree.
        row = _sparse.plan_sparse_exchange(
            leaf, group=group, algo=sparse_algo, compression=comp,
            index=i, label=paths[i])
        sparse_rows.append(row)
        out[i] = _sparse.allreduce_indexed_slices(
            leaf, group=group, average=average, algo=row.algo,
            compression=comp, compression_key=compression_key,
            _plan=row)

    resid_leaves = None
    if error_residual is not None:
        resid_leaves = jax.tree.flatten(error_residual,
                                        is_leaf=is_sparse)[0]
        if len(resid_leaves) != len(leaves):
            raise HorovodError(
                f"error_residual pytree has {len(resid_leaves)} leaves "
                f"for {len(leaves)} gradient leaves; it must mirror the "
                f"gradient structure (ErrorFeedbackState.residual).")
    new_resid = list(resid_leaves) if resid_leaves is not None else None

    dense = [leaves[i] for i in dense_idx]
    dense_labels = [paths[i] for i in dense_idx]
    if dense or sparse_rows:
        # The whole-step plan, computed host-side at trace time
        # (ops/exchange.py): issue order, per-bucket sizes, algo tags,
        # and the sparse exchange rows — one artifact for the entire
        # exchange, registered so the lint gate / bench can export and
        # verify it. Sparse rows serialize only when present, keeping
        # dense-only plan hashes byte-identical.
        plan = _exchange.plan_exchange(
            dense, fusion_threshold, mode=exchange_mode,
            compression=comp, algo=bucket_algo, labels=dense_labels,
            topo=bucket_topo, world_size=gsize, priority_fn=priority_fn,
            cross_compression=cross_spec,
            channels=explicit_channels, max_channels=channel_cap,
            sparse=sparse_rows or None)
        _exchange.register_live_plan(plan)
    if dense:
        if resid_leaves is not None:
            # Compensated contribution: compress grad + residual; only
            # float leaves carry residuals (integer gradients are exact).
            dense = [
                dense[j] + resid_leaves[i].astype(dense[j].dtype)
                if jnp.issubdtype(jnp.dtype(dense[j].dtype), jnp.floating)
                else dense[j]
                for j, i in enumerate(dense_idx)
            ]

        # average is applied inside allreduce: the traced path masks
        # non-member devices back to their own gradient (subset groups),
        # which an outer divide would corrupt.
        # x: a packed bucket's flat buffer, or the tuple of a plain-sum
        # bucket's leaves (ops/fusion.py fused_apply).
        def reduce_bucket(x, members=None, algo="flat", channels=1):
            return _coll.allreduce(x, group=group, average=average,
                                   members=members, compression=comp,
                                   compression_key=compression_key,
                                   algo=algo,
                                   cross_compression=cross_spec,
                                   channels=channels)
        if resid_leaves is None:
            reduced = _fusion.fused_apply(
                dense, reduce_bucket, fusion_threshold,
                labels=dense_labels, compression=comp,
                algo=bucket_algo, schedule=plan)
        else:
            with _compression.collect_local_contributions() as locals_:
                reduced = _fusion.fused_apply(
                    dense, reduce_bucket, fusion_threshold,
                    labels=dense_labels, compression=comp,
                    algo=bucket_algo, schedule=plan)
            # One recorded entry per bucket in the order fused_apply
            # traced them: slice each bucket's local dequantized
            # contribution back onto its leaves (a compressed bucket is
            # always packed, so the offsets are the flat buffer's). None
            # = the leaf's contribution was exact — residual telescopes
            # to zero.
            dense_resid = [None] * len(dense)
            traced = _fusion.trace_order(plan.buckets, dense,
                                         plan.world_size)
            for bucket, local in zip(traced, locals_):
                offset = 0
                for di in bucket.indices:
                    n = dense[di].size
                    if local is None:
                        dense_resid[di] = jnp.zeros_like(dense[di])
                    else:
                        dense_resid[di] = (
                            dense[di]
                            - local[offset: offset + n].reshape(
                                dense[di].shape).astype(dense[di].dtype))
                    offset += n
            for j, i in enumerate(dense_idx):
                r = dense_resid[j]
                new_resid[i] = (jnp.zeros_like(resid_leaves[i]) if r is None
                                else r.astype(resid_leaves[i].dtype))
        for i, r in zip(dense_idx, reduced):
            out[i] = r
    result = jax.tree.unflatten(treedef, out)
    if error_residual is None:
        return result
    resid_tree = jax.tree.unflatten(
        jax.tree.flatten(error_residual, is_leaf=is_sparse)[1], new_resid)
    return result, resid_tree


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         group: int = 0, average: bool = True,
                         fusion_threshold: int | None = None,
                         sharded: bool = False,
                         compression=None,
                         algo=None,
                         schedule=None,
                         cross_compression=None,
                         error_feedback: bool | None = None,
                         channels=None,
                         sparse_algo=None,
                         sharding: str | None = None,
                         fsdp_size: int | None = None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so each update first averages gradients across
    the group — the drop-in analog of ``hvd.DistributedOptimizer``
    (tensorflow/__init__.py:132-192). Use inside ``hvd.spmd`` step functions.

    ``sharded=True`` turns the wrapper into a ZeRO-1 sharded-state
    optimizer: gradients are **reduce-scattered** instead of allreduced,
    each rank updates only its 1/n shard of the (flattened) parameter
    space with a 1/n shard of the optimizer state, and the updated shards
    are **allgathered** back — the same bytes on the wire as an allreduce
    (RS + AG *is* a ring allreduce), but optimizer state HBM drops by the
    group size. This is the TPU-first evolution of the reference's whole
    reason to exist (gradient exchange, tensorflow/__init__.py:132-232).
    See :func:`sharded_optimizer` for the semantics and limitations.

    ``compression``: wire compression for the gradient exchange
    (``"bf16"``/``"int8"``; ops/compression.py) — the knob that halves or
    quarters the bytes every step puts on ICI. ``None`` defers to
    ``HOROVOD_COMPRESSION`` (unset = off, bit-identical to today's path).

    ``algo``: allreduce decomposition per fusion bucket
    (``"flat"``/``"rs_ag"``/``"hierarchical"``/``"auto"``;
    ops/strategy.py — see :func:`allreduce_gradients`). ``None`` defers
    to ``HOROVOD_ALLREDUCE_ALGO`` (unset = flat, the exact pre-strategy
    lowering). Not applicable to ``sharded=True`` (ZeRO-1 already IS the
    reduce-scatter/all-gather decomposition).

    ``schedule``: the whole-step exchange schedule (``"enum"`` /
    ``"priority"``; ops/exchange.py — see :func:`allreduce_gradients`).
    ``None`` defers to ``HOROVOD_EXCHANGE_SCHEDULE`` (unset = ``enum``).
    Not applicable to ``sharded=True`` (its exchange is one flat
    reduce-scatter per dtype — there is no bucket order to schedule).

    ``cross_compression``: hierarchical cross-slice wire override — see
    :func:`allreduce_gradients`. ``error_feedback``: carry per-rank
    error-feedback residuals in the optimizer state
    (:class:`ErrorFeedbackState` wraps the inner state; the PR 4
    checkpoint layer persists it like any other state pytree) so each
    step compresses ``gradient + residual`` and keeps the local
    quantization error for the next — the compensation that lets
    aggressive formats (``int4``) hold convergence. ``None`` defers to
    ``HOROVOD_ERROR_FEEDBACK`` (default off). Neither applies to
    ``sharded=True``.

    ``channels``: channel count for the channelized bucket lowerings —
    see :func:`allreduce_gradients`. ``None`` defers to
    ``HOROVOD_EXCHANGE_CHANNELS`` / the planner under
    ``HOROVOD_MAX_CHANNELS``. Not applicable to ``sharded=True`` (its
    exchange is one flat reduce-scatter per dtype).

    ``sparse_algo``: lowering for sparse IndexedSlices gradient leaves
    (``"gather"``/``"dense"``/``"auto"`` — see
    :func:`allreduce_gradients`; ops/sparse.py). Not applicable to
    ``sharded=True`` (sparse gradients are refused there).

    ``sharding``: the FSDP modes over the ``data × fsdp`` mesh
    (ops/mesh.py) — ``"zero2"`` (gradients reduce-scattered, optimizer
    state permanently sharded 1/fsdp_size per chip, parameters
    replicated) or ``"zero3"`` (parameters additionally sharded,
    all-gathered on use; returns a :class:`Zero3Optimizer`, which
    ``Trainer(sharding='zero3')`` drives — its step shape differs from a
    plain GradientTransformation). ``None`` defers to
    ``HOROVOD_SHARDING`` (tuned configs may set it; explicit env beats
    tuned — tune/apply.py). ``fsdp_size`` overrides the fsdp-axis size
    (default ``HOROVOD_FSDP_AXIS_SIZE``, else one ICI slice). Gradient
    ``compression`` composes (none/bf16/int8/int8_block — the exchange
    keeps each replicated lowering's reduce-scatter prefix, so the
    3-step LM loss is bit-identical to the replicated path;
    tests/test_fsdp.py); the per-leaf exchange leaves no room for
    ``algo=``/``schedule=``/``channels=``/``cross_compression=``/
    ``error_feedback``/``fusion_threshold=``/``sparse_algo=``, which
    all raise, as does combining with ``sharded=True`` (ZeRO-1).
    """
    if error_feedback is None:
        error_feedback = _env.error_feedback_default()
    if sharding is None:
        tuned_sharding = _tune_apply.override("HOROVOD_SHARDING")
        sharding_mode = (_mesh.resolve_sharding(tuned_sharding)
                         if tuned_sharding is not None
                         else _env.sharding_mode())
    else:
        sharding_mode = _mesh.resolve_sharding(sharding)
    if fsdp_size is None:
        tuned_axis = _tune_apply.override("HOROVOD_FSDP_AXIS_SIZE")
        if tuned_axis is not None:
            fsdp_size = int(tuned_axis)
    if sharding_mode != "off":
        if sharded:
            raise HorovodError(
                f"sharded=True (ZeRO-1) and sharding={sharding_mode!r} "
                f"(ZeRO-2/3) are different sharded-state schemes; pick "
                f"one. Drop sharded=True to use the FSDP modes.")
        for arg, value, why in (
                ("sparse_algo", sparse_algo,
                 "sparse IndexedSlices gradients are not supported"),
                ("channels", channels,
                 "its per-leaf exchange has no bucket channel instances"),
                ("cross_compression", cross_compression,
                 "the cross-slice wire format is fixed by the "
                 "compressor's own phase-asymmetric policy"),
                ("fusion_threshold", fusion_threshold,
                 "the exchange is per-leaf by construction (shards must "
                 "map back to layers for gather-on-use)"),
                ("algo", algo,
                 "the exchange already IS the reduce-scatter prefix of "
                 "the topology's own decomposition"),
                ("schedule", schedule,
                 "issue order is the plan's fsdp gather order, not a "
                 "bucket schedule")):
            if value is not None:
                raise HorovodError(
                    f"{arg}= does not apply to the sharded "
                    f"({sharding_mode}) optimizer: {why}. Drop the "
                    f"argument or use sharding='off'.")
        if error_feedback:
            raise HorovodError(
                f"error_feedback is not supported by the sharded "
                f"({sharding_mode}) optimizer: its state is a flat "
                f"per-leaf shard pytree and the shard-keeping exchange "
                f"has no per-rank attributable quantization error. Use "
                f"sharding='off' (or compression='bf16', which needs no "
                f"compensation).")
        if sharding_mode == "zero2":
            return sharded_zero2_optimizer(
                optimizer, group=group, average=average,
                compression=compression, fsdp_size=fsdp_size)
        return Zero3Optimizer(
            optimizer, group=group, average=average,
            compression=compression, fsdp_size=fsdp_size)
    if sharded:
        if sparse_algo is not None:
            raise HorovodError(
                "sparse_algo= does not apply to the sharded (ZeRO-1) "
                "optimizer: sparse IndexedSlices gradients are not "
                "supported there at all. Drop the argument or use "
                "sharded=False.")
        if channels is not None:
            raise HorovodError(
                "channels= does not apply to the sharded (ZeRO-1) "
                "optimizer: its exchange is one flat reduce-scatter per "
                "dtype, not per-bucket channel instances. Drop the "
                "argument or use sharded=False.")
        if cross_compression is not None:
            raise HorovodError(
                "cross_compression does not apply to the sharded "
                "(ZeRO-1) optimizer: its exchange is one flat "
                "reduce-scatter per dtype with no hierarchical phases. "
                "Drop the argument or use sharded=False.")
        if error_feedback:
            raise HorovodError(
                "error_feedback is not supported by the sharded (ZeRO-1) "
                "optimizer: its state is a flat 1/n shard pytree, not "
                "per-parameter, so there is nowhere to carry per-leaf "
                "residuals. Use sharded=False (or compression='bf16', "
                "which needs no compensation).")
        if fusion_threshold is not None:
            raise HorovodError(
                "fusion_threshold does not apply to the sharded (ZeRO-1) "
                "optimizer: it already moves one flat reduce-scatter per "
                "dtype, so there is nothing to fuse. Drop the argument or "
                "use sharded=False.")
        if algo is not None:
            raise HorovodError(
                "algo= does not apply to the sharded (ZeRO-1) optimizer: "
                "its exchange already IS the reduce-scatter + all-gather "
                "decomposition. Drop the argument or use sharded=False.")
        if schedule is not None:
            raise HorovodError(
                "schedule= does not apply to the sharded (ZeRO-1) "
                "optimizer: it moves one flat reduce-scatter per dtype, "
                "so there is no bucket issue order to schedule. Drop the "
                "argument or use sharded=False.")
        return sharded_optimizer(optimizer, group=group, average=average,
                                 compression=compression)

    def init_fn(params):
        inner = optimizer.init(params)
        if not error_feedback:
            return inner
        # Residuals start at zero on every rank (rank-agnostic init: the
        # Trainer's replicate-after-eager-init layout works unchanged);
        # they diverge per rank as each accumulates its own local
        # quantization error.
        return ErrorFeedbackState(
            inner=inner,
            residual=jax.tree.map(jnp.zeros_like, params))

    def update_fn(updates, opt_state, params=None, **kwargs):
        key = kwargs.pop("compression_key", None)
        if error_feedback:
            with jax.named_scope(EXCHANGE_SCOPE):
                updates, new_residual = allreduce_gradients(
                    updates, group=group, average=average,
                    fusion_threshold=fusion_threshold,
                    compression=compression,
                    compression_key=key, algo=algo, schedule=schedule,
                    cross_compression=cross_compression,
                    error_residual=opt_state.residual,
                    channels=channels, sparse_algo=sparse_algo)
            with jax.named_scope(UPDATE_SCOPE):
                inner_updates, inner_state = optimizer.update(
                    updates, opt_state.inner, params, **kwargs)
            return inner_updates, ErrorFeedbackState(inner_state,
                                                     new_residual)
        with jax.named_scope(EXCHANGE_SCOPE):
            updates = allreduce_gradients(
                updates, group=group, average=average,
                fusion_threshold=fusion_threshold, compression=compression,
                compression_key=key, algo=algo, schedule=schedule,
                cross_compression=cross_compression, channels=channels,
                sparse_algo=sparse_algo)
        with jax.named_scope(UPDATE_SCOPE):
            return optimizer.update(updates, opt_state, params, **kwargs)

    return optax.GradientTransformation(init_fn, update_fn)


def _zero_buckets(leaves, gsize):
    """Group leaf indices by dtype; layout for the flat shard vectors.

    Returns ``[(dtype_str, [leaf indices], total_elems, shard_len)]`` in
    first-appearance order. Each bucket flattens to one vector padded to
    ``gsize * shard_len`` so reduce-scatter splits it evenly.
    """
    order: list[str] = []
    by_dt: dict[str, list[int]] = {}
    for i, leaf in enumerate(leaves):
        dt = str(leaf.dtype)
        if dt not in by_dt:
            by_dt[dt] = []
            order.append(dt)
        by_dt[dt].append(i)
    out = []
    for dt in order:
        idx = by_dt[dt]
        total = sum(int(np.prod(leaves[i].shape)) for i in idx)
        shard_len = -(-total // gsize)
        out.append((dt, idx, total, shard_len))
    return out


def sharded_optimizer(optimizer: optax.GradientTransformation,
                      group: int = 0, average: bool = True,
                      compression=None
                      ) -> optax.GradientTransformation:
    """ZeRO-1: reduce-scatter grads → update a 1/n state shard → allgather.

    The parameter space is flattened per dtype into one vector, padded to a
    multiple of the group size; rank i owns slice i. The inner optimizer
    sees a pytree of flat shard vectors, so its state (momentum, Adam
    moments, …) is allocated at 1/n of the parameter memory per device.
    Works for any elementwise inner transformation (sgd/momentum/adam/
    rmsprop/adamw...); per-parameter-SHAPE logic (e.g. adafactor's factored
    second moment, per-layer clipping) would see flat shards instead of the
    real shapes — use the unsharded wrapper for those.

    ``init`` is rank-agnostic (state inits are zeros over same-shaped
    shards on every rank), so the Trainer's replicate-after-eager-init
    state layout works unchanged. Sparse :class:`IndexedSlices` gradients
    are not supported in sharded mode. Non-members of a subset ``group``
    get ZERO updates (their parameters hold still — a raw-gradient
    passthrough would be applied unscaled by ``optax.apply_updates``);
    their shard state advances with meaningless slices and should be
    ignored.

    ``compression``: ``"bf16"`` moves BOTH collectives (gradient
    reduce-scatter and update allgather) in bfloat16 — the same wire
    saving as the unsharded path, deterministic. ``"int8"`` is refused:
    the update allgather does not average anything, so stochastic
    quantization noise would land directly (unaveraged) in the
    parameters; use ``compression="bf16"`` or ``sharded=False``.
    """
    comp = _compression.resolve(compression)
    if isinstance(comp, _compression.NoneCompressor):
        comp = None
    if comp is not None and not comp.elementwise:
        # Covers int8 AND the block formats (int8_block/int4): the
        # update allgather does not average, so stochastic quantization
        # noise would land unaveraged in parameters — and int4's packed
        # wire cannot ride the summing reduce-scatter at all.
        raise HorovodError(
            f"{comp.name} compression is not supported by the sharded "
            f"(ZeRO-1) optimizer: the update allgather would inject "
            f"stochastic quantization noise directly into parameters "
            f"(and unsummable wire formats cannot ride its summing "
            f"reduce-scatter). Use compression='bf16' or "
            f"sharded=False.")

    def _gsize():
        return _state.get_group(group).size

    def init_fn(params):
        leaves = jax.tree.leaves(params)
        shards = {
            dt: jnp.zeros((shard_len,), dtype=dt)
            for dt, _, _, shard_len in _zero_buckets(leaves, _gsize())
        }
        return optimizer.init(shards)

    def update_fn(updates, opt_state, params=None, **kwargs):
        tctx = _ctx.current()
        if tctx is None:
            raise HorovodError(
                "sharded (ZeRO-1) DistributedOptimizer.update must run "
                "inside an hvd.spmd-wrapped step function.")
        if not isinstance(group, int):
            raise HorovodError(
                "sharded DistributedOptimizer takes a single group index, "
                "not a group family.")
        gsize = _gsize()
        is_sparse = lambda leaf: isinstance(leaf, _sparse.IndexedSlices)
        leaves, treedef = jax.tree.flatten(updates, is_leaf=is_sparse)
        for leaf in leaves:
            if is_sparse(leaf):
                raise HorovodError(
                    "Sparse IndexedSlices gradients are not supported by "
                    "the sharded (ZeRO-1) optimizer; use sharded=False.")
        pleaves = jax.tree.leaves(params) if params is not None else None
        # Bucket layout must match what init_fn built from the PARAMETER
        # dtypes — a casting transform can hand us fp32 gradients for bf16
        # params, and gradient-dtype buckets would then feed the inner
        # optimizer a state pytree it has never seen. Bucket by param dtype
        # and cast gradients (flat_pad casts); without params we can only
        # use gradient dtypes — init saw the same layout unless dtypes
        # diverged, which we cannot detect here.
        buckets = _zero_buckets(pleaves if pleaves is not None else leaves,
                                gsize)
        grank = tctx.rank(group)
        grank_c = jnp.maximum(grank, 0)

        def flat_pad(vals, idx, total, shard_len, dt):
            flat = jnp.concatenate(
                [jnp.ravel(vals[i]).astype(dt) for i in idx])
            pad = gsize * shard_len - total
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return flat

        with jax.named_scope(EXCHANGE_SCOPE):
            gshards, pshards = {}, ({} if pleaves is not None else None)
            for dt, idx, total, shard_len in buckets:
                # Reduce in the gradients' own (promoted) dtype — casting
                # bf16ward BEFORE the sum would accumulate across ranks at
                # bf16 precision, which the unsharded allreduce path never
                # does. The cast to the bucket's param dtype happens after
                # the collective. With wire compression on,
                # reduced-precision accumulation IS the requested trade
                # (same as the compressed allreduce path).
                reduce_dt = jnp.result_type(*[leaves[i].dtype for i in idx])
                gflat = flat_pad(leaves, idx, total, shard_len, reduce_dt)
                if comp is not None and comp.applies_to(gflat.dtype):
                    wctx = _compression.WireContext(group_size=gsize)
                    with jax.named_scope("QUANTIZE"):
                        gwire, gmeta = comp.compress(gflat, wctx)
                    gshard = _coll.reducescatter(gwire, group=group)
                    with jax.named_scope("DEQUANTIZE"):
                        gshard = comp.decompress(gshard, gmeta,
                                                 jnp.dtype(reduce_dt), wctx)
                else:
                    gshard = _coll.reducescatter(gflat, group=group)
                if average:
                    gshard = gshard / gsize
                gshards[dt] = gshard.astype(dt)
                if pleaves is not None:
                    pflat = flat_pad(pleaves, idx, total, shard_len, dt)
                    pshards[dt] = jax.lax.dynamic_slice_in_dim(
                        pflat, grank_c * shard_len, shard_len)

        with jax.named_scope(UPDATE_SCOPE):
            upd_shards, new_state = optimizer.update(
                gshards, opt_state, pshards, **kwargs)

        # Subset groups: non-members get zero updates (params hold still —
        # see the docstring; raw-gradient passthrough would be applied
        # unscaled by optax.apply_updates).
        program_size = _state.get_group(tctx.group_index).size
        member = None if gsize == program_size else (grank >= 0)

        with jax.named_scope(EXCHANGE_SCOPE):
            out = list(leaves)
            for dt, idx, total, shard_len in buckets:
                upd = upd_shards[dt]
                if comp is not None and comp.applies_to(upd.dtype):
                    # The allgather moves each rank's shard once; a bf16
                    # wire halves it. Deterministic cast only (int8 refused
                    # above).
                    wctx = _compression.WireContext(group_size=gsize)
                    with jax.named_scope("QUANTIZE"):
                        uwire, umeta = comp.compress(upd, wctx)
                    gathered = _coll.allgather(uwire, group=group)
                    with jax.named_scope("DEQUANTIZE"):
                        full = comp.decompress(gathered, umeta,
                                               upd.dtype, wctx)[:total]
                else:
                    full = _coll.allgather(upd, group=group)[:total]
                off = 0
                for i in idx:
                    n = int(np.prod(leaves[i].shape))
                    new_leaf = full[off:off + n].reshape(
                        leaves[i].shape).astype(leaves[i].dtype)
                    if member is not None:
                        new_leaf = jnp.where(member, new_leaf,
                                             jnp.zeros_like(new_leaf))
                    out[i] = new_leaf
                    off += n
        return jax.tree.unflatten(treedef, out), new_state

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# FSDP (ZeRO-2/3) over the data × fsdp mesh (ops/mesh.py). Gradients move
# by the shard-keeping reduce-scatter prefix of the replicated lowerings
# (ops/strategy.py lower_fsdp_grad_exchange — the bit-identity contract);
# optimizer state lives permanently sharded per leaf; ZeRO-3 additionally
# shards the parameters and all-gathers them on use.
# ---------------------------------------------------------------------------


def _fsdp_setup(group, fsdp_size):
    """(FsdpMesh, Topology) for a live group — trace- or init-time."""
    g_obj = _state.get_group(group)
    topo = _topology.discover(g_obj)
    return _mesh.layout(topo, fsdp_size), topo


def _fsdp_multiple(comp, fmesh):
    """The extra pad multiple of the flat shard layout: a blocked
    compressor with one data group exchanges the BLOCK-wire flat layout
    (strategy.py case 2), so shards live in block-padded coordinates;
    every other case pads to the fsdp size only."""
    block = getattr(comp, "block", None) if comp is not None else None
    return block if (block and fmesh.data_size == 1) else 1


def _fsdp_resolve_comp(compression):
    """Gradient-wire compressor for the sharded modes: summable formats
    only (the exchange keeps a reduce-scatter prefix; int4's gather
    scheme has none)."""
    comp = _compression.resolve(
        compression if compression is not None
        else _tune_apply.override("HOROVOD_COMPRESSION"))
    if isinstance(comp, _compression.NoneCompressor):
        comp = None
    if comp is not None and not comp.summable:
        raise HorovodError(
            f"{comp.name} compression is not supported by the sharded "
            f"(ZeRO-2/3) modes: its wire format is unsummable, so the "
            f"gather-based exchange has no reduce-scatter prefix to "
            f"keep a shard from. Use none/bf16/int8/int8_block, or "
            f"sharding='off'.")
    return comp


def _fsdp_labels(tree, is_leaf=None):
    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in jax.tree_util.tree_flatten_with_path(
                tree, is_leaf=is_leaf)[0]]


def _fsdp_check_ctx(mode: str, group):
    tctx = _ctx.current()
    if tctx is None:
        raise HorovodError(
            f"the sharded ({mode}) optimizer must run inside an "
            f"hvd.spmd-wrapped step function: its shard layout is a "
            f"per-rank view with no eager rank-stacked equivalent.")
    if not isinstance(group, (int, np.integer)):
        raise HorovodError(
            f"the sharded ({mode}) optimizer takes a single group "
            f"index, not a group family: shards partition one group's "
            f"rank space.")
    if int(group) != tctx.group_index:
        raise HorovodError(
            f"the sharded ({mode}) optimizer requires the full-axis "
            f"single group (group {int(group)} inside a group-"
            f"{tctx.group_index} program): subset groups have no "
            f"uniform fsdp partition. Run the spmd program on group "
            f"{int(group)} itself.")
    return tctx


def _fsdp_register_plan(mode, leaves, labels, comp, fmesh, topo,
                        gather_order):
    """Commit the whole-step FSDP exchange plan (ops/exchange.py): the
    per-leaf reduce rows (threshold 0 — the exchange is per-leaf by
    construction) plus the ``fsdp`` section recording mode, mesh shape,
    and the zero3 gather-on-use order/bytes. Registered so the lint gate
    and bench export exactly what the compiled program runs."""
    algo_tag = ("hierarchical"
                if fmesh.multi_slice and fmesh.matches_slices()
                else "rs_ag")
    plan = _exchange.plan_exchange(
        leaves, 0, mode="enum", compression=comp,
        algo=lambda bucket: algo_tag, labels=labels, topo=topo,
        world_size=fmesh.group_size)
    meta = _exchange.FsdpMeta(
        mode=mode, fsdp_size=fmesh.fsdp_size, data_size=fmesh.data_size,
        gather_order=tuple(gather_order),
        leaf_bytes=tuple(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in leaves),
        wire_dtypes=tuple(str(jnp.dtype(l.dtype)) for l in leaves))
    plan = plan.with_fsdp(meta)
    _exchange.register_live_plan(plan)
    return plan


def _fsdp_grad_shard(leaf, label, comp, key, fmesh, topo, average):
    with jax.named_scope(EXCHANGE_SCOPE):
        shard, _ = _strategy.lower_fsdp_grad_exchange(
            leaf, fmesh, label, comp, key, topo=topo)
        if average:
            shard = _coll._divide_avg(shard, fmesh.group_size, shard.dtype)
        return shard


def _fsdp_gather(shard, fmesh, label, topo):
    with jax.named_scope(EXCHANGE_SCOPE):
        return _strategy.lower_fsdp_param_gather(shard, fmesh, label,
                                                 topo=topo)


def _fsdp_pad_flat(leaf, padded: int):
    flat = jnp.ravel(leaf)
    if padded > flat.shape[0]:
        flat = jnp.pad(flat, (0, padded - flat.shape[0]))
    return flat


def sharded_zero2_optimizer(optimizer: optax.GradientTransformation,
                            group: int = 0, average: bool = True,
                            compression=None, fsdp_size: int | None = None
                            ) -> optax.GradientTransformation:
    """ZeRO-2 on the ``data × fsdp`` mesh: reduce-scatter each gradient
    leaf to a 1/fsdp_size shard (summing over the ``data`` axis in the
    same collective chain — the replicated lowering's prefix), update
    that shard with a permanently sharded per-leaf optimizer state, and
    all-gather the UPDATE shards back onto the replicated parameters.

    Differences from :func:`sharded_optimizer` (ZeRO-1): shards are
    per-LEAF flat vectors (not per-dtype buckets), so they map back to
    layers — the layout ZeRO-3's gather-on-use needs — and the gradient
    exchange composes with the summable compressors per the replicated
    scale-coupling rules (bit-identical loss; tests/test_fsdp.py). The
    all-gather always moves the parameter dtype: compressing it would
    put unaveraged quantization noise straight into parameters AND
    break the bit-identity contract. Elementwise inner transformations
    only (the ZeRO-1 caveat, per leaf instead of per dtype bucket).

    ``update(..., fsdp_apply=True)`` (what ``Trainer(sharding='zero2')``
    passes) applies the update SHARD-side and returns ``(new_params,
    state)`` — new full parameters, already gathered — instead of
    ``(updates, state)``. This is the bit-identity path: applying
    shard-side keeps the update multiply feeding the parameter add
    directly, so XLA's FMA contraction fires (or not) exactly as in the
    replicated arm's compiled step. The plain GradientTransformation
    path gathers the UPDATE shards, and the user's later
    ``optax.apply_updates`` add cannot contract across the all-gather —
    mathematically identical, but ULP-level contraction may differ from
    the replicated arm's fused multiply-add."""
    comp = _fsdp_resolve_comp(compression)

    def init_fn(params):
        fmesh, _ = _fsdp_setup(group, fsdp_size)
        m = _fsdp_multiple(comp, fmesh)
        leaves, treedef = jax.tree.flatten(params)
        shards = [
            jnp.zeros((fmesh.shard_len(fmesh.padded_numel(
                int(np.prod(l.shape)), m)),), dtype=l.dtype)
            for l in leaves]
        return optimizer.init(jax.tree.unflatten(treedef, shards))

    def update_fn(updates, opt_state, params=None, **kwargs):
        key = kwargs.pop("compression_key", None)
        fsdp_apply = kwargs.pop("fsdp_apply", False)
        if fsdp_apply and params is None:
            raise HorovodError(
                "sharded (zero2) optimizer: update(..., fsdp_apply=True) "
                "applies shard-side and needs params=.")
        tctx = _fsdp_check_ctx("zero2", group)
        is_sparse = lambda leaf: isinstance(leaf, _sparse.IndexedSlices)
        leaves, treedef = jax.tree.flatten(updates, is_leaf=is_sparse)
        for leaf in leaves:
            if is_sparse(leaf):
                raise HorovodError(
                    "Sparse IndexedSlices gradients are not supported "
                    "by the sharded (zero2) optimizer; use "
                    "sharding='off'.")
        labels = _fsdp_labels(updates, is_leaf=is_sparse)
        fmesh, topo = _fsdp_setup(group, fsdp_size)
        m = _fsdp_multiple(comp, fmesh)
        _fsdp_register_plan("zero2", leaves, labels, comp, fmesh, topo,
                            gather_order=())
        pleaves = jax.tree.leaves(params) if params is not None else None
        f_idx = jnp.maximum(tctx.rank(group), 0) % fmesh.fsdp_size
        gshards, pshards = [], ([] if pleaves is not None else None)
        for i, leaf in enumerate(leaves):
            shard = _fsdp_grad_shard(leaf, labels[i], comp, key, fmesh,
                                     topo, average)
            dt = pleaves[i].dtype if pleaves is not None else leaf.dtype
            gshards.append(shard.astype(dt))
            if pleaves is not None:
                P = fmesh.padded_numel(int(np.prod(pleaves[i].shape)), m)
                L = fmesh.shard_len(P)
                pshards.append(jax.lax.dynamic_slice_in_dim(
                    _fsdp_pad_flat(pleaves[i], P), f_idx * L, L))
        pshard_tree = (jax.tree.unflatten(treedef, pshards)
                       if pshards is not None else None)
        with jax.named_scope(UPDATE_SCOPE):
            upd_shards, new_state = optimizer.update(
                jax.tree.unflatten(treedef, gshards), opt_state,
                pshard_tree, **kwargs)
        upd_leaves = jax.tree.leaves(upd_shards)
        if fsdp_apply:
            # Shard-side apply, then gather the NEW PARAMS (docstring:
            # the bit-identity path — contraction-consistent with the
            # replicated arm's fused apply).
            new_pshards = jax.tree.leaves(
                optax.apply_updates(pshard_tree, upd_shards))
            out = []
            for i, pleaf in enumerate(pleaves):
                full = _fsdp_gather(new_pshards[i], fmesh, labels[i], topo)
                n = int(np.prod(pleaf.shape))
                out.append(full[:n].reshape(pleaf.shape)
                           .astype(pleaf.dtype))
            return jax.tree.unflatten(treedef, out), new_state
        out = []
        for i, leaf in enumerate(leaves):
            full = _fsdp_gather(upd_leaves[i], fmesh, labels[i], topo)
            n = int(np.prod(leaf.shape))
            out.append(full[:n].reshape(leaf.shape).astype(leaf.dtype))
        return jax.tree.unflatten(treedef, out), new_state

    return optax.GradientTransformation(init_fn, update_fn)


class Zero3Optimizer:
    """ZeRO-3 on the ``data × fsdp`` mesh: parameters AND optimizer
    state live permanently sharded per leaf; the forward all-gathers
    each layer's parameter shard on use (``gather_params``, issued in
    first-needed order so XLA's latency-hiding scheduler overlaps the
    gather with forward compute — the gathered full tensors are
    trace-local and freed after backward), gradients reduce to shards by
    the replicated lowerings' reduce-scatter prefix, and the update
    applies shard-to-shard with no parameter all-gather at all.

    Not an ``optax.GradientTransformation`` — the step SHAPE differs
    (params must be gathered before the loss runs), so
    ``Trainer(sharding='zero3')`` drives it:

        opt = hvd.DistributedOptimizer(inner, sharding='zero3')
        opt.bind(params_template)                      # eager, once
        shards = opt.init_shards(params)               # eager, stacked
        state  = opt.init(shard_view)                  # inner state
        # traced step:
        params = opt.gather_params(shards)             # FSDP_GATHER ×L
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        shards, state = opt.apply_gradients(grads, state, shards)

    Elementwise inner transformations only: a parameter-shard update
    followed by the NEXT step's all-gather is element-for-element the
    replicated update (the bit-identity contract, tests/test_fsdp.py);
    shape-dependent transforms (adafactor's factored moments) would see
    flat shards instead of the real shapes."""

    def __init__(self, optimizer: optax.GradientTransformation,
                 group: int = 0, average: bool = True, compression=None,
                 fsdp_size: int | None = None):
        self.inner = optimizer
        self.group = group
        self.average = average
        self.comp = _fsdp_resolve_comp(compression)
        self._fsdp_size = fsdp_size
        self._treedef = None

    # -- eager (host-side) layout -----------------------------------

    def mesh(self) -> "_mesh.FsdpMesh":
        return _fsdp_setup(self.group, self._fsdp_size)[0]

    def bind(self, params_template) -> "Zero3Optimizer":
        """Record the parameter pytree's layout (shapes, dtypes, labels,
        padded flat sizes, gather order) — eager, once, before any
        traced method. The gather order is leaf-enumeration order:
        first-needed-first for the FORWARD pass, the mirror image of
        the priority scheduler's reverse-layer gradient order."""
        is_sparse = lambda leaf: isinstance(leaf, _sparse.IndexedSlices)
        leaves, treedef = jax.tree.flatten(params_template,
                                           is_leaf=is_sparse)
        for leaf in leaves:
            if is_sparse(leaf):
                # Without is_leaf= above, tree.flatten would descend
                # INTO the registered IndexedSlices node and this check
                # could never fire.
                raise HorovodError(
                    "Sparse IndexedSlices parameters are not supported "
                    "by the sharded (zero3) optimizer.")
        fmesh, _ = _fsdp_setup(self.group, self._fsdp_size)
        m = _fsdp_multiple(self.comp, fmesh)
        self._treedef = treedef
        self._shapes = [tuple(int(d) for d in leaf.shape)
                        for leaf in leaves]
        self._dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves]
        self._labels = _fsdp_labels(params_template)
        self._padded = [fmesh.padded_numel(int(np.prod(s)), m)
                        for s in self._shapes]
        self._order = tuple(range(len(leaves)))
        return self

    def _require_bound(self):
        if self._treedef is None:
            raise HorovodError(
                "Zero3Optimizer.bind(params_template) must run (eagerly, "
                "once) before any traced method — the shard layout is "
                "host-side static metadata.")

    def init_shards(self, params):
        """Rank-stacked (leading axis = group size) parameter shards
        from eagerly initialized full parameters — the Trainer
        ``init_state`` layout. Rank ``r = d*F + f`` holds shard ``f`` of
        each leaf's zero-padded flat layout."""
        self._require_bound()
        fmesh, _ = _fsdp_setup(self.group, self._fsdp_size)
        F, W = fmesh.fsdp_size, fmesh.group_size
        leaves = jax.tree.leaves(params)
        out = []
        for i, leaf in enumerate(leaves):
            P = self._padded[i]
            L = fmesh.shard_len(P)
            flat = np.zeros((P,), dtype=self._dtypes[i])
            flat[:int(np.prod(self._shapes[i]))] = np.ravel(
                np.asarray(leaf))
            rows = flat.reshape(F, L)
            out.append(jnp.asarray(
                np.stack([rows[r % F] for r in range(W)])))
        return jax.tree.unflatten(self._treedef, out)

    def init(self, param_shards):
        """Inner optimizer state over the shard pytree (shard-shaped
        moments — 1/fsdp_size of the replicated state per chip)."""
        return self.inner.init(param_shards)

    # -- traced (inside hvd.spmd) -----------------------------------

    def shard_params(self, params):
        """This rank's shard view of full (replicated) parameters —
        traced; the checkpoint-restore re-shard path."""
        self._require_bound()
        tctx = _fsdp_check_ctx("zero3", self.group)
        fmesh, _ = _fsdp_setup(self.group, self._fsdp_size)
        f_idx = jnp.maximum(tctx.rank(self.group), 0) % fmesh.fsdp_size
        leaves = jax.tree.leaves(params)
        out = []
        for i, leaf in enumerate(leaves):
            L = fmesh.shard_len(self._padded[i])
            out.append(jax.lax.dynamic_slice_in_dim(
                _fsdp_pad_flat(leaf, self._padded[i]), f_idx * L, L))
        return jax.tree.unflatten(self._treedef, out)

    def gather_params(self, param_shards):
        """Gather-on-use: all-gather every leaf's shard over the fsdp
        partition, issued in the plan's gather order, and rebuild the
        full parameter pytree for the forward pass."""
        self._require_bound()
        _fsdp_check_ctx("zero3", self.group)
        fmesh, topo = _fsdp_setup(self.group, self._fsdp_size)
        leaves = jax.tree.leaves(param_shards)
        out = [None] * len(leaves)
        for i in self._order:
            full = _fsdp_gather(leaves[i], fmesh, self._labels[i], topo)
            n = int(np.prod(self._shapes[i]))
            out[i] = full[:n].reshape(self._shapes[i])
        return jax.tree.unflatten(self._treedef, out)

    def apply_gradients(self, grads, opt_state, param_shards,
                        compression_key=None, **kwargs):
        """Reduce each gradient leaf to this rank's shard (quantize →
        reduce-scatter → cross-data psum → dequantize, ops/strategy.py),
        run the inner update shard-to-shard, and apply it to the
        parameter shards. Returns ``(new_param_shards,
        new_opt_state)``."""
        self._require_bound()
        _fsdp_check_ctx("zero3", self.group)
        is_sparse = lambda leaf: isinstance(leaf, _sparse.IndexedSlices)
        leaves = jax.tree.flatten(grads, is_leaf=is_sparse)[0]
        for leaf in leaves:
            if is_sparse(leaf):
                raise HorovodError(
                    "Sparse IndexedSlices gradients are not supported "
                    "by the sharded (zero3) optimizer; use "
                    "sharding='off'.")
        fmesh, topo = _fsdp_setup(self.group, self._fsdp_size)
        _fsdp_register_plan("zero3", leaves, self._labels, self.comp,
                            fmesh, topo, gather_order=self._order)
        gshards = []
        for i, leaf in enumerate(leaves):
            shard = _fsdp_grad_shard(leaf, self._labels[i], self.comp,
                                     compression_key, fmesh, topo,
                                     self.average)
            gshards.append(shard.astype(self._dtypes[i]))
        gtree = jax.tree.unflatten(self._treedef, gshards)
        with jax.named_scope(UPDATE_SCOPE):
            upd_shards, new_state = self.inner.update(
                gtree, opt_state, param_shards, **kwargs)
        new_shards = optax.apply_updates(param_shards, upd_shards)
        return new_shards, new_state


def broadcast_variables(variables, root_rank: int = 0, group: int = 0):
    """Sync a variable pytree from ``root_rank`` to every rank.

    Analog of ``hvd.broadcast_global_variables`` (tensorflow/__init__.py:86-94)
    — run once after init / checkpoint restore so all replicas start
    identical (the consistency mechanism the reference documents at
    tensorflow/__init__.py:97-104).

    Inside ``hvd.spmd``: operates on the rank-view pytree. Eagerly: operates
    on the rank-stacked layout (leading axis = group size) and returns the
    same layout with every rank's row replaced by the root's.
    """
    if _ctx.current() is not None:
        return jax.tree.map(
            lambda t: _coll.broadcast(t, root_rank=root_rank, group=group),
            variables)

    from horovod_tpu.core import timeline as _timeline
    from horovod_tpu.parallel import spmd as _spmd

    def broadcast_step(v):
        return jax.tree.map(
            lambda t: _coll.broadcast(t, root_rank=root_rank, group=group), v)

    with _timeline.span("hvd/broadcast"):
        return _spmd.spmd(broadcast_step, group=group)(variables)


# Alias matching the reference's TF-level name.
broadcast_global_variables = broadcast_variables
