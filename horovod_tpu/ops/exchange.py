"""Whole-step exchange scheduler: plan the ENTIRE gradient exchange.

PR 5 made each fusion bucket individually cheap (per-bucket algorithm
selection over the α–β cost model); this module makes the *step* cheap.
The pre-scheduler gradient path sizes buckets with one global threshold
and issues them in pytree-enumeration order — so the gradients the next
forward pass needs first wait behind the ones it needs last, exactly the
exposed-communication tax Horovod's own fusion/ordering design targets
(arXiv:1802.05799) and that whole-exchange scheduling work (arXiv:
2508.13397) shows is where the remaining wins live. Three pieces:

**Priority ordering** (the Horovod/ByteScheduler insight): backward
produces gradients in reverse layer order, so issuing buckets in
*reverse pytree-enumeration* order starts each bucket's collective while
the rest of the backward pass is still computing — backward-early /
forward-late gradients overlap with remaining compute instead of queueing
behind first-layer buckets whose data is not even ready. An optional
``priority_fn(label, index) -> key`` hook lets a user re-rank leaves
(lower key = issued earlier); the default is reverse enumeration.
Computed host-side at trace time from the pytree structure — pure,
deterministic, identical on every rank for identical shapes.

**Per-region overlap-aware bucket sizing**: one global threshold is the
wrong size at both ends of the step — early buckets should be small so
communication starts sooner, late buckets large to amortize the α
latency once there is no compute left to hide behind. The reversed leaf
sequence is split into contiguous byte-quantile regions; region k's
threshold ramps geometrically from a cost-model floor up to the resolved
global threshold, power-of-two quantized so per-rank cost-model drift
(slightly different tuning caches) cannot split ranks across a boundary.
When the active compressor couples bucket members (int8's shared
group-max scale — ``Compressor.elementwise`` False), sizing is disabled
and the scheduler preserves enumeration-order bucket MEMBERSHIP,
reordering issue order only, so gradients stay bit-exact by
construction.

**Always-on α–β recalibration**: :class:`Recalibrator` keeps an online
least-squares fit of ``t(S) = α + ring·S/β`` per interconnect level,
fed by measured collective span durations (device-timeline samples via
``observe_xla_spans``, bench rows via ``observe``), and periodically
persists the refreshed constants into the schema-versioned tuning cache
(``HOROVOD_TUNING_CACHE``, utils/costs.py — schema v3: running-fit
section + per-level channel efficiency) so the cost model tracks the
live machine instead of a one-shot ``--calibrate``. The same loop fits
each level's per-extra-channel efficiency from measured multi-channel
collectives (``observe_channels``) — the closed loop the channelized
lowerings' planner rides on. ``HOROVOD_RECALIBRATION=0`` turns the
loop off; a stale/corrupt cache is ignored, never misread (the loop
then starts a fresh fit).

The committed plan is an :class:`ExchangeSchedule` — a serializable JSON
artifact (`.exchange.json`) that ``tools/hvd_lint.py --schedule`` can
ingest and statically verify for per-rank identity (HVD103) and phase
shape (HVD105). Bit-exactness contract: the scheduler changes bucket
ORDER and SIZE only — same summands, same algorithms available; every
gradient element is still summed over the same rank set by the same
lowering family (tests/test_exchange.py pins bit-exact results vs the
enumeration order for every algo × compression combination).
"""

from __future__ import annotations

import dataclasses
import json
import zlib

import numpy as np

from horovod_tpu.core.state import HorovodError
from horovod_tpu.ops import fusion as _fusion
from horovod_tpu.utils import costs as _costs
from horovod_tpu.utils import env as _env

# Artifact layout version — bump on layout change; hvd-lint refuses (with
# a finding, not a guess) artifacts whose schema it does not know.
ARTIFACT_SCHEMA = "horovod_tpu/exchange-schedule/v1"

MODES = ("enum", "priority")

# Regions of the per-layer sizing ramp. Four quantile regions keep the
# ramp meaningful for real models (hundreds of leaves) without shredding
# tiny test pytrees.
N_REGIONS = 4


def resolve_mode(spec) -> str:
    """Normalize a ``schedule=`` argument: ``None`` defers to
    ``HOROVOD_EXCHANGE_SCHEDULE`` (default ``enum``, the pre-scheduler
    behavior); strings are validated — typos raise."""
    if spec is None:
        return _env.exchange_schedule_default()
    if not isinstance(spec, str):
        raise HorovodError(
            f"schedule= must be None or a string, got "
            f"{type(spec).__name__}.")
    value = spec.strip().lower()
    if value not in MODES:
        raise HorovodError(
            f"Unknown exchange schedule {spec!r}; choose one of "
            f"{list(MODES)} (HOROVOD_EXCHANGE_SCHEDULE / schedule=).")
    return value


@dataclasses.dataclass(frozen=True)
class ElasticMeta:
    """Provenance of an elastically re-planned schedule (core/elastic.py):
    the surviving/current global ranks the plan was re-resolved for, the
    ranks the transition dropped (empty for a regrow), and the runtime
    generation the plan belongs to. Serialized into the artifact ONLY
    when present, so every non-elastic plan keeps its byte-identical
    JSON and hash; hvd-lint cross-checks these fields against the plan's
    ``world_size`` (a post-shrink plan still referencing a dropped rank
    is the HVD103 corpus fixture)."""

    survivors: tuple[int, ...]
    dropped: tuple[int, ...]
    generation: int


@dataclasses.dataclass(frozen=True)
class FsdpMeta:
    """The plan's FSDP section (parallel/optimizer.py ZeRO-2/3 over the
    ``data × fsdp`` mesh, ops/mesh.py): the sharding mode, the mesh
    factorization the shards partition, and — for zero3 — the
    gather-on-use issue order with each leaf's gathered bytes and wire
    dtype. Serialized into the artifact ONLY when present, so every
    replicated plan keeps its byte-identical JSON and hash; hvd-lint
    cross-checks the section against the plan's ``world_size`` and the
    lowered HLO's FSDP_GATHER order (a rank-divergent gather order is
    the ``bad_fsdp_gather_order`` corpus fixture)."""

    mode: str                       # "zero2" | "zero3"
    fsdp_size: int
    data_size: int
    gather_order: tuple[int, ...]   # leaf indices, issue order (zero3)
    leaf_bytes: tuple[int, ...]     # gathered bytes per leaf, leaf order
    wire_dtypes: tuple[str, ...]    # gather wire dtype per leaf


@dataclasses.dataclass(frozen=True)
class ExchangeSchedule:
    """The committed whole-step exchange plan.

    ``buckets`` are :class:`~horovod_tpu.ops.fusion.Bucket` records in
    ISSUE order (``bucket.priority`` == position); ``members`` carries
    each bucket's tensor labels (empty tuples when the caller had no
    labels). ``leaf_bytes`` are the logical bytes of every gradient leaf
    in pytree-enumeration order — what the exposed-communication model
    needs to place each bucket's ready time inside the backward pass.
    ``sparse_buckets`` are the plan's sparse (IndexedSlices) exchanges
    (:class:`~horovod_tpu.ops.fusion.SparseBucket`, issued before the
    dense buckets in leaf-enumeration order) — serialized into the
    artifact ONLY when present, so every dense-only plan keeps its
    pre-sparse byte-identical JSON and hash.
    """

    mode: str
    world_size: int
    num_slices: int
    threshold_bytes: int
    region_thresholds: tuple[int, ...]
    leaf_bytes: tuple[int, ...]
    buckets: tuple[_fusion.Bucket, ...]
    members: tuple[tuple[str, ...], ...]
    sparse_buckets: tuple = ()
    elastic: "ElasticMeta | None" = None
    fsdp: "FsdpMeta | None" = None

    def to_json(self) -> str:
        """Canonical (sorted-keys, compact) JSON — byte-identical across
        processes/retraces for identical inputs, the determinism the
        plan hash and the multi-host schedule contract both ride on."""
        data = {
            "schema": ARTIFACT_SCHEMA,
            "mode": self.mode,
            "world_size": self.world_size,
            "num_slices": self.num_slices,
            "threshold_bytes": self.threshold_bytes,
            "region_thresholds": list(self.region_thresholds),
            "leaf_bytes": list(self.leaf_bytes),
            "buckets": [
                self._bucket_row(b, m)
                for b, m in zip(self.buckets, self.members)
            ],
        }
        # Sparse rows serialize ONLY when present (the per-phase wire
        # field precedent below): a dense-only plan's JSON — and
        # therefore its hash and every golden snapshot — is byte-
        # identical to the pre-sparse layout.
        if self.sparse_buckets:
            data["sparse_buckets"] = [self._sparse_row(b)
                                      for b in self.sparse_buckets]
        # Elastic provenance follows the same only-when-present rule:
        # plans from non-elastic runs keep their pre-elastic hashes.
        if self.elastic is not None:
            data["elastic"] = {
                "survivors": list(self.elastic.survivors),
                "dropped": list(self.elastic.dropped),
                "generation": self.elastic.generation,
            }
        # The FSDP section (ZeRO-2/3) is only-when-present too: the plan
        # hash rolls exactly when sharding is on, never retroactively.
        if self.fsdp is not None:
            data["fsdp"] = {
                "mode": self.fsdp.mode,
                "fsdp_size": self.fsdp.fsdp_size,
                "data_size": self.fsdp.data_size,
                "gather_order": list(self.fsdp.gather_order),
                "leaf_bytes": list(self.fsdp.leaf_bytes),
                "wire_dtypes": list(self.fsdp.wire_dtypes),
            }
        return json.dumps(data, sort_keys=True, separators=(",", ":"))

    @staticmethod
    def _bucket_row(b: "_fusion.Bucket", m) -> dict:
        row = {
            "priority": b.priority,
            "indices": list(b.indices),
            "dtype": np.dtype(b.dtype).name,
            "total_bytes": b.total_bytes,
            "wire_dtype": (None if b.wire_dtype is None
                           else np.dtype(b.wire_dtype).name),
            "algo": b.algo,
            "members": list(m),
        }
        # Per-phase wire fields (phase-asymmetric compression,
        # ops/fusion.py Bucket): serialized only when set, so plans from
        # the pre-existing single-wire paths keep byte-identical JSON —
        # and therefore stable plan hashes / golden snapshots. The
        # channel assignment follows the same rule: single-channel
        # buckets (the default) serialize no "channels" field, so every
        # pre-channel plan hash is unchanged.
        if b.channels != 1:
            row["channels"] = b.channels
        if b.wire_bits:
            row["wire_bits"] = b.wire_bits
        if b.cross_wire_dtype is not None:
            row["cross_wire_dtype"] = np.dtype(b.cross_wire_dtype).name
            if b.cross_wire_bits:
                row["cross_wire_bits"] = b.cross_wire_bits
            if b.intra_wire_dtype is not None:
                row["intra_wire_dtype"] = np.dtype(b.intra_wire_dtype).name
        return row

    @staticmethod
    def _sparse_row(b: "_fusion.SparseBucket") -> dict:
        row = {
            "leaf": b.index,
            "dtype": np.dtype(b.dtype).name,
            "rows": b.rows,
            "row_elems": b.row_elems,
            "dense_rows": b.dense_rows,
            "algo": b.algo,
            "index_itemsize": b.index_itemsize,
        }
        if b.label:
            row["label"] = b.label
        if b.wire_dtype is not None:
            row["wire_dtype"] = np.dtype(b.wire_dtype).name
            if b.wire_bits:
                row["wire_bits"] = b.wire_bits
        return row

    def plan_hash(self) -> str:
        """Stable 8-hex-digit identity of the plan (crc32 of the
        canonical JSON — crc32, not hash(), so it matches across
        processes), logged on the timeline SCHEDULE row and carried in
        BENCH output as ``exchange_schedule_hash``."""
        return f"{zlib.crc32(self.to_json().encode('utf-8')) & 0xFFFFFFFF:08x}"

    def save(self, path: str) -> str:
        """Write the artifact (pretty-printed; the hash is computed over
        the canonical form, so formatting doesn't change identity)."""
        with open(path, "w") as f:
            json.dump(json.loads(self.to_json()), f, indent=1,
                      sort_keys=True)
            f.write("\n")
        return path

    @staticmethod
    def from_json(text: str) -> "ExchangeSchedule":
        """Parse a serialized artifact; unknown schema raises (never
        field-guessed — the tuning-cache convention)."""
        try:
            data = json.loads(text)
        except ValueError as e:
            raise HorovodError(f"unreadable ExchangeSchedule JSON: {e}")
        if not isinstance(data, dict) \
                or data.get("schema") != ARTIFACT_SCHEMA:
            raise HorovodError(
                f"ExchangeSchedule schema mismatch: expected "
                f"{ARTIFACT_SCHEMA!r}, got {data.get('schema')!r} — "
                f"refusing to guess a stale layout.")
        buckets, members = [], []
        for row in data["buckets"]:
            buckets.append(_fusion.Bucket(
                indices=tuple(row["indices"]),
                dtype=np.dtype(row["dtype"]),
                total_bytes=int(row["total_bytes"]),
                wire_dtype=(None if row["wire_dtype"] is None
                            else np.dtype(row["wire_dtype"])),
                algo=row["algo"],
                priority=int(row["priority"]),
                wire_bits=int(row.get("wire_bits", 0)),
                intra_wire_dtype=(np.dtype(row["intra_wire_dtype"])
                                  if row.get("intra_wire_dtype") else None),
                cross_wire_dtype=(np.dtype(row["cross_wire_dtype"])
                                  if row.get("cross_wire_dtype") else None),
                cross_wire_bits=int(row.get("cross_wire_bits", 0)),
                channels=int(row.get("channels", 1))))
            members.append(tuple(row["members"]))
        sparse = []
        for row in data.get("sparse_buckets", []):
            sparse.append(_fusion.SparseBucket(
                index=int(row["leaf"]),
                dtype=np.dtype(row["dtype"]),
                rows=int(row["rows"]),
                row_elems=int(row["row_elems"]),
                dense_rows=int(row["dense_rows"]),
                algo=row["algo"],
                wire_dtype=(np.dtype(row["wire_dtype"])
                            if row.get("wire_dtype") else None),
                wire_bits=int(row.get("wire_bits", 0)),
                index_itemsize=int(row.get("index_itemsize", 4)),
                label=row.get("label", "")))
        el = data.get("elastic")
        elastic = (None if el is None else ElasticMeta(
            survivors=tuple(int(r) for r in el["survivors"]),
            dropped=tuple(int(r) for r in el["dropped"]),
            generation=int(el["generation"])))
        fs = data.get("fsdp")
        fsdp = (None if fs is None else FsdpMeta(
            mode=str(fs["mode"]),
            fsdp_size=int(fs["fsdp_size"]),
            data_size=int(fs["data_size"]),
            gather_order=tuple(int(i) for i in fs["gather_order"]),
            leaf_bytes=tuple(int(b) for b in fs["leaf_bytes"]),
            wire_dtypes=tuple(str(d) for d in fs["wire_dtypes"])))
        return ExchangeSchedule(
            mode=data["mode"],
            world_size=int(data["world_size"]),
            num_slices=int(data["num_slices"]),
            threshold_bytes=int(data["threshold_bytes"]),
            region_thresholds=tuple(data["region_thresholds"]),
            leaf_bytes=tuple(data["leaf_bytes"]),
            buckets=tuple(buckets),
            members=tuple(members),
            sparse_buckets=tuple(sparse),
            elastic=elastic,
            fsdp=fsdp)

    def with_elastic(self, survivors, dropped,
                     generation: int) -> "ExchangeSchedule":
        """A copy of the plan stamped with elastic provenance (the plan
        hash changes — an elastic transition IS a new plan identity)."""
        return dataclasses.replace(self, elastic=ElasticMeta(
            survivors=tuple(int(r) for r in survivors),
            dropped=tuple(int(r) for r in dropped),
            generation=int(generation)))

    def with_fsdp(self, meta: "FsdpMeta") -> "ExchangeSchedule":
        """A copy of the plan carrying the FSDP section (the plan hash
        changes — a sharded exchange IS a new plan identity)."""
        return dataclasses.replace(self, fsdp=meta)

    def describe_rows(self) -> list[str]:
        """One line per bucket in issue order (priority included via
        Bucket.describe) — the timeline SCHEDULE row content. Sparse
        exchanges (issued before the dense buckets) lead."""
        return ([b.describe() for b in self.sparse_buckets]
                + [b.describe() for b in self.buckets])


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------


def _pow2(x: int) -> int:
    """Round to the nearest power of two (>= 1). The quantization that
    keeps per-rank cost-model drift from splitting ranks across a region
    threshold: a calibrated constant must move 2x before the plan moves."""
    if x <= 1:
        return 1
    lower = 1 << (x.bit_length() - 1)
    return lower << 1 if x - lower > (lower >> 1) else lower


def _region_thresholds(base: int, model, topo,
                       compute_window_s: float | None) -> tuple[int, ...]:
    """Per-region bucket-size thresholds, issue order (small early, large
    late), clamped and power-of-two quantized. ``base`` is the resolved
    global threshold (the ceiling — an explicit user threshold always
    caps the plan); the floor comes from the α–β model's 90%-busbw point
    (α-amortization) and, when a measured compute window is known, from
    the bytes a 1/(2R)-window communication chunk can carry (start the
    wire early without paying a fresh α per tiny bucket)."""
    if base <= 0:
        return ()  # fusion disabled: every leaf is its own bucket
    floor = max(1, base >> (N_REGIONS - 1))
    hint = None
    if model is not None and topo is not None and topo.group_size > 1:
        hint = model.fusion_threshold_bytes(topo) >> 3
        if compute_window_s is not None and compute_window_s > 0:
            link = model.dcn if topo.multi_slice else model.ici
            window_bytes = int(link.gbps * 1e9 * compute_window_s
                               / (2 * N_REGIONS))
            hint = max(hint, window_bytes)
        hint = min(base, max(1 << 20, _pow2(hint)))
    if hint is not None:
        floor = min(base, max(floor, hint))
    out = []
    for k in range(N_REGIONS):
        out.append(min(base, _pow2(floor << k)))
    out[-1] = base
    # Non-decreasing by construction; assert the invariant cheaply.
    return tuple(out)


def _plan_ordered(order, leaves, thresholds, total_bytes):
    """Bucket the leaf sequence ``order`` (original indices) into
    contiguous same-dtype runs, using region thresholds by cumulative
    byte position — the reference's consecutive-run rule
    (mpi_ops.cc:1604-1637) applied to the reordered sequence."""
    import jax.numpy as jnp

    buckets: list[_fusion.Bucket] = []
    cur: list[int] = []
    cur_dtype = None
    cur_bytes = 0
    seen_bytes = 0
    n_regions = max(1, len(thresholds))

    def threshold_at(pos_bytes: int) -> int:
        if not thresholds:
            return 0
        region = min(n_regions - 1,
                     pos_bytes * n_regions // max(1, total_bytes))
        return thresholds[region]

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            buckets.append(_fusion.Bucket(tuple(cur), cur_dtype, cur_bytes))
            cur, cur_bytes = [], 0

    for i in order:
        leaf = leaves[i]
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        limit = threshold_at(seen_bytes)
        seen_bytes += nbytes
        if limit <= 0:
            flush()
            buckets.append(_fusion.Bucket((i,), leaf.dtype, nbytes))
            cur_dtype = None
            continue
        if cur and (leaf.dtype != cur_dtype
                    or cur_bytes + nbytes > limit):
            flush()
        cur_dtype = leaf.dtype
        cur.append(i)
        cur_bytes += nbytes
    flush()
    return buckets


def plan_exchange(leaves, threshold_bytes: int, *, mode: str,
                  compression=None, algo=None, labels=None,
                  topo=None, model=None, world_size: int | None = None,
                  priority_fn=None,
                  compute_window_s: float | None = None,
                  cross_compression=None,
                  channels: int | None = None,
                  max_channels: int | None = None,
                  sparse=None
                  ) -> ExchangeSchedule:
    """Plan the whole-step exchange over ``leaves`` (arrays or
    ShapeDtypeStructs — only ``.size``/``.dtype`` are read, so plans can
    be computed from ``jax.eval_shape`` results without data).

    ``mode``: ``enum`` reproduces the classic plan exactly (single
    threshold, enumeration order); ``priority`` applies reverse-layer
    issue order + per-region sizing (module docstring). ``compression``
    is a resolved Compressor or None; ``algo`` a concrete name or
    per-bucket selector (the :func:`~horovod_tpu.ops.fusion.plan_buckets`
    contract). ``topo``/``model`` feed the sizing floor and the artifact's
    declared partition shape; omitted, the plan still works (world 1,
    one slice, byte-ramp floor only) — determinism never depends on
    having discovered a topology.

    Cross-rank determinism: when no explicit ``model`` is passed, the
    sizing floor is derived from the topology's ANALYTIC seed constants
    (identical on every rank of a device kind) — deliberately NOT the
    per-host tuning cache, which the always-on recalibrator rewrites
    with host-local measurements; a cache-fed floor could cross a
    power-of-two boundary on one rank only and split the fleet across
    two different plans (the HVD103 divergence this scheduler must
    never cause). Pass ``model=`` explicitly only when every rank is
    guaranteed the same constants.

    ``channels``: explicit channel count for every eligible bucket (the
    ``HOROVOD_EXCHANGE_CHANNELS`` override); ``max_channels``: cap for
    the planner's per-bucket choice (``HOROVOD_MAX_CHANNELS``; default 1
    = channelization off, plans byte-identical to the pre-channel era).
    When the cap is raised the planner picks the cheapest power-of-two
    channel count per bucket from the per-channel α–β model
    (:meth:`~horovod_tpu.utils.costs.CostModel.choose_channels`) — the
    same analytic-constants determinism rule as the sizing floor.

    ``sparse``: resolved :class:`~horovod_tpu.ops.fusion.SparseBucket`
    rows for the step's IndexedSlices exchanges (ops/sparse.py
    ``plan_sparse_exchange``) — recorded on the schedule and serialized
    into the artifact ONLY when present, so dense-only plans keep their
    pre-sparse hashes byte-identical."""
    import jax.numpy as jnp

    leaves = list(leaves)
    if mode not in MODES:
        raise HorovodError(f"unknown exchange mode {mode!r}")
    if labels is not None and len(labels) != len(leaves):
        raise HorovodError(
            f"plan_exchange: {len(labels)} labels for {len(leaves)} "
            f"leaves.")
    leaf_bytes = tuple(int(l.size) * jnp.dtype(l.dtype).itemsize
                       for l in leaves)
    world = (topo.group_size if topo is not None
             else (world_size or 1))
    slices = topo.num_slices if topo is not None else 1
    if model is None and topo is not None:
        model = _costs.CostModel(ici=topo.ici, dcn=topo.dcn)

    comp_elementwise = (compression is None
                        or getattr(compression, "elementwise", False))
    regions: tuple[int, ...] = ()
    if mode == "enum":
        buckets = _fusion.plan_buckets(leaves, threshold_bytes,
                                       compression=compression, algo=algo,
                                       group_size=world,
                                       cross_compression=cross_compression)
    elif not comp_elementwise:
        # Scale-coupled compressor (int8 and the block formats): bucket
        # membership IS numerics (shared scales / the block grid) —
        # preserve the enumeration plan's membership, reorder issue
        # only. Bit-exact by construction.
        planned = _fusion.plan_buckets(leaves, threshold_bytes,
                                       compression=compression, algo=algo,
                                       group_size=world,
                                       cross_compression=cross_compression)
        buckets = [dataclasses.replace(b, priority=i)
                   for i, b in enumerate(reversed(planned))]
    else:
        order = list(range(len(leaves)))[::-1]  # reverse enumeration
        if priority_fn is not None:
            def key(i):
                label = labels[i] if labels is not None else str(i)
                # Stable among equal keys: keep reverse-enumeration order.
                return (priority_fn(label, i), -i)
            order = sorted(range(len(leaves)), key=key)
        regions = _region_thresholds(threshold_bytes, model, topo,
                                     compute_window_s)
        raw = _plan_ordered(order, leaves, regions, sum(leaf_bytes))
        raw = _fusion._annotate_algo(
            _fusion._annotate_wire(raw, compression, world), algo)
        raw = _fusion._annotate_phase_wire(raw, compression,
                                           cross_compression)
        buckets = [dataclasses.replace(b, priority=i)
                   for i, b in enumerate(raw)]
    buckets = _assign_channels(buckets, topo, model, world, slices,
                               channels, max_channels, compression)
    members = tuple(
        tuple(labels[i] for i in b.indices) if labels is not None else ()
        for b in buckets)
    return ExchangeSchedule(
        mode=mode, world_size=world, num_slices=slices,
        threshold_bytes=int(threshold_bytes),
        region_thresholds=regions, leaf_bytes=leaf_bytes,
        buckets=tuple(buckets), members=members,
        sparse_buckets=tuple(sparse or ()))


def _split_units(b, world: int, slices: int, compression) -> int:
    """How many units the channelized lowering actually splits for this
    bucket — per-rank shard elements for the phased algos, packed block
    rows where a block wire is what splits (ops/strategy.py). The honest
    clamp for a committed channel count: clamping on ``b.elems`` alone
    would let a plan claim more channel instances than the compiled
    program emits (a 16-element rs_ag bucket over 8 ranks has a 2-element
    shard — 2 instances max), mispricing per-channel α and breaking the
    span grouping the channel-efficiency fit relies on."""
    elems = max(1, b.elems)
    block = getattr(compression, "block", 0) or 0
    unsummable = b.wire_bits == 4 or b.cross_wire_bits == 4
    if b.algo == "rs_ag":
        if unsummable and block:
            nb = -(-elems // block)          # packed block rows
            return max(1, -(-nb // world))   # per-rank chunk rows
        return max(1, -(-elems // world))    # per-rank shard elements
    if b.algo == "hierarchical":
        # Per-rank shard elements bind the RS/AG stages; the asym cross
        # hop splits its own (possibly coarser) block-row grid and
        # degrades to fewer instances on its own — by design, the
        # quantize barrier's stage, not the bucket's channel count.
        local = (world // slices
                 if slices > 1 and world % slices == 0 else 0)
        if local > 1:
            return max(1, -(-elems // local))
        return elems
    if unsummable and block:  # flat int4: the gather splits block rows
        return max(1, -(-elems // block))
    return elems


def _assign_channels(buckets, topo, model, world: int, slices: int,
                     channels: int | None,
                     max_channels: int | None, compression) -> list:
    """Stamp each bucket's channel count — the multi-channel analog of
    the ``auto`` algorithm selector.

    ``channels`` (the explicit ``HOROVOD_EXCHANGE_CHANNELS`` override)
    wins outright; otherwise the planner asks the per-channel α–β model
    for the cheapest power-of-two count <= ``max_channels`` per bucket.
    Both resolve to 1 on 1-rank worlds and for buckets whose algo tag
    has no channelized lowering (``auto`` left unresolved: the lowering
    decides the algorithm per call, so the plan cannot commit a split
    for it). A channel never carries less than one split unit: the
    count is clamped to what the lowering can actually cut
    (:func:`_split_units`)."""
    if channels is not None and channels < 1:
        raise HorovodError(
            f"plan_exchange: channels must be >= 1, got {channels}.")
    cap = 1 if max_channels is None else int(max_channels)
    if (channels is None and cap <= 1) or world <= 1:
        return buckets
    out = []
    for b in buckets:
        c = 1
        if b.algo in _costs.ALGORITHMS:
            if channels is not None:
                c = channels
            elif model is not None and topo is not None:
                kwargs = {}
                if b.algo == "hierarchical" \
                        and b.cross_wire_dtype is not None:
                    kwargs["cross_nbytes"] = b.cross_bytes_on_wire
                    nbytes = b.intra_bytes_on_wire
                else:
                    nbytes = b.bytes_on_wire
                if b.wire_bits == 4 and b.algo == "flat":
                    kwargs["gather"] = True  # int4 gather-form pricing
                c = model.choose_channels(b.algo, nbytes, topo, cap,
                                          **kwargs)
            c = max(1, min(c, _split_units(b, world, slices,
                                           compression)))
        out.append(dataclasses.replace(b, channels=c) if c != b.channels
                   else b)
    return out


# ---------------------------------------------------------------------------
# Exposed-communication accounting
# ---------------------------------------------------------------------------


def planned_exposed_comm_ms(sched: ExchangeSchedule, topo, model,
                            compute_ms: float,
                            comm_scale: float = 1.0) -> float:
    """Deterministic exposed (non-overlapped) communication time of one
    step under ``sched``, in ms.

    The overlap model matches how the compiled program actually behaves
    with the CRS combiner pinned to the framework's buckets
    (docs/tensor-fusion.md): backward compute runs ``[0, compute_ms]``
    producing gradient leaves in REVERSE enumeration order at a rate
    proportional to their bytes; a bucket's collective may start once all
    its members exist AND all earlier-issued buckets' collectives have
    finished (one serial wire); each collective lasts the α–β model's
    prediction for its wire bytes (× ``comm_scale``, the measured-total
    anchor the bench applies). Exposed time is the wire-busy time falling
    after compute ends — the tax the scheduler exists to shrink.

    Enumeration order worst-cases this (bucket 0 holds the LAST-produced
    gradients, so nothing starts until backward is nearly done); the
    priority order overlaps by construction, which is what the bench
    assertion ``exposed_priority <= exposed_enum`` pins."""
    total = sum(sched.leaf_bytes) or 1
    # Production time of each leaf: cumulative-byte fraction of the
    # backward pass, walking leaves in reverse enumeration order.
    ready_at = {}
    cum = 0
    for i in reversed(range(len(sched.leaf_bytes))):
        cum += sched.leaf_bytes[i]
        ready_at[i] = compute_ms * cum / total
    t = 0.0
    exposed = 0.0
    for b in sched.buckets:
        ready = max((ready_at[i] for i in b.indices), default=0.0)
        algo = b.algo
        if algo == "auto":
            algo = (model.choose(b.bytes_on_wire, topo)
                    if model is not None and topo is not None else "flat")
        dur = 0.0
        if model is not None and topo is not None and topo.group_size > 1:
            if algo == "hierarchical" and b.cross_wire_dtype is not None:
                # Phase-asymmetric bucket: price each phase on the bytes
                # it actually moves (fusion.Bucket per-phase fields).
                pred = model.predict_us(
                    algo, b.intra_bytes_on_wire, topo,
                    cross_nbytes=b.cross_bytes_on_wire,
                    channels=b.channels)
            else:
                pred = model.predict_us(algo, b.bytes_on_wire, topo,
                                        channels=b.channels)
            if pred != float("inf"):
                dur = pred * 1e-3 * comm_scale
        start = max(t, ready)
        end = start + dur
        if end > compute_ms:
            exposed += end - max(start, compute_ms)
        t = end
    return exposed


def exposed_comm_from_spans(comm_spans, compute_spans) -> float:
    """Exposed communication from MEASURED timeline spans: the portion of
    the union of ``comm_spans`` not covered by the union of
    ``compute_spans``. Spans are ``(start, duration)`` in any one unit;
    the result is in that unit. Pure interval arithmetic (unit-tested),
    fed by device-timeline captures on TPU."""
    def union(spans):
        ivs = sorted((s, s + d) for s, d in spans if d > 0)
        out = []
        for s, e in ivs:
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], e))
            else:
                out.append((s, e))
        return out

    comm = union(comm_spans)
    compute = union(compute_spans)
    exposed = 0.0
    for cs, ce in comm:
        covered = 0.0
        for ks, ke in compute:
            lo, hi = max(cs, ks), min(ce, ke)
            if hi > lo:
                covered += hi - lo
        exposed += (ce - cs) - covered
    return exposed


def measured_exposed_comm_ms(run_once, steps: int = 1) -> float | None:
    """Device-true exposed comm per step: profile one execution, classify
    each device's ops into communication (collective opcodes, read from
    the instruction's text: core/xprof.hlo_base) vs compute (everything
    else), and return the worst device's non-overlapped comm ms via
    :func:`exposed_comm_from_spans`. None when the capture has no device
    plane (CPU backends) — callers fall back to the planned estimate."""
    import shutil
    import tempfile

    import jax

    from horovod_tpu.core import xprof as _xprof

    d = tempfile.mkdtemp(prefix="hvd_exposed_")
    try:
        jax.profiler.start_trace(d)
        try:
            run_once()
        finally:
            jax.profiler.stop_trace()
        planes = _xprof.device_planes(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if not planes:
        return None
    return max(exposed_comm_ms_of_events(ev) for ev in planes.values()) \
        / max(1, steps)


def exposed_comm_ms_of_events(events) -> float:
    """Exposed comm ms of one device plane's ``[(name, start_us,
    dur_us)]`` (core/xprof.device_planes)."""
    from horovod_tpu.core import xprof as _xprof

    comm, compute = [], []
    for name, start, dur in events:
        is_comm = _xprof.is_collective(_xprof.hlo_base(name))
        (comm if is_comm else compute).append((start, dur))
    return exposed_comm_from_spans(comm, compute) / 1e3


# ---------------------------------------------------------------------------
# Always-on α–β recalibration
# ---------------------------------------------------------------------------


class Recalibrator:
    """Online least-squares refresh of the α–β constants from measured
    collective times, persisted to the v3 tuning cache.

    Per level ("ici"/"dcn") the running sums of a straight-line fit
    ``t = α + x/β`` over the RING-NORMALIZED regressor ``x = ring·S``
    (ring folded in per observation, so samples from different world
    sizes — including sums continued from a prior run's cache — mix
    correctly) are kept (n, Σx, Σt, Σxt, Σx²); every
    ``PERSIST_EVERY`` observations the merged constants are written to
    ``HOROVOD_TUNING_CACHE``, continuing any prior run's sums (read from
    the cache's ``recalibration`` section; a stale/corrupt cache is
    ignored and the fit starts fresh — never misread). Constants are
    rounded (α to 0.01 µs, β to 0.001 GB/s) so equal measurements on
    different ranks write byte-identical caches."""

    PERSIST_EVERY = 8

    def __init__(self) -> None:
        self._sums: dict[str, dict] = {}
        self._since_persist = 0
        self._seeded = False

    # -- observation ---------------------------------------------------------

    def observe(self, level: str, nbytes: int, seconds: float,
                world: int) -> None:
        """One measured collective: ``nbytes`` on the wire took
        ``seconds`` over a ``world``-rank group at interconnect
        ``level``."""
        if nbytes <= 0 or seconds <= 0 or world < 2:
            return
        x = 2 * (world - 1) / world * float(nbytes)  # ring-normalized
        s = self._sums.setdefault(level, dict(
            n=0, s=0.0, t=0.0, st=0.0, ss=0.0))
        s["n"] += 1
        s["s"] += x
        s["t"] += float(seconds)
        s["st"] += x * float(seconds)
        s["ss"] += x ** 2
        self._since_persist += 1

    def observe_channels(self, level: str, channels: int, nbytes: int,
                         seconds: float, world: int) -> None:
        """One measured MULTI-CHANNEL collective: ``channels`` concurrent
        channel instances together moved ``nbytes`` total wire bytes in
        ``seconds`` of wall time over a ``world``-rank group at
        ``level``. The implied aggregate-bandwidth multiplier vs the
        level's current single-channel β fit yields a per-extra-channel
        efficiency sample (utils/costs.py ``channel_eta`` semantics:
        ``eta = 1 + (C-1)·eff``), folded into a running mean that
        persists as the level's ``ch_eff`` constant. Skipped when the
        level has no usable β yet — an efficiency without a
        single-channel reference would be a guess."""
        if channels < 2 or nbytes <= 0 or seconds <= 0 or world < 2:
            return
        fit = self._fit(self._sums.get(level, {}) or {"n": 0})
        if fit is None:
            return
        _, gbps = fit
        ring = 2 * (world - 1) / world
        t1 = ring * float(nbytes) / (gbps * 1e9)  # single-channel bw time
        eta = t1 / float(seconds)
        eff = max(0.0, min(1.0, (eta - 1.0) / (channels - 1)))
        s = self._sums.setdefault(level, dict(
            n=0, s=0.0, t=0.0, st=0.0, ss=0.0))
        s["ch_n"] = int(s.get("ch_n", 0)) + 1
        s["ch_e"] = float(s.get("ch_e", 0.0)) + eff
        self._since_persist += 1

    def _fit(self, s: dict):
        """(alpha_us, gbps) from one level's sums, or None when the fit
        is degenerate (fewer than 2 distinct sizes)."""
        n = s["n"]
        if n < 2:
            return None
        var = n * s["ss"] - s["s"] ** 2
        if var <= 0:
            return None  # one size observed repeatedly: no slope
        slope = (n * s["st"] - s["s"] * s["t"]) / var
        intercept = (s["t"] - slope * s["s"]) / n
        # Clamp to physical values rather than poisoning the cache (the
        # --calibrate convention): noisy hosts can fit a negative α.
        # slope is 1/β directly (the regressor already carries ring).
        alpha_us = max(intercept * 1e6, 0.1)
        gbps = max(1.0 / max(slope, 1e-15) / 1e9, 0.01)
        return round(alpha_us, 2), round(gbps, 3)

    def constants(self) -> dict:
        """Fitted ``{"ici": {"alpha_us", "gbps"[, "ch_eff"]}, ...}`` for
        every level with a non-degenerate fit (cache-layout form); the
        per-extra-channel efficiency rides along once any multi-channel
        observation has been folded in (rounded to 0.01 so equal
        measurements write byte-identical caches)."""
        out = {}
        for level, s in self._sums.items():
            fit = self._fit(s)
            if fit is not None:
                entry = {"alpha_us": fit[0], "gbps": fit[1]}
                if s.get("ch_n", 0) > 0:
                    entry["ch_eff"] = round(s["ch_e"] / s["ch_n"], 2)
                out[level] = entry
        return out

    # -- persistence ---------------------------------------------------------

    def _seed_from_cache(self, device_kind: str, path=None) -> None:
        """Continue a previous run's fit: fold the cache's recalibration
        sums into ours, once. Anything unreadable/stale is simply absent
        (load_tuning_cache already refuses unknown schemas)."""
        self._seeded = True
        cache = _costs.load_tuning_cache(path)
        if not cache or cache.get("device_kind") != device_kind:
            return
        prior = cache.get("recalibration")
        if not isinstance(prior, dict):
            return
        for level, p in prior.items():
            if not isinstance(p, dict):
                continue
            try:
                vals = {k: float(p[k]) for k in ("s", "t", "st", "ss")}
                n = int(p["n"])
            except (KeyError, TypeError, ValueError):
                continue  # corrupt section: ignored, never misread
            if n < 0 or vals["s"] < 0 or vals["t"] < 0:
                continue
            s = self._sums.setdefault(level, dict(
                n=0, s=0.0, t=0.0, st=0.0, ss=0.0))
            s["n"] += n
            for k in ("s", "t", "st", "ss"):
                s[k] += vals[k]
            # Channel-efficiency sums are optional (pre-channel runs
            # wrote none) and individually validated — a corrupt pair is
            # dropped without discarding the level's α–β continuation.
            try:
                ch_n = int(p.get("ch_n", 0))
                ch_e = float(p.get("ch_e", 0.0))
            except (TypeError, ValueError):
                continue
            if ch_n > 0 and 0.0 <= ch_e <= ch_n:
                s["ch_n"] = int(s.get("ch_n", 0)) + ch_n
                s["ch_e"] = float(s.get("ch_e", 0.0)) + ch_e

    def maybe_persist(self, topo, path=None, force: bool = False) -> bool:
        """Write the refreshed constants when due (every
        ``PERSIST_EVERY`` observations, or ``force``). Returns whether a
        write happened."""
        if not _env.recalibration_enabled():
            return False
        if not force and self._since_persist < self.PERSIST_EVERY:
            return False
        if not self._seeded:
            self._seed_from_cache(topo.device_kind, path)
        constants = self.constants()
        if not constants:
            return False
        # Keep everything a prior --calibrate run measured alive: the
        # other level's constants, the MEASURED fusion threshold (a
        # real sweep beats our analytic derivation — clobbering it
        # would silently retune HOROVOD_AUTOTUNE=1 runs), and the raw
        # measurement rows.
        cache = _costs.load_tuning_cache(path)
        merged: dict = {}
        measured = None
        threshold = None
        if cache and cache.get("device_kind") == topo.device_kind:
            merged = dict(cache.get("constants") or {})
            measured = cache.get("measured")
            raw = cache.get("fusion_threshold")
            if isinstance(raw, (int, float)) and raw > 0:
                threshold = int(raw)
        merged.update(constants)
        if threshold is None:
            # Power-of-two quantized, like the region thresholds: this
            # value feeds HOROVOD_AUTOTUNE=1 bucket planning on every
            # rank, and a raw host-local fit would hand each rank a
            # slightly different threshold — a per-rank PLAN divergence
            # (HVD103 class). Quantized, fits must differ 2x before any
            # rank's plan moves.
            model = _costs.model_from_constants(merged, topo)
            threshold = min(256 << 20, max(
                1 << 20, _pow2(model.fusion_threshold_bytes(topo))))
        _costs.save_tuning_cache(
            merged, device_kind=topo.device_kind, world=topo.group_size,
            fusion_threshold=threshold, measured=measured,
            recalibration={level: dict(s)
                           for level, s in self._sums.items()},
            path=path)
        self._since_persist = 0
        return True


_recalibrator = Recalibrator()


def recalibrator() -> Recalibrator:
    return _recalibrator


def reset_recalibration() -> None:
    """Fresh in-process recalibration state (tests / shutdown)."""
    global _recalibrator
    _recalibrator = Recalibrator()


# ---------------------------------------------------------------------------
# Live-plan registry + device-span feedback
# ---------------------------------------------------------------------------

_live_plan: ExchangeSchedule | None = None


def register_live_plan(sched: ExchangeSchedule) -> None:
    """Record the most recent traced gradient-exchange plan — consulted
    by the device-span feedback below (interconnect level, wire bytes)
    and exported by :func:`last_plan` for the lint gate / bench hash."""
    global _live_plan
    _live_plan = sched


def last_plan() -> ExchangeSchedule | None:
    return _live_plan


_SPAN_ACTIVITIES = ("XLA_ALLREDUCE", "XLA_REDUCESCATTER", "XLA_ALLGATHER")


def observe_xla_spans(spans, sched_entries) -> None:
    """Feed device-timeline collective spans into the recalibrator — the
    always-on loop's trickle source during real training. ``spans`` are
    ``(row, activity, start_us, dur_us)`` from core/xprof.py;
    ``sched_entries`` the negotiated trace-time schedule rows
    ``[name, op, dtype, shape, group, root, members]`` that give each
    row its payload bytes. Never raises — a feedback bug must not take
    down the timeline path."""
    if not _env.recalibration_enabled():
        return
    try:
        from horovod_tpu.core import state as _state
        from horovod_tpu.ops import topology as _topology

        by_name = {e[0]: e for e in sched_entries}
        plan = _live_plan
        wire_by_members = {}
        ch_by_members = {}
        if plan is not None:
            for b, m in zip(plan.buckets, plan.members):
                wire_by_members[m] = b.bytes_on_wire
                ch_by_members[m] = b.channels
        # Discovery is memoized per (devices, override), so this is a
        # dict hit on sampled steps after the first; it anchors the
        # persist's device_kind. The level/world come from the
        # registered plan when one exists — it carries the exchange's
        # own group shape, where group 0 would be a guess.
        topo = _topology.discover(_state.get_group(0))
        if plan is not None:
            level = "dcn" if plan.num_slices > 1 else "ici"
            world = plan.world_size
        else:
            level = "dcn" if topo.multi_slice else "ici"
            world = topo.group_size
        rec = recalibrator()
        fed = False
        # Channelized buckets: the C per-channel spans of one bucket are
        # ONE concurrent-instance observation — their union wall time vs
        # the bucket's total wire bytes feeds the per-level channel
        # efficiency, while each span individually would pair partial
        # bytes with the α–β fit and corrupt β. Group per row first.
        by_row: dict = {}
        for row, activity, start, dur_us in spans:
            if activity not in _SPAN_ACTIVITIES or dur_us <= 0:
                continue
            by_row.setdefault(row, []).append((start, dur_us))
        for row, row_spans in by_row.items():
            entry = by_name.get(row)
            if entry is None:
                continue
            members = tuple(entry[6]) if len(entry) > 6 else ()
            nbytes = wire_by_members.get(members)
            if nbytes is None:
                shape, dtype = entry[3], entry[2]
                # One shape, or a plain-sum bucket's: one a leaf.
                shapes = (shape if shape and not np.isscalar(shape[0])
                          else [shape])
                nbytes = (sum(int(np.prod(s or [1])) for s in shapes)
                          * np.dtype(dtype).itemsize)
            ch = ch_by_members.get(members, 1)
            if ch > 1:
                if len(row_spans) < ch:
                    # A partial capture (span dropped, dur filtered):
                    # feeding each 1/C-duration span paired with the
                    # bucket's FULL wire bytes would corrupt β — skip
                    # the row entirely, never fall back to per-span
                    # observes.
                    continue
                wall_us = (max(s + d for s, d in row_spans)
                           - min(s for s, _ in row_spans))
                rec.observe_channels(level, ch, nbytes, wall_us * 1e-6,
                                     world)
                fed = True
                continue
            for _start, dur_us in row_spans:
                rec.observe(level, nbytes, dur_us * 1e-6, world)
                fed = True
        if fed:
            rec.maybe_persist(topo)
    except Exception:
        pass  # feedback is best-effort by contract
