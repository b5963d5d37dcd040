"""Process-wide runtime state: device groups, meshes, lifecycle.

TPU-native redesign of the reference's ``HorovodGlobalState`` / ``HorovodGlobal``
(/root/reference/horovod/tensorflow/mpi_ops.cc:140-254). The reference keeps one
full runtime per MPI group — sub-communicator, background coordinator thread,
tensor table — because MPI processes are independent and must negotiate a common
collective order. On TPU the program is SPMD: one Python process (per host)
drives all local devices through XLA, so dispatch order is already globally
consistent and no coordinator thread is needed. What remains, and what this
module provides, is the *group model*:

* a **rank** is a global device index (``jax.devices()`` order) — the analog of
  an MPI rank in the reference,
* a **Group** is an ordered subset of ranks — the analog of a sub-communicator
  built via ``MPI_Group_incl``/``MPI_Comm_create`` (mpi_ops.cc:1775-1787) —
  realised as a ``jax.sharding.Mesh`` over the group's devices with a single
  ``"hvd"`` axis, plus the ``replica_groups`` partition used when the group's
  collectives are issued inside a larger SPMD program,
* overlapping groups are allowed, exactly as the reference allows a rank to be
  a member of several communicators (README.md:10): each group is an
  independent mesh, and collectives on different groups are independent
  dispatches.

``init(group_ranks)`` mirrors ``horovod_tensorflow_init`` (mpi_ops.cc:1905) but
fixes the fork's API inconsistency (SURVEY §2.9): calling ``init()`` with no
arguments creates the default *global* group 0 containing every device, so both
the upstream-style API (``hvd.init(); hvd.allreduce(t)``) and the fork's
explicit-group API (``hvd.init([[0,1,2],[2,3,4]])``, ``group=`` kwarg) work.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh

from horovod_tpu.utils import env as _env

# The single mesh axis name used by every collective this framework issues.
AXIS_NAME = "hvd"


class HorovodError(RuntimeError):
    """Raised when collective negotiation fails.

    The analog of the reference's ``MPIResponse::ERROR`` surfacing as
    ``tf.errors.FailedPreconditionError`` in user code (mpi_ops.cc:1356-1363,
    tested at mpi_ops_test.py:284-356).
    """


class NotInitializedError(HorovodError):
    """Operation requires ``hvd.init()`` first (mirrors mpi_ops.py's -1/'not
    initialized' contract, mpi_ops.cc:1913-1918)."""


@dataclasses.dataclass(frozen=True)
class Group:
    """One collective group: an ordered set of device ranks.

    Equivalent of one ``HorovodGlobalState``'s MPI communicator
    (mpi_ops.cc:192). ``ranks`` are *global* device indices; a device's rank
    within the group is its position in ``ranks``.
    """

    index: int
    ranks: tuple[int, ...]
    devices: tuple[jax.Device, ...]
    mesh: Mesh  # 1-D mesh over `devices`, axis AXIS_NAME

    @property
    def size(self) -> int:
        return len(self.ranks)

    def group_rank_of(self, global_rank: int) -> int:
        """Group-local rank of a global device rank, or -1 if not a member."""
        try:
            return self.ranks.index(global_rank)
        except ValueError:
            return -1

    def local_member_ranks(self) -> tuple[int, ...]:
        """Group-local ranks whose devices THIS process drives.

        Single-controller: every rank. Multi-controller (one process per
        host): the ranks backed by ``jax.local_devices()`` — the set a
        process submits eager values/requests for, the analog of 'the ranks
        this MPI process is' (a process is exactly one rank in the
        reference; here a process hosts several device-ranks)."""
        pidx = jax.process_index()
        return tuple(i for i, d in enumerate(self.devices)
                     if d.process_index == pidx)

    def replica_groups(self, world_size: int) -> list[list[int]]:
        """Partition of all ranks for use as ``axis_index_groups`` inside a
        global-mesh SPMD program: this group's ranks collectively, every other
        rank alone (so non-members see the collective as identity)."""
        members = set(self.ranks)
        return [list(self.ranks)] + [[r] for r in range(world_size) if r not in members]


class _State:
    """Process singleton, analog of ``HorovodGlobal`` (mpi_ops.cc:234-247)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.devices: tuple[jax.Device, ...] = ()
        self.groups: list[Group] = []
        self.fusion_threshold = _env.DEFAULT_FUSION_THRESHOLD
        # Bumped on every successful init; compiled-program caches include it
        # in their keys so a shutdown/re-init with a different group layout
        # (but an equal mesh) can never replay a stale closure.
        self.generation = 0

    def reset(self) -> None:
        self.initialized = False
        self.devices = ()
        self.groups = []


_state = _State()


def _build_group(index: int, ranks: Sequence[int], devices: Sequence[jax.Device]) -> Group:
    group_devices = tuple(devices[r] for r in ranks)
    import numpy as np

    mesh = Mesh(np.array(group_devices), (AXIS_NAME,))
    return Group(index=index, ranks=tuple(ranks), devices=group_devices, mesh=mesh)


def init(group_ranks: Sequence[Sequence[int]] | None = None,
         devices: Sequence[jax.Device] | None = None) -> None:
    """Initialize the runtime.

    ``group_ranks`` is the reference's 2-D group list
    (``hvd.init([[0,1,2],[2,3,4]])``, mpi_ops.py:81-110). With no argument a
    single global group 0 over every device is created — the intended default
    the fork never finished wiring up (SURVEY §2.9). When explicit groups are
    given, group 0 is ALWAYS the implicit global group and user groups start at
    index 1 if the first user group is not itself the full world; if the first
    user group covers every rank it becomes group 0, matching the reference's
    ``MPI_Comm_dup(MPI_COMM_WORLD)`` special case (mpi_ops.cc:1777-1778).

    ``devices`` overrides the device list (testing); defaults to
    ``jax.devices()``.
    """
    from horovod_tpu.core import timeline as _timeline

    if _state.initialized:
        return  # InitializeHorovodOnce semantics (mpi_ops.cc:1815)
    # A new world starts a new record (the last one stayed readable
    # after shutdown); its first span is the program's share of set-up.
    _timeline.session().clear_record()
    _timeline.listen()
    with _timeline.span("hvd/init"):
        _init(group_ranks, devices)


def _init(group_ranks, devices) -> None:
    with _state.lock:
        if _state.initialized:
            return
        # Unknown HOROVOD_* variables are almost certainly typo'd knob
        # names (HOROVOD_COMPRESION=int8), which — unlike typo'd values —
        # would otherwise be silently ignored. hvd-lint flags the same
        # registry (HVD006).
        _env.warn_unknown_env()
        # Newer-knob convention: typo'd VALUES raise here, at init, not
        # at the first compressed exchange minutes into a run.
        _env.compression_block()
        _env.error_feedback_default()
        _env.compression_cross_slice_default()
        _env.exchange_channels_default()
        _env.max_channels()
        _env.model_max_states()
        _env.model_faults()
        _env.sparse_density_threshold()
        _env.sparse_pad_capacity()
        _env.serve_kv_dtype()
        _env.serve_prefix_cache()
        _env.serve_speculate()
        _env.serve_draft_kv_dtype()
        _env.serve_deadline_ms()
        _env.serve_journal_path()
        _env.serve_watchdog_timeout()
        _env.serve_min_accept()
        _env.elastic_enabled()
        _env.elastic_min_world()
        _env.elastic_join_timeout_seconds()
        _env.sharding_mode()
        _env.fsdp_axis_size()
        # Elastic reshard logic (core/elastic.py) re-replicates state on
        # shrink/regrow; a sharded layout would silently desync the
        # surviving shards on the first reshard. Refuse the combination
        # loudly, here, rather than minutes into a run.
        if _env.elastic_enabled() and _env.sharding_mode() != "off":
            raise HorovodError(
                f"HOROVOD_ELASTIC=1 is incompatible with "
                f"HOROVOD_SHARDING={_env.sharding_mode()}: the elastic "
                f"shrink/regrow path re-replicates training state and "
                f"would desync sharded (ZeRO-2/3) layouts on reshard. "
                f"Use the replicated path (HOROVOD_SHARDING=off) with "
                f"elastic training, or drop HOROVOD_ELASTIC for "
                f"sharded runs.")
        _env.profile_mode()
        _env.tune_budget_seconds()
        _env.tuned_config_path()
        devs = tuple(devices if devices is not None else jax.devices())
        world = len(devs)
        groups: list[Group] = []
        if not group_ranks:
            groups.append(_build_group(0, range(world), devs))
        else:
            specs: list[tuple[int, ...]] = []
            for g in group_ranks:
                ranks = tuple(int(r) for r in g)
                if not ranks:
                    raise HorovodError("Groups must contain at least one rank.")
                if len(set(ranks)) != len(ranks):
                    raise HorovodError(f"Group {list(ranks)} contains duplicate ranks.")
                for r in ranks:
                    if not 0 <= r < world:
                        raise HorovodError(
                            f"Rank {r} out of range for world size {world}.")
                specs.append(ranks)
            all_ranks = tuple(range(world))
            if specs[0] != all_ranks:
                specs.insert(0, all_ranks)
            for i, ranks in enumerate(specs):
                groups.append(_build_group(i, ranks, devs))
        _state.devices = devs
        _state.groups = groups
        _state.fusion_threshold = _env.fusion_threshold_bytes()
        from horovod_tpu.core import timeline as _timeline

        # Coordinator-only, like the reference ("Open the timeline file on
        # coordinator", mpi_ops.cc:1486-1489): in multi-host mode only
        # process 0 — which drives the negotiation and sees every rank's
        # arrival — writes the timeline.
        from horovod_tpu.core import multihost as _mh

        if not _mh.active() or _mh.process_index() == 0:
            _timeline.maybe_start()
        _state.generation += 1
        _state.initialized = True
        if _mh.active():
            # Liveness publisher (core/resilience.py): every multi-host
            # process heartbeats hvd/hb/g<gen>/p<pid> so blocked peers can
            # tell a slow process from a dead one.
            from horovod_tpu.core import resilience as _res

            _res.start_heartbeat()
    # Profile-guided configuration (horovod_tpu/tune) — deliberately
    # OUTSIDE the init lock: applying a committed artifact calls back
    # into the initialized runtime (hvd.size()), and HOROVOD_PROFILE=auto
    # runs live calibration collectives; either would deadlock on the
    # non-reentrant lock above. Explicit env knobs still beat whatever
    # gets applied here (tune/apply.py precedence).
    if _env.profile_mode() == "auto":
        # "Re-tune NOW" beats loading: with both knobs set, auto
        # calibrates fresh and commits to the HOROVOD_TUNED_CONFIG path
        # (tune/artifact.py default_tuned_path) instead of trusting a
        # possibly stale artifact there.
        from horovod_tpu.tune import tune as _tune

        _tune()
    else:
        tuned_path = _env.tuned_config_path()
        if tuned_path is not None:
            from horovod_tpu.tune import apply_committed as _apply_committed

            _apply_committed(tuned_path)


def shutdown() -> None:
    """Tear down the runtime (analog of §3.5 shutdown; frees group state)."""
    from horovod_tpu.core import resilience as _res
    from horovod_tpu.core import timeline as _timeline

    tl = _timeline.session()
    with tl.span("hvd/shutdown"):
        # First, before any state goes: the scope maps a capture will ask
        # for are read from the live programs, which are then let go.
        tl.resolve_scopes(shutdown=True)
        _res.stop_heartbeat()
    _timeline.stop()
    # Drop any applied tuned configuration with the world it was tuned
    # for — a re-init at a different world must not inherit its knobs.
    from horovod_tpu.tune import apply as _tune_apply

    _tune_apply.deactivate()
    with _state.lock:
        _state.reset()
    # Cached collective programs close over Group objects keyed by group
    # index; a later re-init may bind different meshes to the same indices.
    from horovod_tpu.ops import collectives as _coll

    _coll.clear_caches()


def generation() -> int:
    """Monotonic init counter (cache-key component for compiled programs)."""
    return _state.generation


def bump_generation() -> int:
    """Advance the generation WITHOUT re-initializing — the checkpoint-resume
    path (``Trainer.restore``). Compiled-program caches, the multi-host
    Negotiator's KV namespace, and the heartbeat keys all include the
    generation, so after a crash-restart the resumed run's coordination can
    never collide with stale pre-crash keys or replay a stale verdict."""
    with _state.lock:
        _state.generation += 1
        return _state.generation


def reconfigure(ranks: Sequence[int]) -> Group:
    """Elastic world change (core/elastic.py): rebuild the group layout
    as a single group 0 over ``ranks`` — a subset of the previous
    membership after a shrink, a superset after a regrow — WITHOUT
    tearing the runtime down. The device list is untouched (ranks stay
    global device indices, so a dropped rank's row simply leaves every
    group); the generation bumps exactly like ``Trainer.restore`` so
    compiled-program caches, the multi-host KV namespace, and the
    heartbeat keys all roll to a fresh namespace. User subset
    groups are deliberately NOT carried across — a subset referencing a
    dropped rank has no meaning in the new world, and the elastic
    training loop only drives group 0."""
    with _state.lock:
        if not _state.initialized:
            raise NotInitializedError(
                "horovod_tpu has not been initialized; call hvd.init() "
                "first.")
        world = len(_state.devices)
        rs = tuple(int(r) for r in ranks)
        if not rs:
            raise HorovodError(
                "Elastic reconfigure needs at least one surviving rank.")
        if len(set(rs)) != len(rs):
            raise HorovodError(
                f"Group {list(rs)} contains duplicate ranks.")
        for r in rs:
            if not 0 <= r < world:
                raise HorovodError(
                    f"Rank {r} out of range for world size {world}.")
        _state.groups = [_build_group(0, rs, _state.devices)]
        _state.generation += 1
        new_group = _state.groups[0]
    # Cached collective programs close over the OLD Group objects under
    # the same group index — exactly the shutdown/re-init hazard the
    # generation exists for; drop them eagerly like shutdown does.
    from horovod_tpu.ops import collectives as _coll

    _coll.clear_caches()
    return new_group


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _State:
    if not _state.initialized:
        raise NotInitializedError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return _state


def get_group(group: int = 0) -> Group:
    st = _require_init()
    if not 0 <= group < len(st.groups):
        raise HorovodError(
            f"Unknown group {group}; {len(st.groups)} group(s) are defined.")
    return st.groups[group]


def num_groups() -> int:
    return len(_require_init().groups)


def world_devices() -> tuple[jax.Device, ...]:
    return _require_init().devices


def fusion_threshold() -> int:
    return _require_init().fusion_threshold


def target_device() -> jax.Device:
    """The device compiled programs are built for: rank 0 of the world
    ``hvd.init`` was given, else JAX's first default device (code that
    runs without a runtime — a bare kernel call, the serving engine).

    Every implementation choice that depends on the hardware (compiled or
    interpreted Pallas, flash or blockwise attention, device or host
    clock) asks HERE and not ``jax.default_backend()``: a process whose
    default backend is the CPU can build programs for TPU devices (AOT
    topologies, ``hvd.init(devices=...)``), and a choice keyed on the
    process would silently compile the CPU fallbacks into them."""
    if _state.initialized and _state.devices:
        return _state.devices[0]
    return jax.devices()[0]


def target_platform() -> str:
    """``target_device().platform`` — ``"tpu"``, ``"cpu"``, ..."""
    return target_device().platform


# ---------------------------------------------------------------------------
# Rank/size queries: the ctypes surface of the reference (mpi_ops.cc:1905-2001).
# On TPU a "rank" is a device; the per-process eager answer is the rank of the
# first device this process drives (single-controller: rank 0). Inside an SPMD
# traced region these return traced per-device values instead (see
# core/context.py), which is how user step functions observe their own rank.
# ---------------------------------------------------------------------------

def _first_local_global_rank() -> int:
    st = _require_init()
    local = jax.local_devices()
    by_id = {d.id: i for i, d in enumerate(st.devices)}
    for d in local:
        if d.id in by_id:
            return by_id[d.id]
    return 0


def size(group: int = 0) -> int:
    """Number of ranks (devices) in the group (mpi_ops.cc:1937-1944)."""
    return get_group(group).size


def rank(group: int = 0) -> int:
    """This controller's rank within the group (mpi_ops.cc:1923-1935).

    Eager/host view: the group-local rank of the first local device. Inside
    ``hvd.spmd`` traced code, use the traced ``hvd.rank()`` from the context,
    which evaluates per device.
    """
    from horovod_tpu.core import context as _ctx

    tctx = _ctx.current()
    if tctx is not None:
        return tctx.rank(group)
    return get_group(group).group_rank_of(_first_local_global_rank())


def global_size() -> int:
    """Total number of ranks across all hosts (mpi_ops.cc:1957-1963)."""
    return len(_require_init().devices)


def global_rank() -> int:
    """World rank regardless of group (mpi_ops.cc:1947-1954)."""
    from horovod_tpu.core import context as _ctx

    tctx = _ctx.current()
    if tctx is not None:
        return tctx.global_rank()
    return _first_local_global_rank()


def local_size() -> int:
    """Ranks co-located on this host (MPI_Comm_split_type analog,
    mpi_ops.cc:1762-1766). Note the reference's C API has a bug returning
    local_rank here (mpi_ops.cc:1998) — we implement the intended semantics."""
    _require_init()
    return len(jax.local_devices())


def local_rank() -> int:
    """This controller's rank among the host's devices (mpi_ops.cc:1966-1972)."""
    from horovod_tpu.core import context as _ctx

    tctx = _ctx.current()
    if tctx is not None:
        return tctx.local_rank()
    st = _require_init()
    local_ids = [d.id for d in jax.local_devices()]
    first = _first_local_global_rank()
    try:
        return local_ids.index(st.devices[first].id)
    except (ValueError, IndexError):
        return 0
