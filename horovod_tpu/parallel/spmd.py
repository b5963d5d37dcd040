"""``hvd.spmd`` — run a per-rank step function as one SPMD mesh program.

This is the TPU-native replacement for the reference's execution engine: where
the reference launches N processes under ``mpirun`` and each builds the same TF
graph (docs/running.md), here ONE controller traces the per-rank function once
and ``jax.shard_map`` + ``jit`` compile it into a single XLA program over the
group's device mesh, with the collectives riding ICI. A rank's view inside the
function (``hvd.rank()``, ``hvd.allreduce`` …) matches what a process sees in
the reference.

Calling convention: every argument and result carries a leading *rank axis* of
length ``group size`` — argument leaf shape ``(g, *s)`` means rank i sees
``s``-shaped data ``arg[i]``. Sharded over the mesh this leading axis IS the
data-parallel layout: each device holds exactly its rank's slice (for model
parameters, one replica per device). Arguments listed in
``replicated_argnums`` are instead passed whole to every rank.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.core import context as _ctx
from horovod_tpu.core import multihost as _mh
from horovod_tpu.core import state as _state
from horovod_tpu.core import timeline as _timeline
from horovod_tpu.core.state import AXIS_NAME, HorovodError
from horovod_tpu.utils import env as _env
from horovod_tpu.utils import jax_compat as _compat


def spmd(fn: Callable, group: int = 0,
         replicated_argnums: tuple[int, ...] = (),
         donate_argnums: tuple[int, ...] = ()) -> Callable:
    """Wrap ``fn(rank_view_args...) -> rank_view_outputs`` into a compiled
    SPMD program over group ``group``'s mesh.

    The wrapped callable takes rank-stacked arguments (leading axis = group
    size, except ``replicated_argnums``) and returns rank-stacked outputs.

    ``donate_argnums``: argument indices whose device buffers XLA may reuse
    for outputs (halves parameter/optimizer-state HBM traffic in a training
    step where the old state is dead after the update). Donated inputs must
    not be used again by the caller — the step-loop pattern
    ``params, ... = step(params, ...)`` is exactly safe.

    Every program is lowered under ``utils/jax_compat.named_locations``,
    for every caller and whatever
    ``jax_include_full_tracebacks_in_locations`` says: each instruction's
    ``op_name`` holds its whole name stack (what ``core/timeline.record``'s
    scope map and hvd-lint read) and its location one frame, so the
    compile cache's key holds no call stack.
    """
    repl = set(replicated_argnums)
    # One compiled program per (init generation, mesh, argument
    # signature). Rebuilding shard_map per call would defeat the jit cache
    # (it is keyed on function identity) and retrace every step.
    compiled: dict = {}

    # HOROVOD_XLA_OPTIONS is latched when the step function is wrapped:
    # the compiled-program cache is not keyed on it, so honoring a mid-run
    # flip would silently serve executables built under the old options.
    xla_opts = _env.xla_compiler_options()

    def build(g, nargs):
        """``(jitted, schedule)``: the program for ``nargs`` arguments over
        ``g``'s mesh, and the list its trace fills with the collective
        schedule."""
        in_specs = tuple(P() if i in repl else P(AXIS_NAME)
                         for i in range(nargs))
        # Trace-time collective schedule, captured for multi-host
        # validation (the analog of per-tensor negotiation, hoisted to
        # compile time — see core/multihost.py).
        schedule: list = []

        def shard_fn(*sargs):
            rank_view = []
            for i, a in enumerate(sargs):
                if i in repl:
                    rank_view.append(a)
                else:
                    # shard_map hands each device a (1, *s) slice; present
                    # the natural per-rank shape (*s) to the user function.
                    rank_view.append(jax.tree.map(lambda t: t[0], a))
            with _ctx.enter(AXIS_NAME, group) as tctx:
                out = fn(*rank_view)
            schedule.clear()
            for nm, meta in tctx.names.items():
                op, dtype, shape, grp, root = meta
                # Group families register as tuples; serialize as lists
                # so the JSON round-trip compares clean across processes.
                grp = grp if isinstance(grp, int) else list(grp)
                # Trailing element: fusion-bucket member labels (empty
                # for plain collectives) — deterministic from the traced
                # gradient pytree, so multi-host schedule validation
                # still compares byte-identical payloads.
                # (A bucket reduced in its leaves' own shapes registers
                # them all: lists too, as the JSON round-trip gives.)
                shape = [list(d) if isinstance(d, tuple) else d
                         for d in shape]
                schedule.append([nm, op, dtype, shape, grp,
                                 -1 if root is None else root,
                                 list(tctx.members.get(nm, ()))])
            return jax.tree.map(lambda t: jnp.asarray(t)[None], out)

        # The program carries the user's function's name: the capture's
        # ``XLA Modules`` line and JAX's compile log say ``jit_train_step``.
        shard_fn.__name__ = shard_fn.__qualname__ = getattr(
            fn, "__name__", "shard_fn")
        # check_vma=False: jax 0.9's varying-manual-axes checker does not
        # support axis_index_groups (parallel.py bind_psum_invariant),
        # which grouped collectives — the fork's core feature — depend on.
        jitted = jax.jit(jax.shard_map(
            shard_fn, mesh=g.mesh, in_specs=in_specs,
            out_specs=P(AXIS_NAME), check_vma=False),
            donate_argnums=tuple(donate_argnums))
        return jitted, schedule

    def compile_program(g, args, tl, multihost) -> _Program:
        """Build the program of this argument signature by one of the
        three compile paths (inside the caller's ``hvd/spmd/build`` span,
        which also holds its first call: the lazy path compiles there)."""
        jitted, schedule = build(g, len(args))
        prog = _Program(jitted, schedule, args)
        prog.tag = tl.add_program(
            f"{getattr(fn, '__qualname__', 'fn')}/{len(args)}", prog)
        tl.building = prog.tag
        # HOROVOD_XLA_OPTIONS (e.g. pinning the CRS combiner to the
        # framework's fusion buckets for comm/compute overlap —
        # docs/tensor-fusion.md) requires the explicit compile path.
        copts = dict(compiler_options=xla_opts) if xla_opts else {}
        if multihost:
            # Explicit lower → validate → compile: every process must
            # have traced the identical collective schedule BEFORE the
            # program may execute; a divergence raises on all processes
            # instead of hanging in a mismatched XLA collective.
            lowered = jitted.lower(*args)
            _mh.negotiator().validate_schedule(prog.tag, schedule)
            prog.call = lowered.compile(**copts)
        elif xla_opts or (tl.active and tl.device_mode):
            # The device-fidelity timeline samples the FIRST execution
            # against the trace-time schedule: it must exist before it.
            prog.call = jitted.lower(*args).compile(**copts)
        return prog

    def dispatch(prog, args, tl):
        """One call of the program: never waits for the device (the
        device-fidelity timeline's sampled executions apart)."""
        n, prog.runs = prog.runs, prog.runs + 1
        tl.dispatched = True
        entry = tl.programs.get(prog.tag)
        if entry is not None:
            entry["dispatches"] += 1
            if _compat.profiler_session_active():
                tl.pin(prog.tag, prog)  # a capture will ask for its scopes
        with jax.profiler.StepTraceAnnotation("hvd/step", step_num=n), \
                tl.span("hvd/spmd/dispatch"):
            if tl.active and tl.device_mode and prog.schedule:
                # Device-fidelity mode: sample executions under
                # jax.profiler, map the xplane back onto the schedule
                # (core/xprof.py), and emit spans with device timestamps.
                # The first execution is always sampled; with
                # HOROVOD_TIMELINE_DEVICE_INTERVAL=N every N-th re-samples
                # so steady-state regressions show up. Unsampled steps
                # dispatch untouched.
                interval = _env.timeline_device_interval()
                if n == 0 or (interval > 0 and n % interval == 0):
                    return _sample_device_step(tl, prog, args)
            return prog.call(*args)

    @functools.wraps(fn)
    def wrapper(*args):
        g = _state.get_group(group)
        tl = _timeline.session()
        # The generation component invalidates entries across
        # shutdown()/init() cycles: an equal mesh can carry a different
        # group layout, and the closed-over group index must not replay
        # against it. The argument signature makes every traced program
        # its own entry: its build is a span and a count of the record,
        # its schedule is validated (multi-host) and its executable may
        # be pinned to one signature (the ahead-of-time paths).
        key = (_state.generation(), g.mesh, _args_signature(args))
        prog = compiled.get(key)
        if prog is not None:
            return dispatch(prog, args, tl)
        # Programs from earlier init generations can never be hit again;
        # drop them so shutdown()/init() cycles don't pin dead
        # executables (host + device memory) in this closure forever.
        for stale in [k for k in compiled if k[0] != key[0]]:
            del compiled[stale]
        # Lowered with the whole name stack in every op_name and one frame
        # in every location, whatever the caller's settings: the record's
        # scope map is read from the names (utils/jax_compat).
        with tl.span("hvd/spmd/build"), _compat.named_locations():
            try:
                prog = compiled[key] = compile_program(g, args, tl,
                                                       _mh.active())
                out = dispatch(prog, args, tl)
                # Every collective the trace negotiated, on its own row
                # of the timeline file (the reference's per-step
                # NEGOTIATE_* phases, hoisted to compile time like the
                # negotiation itself).
                for nm, op, *_ in prog.schedule:
                    tl.start_activity(nm, f"NEGOTIATE_{op}")
                    tl.end_activity(nm, f"NEGOTIATE_{op}")
                return out
            finally:
                tl.building = None

    def lower(*args):
        """``jax.stages.Lowered`` of the program ``wrapper(*args)`` traces —
        for inspection (``.as_text()``, ``.compile().as_text()``, memory
        analysis), as ``jax.jit(f).lower`` is. ``args`` may be
        rank-stacked ``jax.ShapeDtypeStruct``s. A fresh trace: the
        wrapper's program cache and the auto-name counters are left as
        they were.

        The wrapper compiles with ``HOROVOD_XLA_OPTIONS`` where that is
        set; a bare ``.compile()`` on the result does not. To inspect that
        build, pass them:
        ``.compile(compiler_options=env.xla_compiler_options())``."""
        from horovod_tpu.ops import collectives as _coll

        with _coll.preserve_auto_names(), _compat.named_locations():
            return build(_state.get_group(group), len(args))[0].lower(*args)

    wrapper.lower = lower
    return wrapper


class _Program:
    """One compiled program of a wrapper: what a dispatch calls (the
    jitted function, or the executable an ahead-of-time path compiled),
    its trace-time collective schedule, and the shapes and shardings of
    its first call (no arrays) — enough to read its optimized text again
    without compiling."""

    __slots__ = ("tag", "call", "jitted", "schedule", "structs", "runs",
                 "__weakref__")

    def __init__(self, jitted, schedule, args):
        self.call = self.jitted = jitted
        self.schedule, self.runs = schedule, 0
        self.structs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None),
                weak_type=getattr(a, "weak_type", False))
            if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)

    def executable(self) -> jax.stages.Compiled | None:
        """What the first call built, to read its optimized text and its
        memory analysis from (``lower`` and ``compile`` on the first
        call's shapes and shardings are two cache hits); None where JAX's
        caches no longer hold it and asking would trace and compile
        anew."""
        if self.call is not self.jitted:
            return self.call
        if not _compat.jit_cache_size(self.jitted):
            return None
        return self.jitted.lower(*self.structs).compile()


def _sample_device_step(tl, prog, args):
    """One profiled execution for the device-fidelity timeline mode.

    Runs the compiled step under ``jax.profiler``, maps the slowest device
    plane's ``XLA Ops`` events (core/xprof.slowest_plane) onto the
    negotiated schedule and writes its spans with device timestamps
    anchored at the host clock of the capture start (sub-ms skew; the
    *relative* device timing is exact), each of its idle gaps named by
    the ``hvd/*`` span that covers most of it. On backends whose
    profiler has no device plane (CPU) the sample yields no spans —
    recorded as an instant note on ``_device``.
    """
    import shutil
    import tempfile
    import time as _time

    from horovod_tpu.core import xprof as _xprof

    trace_dir = tempfile.mkdtemp(prefix="hvd_tl_dev_")
    try:
        anchor_us = _time.monotonic_ns() / 1e3
        jax.profiler.start_trace(trace_dir)
        try:
            out = prog.call(*args)
            with tl.span("hvd/timeline/wait_sample"):
                jax.block_until_ready(out)
        finally:
            # A failing step must not leave the global profiler session
            # open — that would break every later start_trace in-process.
            jax.profiler.stop_trace()
        tl.pin(prog.tag, prog)
        # One plane for every row of the sample: its collectives, its
        # packs and its idle gaps.
        events = _xprof.slowest_plane(_xprof.device_planes(trace_dir))
        spans = _xprof.map_device_spans(prog.schedule, events)
        if spans:
            spans += _xprof.idle_spans(events, _xprof.host_spans(trace_dir))
            for row, activity, start_us, dur_us in spans:
                tl.event_at(row, activity, anchor_us + start_us, dur_us)
            # Always-on α–β recalibration: measured collective spans
            # flow back into the tuning cache (ops/exchange.py) so the
            # cost model tracks the live machine. Best-effort by
            # contract — never raises into the timeline path.
            from horovod_tpu.ops import exchange as _exchange

            _exchange.observe_xla_spans(spans, prog.schedule)
        else:
            tl.event("_device", "NO_DEVICE_PLANE", "X")
        return out
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _args_signature(args):
    """Hashable (shape, dtype) of every leaf. Computed on every dispatch
    (≈ 45 µs for the 190 leaves of a training step: half of it
    ``jax.tree.leaves``), so arrays take the short way and no string is
    built."""
    leaves = jax.tree.leaves(args)
    try:
        return tuple([(l.shape, l.dtype) for l in leaves])
    except AttributeError:  # a Python scalar or list among the leaves
        return tuple([(l.shape, l.dtype) if hasattr(l, "dtype")
                      else (np.shape(l), type(l)) for l in leaves])


def _global_from_local_rows(g, local_rows_per_leaf):
    """Assemble a (g.size, *s) global array from this process's per-local-rank
    rows: row i is put on group device i and nowhere else, so no device
    ever holds the whole stack. Non-addressable rows (multi-host) are
    provided by the other processes' identical calls."""
    lranks = g.local_member_ranks()
    sharding = NamedSharding(g.mesh, P(AXIS_NAME))

    def build(*rows):  # one row per local member rank, natural shape (*s)
        # replicate() passes ONE row len(lranks) times: lift it once.
        lifted = {id(r): jnp.asarray(r)[None] for r in rows}
        shards = [jax.device_put(lifted[id(r)], g.devices[i])
                  for r, i in zip(rows, lranks)]
        return jax.make_array_from_single_device_arrays(
            (g.size,) + shards[0].shape[1:], sharding, shards)

    return jax.tree.map(build, *local_rows_per_leaf)


def rank_stack(values, group: int = 0):
    """Stack a per-rank list into the leading rank axis expected by ``spmd``,
    each row placed on its rank's device.

    Single-controller: ``values`` has one entry per group rank. Multi-host:
    one entry per rank THIS process drives (``hvd.local_member_ranks``
    order); the result is a global array spanning all hosts.
    """
    g = _state.get_group(group)
    nloc = len(g.local_member_ranks())
    if len(values) != nloc:
        raise HorovodError(
            f"rank_stack: expected one value per local member rank "
            f"({nloc}), got {len(values)}.")
    with _timeline.span("hvd/rank_stack"):
        return _global_from_local_rows(g, values)


def replicate(value, group: int = 0):
    """Tile a single pytree into the rank-stacked layout (g, ...) — one
    replica per device, the DP parameter layout, built in place: each
    device receives its own copy and none holds g of them. In multi-host
    mode every process must call this with the same value; the result is a
    global array."""
    g = _state.get_group(group)
    nloc = len(g.local_member_ranks())
    if nloc == 0:
        return value  # no local members: nothing to place
    with _timeline.span("hvd/replicate"):
        return _global_from_local_rows(g, [value] * nloc)


def device_put_ranked(value, group: int = 0):
    """Place a rank-stacked pytree on the group mesh, leading axis sharded —
    so each device holds exactly its rank's slice before the program runs.
    Single-controller only (a multi-host process can't hold the full stack;
    use ``rank_stack`` with per-local-rank values instead)."""
    if _mh.active():
        raise HorovodError(
            "device_put_ranked is single-controller-only; in multi-host "
            "mode build global arrays with hvd.rank_stack (per-local-rank "
            "values).")
    g = _state.get_group(group)
    sharding = NamedSharding(g.mesh, P(AXIS_NAME))
    return jax.tree.map(lambda t: jax.device_put(t, sharding), value)


def local_values(stacked, group: int = 0):
    """Read back a rank-stacked result as a list of per-rank numpy pytrees.

    Single-controller: one entry per group rank. Multi-host: one entry per
    local member rank (the only rows this process can address).
    """
    g = _state.get_group(group)

    if not _mh.active():
        # One device->host transfer per leaf, then per-rank views.
        host = jax.tree.map(np.asarray, stacked)
        return [jax.tree.map(lambda t: t[i], host) for i in range(g.size)]

    lranks = g.local_member_ranks()

    def rows(t):
        if not hasattr(t, "addressable_shards"):
            return {i: np.asarray(t)[i] for i in lranks}
        by_row = {}
        for s in t.addressable_shards:
            row = s.index[0].start or 0
            by_row[row] = np.asarray(s.data)[0]
        return by_row

    leaves, treedef = jax.tree.flatten(stacked)
    leaf_rows = [rows(l) for l in leaves]
    return [jax.tree.unflatten(treedef, [lr[i] for lr in leaf_rows])
            for i in lranks]
