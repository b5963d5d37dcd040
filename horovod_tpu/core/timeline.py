"""Horovod Timeline — Chrome-tracing profiler of collective activity.

Reference: ``tensorflow/timeline.{h,cc}`` — a coordinator-side Chrome tracing
(catapult) JSON writer enabled by ``HOROVOD_TIMELINE=<file>``
(mpi_ops.cc:1486-1489, docs/timeline.md). Every tensor is a fake "process"
(pid) with metadata events; negotiation and execution phases appear as B/E
events with µs timestamps; the file flushes every second (timeline.h:35).

Here the writer is :class:`_ChromeTraceWriter` below. Activity vocabulary
keeps the reference's names (docs/timeline.md:25-43) with the MPI-specific
ones mapped to their XLA equivalents:

    NEGOTIATE_<OP>           request submitted → all ranks matched
    QUEUE                    host-side dispatch queueing
    SCHEDULE                 fusion planning / bucket assembly
    MEMCPY_IN_FUSION_BUFFER  pack into the flat fusion buffer (packed
                             buckets only: ops/fusion.py Bucket.packed)
    QUANTIZE                 bucket → wire dtype (gradient compression,
                             ops/compression.py; trace-time stamp like
                             SCHEDULE — the device span carries the same
                             name via jax.named_scope for xplane mapping)
    XLA_ALLREDUCE / XLA_ALLGATHER / XLA_BCAST / XLA_GATHER
                             the device collective (MPI_* in the reference)
    REDUCE_SCATTER /         the phases of a decomposed allreduce
    CROSS_SLICE /            (ops/strategy.py rs_ag/hierarchical; trace-
    ALL_GATHER               time stamps like QUANTIZE, same names on the
                             HLO scopes for xplane mapping)
    DEQUANTIZE               summed wire dtype → original dtype
    MEMCPY_OUT_FUSION_BUFFER unpack

File or no file, the same session keeps the program's account of itself:
:func:`span` (host spans on the profiler's clock, in a bounded in-memory
ring, B/E pairs on the ``_hvd`` row), and per compiled ``hvd.spmd``
program its dispatches, its exchange plan's counters and — lazily, never
by compiling — its instructions' named scopes and its executable's
memory analysis.
:func:`record` snapshots it: readable after ``hvd.shutdown()``, cleared
by the next ``hvd.init()``.

What JAX says of its own work (:func:`listen`: ``jax.monitoring``'s
tracing, lowering and backend-compile durations and the persistent
cache's hits and misses) is kept in the same record. Inside a
``hvd/spmd/build`` span each event is a child row of that span —
``hvd/spmd/build/trace``, ``/lower``, ``/compile`` where the backend
compiled and ``/load`` where the persistent cache hit; what is left of
the span is the first call, which has no row — and a count of the built
program (``build.cache_hits``, ``build.cache_misses``). Outside any build
the events are only summed, under the record's ``compiles``: seconds by
kind, a count of programs (backend events) and the stamp of the last of
them, apart for before the session's first
dispatch, after it, and after it inside a ``hvd/spmd/dispatch`` span —
a program ``jax.jit`` compiled again under a key the wrapper took for
built.
"""

from __future__ import annotations

import atexit
import collections
import json
import threading
import time
import traceback
import weakref

import jax
from jax import monitoring as _monitoring

from horovod_tpu.utils import env as _env

RING_SPANS = 4096  # newest spans kept once the first step was dispatched
SPAN_ROW = "_hvd"  # the Chrome file's row of span() pairs
BUILD, DISPATCH = "hvd/spmd/build", "hvd/spmd/dispatch"
# jax.monitoring's duration events, by the part of a build they are.
_PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE = {"/jax/compilation_cache/cache_hits": "build.cache_hits",
          "/jax/compilation_cache/cache_misses": "build.cache_misses"}


class _ChromeTraceWriter:
    """The Chrome-tracing JSON file: one fake process a tensor, events on
    the monotonic clock in µs since the file was opened, flushed every
    second."""

    def __init__(self, path: str):
        self._f = open(path, "w")
        self._f.write("[\n")
        self._pids: dict[str, int] = {}
        self._t0 = time.monotonic_ns() // 1000
        self._last_flush = time.monotonic()
        self._lock = threading.Lock()
        self._closed = False
        # The last ≤1s of buffered events are exactly the ones a crash
        # post-mortem needs; atexit covers an uncaught exception's interpreter
        # teardown (not SIGKILL — nothing can).
        atexit.register(self.close)

    def _pid(self, tensor: str) -> int:
        pid = self._pids.get(tensor)
        if pid is None:
            pid = len(self._pids) + 1
            self._pids[tensor] = pid
            self._f.write(json.dumps({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": tensor}}) + ",\n")
            self._f.write(json.dumps({
                "name": "process_sort_index", "ph": "M", "pid": pid,
                "args": {"sort_index": pid}}) + ",\n")
        return pid

    def event(self, tensor: str, activity: str, phase: str) -> None:
        with self._lock:
            if self._closed:
                return
            ts = time.monotonic_ns() // 1000 - self._t0
            ev = {"name": activity, "ph": phase, "ts": ts,
                  "pid": self._pid(tensor)}
            if phase == "X":  # instant tick (reference timeline.cc:86-88)
                ev["dur"] = 0
            self._f.write(json.dumps(ev) + ",\n")
            now = time.monotonic()
            if now - self._last_flush > 1.0:
                self._f.flush()
                self._last_flush = now

    def event_at(self, tensor: str, activity: str, ts_us: float,
                 dur_us: float) -> None:
        """Complete ('X') event at an explicit monotonic-clock timestamp —
        how device-true spans (core/xprof.py) enter the file."""
        with self._lock:
            if self._closed:
                return
            self._f.write(json.dumps({
                "name": activity, "ph": "X",
                "ts": round(ts_us - self._t0, 3),
                "dur": round(dur_us, 3),
                "pid": self._pid(tensor)}) + ",\n")

    def close(self) -> None:
        """Flush and close. Idempotent: both Timeline.stop and the atexit
        hook call it, in either order."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._f.flush()
            self._f.close()
        atexit.unregister(self.close)


class _Span:
    """One :meth:`Timeline.span`."""

    __slots__ = ("_tl", "name", "_ann", "_t0", "_parent", "_in_file")

    def __init__(self, tl: "Timeline", name: str):
        self._tl, self.name = tl, name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        tl = self._tl
        stack = tl._stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._in_file = tl.active  # hvd/init opens the file inside itself
        tl.event(SPAN_ROW, self.name, "B")
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tl = self._tl
        tl._stack().pop()
        if self._in_file:
            tl.event(SPAN_ROW, self.name, "E")
        row = (self.name, self._t0, end, self._parent)
        if tl.dispatched:
            tl._ring.append(row)
        elif len(tl._setup) < RING_SPANS:
            tl._setup.append(row)  # set-up is never evicted by the steps


class Timeline:
    """Session timeline: the file's writer while one is open, and the
    program's in-memory account of itself."""

    def __init__(self) -> None:
        self._writer: _ChromeTraceWriter | None = None
        # True when ``HOROVOD_TIMELINE_DEVICE=1`` was set when the timeline
        # started (latched in :meth:`start`): per-step spans come from a
        # sampled ``jax.profiler`` capture with device timestamps.
        self.device_mode = False
        self._local = threading.local()
        self._import: tuple | None = None  # the process's: never cleared
        self.hears = False  # JAX's events, from hvd.init to hvd.shutdown
        self.clear_record()

    def clear_record(self) -> None:
        """Forget spans and programs (``hvd.init`` does)."""
        self._setup: list = []
        self._ring: collections.deque = collections.deque(maxlen=RING_SPANS)
        self.dispatched = False  # set by hvd.spmd's first dispatch
        self.programs: dict = {}
        self.building: str | None = None  # the program being traced
        self._owners: dict = {}  # tag → weak or pinned owner of executable()
        # JAX's work outside any build span, summed by where it fell.
        self.compiles = {where: {"programs": 0, "trace_s": 0.0,
                                 "lower_s": 0.0, "backend_s": 0.0,
                                 "last_ns": 0}
                         for where in ("before_dispatch", "after_dispatch",
                                       "in_dispatch")}

    def imported(self, start_ns: int, end_ns: int) -> None:
        """The ``hvd/import`` row: ``import horovod_tpu`` from its first
        line to its last."""
        self._import = ("hvd/import", start_ns, end_ns, None)

    def _stack(self) -> list:  # the open spans of this thread
        return self._local.__dict__.setdefault("stack", [])

    def span(self, name: str) -> _Span:
        """Context manager: ``name`` as a ``jax.profiler.TraceAnnotation``
        (in whatever capture runs, on the device planes' clock; an atomic
        load when none does), a ``(name, start_ns, end_ns, parent)`` row
        of :meth:`record` and a B/E pair where the file is on."""
        return _Span(self, name)

    def count_plan(self, name: str, n: int) -> None:
        """Add ``n`` to counter ``name`` of the program being traced (its
        plan's numbers: a step, a rank) — dropped where none is (an
        inspection's lowering)."""
        counters = self.programs.get(self.building, {}).get("counters")
        if counters is not None:
            counters[name] = counters.get(name, 0) + n

    def count_measured(self, name: str, value) -> None:
        """Set counter ``name`` of the program with most dispatches (the
        step) to a number its caller MEASURED from the step's own outputs
        (a step, a rank) — what a plan cannot know before the data is
        seen, such as how many (token, choice) pairs the held experts
        took. Dropped where no program was dispatched."""
        if self.programs:
            step = max(self.programs.values(),
                       key=lambda p: p["dispatches"])
            step["counters"][name] = value

    def add_program(self, tag: str, owner) -> str:
        """Open the record of one compiled program; ``owner.executable()``
        gives what it was compiled to or None (held weakly until a
        dispatch of it is profiled: :meth:`pin`). Returns the tag made
        unique."""
        base, k = tag, 1
        while tag in self.programs:
            k += 1
            tag = f"{base}#{k}"
        self.programs[tag] = {
            "dispatches": 0, "scopes": None, "memory": None,
            "counters": {"build.cache_hits": 0, "build.cache_misses": 0}}
        self._owners[tag] = weakref.ref(owner)
        return tag

    def pin(self, tag: str, owner) -> None:
        """A capture holds events of this program: keep it until its
        scope map is resolved (at ``hvd.shutdown()`` at the latest)."""
        if tag in self._owners:
            self._owners[tag] = lambda: owner

    def resolve_scopes(self, shutdown: bool = False) -> None:
        """Fill ``programs[tag]["scopes"]`` from the programs' compiled
        text (``analysis/hlo.scope_map``) and ``["memory"]`` from the
        executable's own memory analysis (bytes a device: arguments,
        outputs, the part of them aliased, XLA's temporaries, generated
        code). Never traces or compiles: a program JAX's caches no longer
        hold keeps None. At ``shutdown`` only pinned programs are read,
        and all are let go."""
        from horovod_tpu.analysis import hlo as _hlo

        for tag, ref in list(self._owners.items()):
            owner = ref()
            if owner is None or (shutdown and isinstance(ref, weakref.ref)):
                continue
            del self._owners[tag]
            try:
                exe = owner.executable()
                if exe is None:
                    continue
                entry = self.programs[tag]
                entry["scopes"] = _hlo.scope_map(exe.as_text())
                m = exe.memory_analysis()  # None where the backend has none
                entry["memory"] = m and {
                    f"{k}_bytes": getattr(m, f"{k}_size_in_bytes")
                    for k in ("argument", "output", "alias", "temp",
                              "generated_code")}
            except Exception:  # the record is best effort: shutdown goes on
                traceback.print_exc()
        if shutdown:
            self._owners.clear()

    def jax_event(self, part: str, seconds: float) -> None:
        """One of JAX's ``_PARTS`` ended on this thread after
        ``seconds``. Inside a build span it is a child row of that span
        (a tracing nested in another is dropped when the outer one
        arrives: they come inner first); elsewhere it is summed."""
        end = time.perf_counter_ns()
        start = end - int(seconds * 1e9)
        local = self._local.__dict__
        if part == "compile" and local.pop("cache_hit", False):
            part = "load"
        stack = self._stack()
        if BUILD in stack:
            name = f"{BUILD}/{part}"
            rows = self._ring if self.dispatched else self._setup
            i = len(rows)
            while i and rows[i - 1][1] >= start:
                i -= 1
                if rows[i][0] == name:
                    del rows[i]
            if self.dispatched or len(rows) < RING_SPANS:
                rows.append((name, start, end, BUILD))
            self.event_at(SPAN_ROW, name,
                          time.monotonic_ns() / 1e3 - seconds * 1e6,
                          seconds * 1e6)
            return
        sums = self.compiles[
            "in_dispatch" if stack and stack[-1] == DISPATCH else
            "after_dispatch" if self.dispatched else "before_dispatch"]
        if part == "trace":
            # (start, seconds) of the tracings summed so far that a later,
            # outer one may still hold: it takes their seconds back.
            summed = local.setdefault("traces", [])
            whole = seconds
            while summed and summed[-1][0] >= start:
                seconds -= summed.pop()[1]
            summed.append((start, whole))
            del summed[:-64]  # deeper nestings than this are not met
        elif part != "lower":  # one backend event a program
            sums["programs"], sums["last_ns"] = sums["programs"] + 1, end
        key = "backend_s" if part in ("compile", "load") else f"{part}_s"
        sums[key] += seconds

    def cache_event(self, counter: str) -> None:
        """The persistent cache hit or missed on this thread: the backend
        event that follows a hit is a load; the program being built
        counts both."""
        if counter == "build.cache_hits":
            self._local.cache_hit = True
        if BUILD in self._stack():
            self.count_plan(counter, 1)

    def record(self, scopes: bool = False) -> dict:
        """A plain, JSON-able snapshot: ``spans`` (``hvd/import``,
        set-up's, then the newest ``RING_SPANS``), ``programs`` (each with
        its ``dispatches``, its ``counters`` — the plan's and the
        build's —, its ``scopes`` and its ``memory``) and ``compiles``
        (JAX's work outside any build). ``scopes`` resolves the live
        programs' scope maps and memory first; else they hold what
        ``hvd.shutdown()`` resolved (profiled programs only) or None."""
        if scopes:
            self.resolve_scopes()
        spans = [self._import] if self._import else []
        return {"spans": [list(s) for s in
                          spans + self._setup + list(self._ring)],
                "programs": {t: dict(p, counters=dict(p["counters"]))
                             for t, p in self.programs.items()},
                "compiles": {w: dict(s) for w, s in self.compiles.items()}}

    def start(self, path: str) -> None:
        if self.active:
            return
        # Latched HERE: a run's per-step rows are either all host-stamped
        # or all device-true, whatever HOROVOD_TIMELINE_DEVICE is flipped
        # to after start().
        self.device_mode = _env.timeline_device_mode()
        self._writer = _ChromeTraceWriter(path)

    @property
    def active(self) -> bool:
        return self._writer is not None

    def event(self, tensor: str, activity: str, phase: str) -> None:
        writer = self._writer  # stop() on another thread may clear it
        if writer is not None:
            writer.event(tensor, activity, phase)

    def rank_ready(self, tensor: str, rank: int) -> None:
        """Per-rank negotiation-ready tick — the NegotiateRankReady analog
        (timeline.cc:117-125): an instant 'X' event named by the rank, so a
        late rank is visible on the tensor's trace row."""
        self.event(tensor, str(rank), "X")

    def start_activity(self, tensor: str, activity: str) -> None:
        self.event(tensor, activity, "B")

    def end_activity(self, tensor: str, activity: str) -> None:
        self.event(tensor, activity, "E")

    def event_at(self, tensor: str, activity: str, ts_us: float,
                 dur_us: float) -> None:
        """Explicit-timestamp complete event (device-true spans)."""
        writer = self._writer
        if writer is not None:
            writer.event_at(tensor, activity, ts_us, dur_us)

    def stop(self) -> None:
        self.hears = False
        writer, self._writer = self._writer, None
        if writer is not None:
            writer.close()


_session = Timeline()


def session() -> Timeline:
    return _session


def _on_duration(event: str, seconds: float, **_kw) -> None:
    part = _PARTS.get(event)
    if part is not None and _session.hears:
        _session.jax_event(part, seconds)


def _on_event(event: str, **_kw) -> None:
    counter = _CACHE.get(event)
    if counter is not None and _session.hears:
        _session.cache_event(counter)


_listening = False


def listen() -> None:
    """Hear ``jax.monitoring``'s compile events into the session's
    record until ``hvd.shutdown()``: one listener a process on its
    durations and one on its plain events, however many times
    ``hvd.init`` runs."""
    global _listening
    if not _listening:
        _listening = True
        _monitoring.register_event_duration_secs_listener(_on_duration)
        _monitoring.register_event_listener(_on_event)
    _session.hears = True


def maybe_start() -> None:
    """Start the timeline if ``HOROVOD_TIMELINE`` is set (mpi_ops.cc:1486)."""
    path = _env.timeline_path()
    if path:
        _session.start(path)


def stop() -> None:
    _session.stop()


def span(name: str) -> _Span:
    return _session.span(name)


def record(scopes: bool = False) -> dict:
    return _session.record(scopes)
