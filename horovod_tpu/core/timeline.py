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
by compiling — its instructions' named scopes.
:func:`record` snapshots it: readable after ``hvd.shutdown()``, cleared
by the next ``hvd.init()``.
"""

from __future__ import annotations

import atexit
import collections
import json
import threading
import time
import weakref

import jax

from horovod_tpu.utils import env as _env

RING_SPANS = 4096  # newest spans kept once the first step was dispatched
SPAN_ROW = "_hvd"  # the Chrome file's row of span() pairs


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
        self.clear_record()

    def clear_record(self) -> None:
        """Forget spans and programs (``hvd.init`` does)."""
        self._setup: list = []
        self._ring: collections.deque = collections.deque(maxlen=RING_SPANS)
        self.dispatched = False  # set by hvd.spmd's first dispatch
        self.programs: dict = {}
        self.building: str | None = None  # the program being traced
        self._texts: dict = {}  # tag → weak or pinned owner of hlo_text()

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
        """Open the record of one compiled program; ``owner.hlo_text()``
        gives its optimized text or None (held weakly until a dispatch of
        it is profiled: :meth:`pin`). Returns the tag made unique."""
        base, k = tag, 1
        while tag in self.programs:
            k += 1
            tag = f"{base}#{k}"
        self.programs[tag] = {"dispatches": 0, "counters": {},
                              "scopes": None}
        self._texts[tag] = weakref.ref(owner)
        return tag

    def pin(self, tag: str, owner) -> None:
        """A capture holds events of this program: keep it until its
        scope map is resolved (at ``hvd.shutdown()`` at the latest)."""
        if tag in self._texts:
            self._texts[tag] = lambda: owner

    def resolve_scopes(self, shutdown: bool = False) -> None:
        """Fill ``programs[tag]["scopes"]`` from the programs' compiled
        text (``analysis/hlo.scope_map``). Never traces or compiles: a
        program JAX's caches no longer hold keeps None. At ``shutdown``
        only pinned programs are read, and all are let go."""
        from horovod_tpu.analysis import hlo as _hlo

        for tag, ref in list(self._texts.items()):
            owner = ref()
            if owner is None or (shutdown and isinstance(ref, weakref.ref)):
                continue
            text = owner.hlo_text()
            if text is not None:
                self.programs[tag]["scopes"] = _hlo.scope_map(text)
            del self._texts[tag]
        if shutdown:
            self._texts.clear()

    def record(self, scopes: bool = False) -> dict:
        """A plain, JSON-able snapshot: ``spans`` (set-up's, then the
        newest ``RING_SPANS``) and ``programs`` (each with its
        ``dispatches``, its plan's ``counters`` and its ``scopes``).
        ``scopes`` resolves the live programs' scope maps first; else they
        hold what ``hvd.shutdown()`` resolved (profiled programs only) or
        None."""
        if scopes:
            self.resolve_scopes()
        return {"spans": [list(s) for s in self._setup + list(self._ring)],
                "programs": {t: dict(p, counters=dict(p["counters"]))
                             for t, p in self.programs.items()}}

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
        writer, self._writer = self._writer, None
        if writer is not None:
            writer.close()


_session = Timeline()


def session() -> Timeline:
    return _session


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
