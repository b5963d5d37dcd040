"""Device-true timeline spans from a ``jax.profiler`` xplane capture.

The reference's timeline stamps its hot-path activities on the coordinator
thread as the ops execute (mpi_ops.cc:741-753, 1238-1281). The XLA analog
cannot hook into a compiled program, so the device-fidelity mode samples
instead: one execution of the compiled step runs under ``jax.profiler``,
the ``XLA Ops`` timeline of the captured xplane's slowest device plane
(:func:`slowest_plane`: every plane is read) is mapped back onto the
negotiated collective schedule, and the spans are written into the Chrome
timeline with **device** timestamps — no
``block_until_ready`` distortion of the steps being measured (the old host
mode forced exactly that; only the sampled execution is waited for).

Mapping rules (pure, unit-tested):

* collective HLOs (``all-reduce``/``all-gather``/``reduce-scatter``/
  ``all-to-all``/``collective-permute``/``collective-broadcast``, plus
  their async ``-start``/``-done`` pairs, merged by instruction suffix)
  are matched IN DEVICE ORDER against same-kind entries of the negotiated
  schedule — the same order contract the auto-naming registry enforces —
  and emitted as ``XLA_<OP>`` on that tensor's row.
* ``concatenate`` ops lying wholly between the previous collective's end
  and the next collective's start are that next bucket's pack:
  ``MEMCPY_IN_FUSION_BUFFER``. ``slice``/``dynamic-slice`` ops in the
  same kind of window are the previous bucket's unpack:
  ``MEMCPY_OUT_FUSION_BUFFER``. Both window edges are enforced — an op
  overlapping a collective is the collective's own work, not a copy —
  and ``bitcast`` is excluded (it is ubiquitous in model HLO and free on
  device). (A heuristic: XLA may fuse packs away entirely, in which case
  no span is emitted — the timeline reports what the device actually
  ran.)
* the whole execution appears as ``DEVICE_STEP`` on the ``_device`` row,
  its longest idle gaps as ``IDLE [<hvd/* span covering most of it>]``.
"""

from __future__ import annotations

import glob
import os
import re

_COLL_KIND = {
    "all-reduce": "ALLREDUCE",
    "all-gather": "ALLGATHER",
    "reduce-scatter": "REDUCESCATTER",
    "all-to-all": "ALLTOALL",
    "collective-permute": "PPERMUTE",
    "collective-broadcast": "BROADCAST",
}
# Schedule op → acceptable device HLO kinds (an op may lower differently:
# broadcast rides a collective-broadcast OR an all-reduce/select; gather
# lowers to all-gather).
_SCHED_ACCEPTS = {
    "ALLREDUCE": {"ALLREDUCE"},
    "GROUPED_ALLREDUCE": {"ALLREDUCE"},
    "ALLGATHER": {"ALLGATHER"},
    "GROUPED_ALLGATHER": {"ALLGATHER"},
    "BROADCAST": {"BROADCAST", "ALLREDUCE", "PPERMUTE"},
    "GATHER": {"ALLGATHER"},
    "REDUCESCATTER": {"REDUCESCATTER", "ALLREDUCE", "PPERMUTE"},
    "ALLTOALL": {"ALLTOALL", "PPERMUTE"},
}
_PACK_BASES = {"concatenate"}
_UNPACK_BASES = {"slice", "dynamic-slice"}


def hlo_base(name: str) -> str:
    """HLO opcode of an ``XLA Ops`` event. Its name is the instruction's
    text, ``%psum.168 = f32[8]{0} all-reduce(f32[8]{0} %x), ...``: the
    opcode follows the result's shape and is NOT the instruction's name
    (the JAX primitive's: on the chip an all-reduce is ``%psum.168``). A
    bare name (``fusion.12``) gives its own stem. Held to
    ``benchmark/trace.py``'s answers by tests/test_tracing.py."""
    _, eq, rest = name.partition(" = ")
    if eq:
        rest = rest.lstrip()
        if rest.startswith("("):  # a tuple shape: skip to its closing paren
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    rest = rest[i + 1:]
                    break
        else:
            rest = rest.partition(" ")[2]
        m = re.match(r"\s*([a-zA-Z][\w-]*)\(", rest)
        if m:
            return m.group(1)
    m = re.match(r"%?([a-zA-Z][a-zA-Z0-9_-]*?)[.\d]*(\s*=|$)", name)
    return m.group(1) if m else name


def _instr_key(name: str) -> str:
    m = re.match(r"%?([a-zA-Z0-9_.-]+)", name)
    return m.group(1) if m else name


def _planes(trace_dir: str):
    """Planes of the newest xplane capture under ``trace_dir`` ([] when
    the capture wrote none)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    return list(ProfileData.from_file(paths[-1]).planes)


def device_planes(trace_dir: str) -> dict:
    """{plane name: [(name, start_us, dur_us)] by start} for EVERY device
    plane with an ``XLA Ops`` line (one a chip; auxiliary device planes
    carry none); {} when the trace has no device plane (CPU)."""
    out = {}
    for plane in _planes(trace_dir):
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    out[plane.name] = sorted(
                        ((ev.name, ev.start_ns / 1e3, ev.duration_ns / 1e3)
                         for ev in ln.events), key=lambda t: t[1])
    return out


def slowest_plane(planes: dict) -> list:
    """Events of the ONE device plane a reader reports (a single-controller
    world runs one program on every chip, and the slowest holds the step):
    most time in collectives, then the longest window. [] where the
    capture has no device plane."""
    def cost(events):
        coll = sum(d for n, _, d in events if is_collective(hlo_base(n)))
        return coll, _window_us(events)

    return max(planes.values(), key=cost, default=[])


def _window_us(events) -> float:
    return max(s + d for _, s, d in events) - min(s for _, s, _ in events)


def host_spans(trace_dir: str, prefix: str = "hvd/"):
    """[(name, start_us, end_us)] of the host planes' events named
    ``prefix``... (``core/timeline.span``), on the device planes' clock."""
    return sorted(
        (ev.name, ev.start_ns / 1e3, (ev.start_ns + ev.duration_ns) / 1e3)
        for plane in _planes(trace_dir) if plane.name.startswith("/host:")
        for ln in plane.lines for ev in ln.events
        if ev.name.startswith(prefix))


def idle_spans(events, host, k: int = 10):
    """The ``k`` longest gaps between one plane's events, each named by
    the host span that covers most of it (``none`` where none does):
    ``[("_device", "IDLE [<span>]", start_us, dur_us)]``."""
    gaps, end = [], None
    for _, s, d in sorted(events, key=lambda t: t[1]):
        if end is not None and s > end:
            gaps.append((s - end, end))
        end = max(s + d, end or 0.0)
    out = []
    for dur, lo in sorted(gaps, reverse=True)[:k]:
        cover, best = max(((min(e, lo + dur) - max(s, lo), n)
                           for n, s, e in host), default=(0.0, "none"))
        out.append(("_device", f"IDLE [{best if cover > 0 else 'none'}]",
                    lo, dur))
    return out


def timed_steps(run_once, steps: int, trials: int = 3,
                info: dict | None = None) -> float:
    """Best per-step seconds over ``trials`` calls of ``run_once`` (each
    executing ``steps`` chained device steps and forcing completion, e.g.
    via a scalar transfer).

    When the programs run on a TPU (``core/state.target_platform``): the
    device op-timeline window (max end − min start of ``XLA Ops`` events,
    on the device plane where it is longest) of a profiler capture — what
    the chip spent, without host dispatch.
    A TPU capture with no device op timeline RAISES, naming the planes it
    did find: a host-clocked number must never appear where a device
    number is expected. Elsewhere: wall clock.

    ``info``, when given, receives ``info["timing"]`` = ``"device"`` or
    ``"host"`` (non-TPU) and ``info["host_s"]`` = the best wall-clock
    seconds per step of the same calls (on TPU that includes dispatch and
    the profiler's own cost — information, not a result).
    """
    import shutil
    import tempfile
    import time

    import jax

    from horovod_tpu.core import state as _state

    on_tpu = _state.target_platform() == "tpu"
    best = host_best = float("inf")
    for _ in range(trials):
        if not on_tpu:
            t0 = time.perf_counter()
            run_once()
            host_best = min(host_best, (time.perf_counter() - t0) / steps)
            continue
        d = tempfile.mkdtemp(prefix="hvd_timed_")
        try:
            jax.profiler.start_trace(d)
            try:
                t0 = time.perf_counter()
                run_once()
                host_best = min(host_best,
                                (time.perf_counter() - t0) / steps)
            finally:
                jax.profiler.stop_trace()
            planes = device_planes(d)
            if not planes:
                found = {pl.name: [ln.name for ln in pl.lines]
                         for pl in _planes(d)}
                raise RuntimeError(
                    f"timed_steps: the TPU profiler capture has no "
                    f"'/device:*' plane with an 'XLA Ops' line, so there "
                    f"is no device time to report. Planes and lines "
                    f"found: {found}")
        finally:
            shutil.rmtree(d, ignore_errors=True)
        window_us = max(_window_us(evs) for evs in planes.values())
        best = min(best, window_us / 1e6 / steps)
    if info is not None:
        info["timing"] = "device" if on_tpu else "host"
        info["host_s"] = host_best
    return best if on_tpu else host_best


def is_collective(base: str) -> bool:
    return any(base == c or base.startswith(c + "-") for c in _COLL_KIND)


def _merge_async(events):
    """Each COLLECTIVE's ``-start``/``-done`` pair as one span from the
    start's beginning to the done's end; everything else passes through
    (between a ``slice-start`` and its done the device does other work).

    Returns [(base, start_us, end_us)] sorted by start.
    """
    merged = []
    pending = {}  # instr suffix key → (base, start)
    for name, start, dur in events:
        base = hlo_base(name)
        if is_collective(base) and base.endswith("-start"):
            pending[_instr_key(name).replace("-start", "")] = (base[:-6],
                                                               start)
            continue
        if is_collective(base) and base.endswith("-done"):
            key = _instr_key(name).replace("-done", "")
            if key in pending:
                b, s = pending.pop(key)
                merged.append((b, s, start + dur))
                continue
            base = base[:-5]
        merged.append((base, start, start + dur))
    # Unterminated -start pairs: emit what we saw.
    for b, s in pending.values():
        merged.append((b, s, s))
    merged.sort(key=lambda t: t[1])
    return merged


def map_device_spans(schedule, events):
    """Map xplane events onto the negotiated schedule.

    ``schedule``: [[name, op, dtype, shape, group, root], ...] in trace
    order. ``events``: ONE plane's [(hlo_name, start_us, dur_us)] in
    device order (:func:`slowest_plane` of a capture's planes).
    Returns [(row, activity, start_us, dur_us)], device-relative times.
    """
    if not events:
        return []
    spans = []
    merged = _merge_async(events)
    start0 = min(s for _, s, _ in merged)
    end_last = max(e for _, _, e in merged)
    spans.append(("_device", "DEVICE_STEP", start0, end_last - start0))

    colls = [(b, s, e) for b, s, e in merged if _COLL_KIND.get(b)]
    queue = list(schedule)
    matched = []  # (tensor_row, kind, start, end, members)
    for base, s, e in colls:
        kind = _COLL_KIND[base]
        for i, entry in enumerate(queue):
            accepts = _SCHED_ACCEPTS.get(entry[1], {entry[1]})
            if kind in accepts:
                members = tuple(entry[6]) if len(entry) > 6 else ()
                matched.append((entry[0], kind, s, e, members))
                del queue[i]
                break
    for row, kind, s, e, members in matched:
        spans.append((row, f"XLA_{kind}", s, e - s))
        # A fusion bucket's span repeats on each member tensor's row — the
        # reference timeline shows every fused tensor individually
        # (timeline.cc WriteEvent per tensor); the bucket row name in the
        # activity keeps the grouping visible.
        for m in members:
            spans.append((m, f"XLA_{kind} [{row}]", s, e - s))

    # Pack/unpack heuristics relative to matched collective windows. An op
    # qualifies only when it lies WHOLLY inside one inter-collective gap:
    # after the previous matched collective's end AND before the next
    # matched collective's start, with prev/next ADJACENT in the window
    # list (an op spanning an intermediate collective is that collective's
    # own work, not a copy). Start-of-trace counts as a gap edge for
    # packs, end-of-trace for unpacks.
    if matched:
        windows = sorted([(s, e) for _, _, s, e, _ in matched])
        for base, s, e in merged:
            if base not in _PACK_BASES and base not in _UNPACK_BASES:
                continue
            pi = next((i for i in reversed(range(len(windows)))
                       if windows[i][1] <= s), None)
            ni = next((i for i in range(len(windows))
                       if windows[i][0] >= e), None)
            adjacent = (pi is not None and ni is not None
                        and ni == pi + 1)
            if base in _PACK_BASES and (
                    adjacent or (pi is None and ni == 0)):
                spans.append(("_fusion_buffer",
                              "MEMCPY_IN_FUSION_BUFFER", s, e - s))
            elif base in _UNPACK_BASES and (
                    adjacent or (ni is None and pi == len(windows) - 1)):
                spans.append(("_fusion_buffer",
                              "MEMCPY_OUT_FUSION_BUFFER", s, e - s))
    return spans
