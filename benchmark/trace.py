"""From a ``jax.profiler`` capture to numbers: the events of EVERY device
plane's ``XLA Ops`` line, async ``-start``/``-done`` pairs merged, and the
reductions the per-layer readers share (busy time, collective time that no
compute hides, the operations that took most time, the longest idle gaps).

Copied in idea from the program's ``core/xprof.py`` (which stops at the
first device plane) and kept here so that a change to the program cannot
move the yardstick. Times are microseconds on the capture's own clock.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
HOST_SPAN_PREFIX = "bench/"


def hlo_base(name: str) -> str:
    """HLO opcode of an event. An event's name is the instruction's text,
    ``%psum.168 = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=...``:
    the opcode is what follows the result's shape, NOT the instruction's
    name (which is the JAX primitive's: a ``psum`` is an ``all-reduce``, a
    Pallas call under ``shard_map`` is ``%shard_map.1704 = (...)
    custom-call(...)``). A bare name (``fusion.12``, ``%all-reduce-start.3``)
    gives its own stem."""
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


def is_pallas_call(name: str, base: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    ``tpu_custom_call`` (the bare instruction name where the text is cut)."""
    if base == "custom-call":
        return "tpu_custom_call" in name
    return base == "tpu_custom_call"


def _instr_key(name: str) -> str:
    m = re.match(r"%?([a-zA-Z0-9_.-]+)", name)
    return m.group(1) if m else name


def is_collective(base: str) -> bool:
    return any(base == c or base.startswith(c + "-") for c in COLLECTIVES)


def merge_async(events):
    """[(name, start, dur)] → [(name, base, start, end)] sorted by start,
    each COLLECTIVE's ``-start``/``-done`` pair as one span from the
    start's beginning to the done's end, under the start's name. Other
    async pairs (``slice-start``/``-done``, copies) stay two short events:
    between them the device does other work, and a span over it would count
    that time twice."""
    merged, pending = [], {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        base = hlo_base(name)
        if not is_collective(base):
            merged.append((name, base, start, start + dur))
            continue
        if base.endswith("-start"):
            pending[_instr_key(name).replace("-start", "")] = (
                name, base[:-6], start, start + dur)
            continue
        if base.endswith("-done"):
            key = _instr_key(name).replace("-done", "")
            if key in pending:
                n, b, s, _ = pending.pop(key)
                merged.append((n, b, s, start + dur))
                continue
            base = base[:-5]
        merged.append((name, base, start, start + dur))
    merged.extend(pending.values())  # a start whose done fell outside
    merged.sort(key=lambda e: e[2])
    return merged


def read_planes(trace_dir: str):
    """The newest capture under ``trace_dir``: ({device plane name:
    [(name, start_us, dur_us)]}, [(host span name, start_us, end_us)]).
    Device planes without an ``XLA Ops`` line are left out."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host = {}, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (ev.name, ev.start_ns / 1e3, ev.duration_ns / 1e3)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns / 1e3,
                                     (ev.start_ns + ev.duration_ns) / 1e3))
    host.sort(key=lambda e: e[1])
    return devices, host


def union(intervals):
    """Disjoint, sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(disjoint) -> float:
    return sum(e - s for s, e in disjoint)


def _clip(disjoint, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in disjoint
            if e > lo and s < hi]


class Trace:
    """One capture: ``raw[name]`` is that device plane's events as
    recorded, ``devices[name]`` the same with collectives' async pairs
    merged, both ``(name, base, start, end)``; ``host`` the harness's own
    spans."""

    def __init__(self, devices: dict, host=()):
        self.raw = {n: sorted(((nm, hlo_base(nm), s, s + d)
                               for nm, s, d in ev), key=lambda e: e[2])
                    for n, ev in devices.items() if ev}
        self.devices = {n: merge_async(ev) for n, ev in devices.items()
                        if ev}
        self.host = list(host)

    is_pallas_call = staticmethod(is_pallas_call)

    @classmethod
    def load(cls, trace_dir: str) -> "Trace":
        return cls(*read_planes(trace_dir))

    def window_us(self):
        """(first start, last end) over every device."""
        starts = [ev[0][2] for ev in self.raw.values()]
        ends = [max(e[3] for e in ev) for ev in self.raw.values()]
        return min(starts), max(ends)

    def busy_us(self) -> dict:
        """Per device: the time in which some operation ran (events as
        recorded: the time between an async start and its done counts only
        where another operation, or the done's own wait, fills it)."""
        return {n: _length(union((s, e) for _, _, s, e in ev))
                for n, ev in self.raw.items()}

    def idle_share_pct(self):
        """The share of the traced window in which no operation ran, on the
        idlest device; None where there is nothing to read."""
        lo, hi = self.window_us()
        busy = self.busy_us()
        if hi <= lo or not busy:
            return None
        return 100.0 * (1.0 - min(busy.values()) / (hi - lo))

    def exposed_collective_us(self) -> dict:
        """Per device: the part of its collectives' spans during which no
        other operation ran on that device."""
        out = {}
        for n, ev in self.devices.items():
            coll = union((s, e) for _, b, s, e in ev if is_collective(b))
            work = union((s, e) for _, b, s, e in ev
                         if not is_collective(b))
            hidden = sum(_length(_clip(work, s, e)) for s, e in coll)
            out[n] = _length(coll) - hidden
        return out

    def op_seconds(self, keep) -> dict:
        """Per device: seconds of the events whose (name, base) ``keep``
        accepts, overlaps counted once."""
        return {n: _length(union((s, e) for nm, b, s, e in ev
                                 if keep(nm, b))) / 1e6
                for n, ev in self.devices.items()}

    def top_ops(self, k: int = 10):
        """[[name, seconds]] of the k instructions with most device time,
        averaged over the devices."""
        total: dict = {}
        for ev in self.raw.values():
            for name, _, s, e in ev:
                key = _instr_key(name)
                total[key] = total.get(key, 0.0) + (e - s)
        nd = max(1, len(self.devices))
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, us / 1e6 / nd] for name, us in top]

    def idle_gaps(self, k: int = 10):
        """[[what the host was doing, seconds]]: the k longest gaps on the
        idlest device, each named by the harness span that covers most of
        it (``none`` where no span does)."""
        busy = self.busy_us()
        name = min(busy, key=busy.get)
        spans = union((s, e) for _, _, s, e in self.raw[name])
        gaps = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(spans, spans[1:])), reverse=True)[:k]
        out = []
        for length, lo, hi in gaps:
            best, cover = "none", 0.0
            for hname, s, e in self.host:
                c = min(e, hi) - max(s, lo)
                if c > cover:
                    best, cover = hname[len(HOST_SPAN_PREFIX):], c
            out.append([best, length / 1e6])
        return out
