"""From the program's own record to seconds by named scope: the helper
of the per-layer readers that look INSIDE the compiled step.

The program supplies facts and this file reduces them. The facts are
``horovod_tpu.core.timeline.record()``, read after the runner's
``release()`` (``hvd.shutdown`` has resolved what the capture needs):

* ``spans``: ``[name, start_ns, end_ns, parent]`` of the program's host
  spans (``hvd/init``, ``hvd/spmd/build``, ``hvd/spmd/dispatch``, ...);
* ``programs[tag]``: ``dispatches``, the exchange plan's ``counters``
  (``exchange.wire_bytes``: a step, a rank) and ``scopes`` —
  ``{instruction: [op_name, [op_names of a fusion's members]]}`` of the
  optimized program, or ``None``.

A capture's device events are named by instruction (``%fusion.973 = ...``)
and carry no scope on this libtpu, so the join goes through ``scopes``.
Every reader takes the program with most dispatches (the step) and returns
``None`` where there is nothing to read — a program without a record (the
parent of PR 25) reads nothing and the metric is left out of the line.

A fusion is ONE event and XLA fuses across phases (a weight gradient's
matmul with the AdamW pass that consumes it; a backward matmul with the
forward's elementwise work it recomputes), so a fusion goes to the phase
of the matmul, convolution or kernel it holds — by any reckoning of time
that is what it is — else to the phase most of its members carry.
``mixed`` is left for what no rule decides: matmuls of two phases in one
fusion, or a tie.

An event's time is its SELF time: its duration less that of the events
nested in it (a ``while`` holds its body's), so that a device's phases
add up to its busy time and nothing is counted twice.
"""

from __future__ import annotations

import collections
import re

PHASES = ("forward", "backward", "exchange", "update", "other", "mixed")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
# The primitives (an ``op_name``'s last component) that carry a fusion's
# time where it holds one: the MXU's work and Pallas kernels.
HEAVY = ("dot_general", "conv_general_dilated", "pallas_call")
Event = collections.namedtuple("Event", "name base phase op_name members")


def record():
    """The program's record, or None where the program keeps none."""
    try:
        from horovod_tpu.core import timeline
    except ImportError:
        return None
    read = getattr(timeline, "record", None)
    return read() if read is not None else None


def step_program(rec):
    """The record of the program with most dispatches, or None."""
    programs = (rec or {}).get("programs") or {}
    if not programs:
        return None
    return max(programs.values(), key=lambda p: p.get("dispatches", 0))


def phase(op_name: str) -> str:
    """The phase an ``op_name`` was traced in, found by looking through
    its components (``hvd.spmd`` puts ``jit(step)/shard_map/`` in front):
    a ``transpose(jvp(...))`` is the backward, else a ``jvp(...)`` (or a
    bare ``hvd.model``: a loss nobody differentiates) the forward, else
    ``hvd.exchange`` / ``hvd.update``, else ``other`` — the user's
    ``apply_updates``, the loss's own all-reduce."""
    parts = op_name.split("/")
    if any(p.startswith("transpose(jvp(") for p in parts):
        return "backward"
    if any(p.startswith("jvp(") or p == "hvd.model" for p in parts):
        return "forward"
    for p in parts:
        if p in ("hvd.exchange", "hvd.update"):
            return p[4:]
    return "other"


def phase_of(entry) -> str:
    """The phase of one ``[op_name, members]`` entry of a scope map.
    ``other`` members (parameters, bitcasts, the user's own operations)
    never decide a fusion's. A fusion takes the phase of its ``HEAVY``
    members if they agree (``mixed`` if two phases' matmuls share it);
    holding none, the phase most of its members carry (``mixed`` on a
    tie). An instruction the map does not hold is ``other``."""
    if entry is None:
        return "other"
    op_name, members = entry
    named = [(n, phase(n)) for n in members or [op_name]]
    votes = collections.Counter(p for _, p in named if p != "other")
    if not votes:
        return phase(op_name)
    heavy = {p for n, p in named
             if p != "other" and n.rsplit("/", 1)[-1] in HEAVY}
    if heavy:
        return heavy.pop() if len(heavy) == 1 else "mixed"
    (best, n), *rest = votes.most_common(2)
    return "mixed" if rest and rest[0][1] == n else best


def instr_key(name: str) -> str:
    m = re.match(r"%?([a-zA-Z0-9_.-]+)", name)
    return m.group(1) if m else name


def self_us(events):
    """Self time of each ``(name, base, start, end)`` (sorted by start):
    its duration less the events that lie wholly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    out = [0.0] * len(events)
    open_ = []
    for i in order:
        _, _, s, e = events[i]
        while open_ and events[open_[-1]][3] <= s:
            open_.pop()
        out[i] = e - s
        if open_ and e <= events[open_[-1]][3]:
            out[open_[-1]] -= e - s
        open_.append(i)
    return out


def scoped_rows(run, rec) -> dict:
    """Per device: ``[(Event, self µs)]`` of ``run.trace.raw``, each event
    joined to the step program's scope map by its instruction's name. {}
    where the record has no map. The join is made once a run and kept on
    it: every reader asks."""
    program = step_program(rec)
    scopes = program and program.get("scopes")
    if not scopes:
        return {}
    rows = getattr(run, "_scoped_rows", None)
    if rows is None:
        by_key, rows = {}, {}
        for device, events in run.trace.raw.items():
            joined = []
            for (name, base, _, _), us in zip(events, self_us(events)):
                key = instr_key(name)
                if key not in by_key:
                    entry = scopes.get(key)
                    op_name, members = entry or ("", [])
                    by_key[key] = Event(name, base, phase_of(entry),
                                        op_name, members)
                joined.append((by_key[key], us))
            rows[device] = joined
        run._scoped_rows = rows
    return rows


def seconds_by(run, rec, keep) -> dict:
    """Per device: the self seconds of the events that ``keep(Event)``
    accepts. {} where the record has no scope map."""
    return {device: sum(us for ev, us in joined if keep(ev)) / 1e6
            for device, joined in scoped_rows(run, rec).items()}


def exchange_collectives_per_step(run):
    """Collectives the DEVICE runs a step under ``hvd.exchange`` (an
    async pair counts once, by its start), on the device that runs most;
    None where the capture or the record gives nothing to count. XLA's
    combiner merges the plan's buckets, so this is the device's number
    and not the plan's."""
    def is_call(ev):
        return ev.phase == "exchange" \
            and ev.base.removesuffix("-start") in COLLECTIVES

    counts = [sum(1 for ev, _ in joined if is_call(ev))
              for joined in scoped_rows(run, record()).values()]
    return max(counts) / run.window["steps"] if counts else None


def step_counter(name: str):
    """Counter ``name`` of the step program's exchange plan, or None."""
    program = step_program(record())
    return (program or {}).get("counters", {}).get(name)


def phase_ms_per_step(run, which: str):
    """Device ms a step in phase ``which``, on the busiest device."""
    per_device = seconds_by(run, record(), lambda ev: ev.phase == which)
    if not per_device:
        return None
    return 1e3 * max(per_device.values()) / run.window["steps"]


def kernel_ms_per_step(run, kernel: str):
    """Device ms a step of the Pallas calls named ``kernel`` (the name
    ``pl.pallas_call`` was given: in the event's text on this libtpu, and
    in its ``op_name``); the busiest device."""
    def keep(ev):
        return run.trace.is_pallas_call(ev.name, ev.base) \
            and kernel in ev.name + ev.op_name

    kernel_s = max(seconds_by(run, record(), keep).values(), default=0.0)
    return 1e3 * kernel_s / run.window["steps"] if kernel_s > 0 else None


def window_dispatches(run, rec):
    """The step program's last ``run.window["steps"]`` dispatch spans —
    the window's own (the followed steps of set-up come before)."""
    spans = [s for s in (rec or {}).get("spans", ())
             if s[0] == "hvd/spmd/dispatch"]
    return spans[-run.window["steps"]:]


def span_seconds(rec, name: str):
    """Seconds of all spans called ``name``; None where there is none."""
    found = [(e - s) / 1e9 for n, s, e, _ in (rec or {}).get("spans", ())
             if n == name]
    return sum(found) if found else None
