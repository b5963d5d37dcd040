"""Device ms a step of the events that carry a NAME — a scope of the
program's (``head``) or a mark JAX leaves (``rematted_computation``: a
checkpointed block's forward run again inside the backward) — for the
per-layer readers that cut across ``scopes.py``'s phases.

A fusion is one event with many members: it carries the name if most of
the members that decide its phase do — its matmuls and Pallas calls
(``scopes.HEAVY``) where it holds any, else its members of any phase but
``other`` — the rule ``scopes.phase_of`` uses for phases.
"""

from __future__ import annotations

from benchmark import scopes


def carries(ev, name: str) -> bool:
    """Whether most deciding members of ``ev`` have the path component
    ``name`` in their ``op_name``."""
    names = ev.members or [ev.op_name]
    deciding = [n for n in names if n.rsplit("/", 1)[-1] in scopes.HEAVY] \
        or [n for n in names if scopes.phase(n) != "other"]
    hits = sum(1 for n in deciding if name in n.split("/"))
    return 2 * hits > len(deciding)


def ms_per_step(run, name: str, phase: str | None = None):
    """Device ms a step of the events that carry ``name`` (in ``phase``
    only, where one is given), on the busiest device; None where the
    record has no scope map or nothing carries the name."""
    def keep(ev):
        return (phase is None or ev.phase == phase) and carries(ev, name)

    per_device = scopes.seconds_by(run, scopes.record(), keep)
    total = max(per_device.values(), default=0.0)
    return 1e3 * total / run.window["steps"] if total > 0 else None
