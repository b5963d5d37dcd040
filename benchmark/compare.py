"""The comparison that decides ``correct`` for a training cell.

``observed`` is what the timed object produced in its first steps,
``reference`` what the plain reference computed from the same seed. Both:
``{"loss": [a step], "grad_norm": {leaf: norm}, "change_norm": {leaf:
norm}}``; an observed value may be a list, one per rank, and every rank is
held to the reference.

Numbers, each with a limit of its own (``limits/<cell>.json``; a limit of
null means reported, not compared):

  loss_step<k>      |observed - reference| / |reference|
  grad_norm_gap     worst leaf of | ||g|| - ||g_ref|| | over the larger of
                    that leaf's and the median leaf's reference norm
  change_norm_gap   the same of the parameters' change after the followed
                    steps, leaving out leaves whose reference gradient is
                    under a thousandth of the median leaf's (they move by
                    round-off alone)
  grad_norm_gap_median, change_norm_gap_median
                    the median leaf's gap in place of the worst leaf's: for
                    a cell whose worst leaf is one small leaf's rounding
                    noise (PERF.md says which and why)
"""

from __future__ import annotations

import math
import statistics


def _ranks(value):
    return list(value) if isinstance(value, (list, tuple)) else [value]


def _leaf_gaps(observed: dict, reference: dict, skip=()):
    """{leaf: its gap on the rank that reads worst}."""
    floor = statistics.median(reference.values())
    gaps = {}
    for name, ref in reference.items():
        if name in skip:
            continue
        gap = max(abs(got - ref) / max(ref, floor)
                  for got in _ranks(observed[name]))
        gaps[name] = gap if math.isfinite(gap) else math.inf
    return gaps


def _worst_and_median(gaps: dict):
    at = max(gaps, key=gaps.get)
    return (gaps[at], at), (statistics.median(gaps.values()), None)


def numbers(observed: dict, reference: dict) -> dict:
    """name -> (value, the leaf it was read at or None)."""
    out = {}
    for k, ref in enumerate(reference["loss"]):
        gap = max(abs(got - ref) / abs(ref)
                  for got in _ranks(observed["loss"][k]))
        out[f"loss_step{k + 1}"] = (gap if math.isfinite(gap) else math.inf,
                                    None)
    grad = _worst_and_median(_leaf_gaps(observed["grad_norm"],
                                        reference["grad_norm"]))
    tiny = statistics.median(reference["grad_norm"].values()) * 1e-3
    skip = {n for n, g in reference["grad_norm"].items() if g < tiny}
    change = _worst_and_median(_leaf_gaps(
        observed["change_norm"], reference["change_norm"], skip))
    out["grad_norm_gap"], out["grad_norm_gap_median"] = grad
    out["change_norm_gap"], out["change_norm_gap_median"] = change
    return out


def decide(observed: dict, reference: dict, limits: dict):
    """(correct, [{"name", "value", "limit", "at"}])."""
    rows, correct = [], True
    for name, (value, at) in numbers(observed, reference).items():
        limit = limits.get(name)
        if limit is not None and not value <= limit:
            correct = False
        rows.append({"name": name, "value": value, "limit": limit,
                     "at": at})
    return correct, rows
