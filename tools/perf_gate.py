"""perf-gate: compare a BENCH json against a committed baseline.

The regression half of the ``hvd.tune()`` loop (ROADMAP open item 5,
docs/tuning.md): the tuner is what makes headline metrics move, this
gate is what keeps them from silently moving back. Two rules, both
per-metric against ``BENCH_baseline.json``:

1. **Banded regression**: every metric the baseline records must be
   present in the candidate and inside its tolerance band
   (``value * (1 - rel_tol)`` floor for higher-is-better metrics, the
   mirrored ceiling for lower-is-better). Bands are committed WITH the
   baseline — CPU-jitter-prone metrics carry wide bands, planned
   (deterministic) quantities carry tight ones.
2. **Tuned-vs-default**: every ``tuned_speedup_*`` field in the
   candidate must be >= ``1 - rel_tol`` of its band (the tuned
   configuration may tie the defaults, never lose to them). A null
   speedup is only acceptable where the baseline also records null
   (metric infeasible on that backend, bench.py's null-when-infeasible
   convention).

Usage:
    python tools/perf_gate.py BENCH.json --baseline BENCH_baseline.json
    python tools/perf_gate.py BENCH.json --make-baseline BENCH_baseline.json
        # distill a bench artifact into a committed baseline (curated
        # metric list + per-metric bands; docs/ci.md has the recipe)

Exit status: 0 pass, 1 regression/failed gate, 2 usage error. Pure
stdlib — the gate must run in any CI job, jax or not.
"""

from __future__ import annotations

import argparse
import json
import sys

# Metrics distilled into a baseline by --make-baseline, with their
# tolerance bands. Absolute CPU wall-clock numbers jitter hard on
# shared CI hosts AND the committed baseline's host is not the CI
# runner — throughput
# bands are deliberately wide; the tuned-vs-default SPEEDUP is a
# same-host same-process A/B ratio, so its band can be much tighter
# than either absolute number. direction: "higher" = higher is better.
BASELINE_METRICS = {
    "resnet50_images_per_sec_per_chip": {"rel_tol": 0.75,
                                         "direction": "higher"},
    "lm_t8k_tokens_per_sec_per_chip": {"rel_tol": 0.75,
                                       "direction": "higher"},
    "lm_t8k_tokens_per_sec_per_chip_tuned": {"rel_tol": 0.75,
                                             "direction": "higher"},
    "tuned_speedup_lm_t8k": {"rel_tol": 0.15, "direction": "higher"},
    "allreduce_busbw_flat_gbps": {"rel_tol": 0.75, "direction": "higher"},
    "allreduce_busbw_rs_ag_gbps": {"rel_tol": 0.75, "direction": "higher"},
    # Speculative decode (docs/inference.md): the absolute spec-decode
    # throughput gets the wide CPU-jitter band; the speedup is a
    # same-process A/B ratio (spec vs plain B=1 decode on the same
    # model), so its band is tighter — and sized so the FLOOR stays
    # above 1.0: a candidate where speculation no longer beats plain
    # decode gates no matter how noisy the host.
    "lm_decode_tokens_per_sec_b1_spec": {"rel_tol": 0.75,
                                         "direction": "higher"},
    "serve_speculative_speedup": {"rel_tol": 0.55, "direction": "higher"},
    # Crash-safe request journal (serving/resilience.py): append+fsync
    # cost per engine step. Lower is better, and the band is wide —
    # fsync latency varies enormously across hosts/filesystems — but a
    # candidate whose journal writes balloon past the ceiling has moved
    # journal work onto the per-step critical path.
    "serve_journal_overhead_ms": {"rel_tol": 8.0, "direction": "lower"},
    # FSDP (ZeRO-3) per-chip parameter footprint vs replicated: a pure
    # bytes ratio (~1/fsdp_size + padding), host-jitter-free, so the
    # band only needs room for layout/padding drift — a candidate whose
    # ratio balloons has stopped sharding what it claims to shard.
    "fsdp_param_bytes_per_chip_ratio": {"rel_tol": 0.5,
                                        "direction": "lower"},
}
BASELINE_SCHEMA = "horovod_tpu/bench-baseline/v1"


def _load(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    # Accept both bench.py's raw stdout dict and the driver's wrapped
    # {"cmd", "rc", "parsed", ...} artifact form (BENCH_rNN.json).
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        return data["parsed"]
    if not isinstance(data, dict):
        raise SystemExit(f"perf_gate: {path} is not a JSON object")
    return data


def _lift_headline(bench: dict) -> dict:
    """The headline metric rides under ``{"metric": name, "value": v}``
    in bench.py's artifact rather than as a named field — lift it to a
    named key so the curated list can band it like every extra."""
    out = dict(bench)
    name = bench.get("metric")
    if isinstance(name, str) and "value" in bench:
        out.setdefault(name, bench["value"])
    return out


def make_baseline(bench: dict) -> dict:
    """Distill a bench artifact into a committed baseline: the curated
    metrics present in the artifact (null values kept — they pin that
    the metric was infeasible on the baseline backend, so a candidate
    null there is acceptable, not missing)."""
    bench = _lift_headline(bench)
    metrics = {}
    for name, band in BASELINE_METRICS.items():
        if name in bench:
            value = bench[name]
            metrics[name] = {"value": value, **band}
    return {"schema": BASELINE_SCHEMA, "metrics": metrics}


def compare(bench: dict, baseline: dict) -> list[str]:
    """All gate failures (empty = pass). Pure function, unit-tested."""
    bench = _lift_headline(bench)
    if baseline.get("schema") != BASELINE_SCHEMA:
        return [f"baseline schema mismatch: expected {BASELINE_SCHEMA!r}, "
                f"got {baseline.get('schema')!r} — refusing to guess a "
                f"stale layout (regenerate: docs/ci.md)"]
    failures: list[str] = []
    metrics = baseline.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        return ["baseline records no metrics — regenerate it (docs/ci.md)"]
    for name, entry in sorted(metrics.items()):
        base = entry.get("value")
        if base is None:
            continue  # infeasible on the baseline backend: nothing to hold
        cand = bench.get(name)
        if cand is None:
            failures.append(
                f"{name}: baseline records {base} but the candidate "
                f"reports {'null' if name in bench else 'no field'} — a "
                f"metric must not vanish")
            continue
        tol = float(entry.get("rel_tol", 0.0))
        if entry.get("direction", "higher") == "higher":
            floor = base * (1.0 - tol)
            if cand < floor:
                failures.append(
                    f"{name}: {cand} < {floor:.6g} "
                    f"(baseline {base} - {tol:.0%} band) — regression")
        else:
            ceil = base * (1.0 + tol)
            if cand > ceil:
                failures.append(
                    f"{name}: {cand} > {ceil:.6g} "
                    f"(baseline {base} + {tol:.0%} band) — regression")
    # Rule 2: tuned never loses to untuned defaults, wherever the
    # candidate measured an A/B — even for speedup fields the baseline
    # predates (new backends/metrics join the gate automatically).
    for name in sorted(bench):
        if not name.startswith("tuned_speedup_"):
            continue
        cand = bench[name]
        if cand is None:
            entry = metrics.get(name)
            if entry is not None and entry.get("value") is not None:
                failures.append(
                    f"{name}: candidate reports null but the baseline "
                    f"measured {entry['value']} — the tuned A/B "
                    f"stopped running")
            continue
        tol = float(metrics.get(name, {}).get("rel_tol", 0.05))
        if cand < 1.0 - tol:
            failures.append(
                f"{name}: {cand} < {1.0 - tol:.3f} — the tuned "
                f"configuration loses to untuned defaults (ties "
                f"allowed, losses gate)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_gate",
        description="Compare a BENCH json against a committed baseline "
                    "with per-metric tolerance bands.")
    ap.add_argument("bench", help="candidate BENCH json (bench.py stdout "
                                  "or the wrapped BENCH_rNN.json form)")
    ap.add_argument("--baseline", help="committed BENCH_baseline.json")
    ap.add_argument("--make-baseline", metavar="OUT",
                    help="instead of gating, distill the bench artifact "
                         "into a baseline at OUT")
    args = ap.parse_args(argv)
    if bool(args.baseline) == bool(args.make_baseline):
        ap.error("exactly one of --baseline / --make-baseline is required")

    bench = _load(args.bench)
    if args.make_baseline:
        baseline = make_baseline(bench)
        if not baseline["metrics"]:
            print("perf_gate: bench artifact carries none of the curated "
                  "metrics — refusing to write an empty baseline",
                  file=sys.stderr)
            return 2
        with open(args.make_baseline, "w") as f:
            json.dump(baseline, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"perf_gate: wrote {args.make_baseline} "
              f"({len(baseline['metrics'])} metric(s))")
        return 0

    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = compare(bench, baseline)
    if failures:
        for line in failures:
            print(f"perf_gate: FAIL {line}")
        print(f"perf_gate: {len(failures)} gate failure(s) vs "
              f"{args.baseline}.", file=sys.stderr)
        return 1
    held = sum(1 for e in baseline.get("metrics", {}).values()
               if e.get("value") is not None)
    print(f"perf_gate: pass ({held} banded metric(s) held, "
          f"tuned >= defaults).")
    return 0


if __name__ == "__main__":
    sys.exit(main())
