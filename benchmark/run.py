"""One run of one cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one cell, configuration, traffic mix, runner or
per-layer metric is a file found by the name ``BENCHMARK.json`` gives it
(see README.md); this file holds only what every cell shares: the look for
the chip, the compile cache, the measured window, the reading of the trace,
the comparison with the plain reference, and the result's line.

Exit codes: 0 with a result; 3 and no result when JAX finds no TPU or fewer
chips than the cell asks for (``--rehearse`` lifts that: a tiny preset on
CPU devices, for finding faults in the harness — it reports no metric).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse
import collections
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """``benchmark/<parts...>.py`` as a module, found by name (never
    through ``sys.path``: ``trace`` is also a module of the standard
    library)."""
    path = os.path.join(HERE, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + "_".join(parts).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_cell(name: str, rehearse: bool):
    """(manifest, cell, config, traffic) of a cell, by the names in
    ``BENCHMARK.json``; a rehearsal takes the files' ``rehearsal`` sizes
    and tells JAX to use CPU devices, as many as the cell has chips (call
    it before JAX is imported)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = find_cell(manifest, name)
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        config.update(config.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # the program: horovod_tpu
    return manifest, cell, config, traffic


def configure_jax(rehearse: bool) -> None:
    """The compile cache: a fixed directory inside the checkout, so that
    only a checkout's first run of a cell compiles. Every program goes in,
    the small ones too: a run makes some hundred of them (placement, norms,
    the reference's pieces), and uncached each is compiled again in every
    process. (A rehearsal keeps none: CPU programs are cheap.)"""
    import jax

    if not rehearse:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Pallas kernel is serialized with its MLIR locations; with whole
    # tracebacks in them every call site is another cache key (PERF.md,
    # PR 21).
    jax.config.update("jax_include_full_tracebacks_in_locations", False)


def metrics_of(manifest: dict, group: str, cell: str, reported=None):
    """The manifest's metrics of ``group`` that this cell reports."""
    out = []
    for m in manifest[group]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if cells is None and reported is not None and group == "per_layer" \
                and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def measure_window(session, seconds: float, annotate):
    """Drive the session for ``seconds``: one framework step a host call,
    at most two in flight, every step's result waited for. Returns the
    steps, the seconds from the first dispatch to the last completion, each
    step's completion-to-completion seconds and the results."""
    first = session.next_batch
    in_flight = collections.deque()
    ends, results = [], []
    t0 = time.perf_counter()
    with annotate("bench/dispatch"):
        in_flight.append(session.dispatch(first))
    k = 1
    while in_flight:
        if time.perf_counter() - t0 < seconds:
            with annotate("bench/dispatch"):
                in_flight.append(session.dispatch(first + k))
            k += 1
        with annotate("bench/wait_step"):
            results.append(session.finish(in_flight.popleft()))
        ends.append(time.perf_counter())
    step_seconds = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    return {"steps": k, "seconds": ends[-1] - t0,
            "step_seconds": step_seconds, "results": results}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on CPU devices; no metric is printed")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the capture under .bench_trace/ (to look at "
                         "one by hand: tests/dump_trace.py)")
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = load_cell(args.workload,
                                                args.rehearse)
    chips = cell["chips"]

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse and platform != "tpu":
        say(f"no accelerator: JAX found platform={platform!r} x"
            f"{len(devices)}; this benchmark measures a TPU only")
        return 3
    if len(devices) < chips:
        say(f"{args.workload} needs {chips} chips, JAX found {len(devices)}")
        return 3
    peaks = None
    if not args.rehearse:
        table = load_json("peaks.json")
        if kind not in table:
            say(f"device kind {kind!r} is not in benchmark/peaks.json")
            return 3
        peaks = table[kind]
    configure_jax(args.rehearse)

    seeded = load_module("seeded")
    runner = load_module("runners", config["runner"])
    reference = load_module("reference", config["runner"])
    limits = load_json("limits", cell["name"] + ".json")
    if args.rehearse:
        limits = limits.get("rehearsal", limits)
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, seed=args.seed, chips=chips,
        seeded=seeded, reference=reference,
        readings=load_module("readings"), rehearse=args.rehearse, say=say,
        t0=_T0)

    session = runner.setup(ctx)
    setup_s = time.perf_counter() - _T0
    say(f"set-up {setup_s:.2f} s")

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    annotate = lambda name: contextlib.nullcontext()
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        annotate = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(trace_dir)
    try:
        window = measure_window(session, args.seconds, annotate)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    say(f"window {window['seconds']:.3f} s, {window['steps']} steps")

    used = devices[:chips]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)
    observed = session.observed
    failed = sum(1 for r in window["results"]
                 if not np.all(np.isfinite(r)))
    units_per_step = session.units_per_step
    e2e = runner.end_to_end(session, window)
    e2e["setup_s"] = (setup_s, "s")
    session.release()
    del session

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": window["steps"],
              "failed": failed, "metrics": {}, "device": device}
    if args.trace:
        t_read = time.perf_counter()
        tr = load_module("trace").Trace.load(trace_dir)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if not tr.devices:
            say("the capture holds no device plane with an 'XLA Ops' line "
                "(a CPU has none: rehearse with --trace 0)")
            return 4
        lo, hi = tr.window_us()
        busy = tr.busy_us()
        device["busy_s"] = sum(busy.values()) / len(busy) / 1e6
        device["window_s"] = (hi - lo) / 1e6
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
        run = types.SimpleNamespace(
            cell=cell, config=config, traffic=traffic, chips=chips,
            peaks=peaks, window=window, memory_peak_bytes=memory_peak,
            trace=tr, flops=load_module("flops"),
            units_per_step=units_per_step)
        for m in metrics_of(manifest, "per_layer", cell["name"],
                            reported=set(e2e)):
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        say(f"trace read in {time.perf_counter() - t_read:.1f} s")
    else:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            if m["name"] in e2e:
                value, unit = e2e[m["name"]]
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": unit}
    if args.rehearse:
        # A CPU made these: no number of it goes under a device metric.
        result["metrics"] = {}
        result["rehearsal"] = True

    t_ref = time.perf_counter()
    expected = reference.run(config, traffic, args.seed, chips, seeded,
                             devices=used, log=say)
    correct, compared = _decide(observed, expected, limits)
    say(f"reference {time.perf_counter() - t_ref:.1f} s")
    result["correct"] = bool(correct and failed == 0)
    result["compared"] = compared  # last, as the contract asks
    for row in compared:
        say(f"compared {row['name']} = {row['value']:.6g} "
            f"(limit {row['limit']}) at {row['at']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _decide(observed, expected, limits):
    correct, rows = load_module("compare").decide(observed, expected, limits)
    for row in rows:  # JSON has no infinity
        if not math.isfinite(row["value"]):
            row["value"] = 1e30
    return correct, rows


if __name__ == "__main__":
    sys.exit(main())
