"""Where a benchmark cell's ``hvd/spmd/build`` seconds go: ``python
tools/build_split.py <cell> [--seed N] [--root DIR]`` ON THE CHIP.

Runs the cell's set-up exactly as ``benchmark/run.py`` does (the runner's
``setup``: ``hvd.init``, the seed's weights, the followed steps through
``hvd.spmd`` — no window, no reference) with JAX's own monitoring events
listened to, and prints one JSON line: ``setup_s``, every
``hvd/spmd/build`` span of the record, and inside each the seconds JAX
spent tracing (``jaxpr_trace_duration``: the step's Python, the models',
the kernels' bodies), lowering (``jaxpr_to_mlir_module_duration``: a
Pallas kernel is lowered to Mosaic there, a call site at a time), and
compiling OR loading (``backend_compile_duration``; with
``cache_hits`` / ``cache_misses`` of the persistent cache and the seconds
its retrieval took). What is left of the span is the first call: the
dispatch of the loaded program, and whatever it waits for. ``--root``
takes the program and the benchmark from another checkout (the parent's,
unpacked beside this one): its compile cache is that checkout's own, so
run a tree twice in one call for a warm reading.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import types

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _union_s(intervals) -> float:
    """Seconds covered by the ``(start_ns, end_ns)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        total += max(end - max(start, reach), 0.0)
        reach = max(reach, end)
    return total / 1e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=2147480001)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny preset on CPU devices: finds "
                         "faults in this tool, its seconds mean nothing")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)  # set-up's clock starts here, as run.py's

    _, cell, config, traffic = run.load_cell(args.cell, args.rehearse)
    import jax
    from jax import monitoring

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: this tool reads a chip's set-up", file=sys.stderr)
        return 3
    run.configure_jax(args.rehearse)
    seen = []  # (kind, end_ns, seconds or 1, fun_name)
    monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: seen.append(
            (EVENTS[event], time.perf_counter_ns(), seconds,
             kw.get("fun_name", ""))) if event in EVENTS else None)
    monitoring.register_event_listener(
        lambda event, **kw: seen.append(
            (COUNTS[event], time.perf_counter_ns(), 1, ""))
        if event in COUNTS else None)

    runner = run.load_module("runners", config["runner"])
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, seed=args.seed, chips=cell["chips"],
        seeded=run.load_module("seeded"),
        reference=run.load_module("reference", config["runner"]),
        readings=run.load_module("readings"), rehearse=args.rehearse,
        say=run.say, t0=run._T0)
    session = runner.setup(ctx)
    setup_s = time.perf_counter() - run._T0

    from horovod_tpu.core import timeline

    builds = []
    for name, start, end, _ in timeline.session().record()["spans"]:
        if name != "hvd/spmd/build":
            continue
        row = {"build_s": (end - start) / 1e9}
        inside = [e for e in seen if start <= e[1] <= end]
        for kind in EVENTS.values():  # nested traces are counted once
            row[kind] = _union_s([(e[1] - e[2] * 1e9, e[1]) for e in inside
                                  if e[0] == kind])
        for kind in COUNTS.values():
            row[kind] = sum(e[2] for e in inside if e[0] == kind)
        row["first_call_and_rest_s"] = row["build_s"] - sum(
            row[k] for k in ("trace_s", "lower_s", "compile_or_load_s"))
        row["largest"] = sorted(
            ([e[0], round(e[2], 3), e[3]] for e in inside
             if e[0] in EVENTS.values()), key=lambda r: -r[1])[:4]
        builds.append(row)
    print(json.dumps({"cell": args.cell, "root": root, "seed": args.seed,
                      "setup_s": setup_s, "builds": builds}), flush=True)
    session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
