"""Where a benchmark cell's ``hvd/spmd/build`` seconds go: ``python
tools/build_split.py <cell> [--seed N] [--root DIR]`` ON THE CHIP.

Runs the cell's set-up exactly as ``benchmark/run.py`` does (the runner's
``setup``: ``hvd.init``, the seed's weights, the followed steps through
``hvd.spmd`` — no window, no reference) and prints one JSON line from the
program's own record (``core/timeline.record()``): ``setup_s``, every
``hvd/spmd/build`` span, and inside each the child rows the program keeps
of JAX's monitoring events — the seconds JAX spent tracing (the step's
Python, the models', the kernels' bodies), lowering (a Pallas kernel is
lowered to Mosaic there, a call site at a time), and compiling OR loading
(with the built program's ``cache_hits`` / ``cache_misses`` of the
persistent cache; a load's seconds are the cache's retrieval). What is
left of the span is the first call: the dispatch of the loaded program,
and whatever it waits for. ``compiles`` is the record's account of the
JAX programs outside any build. ``--root`` takes the program and the benchmark
from another checkout (the parent's, unpacked beside this one): its
compile cache is that checkout's own, so run a tree twice in one call for
a warm reading. A tree older than PR 36 records no parts: the tool says
so and exits 4.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import types

BUILD = "hvd/spmd/build"


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

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: this tool reads a chip's set-up", file=sys.stderr)
        return 3
    run.configure_jax(args.rehearse)

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

    record = timeline.session().record()
    if "compiles" not in record:
        print(f"{root} records no parts of a build (a tree older than "
              f"PR 36): nothing to split", file=sys.stderr)
        session.release()
        return 4
    builds = []
    spans = [s for s in record["spans"] if s[0] == BUILD]
    # every build opened one program's record, in this order
    for (_, start, end, _), program in zip(spans,
                                           record["programs"].values()):
        inside = [r for r in record["spans"] if r[0].startswith(BUILD + "/")
                  and start <= r[1] and r[2] <= end]
        # the program drops a tracing nested in another: plain sums
        part = {p: sum((r[2] - r[1]) / 1e9 for r in inside
                       if r[0] == f"{BUILD}/{p}")
                for p in ("trace", "lower", "compile", "load")}
        row = {"build_s": (end - start) / 1e9, "trace_s": part["trace"],
               "lower_s": part["lower"],
               "compile_or_load_s": part["compile"] + part["load"],
               "cache_retrieval_s": part["load"],
               "cache_hits": program["counters"]["build.cache_hits"],
               "cache_misses": program["counters"]["build.cache_misses"]}
        row["first_call_and_rest_s"] = row["build_s"] - sum(
            row[k] for k in ("trace_s", "lower_s", "compile_or_load_s"))
        row["largest"] = sorted(
            ([r[0].rsplit("/", 1)[1], round((r[2] - r[1]) / 1e9, 3)]
             for r in inside), key=lambda r: -r[1])[:4]
        builds.append(row)
    print(json.dumps({"cell": args.cell, "root": root, "seed": args.seed,
                      "setup_s": setup_s, "builds": builds,
                      "compiles": record["compiles"]}), flush=True)
    session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
