"""Measure a cell as the contract's ``bound`` rule asks, on the chip:

    python3 benchmark/tests/measure.py <cell> [sets] [runs] [traced]

``sets`` (2) sets of ``runs`` (6) runs with ``--trace 0``, the same seeds in
every set, then ``traced`` (3) runs with ``--trace 1`` on other seeds; each
run a process of its own (this parent never touches JAX). Every result line
goes to ``chiprun_out/measure_<cell>.jsonl``; at the end, for each metric
and set, the median and the spread (third minus first quartile of
``statistics.quantiles(values, n=4)``, as a share of the median).
"""

import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def main():
    cell = sys.argv[1]
    sets = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    runs = int(sys.argv[3]) if len(sys.argv) > 3 else 6
    traced = int(sys.argv[4]) if len(sys.argv) > 4 else 3
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = manifest["run_seconds"]
    base = 2_147_000_000 + (sum(map(ord, cell)) % 1000) * 1000
    seeds = [base + 7 * i for i in range(runs)]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", f"measure_{cell}.jsonl"),
               "a")
    plan = [(f"set{k}", s, 0) for k in range(sets) for s in seeds]
    plan += [("traced", base + 500 + i, 1) for i in range(traced)]
    values: dict = {}
    for tag, seed, trace in plan:
        t0 = time.perf_counter()
        proc = subprocess.run(
            manifest["command"] + ["--workload", cell, "--seed", str(seed),
                                   "--seconds", str(seconds), "--trace",
                                   str(trace)],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else "{}"
        try:
            result = json.loads(last)
        except json.JSONDecodeError:
            result = {}
        line = {"cell": cell, "tag": tag, "seed": seed, "rc": proc.returncode,
                "wall_s": wall, "result": result,
                "stderr_tail": [ln for ln in proc.stderr.splitlines()
                                if ln.startswith("[bench]")][-24:]}
        out.write(json.dumps(line) + "\n")
        out.flush()
        short = {k: v["value"] for k, v in result.get("metrics", {}).items()}
        print(tag, seed, "rc", proc.returncode, f"wall {wall:.0f}s",
              "correct", result.get("correct"), short, flush=True)
        if proc.returncode != 0 or not result.get("correct"):
            print(proc.stderr[-3000:], flush=True)
        for k, v in short.items():
            values.setdefault((k, tag), []).append(v)
    for (name, tag), vals in sorted(values.items()):
        if len(vals) < 3:
            continue
        q = statistics.quantiles(vals, n=4)
        med = statistics.median(vals)
        print(f"{name} {tag}: n={len(vals)} median {med:.6g} spread "
              f"{(q[2] - q[0]) / med:.5f} first {vals[0]:.6g} "
              f"min {min(vals):.6g} max {max(vals):.6g}")


if __name__ == "__main__":
    main()
