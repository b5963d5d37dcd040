"""Take one traced run's device events home: ``python3
benchmark/tests/dump_events.py --workload <cell> --seed <n> --seconds <s>``
runs the cell as ``run.py --trace 1 --keep-trace`` does (its result line
comes first), then writes ``chiprun_out/events_<cell>.json.gz``:
``{"steps", "scopes": the step program's scope map, "devices": {plane:
[[instruction, opcode, start_us, end_us], ...]}}`` — ``Trace.raw`` with the
instruction's text cut to its name. What ``scopes.self_us`` and the
readers make of a capture can then be looked into off the chip."""

import gzip
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    run = _load("run")
    argv = sys.argv[1:] + ["--trace", "1", "--keep-trace"]
    rc = run.main(argv)
    if rc:
        return rc
    cell = argv[argv.index("--workload") + 1]
    scopes, trace = _load("scopes"), _load("trace")
    program = scopes.step_program(scopes.record()) or {}
    root = os.path.dirname(BENCH)
    tr = trace.Trace.load(os.path.join(root, ".bench_trace", cell))
    out = {"steps": program.get("dispatches", 0) - 3,  # set-up's came first
           "scopes": program.get("scopes"),
           "devices": {device: [[scopes.instr_key(n), b, s, e]
                                for n, b, s, e in events]
                       for device, events in tr.raw.items()}}
    path = os.path.join(root, "chiprun_out", f"events_{cell}.json.gz")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(out, f)
    print(json.dumps({"events": path, "bytes": os.path.getsize(path)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
