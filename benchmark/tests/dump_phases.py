"""Look at one traced run by phase: ``python3 benchmark/tests/dump_phases.py
--workload <cell> --seed <n> --seconds <s>`` runs the cell as ``run.py
--trace 1 --keep-trace`` does (its result line comes first), then prints
one JSON line: for every device the six phases' ms a step
(``scopes.PHASES``, self times), their sum beside the events' own
durations and the busy time, the collectives a step by phase (what the
device ran: XLA's combiner merges the plan's), and the instructions with
most ``mixed`` and ``other`` time with the phases their members carry —
what the scopes could not separate."""

import collections
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
    record = scopes.record()
    program = scopes.step_program(record)
    if not program or not program.get("scopes"):
        print(json.dumps({"phases": None, "why": "no scope map"}))
        return 0
    tr = trace.Trace.load(os.path.join(os.path.dirname(BENCH),
                                       ".bench_trace", cell))
    steps = program["dispatches"] - 3  # set-up's followed steps came first
    out = {"steps": steps, "devices": {}}
    for device, events in tr.raw.items():
        selfs = scopes.self_us(events)
        by, collectives = collections.Counter(), collections.Counter()
        worst = {"mixed": collections.Counter(),
                 "other": collections.Counter()}
        for (name, base, s, e), us in zip(events, selfs):
            key = scopes.instr_key(name)
            ph = scopes.phase_of(program["scopes"].get(key))
            by[ph] += us
            if trace.is_collective(base) and not base.endswith("-done"):
                collectives[ph] += 1
            if ph in worst:
                worst[ph][key] += us
        ms = lambda us: us / 1e3 / steps
        members = lambda key: sorted(collections.Counter(
            scopes.phase(n) for n in (program["scopes"].get(key) or
                                      ["", []])[1]).items())
        out["devices"][device] = {
            "ms_per_step": {p: ms(by[p]) for p in scopes.PHASES},
            "sum_ms": ms(sum(by.values())),
            "own_durations_ms": ms(sum(e - s for _, _, s, e in events)),
            "busy_ms": ms(tr.busy_us()[device]),
            "collectives_per_step": {p: n / steps
                                     for p, n in collectives.items()},
            **{f"top_{ph}": [[k, ms(us), members(k)]
                             for k, us in worst[ph].most_common(8)]
               for ph in worst}}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
