"""The upper readings alone, for more seeds than ``calibrate.py``'s control
seeds: ``python3 benchmark/tests/dump_control.py <cell> <variant,...>
<seed>...`` (``--rehearse`` on the CPU). For each seed the plain reference
and the named variants of it (``fp8``, a planted fault) put in the
program's place, compared as ``compare.py`` compares a run: one JSON line a
seed. No program runs, so a seed costs the reference's time a variant."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402  benchmark/run.py


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    rehearse = "--rehearse" in sys.argv
    _, cell, config, traffic = harness.load_cell(args[0], rehearse)
    import jax

    harness.configure_jax(rehearse)
    seeded = harness.load_module("seeded")
    compare = harness.load_module("compare")
    reference = harness.load_module("reference", config["runner"])
    used = jax.devices()[:cell["chips"]]
    for seed in (int(a) for a in args[2:]):
        t0 = time.perf_counter()
        expected = reference.run(config, traffic, seed, cell["chips"],
                                 seeded, devices=used)
        line = {"cell": args[0], "seed": seed,
                "device": jax.devices()[0].device_kind}
        for variant in args[1].split(","):
            got = reference.run(config, traffic, seed, cell["chips"], seeded,
                                variant=variant, devices=used)
            line[variant] = {k: v[0] for k, v in
                             compare.numbers(got, expected).items()}
        line["s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
