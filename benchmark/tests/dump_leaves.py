"""What a limit could be set from, leaf by leaf, on the chip in one process:

    python3 benchmark/tests/dump_leaves.py <cell> <variant>[,<variant>...] <seed> [<seed> ...]
        [--rehearse]

For every seed: the program's first steps, the plain reference's, and the
reference under each named variant (``fp8``: the control), each as the whole
``{"loss", "grad_norm", "change_norm"}`` that ``compare.numbers`` takes — so
that a number ``calibrate.py`` reads only at its worst leaf can be looked at
on every leaf, and a candidate rule tried on what was read without another
run. One JSON line a seed to ``chiprun_out/leaves_<cell>.jsonl``.
"""

import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402  benchmark/run.py


def _plain(tree):
    return json.loads(json.dumps(tree, default=float))


def main():
    cell_name, variants = sys.argv[1], sys.argv[2].split(",")
    rehearse = "--rehearse" in sys.argv
    seeds = [int(s) for s in sys.argv[3:] if s != "--rehearse"]
    _, cell, config, traffic = harness.load_cell(cell_name, rehearse)
    chips = cell["chips"]
    import jax

    harness.configure_jax(rehearse)
    seeded = harness.load_module("seeded")
    runner = harness.load_module("runners", config["runner"])
    reference = harness.load_module("reference", config["runner"])
    used = jax.devices()[:chips]
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"leaves_{cell_name}.jsonl"), "a")
    for seed in seeds:
        t0 = time.perf_counter()
        ctx = types.SimpleNamespace(
            config=config, traffic=traffic, seed=seed, chips=chips,
            seeded=seeded, reference=reference,
            readings=harness.load_module("readings"), rehearse=rehearse,
            say=harness.say, t0=t0)
        session = runner.setup(ctx)
        line = {"cell": cell_name, "seed": seed,
                "program": _plain(session.observed)}
        session.release()
        del session
        line["reference"] = _plain(reference.run(
            config, traffic, seed, chips, seeded, devices=used))
        for variant in variants:
            line[variant] = _plain(reference.run(
                config, traffic, seed, chips, seeded, variant=variant,
                devices=used))
        line["seconds"] = time.perf_counter() - t0
        out.write(json.dumps(line) + "\n")
        out.flush()
        harness.say(f"seed {seed}: {line['seconds']:.0f} s")


if __name__ == "__main__":
    main()
