"""Read what the limits are set from, on the chip, in one process:

    python3 benchmark/tests/calibrate.py <cell> <seeds> <control seeds>

For each of ``seeds`` seeds (drawn from a fixed stream, none a run's own):
the program's first three steps against the plain reference — the lower
readings. For the first ``control seeds`` of them also the control (the
reference in float8, put in the program's place) and the planted faults
(half of the batch left out; the state left unchanged, where the
reference has that variant; on several chips, the exchange left out) — the
upper readings. One JSON line a seed to ``chiprun_out/calibrate_<cell>.jsonl``
and to standard output. No window is measured.
"""

import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402  benchmark/run.py


def main():
    cell_name, n_seeds, n_control = sys.argv[1], int(sys.argv[2]), \
        int(sys.argv[3])
    rehearse = "--rehearse" in sys.argv
    _, cell, config, traffic = harness.load_cell(cell_name, rehearse)
    chips = cell["chips"]
    import jax

    harness.configure_jax(rehearse)
    seeded = harness.load_module("seeded")
    compare = harness.load_module("compare")
    runner = harness.load_module("runners", config["runner"])
    reference = harness.load_module("reference", config["runner"])
    used = jax.devices()[:chips]
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    tag = ".rehearsal" if rehearse else ""
    out = open(os.path.join(out_dir, f"calibrate_{cell_name}{tag}.jsonl"),
               "a")
    seeds = [int(s) for s in seeded.rng(20260930, 7).integers(
        1, 2 ** 31 + 2 ** 20, size=n_seeds)]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ctx = types.SimpleNamespace(
            config=config, traffic=traffic, seed=seed, chips=chips,
            seeded=seeded, reference=reference,
            readings=harness.load_module("readings"), rehearse=rehearse,
            say=harness.say, t0=t0)
        session = runner.setup(ctx)
        observed = session.observed
        session.release()
        del session
        t1 = time.perf_counter()
        expected = reference.run(config, traffic, seed, chips, seeded,
                                 devices=used)
        t2 = time.perf_counter()
        line = {"cell": cell_name, "seed": seed,
                "device": jax.devices()[0].device_kind,
                "program": {k: v[0] for k, v in
                            compare.numbers(observed, expected).items()},
                "program_at": {k: v[1] for k, v in
                               compare.numbers(observed, expected).items()},
                "program_s": t1 - t0, "reference_s": t2 - t1}
        if i < n_control:
            variants = [v for v in reference.VARIANTS[1:]
                        if v != "no_exchange" or chips > 1]
            for variant in variants:
                got = reference.run(config, traffic, seed, chips, seeded,
                                    variant=variant, devices=used)
                line[variant] = {k: v[0] for k, v in
                                 compare.numbers(got, expected).items()}
            line["variants_s"] = time.perf_counter() - t2
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()


if __name__ == "__main__":
    main()
