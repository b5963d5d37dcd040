"""The routed pairs of an expert-layer cell step by step, on the chip:

    python3 benchmark/tests/dump_pairs.py <cell> <seed> [steps]

One line a step after the three followed ones: the step's (token, choice)
pairs routed to the experts held here, all expert layers together, and
the fullest (layer, expert)'s — what ``end_to_end`` writes into the
program's record as a mean over the window, here as the window's COURSE:
the steps train, and a router's choices move with its weights. No window
is timed and no reference runs.
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
    rehearse = "--rehearse" in sys.argv
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    cell_name, seed = args[0], int(args[1])
    steps = int(args[2]) if len(args) > 2 else 60
    _, cell, config, traffic = harness.load_cell(cell_name, rehearse)
    harness.configure_jax(rehearse)
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, chips=cell["chips"],
        seeded=harness.load_module("seeded"),
        reference=harness.load_module("reference", config["runner"]),
        readings=harness.load_module("readings"), rehearse=rehearse,
        say=harness.say, t0=time.perf_counter())
    session = harness.load_module("runners", config["runner"]).setup(ctx)
    for k in range(steps):
        session.finish(session.dispatch(session.next_batch + k))
        pairs = session.pairs[-1]  # (ranks, expert layers, held)
        print(json.dumps({"step": session.next_batch + k + 1,
                          "pairs": int(pairs[0].sum()),
                          "by_layer": pairs[0].sum(axis=1).tolist(),
                          "max_expert_pairs": int(pairs[0].max())}),
              flush=True)
    session.release()


if __name__ == "__main__":
    main()
