"""How many top-k choices the bfloat16 program makes otherwise than in
float32: ``python3 benchmark/tests/dump_choices.py <cell> <seed>...``
(``--rehearse`` for the tiny preset on the CPU). A top-k is discontinuous:
near a tie the rounding of the activations ahead of the router picks
another expert. For each seed the cell's model is applied twice to pool
batch 0 on the seed's weights — as the cell runs it (bfloat16 activations
and matmuls) and in float32 at ``highest`` precision, which is what the
plain reference computes (``tests/test_moe_lm.py`` holds the two to 1e-6)
— and the tokens' choices (``transformer.EXPERT_CHOICES``) are compared
layer by layer. One JSON line a seed: the share of (token, layer) sets
that differ, of (token, layer, choice) entries, the same among the entries
held here, and by layer."""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402  benchmark/run.py


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    rehearse = "--rehearse" in sys.argv
    _, cell, config, traffic = harness.load_cell(args[0], rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    harness.configure_jax(rehearse)
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel import sequence

    seeded = harness.load_module("seeded")
    runner = harness.load_module("runners", config["runner"])
    reference = harness.load_module("reference", config["runner"])
    hvd.init(devices=jax.devices()[:1])
    mcfg = runner.model_config(config)
    specs = reference.leaf_specs(config)
    make = jax.jit(lambda k: runner._to_tree(seeded.leaves(k, specs)))
    first, held = mcfg.moe.first, mcfg.moe.held

    def choices(cfg):
        def apply(params, toks):
            _, sown = transformer.Transformer(cfg).apply(
                {"params": params}, toks, return_hidden=True,
                return_passes=True, mutable=[transformer.EXPERT_CHOICES])
            layers = sown[transformer.EXPERT_CHOICES]
            return [layers[b]["moe"]["idx"][0] for b in sorted(layers)]
        return jax.jit(apply)

    as_run = choices(mcfg)
    in_f32 = choices(mcfg._replace(dtype=jnp.float32))
    for seed in (int(a) for a in args[1:]):
        params = make(seeded.key(seed))
        toks = jnp.asarray(seeded.lm_tokens(
            seed, 0, 0, traffic["batch_per_chip"], traffic["seq_len"],
            config["vocab_size"]))
        got = [np.sort(np.asarray(a), axis=1) for a in as_run(params, toks)]
        # (float32 attention is the blockwise one: the Pallas kernel takes
        # bfloat16 operands, which Mosaic refuses at ``highest``)
        kernel, sequence.local_attention_impl = \
            sequence.local_attention_impl, lambda t: "blockwise"
        try:
            with jax.default_matmul_precision("highest"):
                want = [np.sort(np.asarray(a), axis=1)
                        for a in in_f32(params, toks)]
        finally:
            sequence.local_attention_impl = kernel
        sets = [np.any(g != w, axis=1).mean() for g, w in zip(got, want)]
        entries, here = [], []
        for g, w in zip(got, want):
            gone = np.array([len(set(a) - set(b)) for a, b in zip(w, g)])
            entries.append(gone.sum() / w.size)
            mine = (w >= first) & (w < first + held)
            lost = np.array([len((set(a[m]) - set(b))) for a, b, m in
                             zip(w, g, mine)])
            here.append(lost.sum() / max(mine.sum(), 1))
        print(json.dumps({
            "cell": args[0], "seed": seed,
            "device": jax.devices()[0].device_kind,
            "sets_differ": float(np.mean(sets)),
            "entries_differ": float(np.mean(entries)),
            "held_entries_differ": float(np.mean(here)),
            "sets_differ_by_layer": [round(float(s), 5) for s in sets]}),
            flush=True)
        del params
    hvd.shutdown()


if __name__ == "__main__":
    main()
