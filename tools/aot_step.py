"""Compile a benchmark cell's training step for v5e WITHOUT a chip and say
what came out: ``python tools/aot_step.py <cell> [<cell> ...] [--root DIR]
[--text FILE] [--lowered]``.

The step is the one the cell's runner builds — ``hvd.init`` →
``hvd.DistributedOptimizer(ops/optim.adamw)`` → ``hvd.spmd`` around the
runner's ``TransformerConfig`` at the cell's sizes — lowered on abstract
arguments for a ``v5e:2x2`` topology's first chip(s) (``jax.experimental.
topologies``: the installed libtpu compiles for a TPU from the CPU
sandbox). One line a cell: the optimized text's lines, Pallas calls and
``while`` loops, the fusions that hold half a rotary head
(``half_head_fusions``: an output of (..., T, H > 1, D/2), D the rotary
width), the compile's memory (arguments and temporaries) and an
md5 of the text less what names a checkout and not the program
(``metadata=``, the location tables, the Pallas bodies) — two trees whose
digests agree run the same instructions. ``--root`` takes the program and
the benchmark from another checkout (the parent's, unpacked beside this
one); ``--text`` writes the last cell's text there (stripped; whole with
``--lowered``). ``--lowered``
stops before the compiler: the seconds tracing and lowering took here and
an md5 of the WHOLE lowered text, which is what the compile cache's key is
made of — two processes of one tree must print the same. Sizes and
instructions only: a time comes from the chip.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import re
import sys
import time


def _without(text: str, opener: str) -> str:
    """``text`` less every ``opener{...}`` group, its braces balanced."""
    out, at = [], 0
    while True:
        start = text.find(opener + "{", at)
        if start < 0:
            return "".join(out) + text[at:]
        out.append(text[at:start])
        depth, at = 0, start + len(opener)
        while True:
            depth += {"{": 1, "}": -1}.get(text[at], 0)
            at += 1
            if depth == 0:
                break


def stripped(text: str) -> str:
    """The optimized text less what names a checkout and not the program:
    ``metadata=``, a Pallas call's body and its ``kernel_metadata`` (both
    carry source paths), the location tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'backend_config="(?:[^"\\]|\\.)*"', 'backend_config=""',
                  text)
    text = _without(text, "frontend_attributes=")
    return "\n".join(
        _without(line, "backend_config=") if "tpu_custom_call" in line
        else line for line in text.splitlines()
        if not re.match(r"\s*(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames|\d+ [\"{])", line))


def model_config(runner, cfg: dict):
    """The runner's ``TransformerConfig`` (the plain LM's runner builds
    its own inline)."""
    if hasattr(runner, "model_config"):
        return runner.model_config(cfg)
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    return transformer.TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], dtype=jnp.bfloat16,
        attention="local", window=cfg["sliding_window"])


def lower_cell(root: str, name: str):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.core.state import AXIS_NAME
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import optim

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell = next(c for c in json.load(f)["workloads"]
                    if c["name"] == name)
    load = lambda *parts: json.load(
        open(os.path.join(root, "benchmark", *parts)))
    cfg = load("configs", cell["config"] + ".json")
    traffic = load("traffic", cell["traffic"] + ".json")
    runner = importlib.import_module("benchmark.runners." + cfg["runner"])
    mcfg = model_config(runner, cfg)
    n = cell["chips"]
    hvd.shutdown()
    hvd.init(devices=topologies.get_topology_desc(
        "v5e:2x2", platform="tpu").devices[:n])
    o = traffic["optimizer"]
    opt = hvd.DistributedOptimizer(optim.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"]))
    kwargs = {"fused_head": traffic["fused_head"]}
    if mcfg.moe is not None:
        kwargs["with_expert_pairs"] = True
    if mcfg.exit_gate:
        kwargs["exit_beta"] = cfg["exit_entropy_beta"]
    loss_fn = transformer.make_loss_fn(mcfg, **kwargs)

    def train_step(p, s, toks):
        out, grads = jax.value_and_grad(
            loss_fn, has_aux=mcfg.moe is not None)(p, toks)
        loss, rest = out if mcfg.moe is not None else (out, None)
        updates, s = opt.update(grads, s, p)
        done = (optax.apply_updates(p, updates), s, hvd.allreduce(loss))
        return done if rest is None else done + (rest,)

    shard = NamedSharding(hvd.get_group(0).mesh, P(AXIS_NAME))
    stacked = lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                             sharding=shard)
    shapes = jax.eval_shape(lambda: transformer.init_params(mcfg))
    params = jax.tree.map(stacked, shapes)
    state = jax.tree.map(stacked, jax.eval_shape(opt.init, shapes))
    tokens = stacked(jax.ShapeDtypeStruct(
        (traffic["batch_per_chip"], traffic["seq_len"]), jnp.int32))
    lowered = hvd.spmd(train_step, donate_argnums=(0, 1)).lower(
        params, state, tokens)
    hvd.shutdown()
    rotary = (mcfg.mla.rope_dim if mcfg.mla is not None
              else mcfg.head_dim or mcfg.embed_dim // mcfg.num_heads)
    return lowered, (traffic["seq_len"], rotary // 2)


def half_head_fusions(text: str, seq_len: int, half: int) -> int:
    """The top-level fusions one of whose outputs is a (..., T, H > 1,
    half) tensor: half of a rotary head, held in HBM between passes."""
    count = 0
    for shape in re.findall(r"= (\S.*?) fusion\(", text):
        for dims in re.findall(r"\w+\[([\d,]+)\]", shape):
            d = [int(v) for v in dims.split(",")]
            if len(d) >= 3 and d[-3] == seq_len and d[-2] > 1 \
                    and d[-1] == half:
                count += 1
                break
    return count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--text")
    ap.add_argument("--lowered", action="store_true")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for name in args.cells:
        t0 = time.perf_counter()
        lowered, (seq_len, half) = lower_cell(root, name)
        if args.lowered:
            text = lowered.as_text()
            print(json.dumps({
                "cell": name, "root": root,
                "trace_and_lower_s": round(time.perf_counter() - t0, 1),
                "lowered_md5": hashlib.md5(text.encode()).hexdigest()}),
                flush=True)
            continue
        compiled = lowered.compile()
        text = stripped(compiled.as_text())
        mem = compiled.memory_analysis()
        gb = lambda b: round(b / 1e9, 3)
        print(json.dumps({
            "cell": name, "root": root, "lines": text.count("\n") + 1,
            "md5": hashlib.md5(text.encode()).hexdigest(),
            "pallas_calls": text.count('custom_call_target="tpu_custom_call"'),
            "whiles": len(re.findall(r"= [^\n]* while\(", text)),
            "half_head_fusions": half_head_fusions(text, seq_len, half),
            "argument_gb": gb(mem.argument_size_in_bytes),
            "temp_gb": gb(mem.temp_size_in_bytes)}), flush=True)
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
