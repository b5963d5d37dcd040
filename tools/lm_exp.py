"""LM-step experiment harness: device-timed variants of the bench LM.

Builds the bench.py lm_t8k step at B=1 (8 layers, GQA 8q/4kv, T=8192,
fused AdamW, flash attention, unrolled fused CE head) with one knob
changed per variant and reports device-true ms/step for each — the
measurement loop behind round-5's "close the LM gap" work. Variants:

  base        bench.py defaults at B=1 (chunk=8192 unrolled CE,
              ops/optim.py AdamW with bf16 moments)
  chunk8k     CE chunk 8192 (same as base since r5 — kept as a control)
  chunk16k    CE chunk 16384 (2 chunks)
  bf16mom     optax.adamw with bf16 FIRST moment only (mu_dtype)
  optaxadam   optax.adamw, fp32 moments (the pre-r5 baseline optimizer)
  autolayout  XLA-chosen (AUTO) entry layouts for the donated state
  bN / bN+auto  batch size N (e.g. b2, b4), optionally with autolayout

Unknown variant names raise (a typo must not silently measure base).

Usage: python tools/lm_exp.py [--variants base,chunk16k,...] [--steps 5]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from horovod_tpu.core import xprof
from horovod_tpu.models import transformer


def make_multi_step(opt, loss_fn, steps):
    """The un-jitted K-step scanned train step — the ONE definition every
    LM measurement tool compiles (variants differ only in jit options),
    so cross-variant comparisons always measure the same program."""

    def multi_step(params, opt_state, tokens):
        def body(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), None, length=steps)
        return params, opt_state, losses[-1]

    return multi_step


def build_step(opt, loss_fn, steps):
    return jax.jit(make_multi_step(opt, loss_fn, steps),
                   donate_argnums=(0, 1))


def run_variant(name: str, steps: int) -> float:
    cfg = transformer.TransformerConfig(
        vocab_size=32_768, num_layers=8, num_heads=8, num_kv_heads=4,
        embed_dim=1024, mlp_dim=4096, max_seq_len=8192,
        dtype=jnp.bfloat16, attention="local")
    KNOWN = {"base", "chunk8k", "chunk16k", "bf16mom", "optaxadam",
             "autolayout"}
    B, T = 1, 8192
    autolayout = name == "autolayout"
    if name.startswith("b") and name[1:].split("+")[0].isdigit():
        B = int(name[1:].split("+")[0])
        autolayout = name.endswith("+auto")
    elif name not in KNOWN:
        raise SystemExit(f"unknown variant {name!r}; see the module "
                         f"docstring for the variant table")
    params = transformer.init_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0,
                                cfg.vocab_size, jnp.int32)

    chunk = None
    from horovod_tpu.ops import optim

    opt = optim.adamw(3e-4, weight_decay=0.1)  # the bench.py optimizer
    if name == "chunk8k":
        chunk = 8192
    elif name == "chunk16k":
        chunk = 16384
    elif name == "bf16mom":
        opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    elif name == "optaxadam":
        opt = optax.adamw(3e-4, weight_decay=0.1)

    if chunk is None:
        loss_fn = transformer.make_loss_fn(cfg, fused_head=True)
    else:
        from horovod_tpu.ops.losses import fused_cross_entropy

        model = transformer.Transformer(cfg)

        def loss_fn(params, tokens, _chunk=chunk):
            hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
            w = params["lm_head"]["kernel"].astype(cfg.dtype)
            x2 = hidden[:, :-1].reshape(-1, hidden.shape[-1])
            tgt = tokens[:, 1:].reshape(-1)
            return fused_cross_entropy(x2, w, tgt, chunk=_chunk)

    opt_state = opt.init(params)
    if autolayout:
        # XLA-chosen entry layouts for the donated training state: the
        # loop-carried lm_head kernel + moments otherwise relayout
        # {1,0}<->{0,1} at the while-loop boundary every step
        # (tools/lm_copies.py, r5).
        from jax.experimental.layout import Format, Layout

        jitted = jax.jit(make_multi_step(opt, loss_fn, steps),
                         donate_argnums=(0, 1),
                         in_shardings=Format(Layout.AUTO),
                         out_shardings=Format(Layout.AUTO))
        shapes = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
            (params, opt_state, tokens))
        compiled = jitted.lower(*shapes).compile()
        fmts = compiled.input_formats[0]
        params, opt_state, tokens = jax.tree.map(
            jax.device_put, (params, opt_state, tokens), fmts)
        step = compiled
    else:
        step = build_step(opt, loss_fn, steps)
    params, opt_state, loss = step(params, opt_state, tokens)
    float(np.asarray(loss))
    state = {"p": params, "o": opt_state}

    def run_once():
        state["p"], state["o"], loss = step(state["p"], state["o"], tokens)
        float(np.asarray(loss))

    t = xprof.timed_steps(run_once, steps, 3)
    return t * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default="base,chunk8k,chunk16k,bf16mom")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    for name in args.variants.split(","):
        try:
            ms = run_variant(name.strip(), args.steps)
            print(f"{name:14s} {ms:8.2f} ms/step", flush=True)
        except Exception as e:
            print(f"{name:14s} FAILED: {e}", flush=True)


if __name__ == "__main__":
    main()
