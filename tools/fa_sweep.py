"""Sweep flash-attention block configs on the real chip.

Usage: python tools/fa_sweep.py [T] [fwd|bwd|both]
Prints one JSON line per config; methodology as tools/fa_bench.py.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import flash_attention as fa

B, H, D = 1, 8, 128
T = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
MODE = sys.argv[2] if len(sys.argv) > 2 else "both"
STEPS = 10


def timeit(run, *args, trials=3):
    """Per-step DEVICE time from the profiler xplane — block-size deltas
    are smaller than host dispatch jitter; shared implementation in
    horovod_tpu.core.xprof.timed_steps."""
    from horovod_tpu.core import xprof

    float(run(*args))  # compile + warm
    return xprof.timed_steps(lambda: float(run(*args)), STEPS, trials)


def fwd_bench(attn, q, k, v):
    @jax.jit
    def run(q, k, v):
        def body(c, _):
            o = attn(c, k, v)
            return c + 0.0 * o, jnp.sum(o.astype(jnp.float32))
        c, s = lax.scan(body, q, None, length=STEPS)
        return jnp.sum(s)
    return timeit(run, q, k, v)


def grad_bench(attn, q, k, v):
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))
    g = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        def body(c, _):
            dq, dk, dv = g(c, k, v)
            s = (jnp.sum(dq.astype(jnp.float32))
                 + jnp.sum(dk.astype(jnp.float32))
                 + jnp.sum(dv.astype(jnp.float32)))
            return c + 0.0 * dq, s
        c, s = lax.scan(body, q, None, length=STEPS)
        return jnp.sum(s)
    return timeit(run, q, k, v)


key = jax.random.PRNGKey(0)
q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
           for kk in jax.random.split(key, 3))

fwd_flops = 2 * 2 * B * H * T * T * D / 2
fb_flops = 7 * 2 * B * H * T * T * D / 2

if MODE in ("fwd", "both"):
    for bq, bk in [(1024, 1024), (2048, 2048), (1024, 2048), (2048, 1024)]:
        try:
            t = fwd_bench(lambda q, k, v: fa.flash_attention(
                q, k, v, True, block_q=bq, block_k=bk), q, k, v)
            print(json.dumps({"kind": "fwd", "bq": bq, "bk": bk,
                              "ms": round(t * 1e3, 2),
                              "tflops": round(fwd_flops / t / 1e12, 1)}),
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"kind": "fwd", "bq": bq, "bk": bk,
                              "err": str(e)[:120]}), flush=True)

if MODE in ("bwd", "both"):
    for bq, bkc, bm in [(512, 1024, 4096), (512, 2048, 4096),
                        (1024, 2048, 4096), (512, 2048, 2048)]:
        if bm % bkc or bm > T:
            continue
        try:
            t = grad_bench(lambda q, k, v: fa.flash_attention(
                q, k, v, True, block_q_bwd=bq, block_k_bwd=bkc,
                block_kv_mem=bm), q, k, v)
            print(json.dumps({"kind": "fb", "bq": bq, "bkc": bkc, "bm": bm,
                              "ms": round(t * 1e3, 2),
                              "tflops": round(fb_flops / t / 1e12, 1)}),
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"kind": "fb", "bq": bq, "bkc": bkc, "bm": bm,
                              "err": str(e)[:120]}), flush=True)
