"""Two sweeps on the real chip for the MLA / expert-layer configuration
(PERF.md, PR 31), methodology as tools/fa_sweep.py (device time from the
profiler's xplane, ``core/xprof.timed_steps``).

Usage: python tools/moe_sweep.py [gmm|flash|both]

* ``gmm``: the grouped matrix product of the routed experts, 8 groups x
  (K 2048, N 1536), bfloat16, over a row buffer of static size M with R
  real rows — ``jax.lax.ragged_dot`` against the Pallas grouped matmul
  (``jax.experimental.pallas.ops.tpu.megablox``), forward alone and
  forward + backward (both operands' gradients). A form whose time follows
  M and not R pays for rows nobody routed here.
* ``flash``: ``ops/flash_attention.py`` at D = 256, 20 heads, T = 8192,
  full causal, over forward and backward block sizes (a ``flash_fb`` point
  is a forward at 512 x 512, 7.88 ms, and the backward under test).

Prints one JSON line per point.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import horovod_tpu as hvd
from horovod_tpu.ops import flash_attention as fa

MODE = sys.argv[1] if len(sys.argv) > 1 else "both"
STEPS = 10


def say(**row):
    print(json.dumps(row), flush=True)


def timeit(run, *args, trials=3):
    from horovod_tpu.core import xprof

    float(run(*args))  # compile + warm
    return xprof.timed_steps(lambda: float(run(*args)), STEPS, trials)


def chained(fn, carry_index=0):
    """``STEPS`` calls of ``fn(*args)`` in one program, each depending on
    the one before through argument ``carry_index``."""
    @jax.jit
    def run(*args):
        def body(c, _):
            a = list(args)
            a[carry_index] = c
            outs = fn(*a)
            outs = outs if isinstance(outs, tuple) else (outs,)
            s = sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
            return c + (0.0 * s).astype(c.dtype), s
        _, s = lax.scan(body, args[carry_index], None, length=STEPS)
        return jnp.sum(s)
    return run


def gmm_sweep():
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    g, k, n = 8, 2048, 1536
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (g, k, n), jnp.bfloat16) * 0.02

    def ragged(x, w, sizes):
        return lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.bfloat16)

    def megablox(tiling):
        return lambda x, w, sizes: mb.gmm(x, w, sizes, jnp.bfloat16, tiling)

    forms = [("ragged_dot", ragged)] + [
        (f"megablox{t}", megablox(t))
        for t in [(128, 128, 128), (256, 1024, 512), (512, 1024, 512),
                  (512, 2048, 512), (256, 2048, 768), (512, 512, 1536)]]
    for m, real in [(32768, 4096), (32768, 32768), (4096, 4096)]:
        x = jax.random.normal(jax.random.fold_in(key, m), (m, k),
                              jnp.bfloat16)
        sizes = jnp.full((g,), real // g, jnp.int32)
        flops = 2 * real * k * n
        for name, fn in forms:
            for kind in ("fwd", "fb"):
                if kind == "fwd":
                    run = chained(fn)
                    mult = 1
                else:
                    loss = lambda x, w, s, fn=fn: jnp.sum(
                        fn(x, w, s).astype(jnp.float32) ** 2)
                    run = chained(jax.grad(loss, argnums=(0, 1)))
                    mult = 3
                try:
                    t = timeit(run, x, w, sizes)
                    say(kind=kind, form=name, m=m, real=real,
                        ms=round(t * 1e3, 3),
                        tflops=round(mult * flops / t / 1e12, 1))
                except Exception as e:  # noqa: BLE001
                    say(kind=kind, form=name, m=m, real=real,
                        err=str(e)[-300:])


def flash_sweep():
    b, h, d, t = 1, 20, 256, 8192
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    fwd_flops = 2 * 2 * b * h * t * t * d / 2
    for bq, bk in [(512, 512), (1024, 512), (512, 1024), (1024, 1024),
                   (256, 512), (256, 1024), (2048, 512), (1024, 2048),
                   (2048, 1024)]:
        try:
            s = timeit(chained(lambda q, k, v: fa.flash_attention(
                q, k, v, True, block_q=bq, block_k=bk)), q, k, v)
            say(kind="flash_fwd", bq=bq, bk=bk, ms=round(s * 1e3, 3),
                tflops=round(fwd_flops / s / 1e12, 1))
        except Exception as e:  # noqa: BLE001
            say(kind="flash_fwd", bq=bq, bk=bk, err=str(e)[-300:])
    for bq, bkc, bm in [(512, 512, 2048), (512, 1024, 2048),
                        (512, 512, 4096), (512, 1024, 4096),
                        (256, 1024, 2048), (256, 512, 2048),
                        (1024, 512, 2048), (512, 1024, 1024),
                        (512, 2048, 2048), (256, 1024, 4096),
                        (512, 512, 8192), (512, 1024, 8192),
                        (256, 512, 4096), (256, 2048, 4096),
                        (256, 1024, 8192), (128, 1024, 4096)]:
        loss = lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, True, block_q=512, block_k=512, block_q_bwd=bq,
            block_k_bwd=bkc, block_kv_mem=bm).astype(jnp.float32))
        try:
            s = timeit(chained(jax.grad(loss, argnums=(0, 1, 2))), q, k, v)
            say(kind="flash_fb", bq=bq, bkc=bkc, bm=bm,
                ms=round(s * 1e3, 3),
                tflops=round(3.5 * fwd_flops / s / 1e12, 1))
        except Exception as e:  # noqa: BLE001
            say(kind="flash_fb", bq=bq, bkc=bkc, bm=bm, err=str(e)[-300:])


hvd.init(devices=jax.devices()[:1])
say(device=jax.devices()[0].device_kind)
if MODE in ("gmm", "both"):
    gmm_sweep()
if MODE in ("flash", "both"):
    flash_sweep()
