"""Two sweeps on the real chip for the MLA / expert-layer configuration
(PERF.md, PR 31), methodology as tools/fa_sweep.py (device time from the
profiler's xplane, ``core/xprof.timed_steps``).

Usage: python tools/moe_sweep.py [gmm|flash|both|--layer [extent|block]]

* ``gmm``: the grouped matrix product of the routed experts, 8 groups x
  (K 2048, N 1536), bfloat16, over a row buffer of static size M with R
  real rows — ``jax.lax.ragged_dot`` against the Pallas grouped matmul
  (``jax.experimental.pallas.ops.tpu.megablox``), forward alone and
  forward + backward (both operands' gradients). A form whose time follows
  M and not R pays for rows nobody routed here.
* ``flash``: ``ops/flash_attention.py`` at D = 256, 20 heads, T = 8192,
  full causal, over forward and backward block sizes (a ``flash_fb`` point
  is a forward at 512 x 512, 7.88 ms, and the backward under test).

* ``--layer``: the whole routed part of one expert layer
  (``ops/moe.routed_experts``: dispatch, the grouped products, the gated
  activation, combine), 8,192 tokens x top-4 = a 32,768-row buffer, 8
  held experts of (2048, 1536), bfloat16, forward alone and forward +
  backward, at R routed rows in {0, 512, 4,096, 8,192, 16,384, 32,768} —
  the form before PR 32 (gathers, masks and the activation over the whole
  buffer: kept here, for the comparison alone) beside the committed one —
  and the committed form over ``ops/moe.ROW_BLOCK`` at R = 5,200 (the
  benchmark cell's routed rows a layer; no block's multiple).

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

MODE = sys.argv[1].lstrip("-") if len(sys.argv) > 1 else "both"
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


def _whole_buffer_part(x, idx, gates, wg, wu, wd):
    """The routed part as it was before PR 32: one gather, two masks, the
    gated activation and ``top_k`` gathers back, each over every row of
    the worst-case buffer (the grouped products alone follow the routed
    rows). For the comparison; ``ops/moe.py`` holds no such form."""
    from horovod_tpu.ops import moe

    n, k = idx.shape
    held = wg.shape[0]
    here = (idx >= 0) & (idx < held)
    key = jnp.where(here, idx, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    slot = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    pairs = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    weight = jnp.where(here, gates, 0.0)
    wg, wu, wd = (w.astype(x.dtype) for w in (wg, wu, wd))

    @jax.checkpoint
    def part(x, weight, wg, wu, wd):
        routed = (jnp.arange(n * k) < jnp.sum(pairs))[:, None]
        xs = jnp.where(routed, x[order // k], 0)
        h = jax.nn.silu(moe.grouped_matmul(xs, wg, pairs)) \
            * moe.grouped_matmul(xs, wu, pairs)
        ys = jnp.where(routed, moe.grouped_matmul(h, wd, pairs), 0)
        out = 0.0
        for j in range(k):
            out = out + weight[:, j, None] * ys[slot[:, j]].astype(
                jnp.float32)
        return out.astype(x.dtype)
    return part(x, weight, wg, wu, wd)


def layer_sweep(n=8192, e=2048, f=1536, parts=("extent", "block")):
    from horovod_tpu.ops import moe

    k, held, total = 4, 8, 64
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (n, e), jnp.bfloat16)
    wg, wu = (0.02 * jax.random.normal(kk, (held, e, f), jnp.float32)
              for kk in ks[1:3])
    wd = 0.02 * jax.random.normal(ks[3], (held, f, e), jnp.float32)
    gates = jax.random.uniform(ks[4], (n, k), jnp.float32, 0.1, 1.0)
    probe = jax.random.normal(ks[5], (n, e), jnp.bfloat16)

    def case(routed):
        """``routed`` of the 32,768 pairs sent to the 8 held experts."""
        mine = jax.random.permutation(ks[6], n * k) < routed
        there = jax.random.randint(ks[7], (n * k,), 0, total - held)
        return jnp.where(mine, there % held, held + there).astype(
            jnp.int32).reshape(n, k)

    def committed(x, idx, gates, wg, wu, wd):
        return moe.routed_experts(x, idx, gates, wg, wu, wd)[0]

    def point(form, fn, routed, **more):
        idx = case(routed)
        loss = lambda x, gates, wg, wu, wd: jnp.sum(
            (probe * fn(x, idx, gates, wg, wu, wd)).astype(jnp.float32))
        for kind, run in (
                ("fwd", chained(lambda x, *a: fn(x, idx, *a))),
                ("fb", chained(jax.grad(loss, argnums=(0, 1, 2, 3, 4))))):
            try:
                t = timeit(run, x, gates, wg, wu, wd)
                say(kind="layer_" + kind, form=form, routed=routed,
                    ms=round(t * 1e3, 3), **more)
            except Exception as err:  # noqa: BLE001
                say(kind="layer_" + kind, form=form, routed=routed,
                    err=str(err)[-300:], **more)

    rows = n * k  # 32,768 at the cell's size
    for routed in (0, rows // 64, rows // 8, rows // 4, rows // 2,
                   rows) if "extent" in parts else ():
        point("whole_buffer", _whole_buffer_part, routed)
        point("routed_rows", committed, routed, row_block=moe.ROW_BLOCK)
    kept = moe.ROW_BLOCK
    for block in (256, 512, 1024, 2048, 4096) if "block" in parts else ():
        moe.ROW_BLOCK = block
        jax.clear_caches()  # the layer's traces were made under another
        point("routed_rows", committed, 325 * rows // 2048, row_block=block)
    moe.ROW_BLOCK = kept
    jax.clear_caches()


hvd.init(devices=jax.devices()[:1])
say(device=jax.devices()[0].device_kind)
if MODE in ("gmm", "both"):
    gmm_sweep()
if MODE in ("flash", "both"):
    flash_sweep()
if MODE == "layer":  # ``--layer extent`` / ``--layer block``: one part
    layer_sweep(parts=tuple(sys.argv[2:]) or ("extent", "block"))
