"""Flash-attention kernel benchmark (run on the real chip).

Methodology notes:
* STEPS chained inside one jitted ``lax.scan`` — a single dispatched call
  of a millisecond kernel is dominated by host dispatch latency.
* Only scalars cross to the host — ``np.asarray(out)`` on a (B,T,H,D)
  tensor copies tens of MB to the host and swamps the kernel time.
* All three gradients are consumed — the dk/dv pallas pass is dead code
  to XLA otherwise and gets eliminated.

Usage: python fa_bench.py [T]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import flash_attention as fa

B, H, D = 1, 8, 128
T = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
STEPS = 10


def timeit(run, *args, calls=2, trials=3):
    out = run(*args)
    float(out)
    best = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = run(*args)
        float(out)
        best = min(best, (time.perf_counter() - t0) / calls / STEPS)
    return best


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

t_flash = grad_bench(lambda q, k, v: fa.flash_attention(q, k, v, True),
                     q, k, v)
t_block = grad_bench(lambda q, k, v: fa.blockwise_attention(q, k, v, True),
                     q, k, v)
# Causal fwd+bwd FLOPs: 2 fwd + 5 bwd matmuls = 7 * 2 * B*H*T^2*D, halved
# by the causal mask.
flops = 7 * 2 * B * H * T * T * D / 2
print(json.dumps({
    "T": T,
    "flash_fb_ms": round(t_flash * 1e3, 2),
    "blockwise_fb_ms": round(t_block * 1e3, 2),
    "speedup": round(t_block / t_flash, 2),
    "flash_tflops": round(flops / t_flash / 1e12, 1),
}))
