"""Minimal XLA-only repro: ResNet conv shapes vs equal-FLOP matmuls on TPU.

The claim under test (docs/profiles/resnet50_v5e.md): ResNet-50's MFU
ceiling is XLA's conv lowering for wide-spatial / shallow-channel stages,
not this framework's scheduling. For each representative convolution in
the ResNet-50 forward pass this script measures achieved TFLOP/s of

* ``lax.conv_general_dilated`` on the real shape (NHWC, bf16, fp32 accum)
* a single ``jnp.einsum`` matmul with the same FLOP count and the same
  contraction depth (the im2col-equivalent GEMM)

so the gap attributable to the conv emitter itself — with zero framework
code in the loop — is directly visible. Usage: python tools/conv_repro.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STEPS = 50                    # scanned steps per measured program
B = 128
# Timing is taken from the DEVICE timeline (jax.profiler xplane), not the
# host clock: per-dispatch host overhead and its jitter drown sub-ms ops
# even under step-count differencing.

# (name, H, W, Cin, Cout, kh, kw, stride) — ResNet-50 forward reps.
SHAPES = [
    ("stem 7x7/2", 224, 224, 3, 64, 7, 7, 2),
    ("s1 3x3", 56, 56, 64, 64, 3, 3, 1),
    ("s1 1x1 expand", 56, 56, 64, 256, 1, 1, 1),
    ("s2 3x3", 28, 28, 128, 128, 3, 3, 1),
    ("s3 3x3", 14, 14, 256, 256, 3, 3, 1),
    ("s4 3x3", 7, 7, 512, 512, 3, 3, 1),
]


def timeit(make_run, *args):
    """Per-step device time from the profiler xplane (best of 3 captures);
    shared implementation in horovod_tpu.core.xprof.timed_steps."""
    from horovod_tpu.core import xprof

    fn = make_run(STEPS)
    float(fn(*args))  # compile + warm; the scalar transfer forces completion
    return xprof.timed_steps(lambda: float(fn(*args)), STEPS, trials=3)


def scan_chain(op):
    def make(steps):
        @jax.jit
        def run(x, w):
            def body(c, _):
                y = op(c, w)
                # Chain a vanishingly-scaled scalar of y back into the
                # input: each step depends on the previous (no DCE, no CSE
                # collapse; a 0.0 multiplier would be constant-folded).
                return c + (jnp.sum(y.astype(jnp.float32)) * 1e-30
                            ).astype(c.dtype), None
            c, _ = lax.scan(body, x, None, length=steps)
            return jnp.sum(c.astype(jnp.float32))
        return run
    return make


key = jax.random.PRNGKey(0)
for name, h, w_, cin, cout, kh, kw, st in SHAPES:
    x = jax.random.normal(key, (B, h, w_, cin), jnp.bfloat16)
    wgt = jax.random.normal(key, (kh, kw, cin, cout), jnp.bfloat16)
    ho, wo = h // st, w_ // st

    def conv(x, wgt):
        # bf16 in/out, exactly like the flax model's nn.Conv(dtype=bf16);
        # the MXU accumulates in fp32 internally either way.
        return lax.conv_general_dilated(
            x, wgt, (st, st), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    flops = 2 * B * ho * wo * cout * kh * kw * cin
    t_conv = timeit(scan_chain(conv), x, wgt)

    # Equal-FLOP GEMM with the im2col contraction depth: (B·Ho·Wo) rows,
    # kh·kw·Cin contraction, Cout columns.
    m, kdim, n = B * ho * wo, kh * kw * cin, cout
    a = jax.random.normal(key, (m, kdim), jnp.bfloat16)
    bmat = jax.random.normal(key, (kdim, n), jnp.bfloat16)

    def mm(a, bmat):
        return jnp.einsum("mk,kn->mn", a, bmat)

    t_mm = timeit(scan_chain(mm), a, bmat)

    # Forward + backward (dx and dW): 3x the forward FLOPs. The loss is
    # sum(y²), NOT sum(y): a sum's cotangent is all-ones and XLA folds
    # conv(ones, w) into a weight reduction — no backward conv runs and
    # the "achieved TFLOP/s" reads above peak.
    def fb(op):
        g = jax.grad(
            lambda p, w2: jnp.sum(op(p, w2).astype(jnp.float32) ** 2),
            argnums=(0, 1))

        def make(steps):
            @jax.jit
            def run(p, w2):
                def body(c, _):
                    dp, dw = g(c, w2)
                    return (c + (jnp.sum(dw.astype(jnp.float32)) * 1e-30
                                 ).astype(c.dtype)
                            + dp.astype(c.dtype)
                            * jnp.asarray(1e-30, c.dtype)), None
                c, _ = lax.scan(body, p, None, length=steps)
                return jnp.sum(c.astype(jnp.float32))
            return run
        return make

    t_conv_fb = timeit(fb(conv), x, wgt)
    t_mm_fb = timeit(fb(mm), a, bmat)
    print(json.dumps({
        "shape": name, "flops_g": round(flops / 1e9, 1),
        "conv_ms": round(t_conv * 1e3, 3),
        "conv_tflops": round(flops / t_conv / 1e12, 1),
        "gemm_ms": round(t_mm * 1e3, 3),
        "gemm_tflops": round(flops / t_mm / 1e12, 1),
        "conv_fb_ms": round(t_conv_fb * 1e3, 3),
        "conv_fb_tflops": round(3 * flops / t_conv_fb / 1e12, 1),
        "gemm_fb_ms": round(t_mm_fb * 1e3, 3),
        "gemm_fb_tflops": round(3 * flops / t_mm_fb / 1e12, 1),
    }), flush=True)
