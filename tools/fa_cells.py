"""Time the flash kernels at the benchmark cells' geometries ON THE CHIP:
``python tools/fa_cells.py [--root DIR] [name ...]``.

One JSON line a geometry: device ms of a forward call and of a forward +
backward pair (methodology as tools/fa_sweep.py: chained calls in one
program, the device op timeline of a profiler capture), and the kernels'
own count of the scores they compute for the visible ones where the tree
has one (``ops/flash_attention.score_counts``). ``--root`` takes the
program from another checkout (the parent's, unpacked beside this one):
one process a tree.
"""

import argparse
import json
import os
import sys

GEOMETRIES = {  # the cells' attention calls, B = 1 (T = 8192)
    "looped_16x128": dict(h=16, hkv=16, d=128),             # ouro_2_6b
    "sc2_24over2x128_w4096": dict(h=24, hkv=2, d=128, window=4096),
    "mla_20x256": dict(h=20, hkv=20, d=256),                # glm_4_7_flash
    "gqa_32over8x64": dict(h=32, hkv=8, d=64),              # lfm2_24b_a2b
}
STEPS = 10


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", default=list(GEOMETRIES))
    ap.add_argument("--t", type=int, default=8192,
                    help="sequence length (a small one rehearses the tool "
                         "on the CPU, interpreted: no time of it counts)")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    root, T = os.path.abspath(args.root), args.t
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.core import xprof
    from horovod_tpu.ops import flash_attention as fa

    def timed(run, *xs):
        float(run(*xs))  # compile + warm
        return xprof.timed_steps(lambda: float(run(*xs)), STEPS, 3)

    for name in args.names:
        g = GEOMETRIES[name]
        window = g.get("window")
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (1, T, g["h"], g["d"]), jnp.bfloat16)
        k, v = (jax.random.normal(kk, (1, T, g["hkv"], g["d"]),
                                  jnp.bfloat16) for kk in keys[1:])
        attn = lambda q, k, v: fa.flash_attention(q, k, v, True,
                                                  window=window)
        grad = jax.grad(lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))

        @jax.jit
        def fwd(q, k, v):
            def body(c, _):
                o = attn(c, k, v)
                return c + 0.0 * o, jnp.sum(o.astype(jnp.float32))
            return jnp.sum(lax.scan(body, q, None, length=STEPS)[1])

        @jax.jit
        def both(q, k, v):
            def body(c, _):
                dq, dk, dv = grad(c, k, v)
                return c + 0.0 * dq, sum(
                    jnp.sum(a.astype(jnp.float32)) for a in (dq, dk, dv))
            return jnp.sum(lax.scan(body, q, None, length=STEPS)[1])

        row = {"geometry": name, "root": root}
        try:
            row["fwd_ms"] = round(timed(fwd, q, k, v) * 1e3, 3)
            row["fwd_bwd_ms"] = round(timed(both, q, k, v) * 1e3, 3)
        except Exception as e:  # noqa: BLE001 — a sweep reports and goes on
            row["err"] = str(e)[-300:]
        if hasattr(fa, "score_counts"):
            visible, computed = fa.score_counts(T, T, g["d"], window=window)
            row["scores_computed_pct"] = round(100.0 * computed / visible, 2)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
