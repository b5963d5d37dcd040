"""Time the conv mixers' gate-and-tap pass ON THE CHIP:
``python tools/conv_cells.py [--root DIR] [--tiles 512x512,256x512,...]``.

One JSON line a form: device ms of a forward call and of a forward +
backward pair at the fifth cell's geometry (``lfm2_24b_a2b``: B = 1,
T = 8192, E = 2048, K = 3; methodology as tools/fa_cells.py: chained calls
in one program, each call's taps depending on the one before through one
element of its result, so that nothing but the pass is timed; the device
op timeline of a profiler capture), for the
plain form (``ops/short_conv._plain``: XLA's fusions inside a
``jax.checkpoint``) and, where the tree has them, the kernel pair at each
of ``--tiles`` (rows of T x lanes of E); beside each kernel line the
largest distance of its output and of its three cotangents and the taps'
gradient from the plain form computed in float32. It is for the block
sweep (``ops/short_conv._BLOCKS``); no cell runs it. ``--root`` takes the
program from another checkout: one process a tree.
"""

import argparse
import json
import os
import sys

STEPS = 10


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="512x512",
                    help="comma-separated ROWSxLANES tiles of the kernels")
    ap.add_argument("--t", type=int, default=8192,
                    help="sequence length (a small one rehearses the tool "
                         "on the CPU, interpreted: no time of it counts)")
    ap.add_argument("--e", type=int, default=2048)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    root, t, e = os.path.abspath(args.root), args.t, args.e
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.core import state, xprof
    from horovod_tpu.ops import short_conv as sc

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcu = jax.random.normal(keys[0], (1, t, 3 * e), jnp.bfloat16)
    w = jax.random.uniform(keys[1], (e, 3), jnp.float32, -0.58, 0.58)
    g = jax.random.normal(keys[2], (1, t, e), jnp.bfloat16)

    def timed(run):
        float(run(bcu, w))  # compile + warm
        return xprof.timed_steps(lambda: float(run(bcu, w)), STEPS, 3)

    def programs(conv):
        """Chained calls that touch nothing but the pass: each call's taps
        depend on the one before through a single element of its result,
        so no pass over the streams is timed beside it."""
        def chained(body):
            @jax.jit
            def run(x, w):
                return jnp.sum(lax.scan(lambda w, _: body(x, w), w, None,
                                        length=STEPS)[1])
            return run

        # (the barrier keeps XLA from computing the plain form's one
        # element alone)
        def fwd(x, w):
            o = lax.optimization_barrier(conv(x, w))
            o = o[0, 0, 0].astype(jnp.float32)
            return w + 0.0 * o, o

        def both(x, w):
            o, vjp = jax.vjp(conv, x, w)
            o, (dx, dw) = lax.optimization_barrier((o, vjp(g)))
            tip = (o[0, 0, 0] + dx[0, 0, 0]).astype(jnp.float32)
            return w + 0.0 * (dw + tip), tip
        return chained(fwd), chained(both)

    def row(form, conv):
        out = {"form": form, "root": root, "t": t, "e": e}
        try:
            fwd, both = programs(conv)
            out["fwd_ms"] = round(timed(fwd) * 1e3, 4)
            out["fwd_bwd_ms"] = round(timed(both) * 1e3, 4)
        except Exception as ex:  # noqa: BLE001 — a sweep reports, goes on
            out["err"] = str(ex)[-300:]
        return out

    print(json.dumps(row("plain", lambda x, w: sc._plain(x, w, None))),
          flush=True)
    if not hasattr(sc, "_kernels"):
        return
    interpret = state.target_platform() != "tpu"
    f32 = lambda x, w: sc._plain(x.astype(jnp.float32), w, None)
    want, vjp = jax.vjp(f32, bcu, w)
    d_want, dw_want = vjp(g.astype(jnp.float32))
    for tiles in args.tiles.split(","):
        tiles = tuple(int(n) for n in tiles.split("x"))
        conv = lambda x, w, tiles=tiles: sc._kernels(x, w, None, tiles,
                                                     interpret)
        out = row(f"kernels {tiles[0]}x{tiles[1]}", conv)
        got, vjp = jax.vjp(conv, bcu, w)
        d_got, dw_got = vjp(g)
        gap = lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        out["gap_out"] = gap(got, want)
        for k, name in enumerate("BCu"):
            sl = slice(k * e, (k + 1) * e)
            out[f"gap_d{name}"] = gap(d_got[..., sl], d_want[..., sl])
        out["gap_dw"] = gap(dw_got, dw_want)
        out["max_dw"] = float(jnp.max(jnp.abs(dw_want)))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
