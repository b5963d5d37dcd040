"""Record the small capture kept in ``recorded/``: a few steps of a tiny
data-parallel program (a matmul, its all-reduce over every local chip, an
update) under ``jax.profiler``, on the chip:

    python3 benchmark/tests/record_trace.py chiprun_out/recorded

``test_trace.py`` reads it back: every device plane, collectives merged.
"""

import os
import shutil
import sys


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    out = sys.argv[1]
    devices = jax.devices()
    mesh = Mesh(devices, ("hvd",))
    sh = NamedSharding(mesh, P("hvd"))

    def step(w, x):
        def rank(w, x):
            g = jnp.dot(x[0].T, jnp.dot(x[0], w[0]))
            g = jax.lax.psum(g, "hvd") / len(devices)
            return (w[0] - 1e-3 * g)[None]

        return jax.shard_map(rank, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
                             out_specs=P("hvd"))(w, x)

    step = jax.jit(step, donate_argnums=(0,))
    n = len(devices)
    w = jax.device_put(jnp.ones((n, 512, 512), jnp.float32), sh)
    x = jax.device_put(jnp.ones((n, 256, 512), jnp.float32) * 0.01, sh)
    w = step(w, x)
    jax.block_until_ready(w)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            w = step(w, x)
    with jax.profiler.TraceAnnotation("bench/wait_step"):
        jax.block_until_ready(w)
    jax.profiler.stop_trace()
    for root, _, files in os.walk(out):
        for f in files:
            path = os.path.join(root, f)
            if not f.endswith(".xplane.pb"):
                os.remove(path)
            else:
                print(path, os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main()
