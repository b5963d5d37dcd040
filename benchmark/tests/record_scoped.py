"""Record the pair kept in ``recorded_scoped/``: three steps of a tiny
SCOPED ``hvd.spmd`` LM step (two layers, T=4096 so that the flash kernels
run, AdamW through ``hvd.DistributedOptimizer``) under ``jax.profiler`` on
every local chip, and the program's own record of it as JSON:

    python3 benchmark/tests/record_scoped.py chiprun_out/recorded_scoped

``test_scopes.py`` joins the two: every event in exactly one phase.
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEPS = 3


def main():
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.core import timeline
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import optim

    out = sys.argv[1]
    hvd.init()
    n = hvd.size()
    cfg = transformer.TransformerConfig(
        vocab_size=1024, num_layers=2, num_heads=2, num_kv_heads=1,
        embed_dim=256, mlp_dim=512, max_seq_len=4096, dtype=jnp.bfloat16,
        attention="local", window=1024)
    params = transformer.init_params(cfg)
    opt = hvd.DistributedOptimizer(optim.adamw(1e-3))
    loss_fn = transformer.make_loss_fn(cfg, fused_head=True)

    def train_step(p, s, toks):
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    ps = hvd.replicate(params)
    ss = hvd.replicate(jax.jit(opt.init)(params))
    toks = hvd.rank_stack([
        np.random.RandomState(r).randint(0, 1024, (1, 4096), np.int32)
        for r in range(n)])
    ps, ss, loss = step(ps, ss, toks)
    jax.block_until_ready(loss)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the kept file stays small: no
    options.enable_hlo_proto = False  # Python frames, no copy of the HLO
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            ps, ss, loss = step(ps, ss, toks)
    with jax.profiler.TraceAnnotation("bench/wait_step"):
        jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    del step, ps, ss
    hvd.shutdown()  # resolves the profiled program's scope map
    for root, _, files in os.walk(out):
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".xplane.pb"):
                shutil.move(path, os.path.join(out, "tiny_scoped.xplane.pb"))
            else:
                os.remove(path)
    shutil.rmtree(os.path.join(out, "plugins"), ignore_errors=True)
    with open(os.path.join(out, "tiny_scoped.record.json"), "w") as f:
        json.dump(timeline.record(), f, separators=(",", ":"))
    for name in sorted(os.listdir(out)):
        print(name, os.path.getsize(os.path.join(out, name)), "bytes")


if __name__ == "__main__":
    main()
