"""List the copy/copy-start ops in the bench LM step's device profile,
with shapes — round-5 hunt for the ~4.4 ms/step of copy traffic the
per-op profile shows. Mirrors bench.py's config (B defaults to 2, fused
AdamW). Usage: python tools/lm_copies.py [--steps 3] [--batch 2]"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.core import xprof
from horovod_tpu.models import transformer
from horovod_tpu.ops import optim


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=2,
                    help="bench.py's B (2: measured throughput-optimal)")
    args = ap.parse_args()

    cfg = transformer.TransformerConfig(
        vocab_size=32_768, num_layers=8, num_heads=8, num_kv_heads=4,
        embed_dim=1024, mlp_dim=4096, max_seq_len=8192,
        dtype=jnp.bfloat16, attention="local")
    B, T = args.batch, 8192
    params = transformer.init_params(cfg)
    opt = optim.adamw(3e-4, weight_decay=0.1)  # bench.py's optimizer
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0,
                                cfg.vocab_size, jnp.int32)
    loss_fn = transformer.make_loss_fn(cfg, fused_head=True)

    from tools.lm_exp import build_step  # ONE step definition for all tools

    step = build_step(opt, loss_fn, args.steps)
    params, opt_state, loss = step(params, opt_state, tokens)
    float(np.asarray(loss))
    d = tempfile.mkdtemp(prefix="lm_cp_")
    jax.profiler.start_trace(d)
    params, opt_state, loss = step(params, opt_state, tokens)
    float(np.asarray(loss))
    jax.profiler.stop_trace()
    evs = xprof.slowest_plane(xprof.device_planes(d))
    agg = collections.Counter()
    for name, _, dur in evs:
        base = xprof.hlo_base(name)
        if "copy" in base or "transpose" in base:
            agg[name[:140]] += dur / 1e3 / args.steps
    for name, ms in agg.most_common(25):
        print(f"{ms:8.3f} ms  {name}")


if __name__ == "__main__":
    main()
