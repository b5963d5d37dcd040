"""Per-op device profile of the bench.py LM training step (lm_t8k_*).

Same xplane aggregation as tools/profile_resnet.py, over the exact
long-context LM step bench.py times: 8 layers, GQA 8q/4kv, T=8192, AdamW,
flash attention, chunked-vocab fused CE head (bench.py's default).
``--unfused`` profiles the plain softmax-CE head instead — the r4
comparison that exposed ~10 ms/step of fp32-logit materialization this
path no longer pays. Usage: python tools/profile_lm.py [--steps 3]
[--unfused]
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from horovod_tpu.core import xprof
from horovod_tpu.models import transformer
from tools.profile_resnet import summarize


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=2,
                    help="bench.py's B (2: measured throughput-optimal)")
    ap.add_argument("--unfused", action="store_true",
                    help="profile the plain softmax-CE head instead of "
                         "the fused chunked-vocab default")
    args = ap.parse_args()

    cfg = transformer.TransformerConfig(
        vocab_size=32_768, num_layers=8, num_heads=8, num_kv_heads=4,
        embed_dim=1024, mlp_dim=4096, max_seq_len=8192,
        dtype=jnp.bfloat16, attention="local")
    B, T = args.batch, 8192
    params = transformer.init_params(cfg)
    from horovod_tpu.ops import optim

    opt = optim.adamw(3e-4, weight_decay=0.1)  # bench.py's optimizer
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0,
                                cfg.vocab_size, jnp.int32)

    loss_fn = transformer.make_loss_fn(cfg, fused_head=not args.unfused)

    from tools.lm_exp import build_step  # ONE step definition for all tools

    step = build_step(opt, loss_fn, args.steps)
    params, opt_state, loss = step(params, opt_state, tokens)
    float(np.asarray(loss))
    d = tempfile.mkdtemp(prefix="lm_prof_")
    jax.profiler.start_trace(d)
    params, opt_state, loss = step(params, opt_state, tokens)
    float(np.asarray(loss))
    jax.profiler.stop_trace()
    evs = xprof.slowest_plane(xprof.device_planes(d))
    if not evs:
        print("no device plane — run on TPU")
        return
    start = min(s for _, s, _ in evs)
    end = max(s + dur for _, s, dur in evs)
    print(summarize([(name, dur / 1e3) for name, _, dur in evs],
                    n_steps=args.steps,
                    step_ms=(end - start) / 1e3 / args.steps, top=20))


if __name__ == "__main__":
    main()
