"""Runner of the windowed-and-full-attention expert-layer LM training cells:
the program's own path — ``hvd.init`` →
``hvd.DistributedOptimizer(ops/optim.adamw)`` → ``hvd.spmd`` — around
``models/transformer.py`` with an attention kind by layer (``layer_types``:
``'sliding'`` layers with the configuration's window and rotary beside
``'full'`` layers with neither), q/k-normed grouped-query heads wider than
``hidden / heads`` (``head_dim``), a sigmoid output gate (``attn_gate``),
the embedding's multiplier (``embed_scale``), sandwich norms, a leading
dense layer and expert layers holding this chip's share of the experts
beside a shared expert (``moe=``). Every leaf trains, the routers
included.

The step returns, beside the loss, how many (token, choice) pairs each
held expert took in each expert layer; the window's side of the session
and ``end_to_end`` (which writes their means into the program's record as
``moe.local_pairs`` / ``moe.max_expert_pairs``) are the expert-layer
runner's. Set-up differs by the model's configuration and by where the
program keeps the reference's leaves.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_window_moe
from benchmark.runners import train_moe_lm

FOLLOWED_STEPS = train_moe_lm.FOLLOWED_STEPS
end_to_end = train_moe_lm.end_to_end
_BLOCK = {"ln_ai": ("RMSNorm_0", "scale"), "ln_ao": ("RMSNorm_1", "scale"),
          "ln_fi": ("RMSNorm_2", "scale"), "ln_fo": ("RMSNorm_3", "scale"),
          "wq": ("attn", "query", "kernel"), "wk": ("attn", "key", "kernel"),
          "wv": ("attn", "value", "kernel"),
          "lnq": ("attn", "q_norm", "scale"),
          "lnk": ("attn", "k_norm", "scale"),
          "wgate": ("attn", "gate", "kernel"), "wo": ("attn", "out", "kernel"),
          "wg": ("gate", "kernel"), "wu": ("up", "kernel"),
          "wd": ("down", "kernel"), "wr": ("moe", "router"),
          "eg": ("moe", "wg"), "eu": ("moe", "wu"), "ed": ("moe", "wd"),
          "sg": ("moe", "shared_gate", "kernel"),
          "su": ("moe", "shared_up", "kernel"),
          "sd": ("moe", "shared_down", "kernel")}
_TOP = {"embed": ("Embed_0", "embedding"), "ln_f": ("RMSNorm_0", "scale"),
        "head": ("lm_head", "kernel")}


def _program_path(name: str):
    """Where the program keeps the reference's leaf ``name``."""
    if name in _TOP:
        return _TOP[name]
    layer, part = name.split(".")
    return (f"block_{layer[1:]}",) + _BLOCK[part]


def _to_tree(by_name: dict) -> dict:
    tree: dict = {}
    for name, leaf in by_name.items():
        node = tree
        *parents, last = _program_path(name)
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree


def _by_name(tree, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for key in _program_path(name):
            node = node[key]
        out[name] = node
    return out


def model_config(cfg: dict):
    """The program's ``TransformerConfig`` of this configuration and this
    chip's share of it."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["n_group"] != 1 or cfg["hidden_act"] != "silu" \
            or cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"]:
        raise ValueError("not a configuration this runner's model builds")
    held = cfg["num_experts"]
    return transformer.TransformerConfig(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], dtype=jnp.bfloat16,
        attention="local", window=cfg["sliding_window"], ffn="swiglu",
        sandwich_norm=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        layer_types=tuple(flops_window_moe.KINDS[kind]["program"]
                          for kind in cfg["layer_types"]),
        qk_norm=True, head_dim=cfg["head_dim"],
        attn_gate=cfg["attention_output_gate"],
        embed_scale=cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"]
        else None,
        moe=transformer.MoEConfig(
            total=cfg["published"]["num_experts"], held=held,
            first=cfg["expert_share"]["index"] * held,
            top_k=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            shared_experts=cfg["num_shared_experts"],
            scale=float(cfg["route_scale"]),
            dense_layers=cfg["num_dense_layers"]))


class Session(train_moe_lm.Session):
    def __init__(self, ctx):
        import jax
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.models import transformer
        from horovod_tpu.ops import optim

        mark = lambda what: ctx.say(
            f"set-up: {what} at +{time.perf_counter() - ctx.t0:.1f} s")
        cfg, traffic, seeded = ctx.config, ctx.traffic, ctx.seeded
        hvd.init(devices=jax.devices()[:ctx.chips])
        n = hvd.size()
        self.hvd, self.n = hvd, n
        mark("imports and hvd.init")
        mcfg = model_config(cfg)
        specs = ctx.reference.leaf_specs(cfg)
        self.names = [s[0] for s in specs]
        key = seeded.key(ctx.seed)
        make = jax.jit(lambda k: _to_tree(seeded.leaves(k, specs)))
        want = jax.eval_shape(lambda: transformer.init_params(mcfg))
        got = jax.eval_shape(make, key)
        if jax.tree.map(lambda a: a.shape, want) != jax.tree.map(
                lambda a: a.shape, got):
            raise ValueError("the program's parameter tree is not the one "
                             "this runner places the seed's weights into")

        o = traffic["optimizer"]
        if o["name"] != "adamw" or o["moment_dtype"] != "bfloat16":
            raise ValueError(f"this runner trains with the program's "
                             f"bfloat16-moment adamw, not {o}")
        self.b1 = o["b1"]
        opt = hvd.DistributedOptimizer(optim.adamw(
            o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"]))
        loss_fn = transformer.make_loss_fn(
            mcfg, fused_head=traffic["fused_head"], with_expert_pairs=True)

        def train_step(p, s, toks):
            (loss, pairs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, toks)
            updates, s = opt.update(grads, s, p)
            return (optax.apply_updates(p, updates), s,
                    hvd.allreduce(loss), pairs)

        self.step = hvd.spmd(train_step, donate_argnums=(0, 1))
        params = make(key)
        self.ps = hvd.broadcast_global_variables(hvd.replicate(params),
                                                 root_rank=0)
        self.ss = hvd.replicate(jax.jit(opt.init)(params))
        del params
        b, t = traffic["batch_per_chip"], traffic["seq_len"]
        self.pool = [
            hvd.rank_stack([seeded.lm_tokens(ctx.seed, r, i, b, t,
                                             cfg["vocab_size"])
                            for r in range(n)])
            for i in range(traffic["pool_batches"])]
        if len(self.pool) < FOLLOWED_STEPS:
            raise ValueError("the pool is shorter than the followed steps")
        self.units_per_step = n * b * t
        self.pairs = []  # a finished step's (ranks, expert layers, held)
        jax.block_until_ready((self.ps, self.ss, self.pool))
        mark("weights, placement, broadcast, optimizer state, pool")

        # The first steps, through the window's own call and feed.
        losses = []
        for k in range(FOLLOWED_STEPS):
            losses.append(self.finish(self.dispatch(k)))
            mark(f"step {k + 1} (the first compiles or loads)")
            if k == 0:
                # AdamW's first moment after one step is (1 - b1) x the
                # gradient the optimizer was given.
                mu = _by_name(ctx.readings.leaf_norms(self.ss.mu),
                              self.names)
                grad_norm = {nm: (np.asarray(v) / (1.0 - self.b1)).tolist()
                             for nm, v in mu.items()}
        change_norm = ctx.readings.change_norms(
            hvd, seeded, ctx.seed, specs, _by_name(self.ps, self.names))
        mark("the followed steps' readings")
        self.observed = {"loss": [np.asarray(l).tolist() for l in losses],
                         "grad_norm": grad_norm,
                         "change_norm": change_norm}
        self.next_batch = FOLLOWED_STEPS
        self.pairs.clear()  # the window's own from here on


def setup(ctx) -> Session:
    return Session(ctx)
