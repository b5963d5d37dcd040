"""Runner of the LM training cells: the program's own path — ``hvd.init``
→ ``hvd.DistributedOptimizer(ops/optim.adamw)`` → ``hvd.spmd`` — around
``models/transformer.py`` at the configuration's sizes.

``setup`` builds ONE object, the compiled step with its state, drives it
through its first three steps on pool batches 0..2 (what the reference
follows), reads what ``correct`` compares, and hands the same object to
the window: ``dispatch(k)`` is one framework step on pool batch ``k``.
"""

from __future__ import annotations

import numpy as np

FOLLOWED_STEPS = 3


def _program_path(name: str):
    """Where the program keeps the reference's leaf ``name``."""
    block = {"ln1": ("RMSNorm_0", "scale"), "ln2": ("RMSNorm_1", "scale"),
             "wq": ("attn", "query", "kernel"),
             "wk": ("attn", "key", "kernel"),
             "wv": ("attn", "value", "kernel"),
             "wo": ("attn", "out", "kernel"),
             "w1": ("Dense_0", "kernel"), "w2": ("Dense_1", "kernel")}
    top = {"embed": ("Embed_0", "embedding"), "ln_f": ("RMSNorm_0", "scale"),
           "head": ("lm_head", "kernel")}
    if name in top:
        return top[name]
    layer, part = name.split(".")
    return (f"block_{layer[1:]}",) + block[part]


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


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.models import transformer
        from horovod_tpu.ops import optim

        import time

        mark = lambda what: ctx.say(
            f"set-up: {what} at +{time.perf_counter() - ctx.t0:.1f} s")
        cfg, traffic, seeded = ctx.config, ctx.traffic, ctx.seeded
        hvd.init(devices=jax.devices()[:ctx.chips])
        n = hvd.size()
        self.hvd, self.n = hvd, n
        mark("imports and hvd.init")
        mcfg = transformer.TransformerConfig(
            vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            embed_dim=cfg["hidden_size"], mlp_dim=cfg["intermediate_size"],
            max_seq_len=cfg["max_position_embeddings"], dtype=jnp.bfloat16,
            attention="local", window=cfg["sliding_window"])
        if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
            raise ValueError("head_dim x heads is not the hidden size")
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
            mcfg, fused_head=traffic["fused_head"])

        def train_step(p, s, toks):
            loss, grads = jax.value_and_grad(loss_fn)(p, toks)
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

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

    def dispatch(self, k: int):
        """One framework step on pool batch ``k`` (modulo the pool);
        returns what ``finish`` waits on."""
        self.ps, self.ss, loss = self.step(
            self.ps, self.ss, self.pool[k % len(self.pool)])
        return loss

    def finish(self, handle):
        """Wait for the step; its loss on every rank."""
        return np.asarray(handle)

    def release(self):
        """Free the program's state before the reference runs."""
        del self.ps, self.ss, self.pool, self.step
        self.hvd.shutdown()


def setup(ctx) -> Session:
    return Session(ctx)


def end_to_end(session: Session, window: dict) -> dict:
    """The rates this runner's cells report, over all the work and all the
    time of the window."""
    return {"lm_tokens_per_s_per_chip": (
        window["steps"] * session.units_per_step / window["seconds"]
        / session.n, "tokens/s/chip")}
