"""Runner of the ResNet-50 training cell: ``bench.py``'s step
(``resnet.ResNet50`` through ``hvd.spmd`` with ``hvd.allreduce_gradients``,
SGD with momentum, the BatchNorm-statistics allreduce) — one framework step
a host call, a fresh batch each step, no ``lax.scan`` device loop.

``setup`` builds ONE object, the compiled step with its state, drives it
through its first three steps on pool batches 0..2, reads what ``correct``
compares, and hands the same object to the window.
"""

from __future__ import annotations

import numpy as np

FOLLOWED_STEPS = 3


def _to_tree(by_name: dict) -> dict:
    tree: dict = {}
    for name, leaf in by_name.items():
        node = tree
        *parents, last = name.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree


def _by_name(tree, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for key in name.split("/"):
            node = node[key]
        out[name] = node
    return out


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.models import resnet

        import time

        mark = lambda what: ctx.say(
            f"set-up: {what} at +{time.perf_counter() - ctx.t0:.1f} s")
        cfg, traffic, seeded = ctx.config, ctx.traffic, ctx.seeded
        hvd.init(devices=jax.devices()[:ctx.chips])
        n = hvd.size()
        self.hvd, self.n = hvd, n
        mark("imports and hvd.init")
        if cfg["compute_dtype"] != "bfloat16":
            raise ValueError("this runner trains in bfloat16")
        model = resnet.ResNet(stage_sizes=cfg["stage_sizes"],
                              num_classes=cfg["num_classes"],
                              num_filters=cfg["num_filters"],
                              dtype=jnp.bfloat16)
        specs = ctx.reference.leaf_specs(cfg)
        self.names = [s[0] for s in specs]
        norms_of = ctx.reference.norm_names(cfg)

        key = seeded.key(ctx.seed)

        def make(k):
            stats = {}
            for name, c in norms_of:
                stats[name + "/mean"] = jnp.zeros((c,), jnp.float32)
                stats[name + "/var"] = jnp.ones((c,), jnp.float32)
            return {"params": _to_tree(seeded.leaves(k, specs)),
                    "batch_stats": _to_tree(stats)}

        make = jax.jit(make)
        want = jax.eval_shape(lambda: resnet.init_variables(
            model, image_size=cfg["image_size"]))
        shapes = lambda t: jax.tree.map(lambda a: a.shape, dict(t))
        if shapes(want) != shapes(jax.eval_shape(make, key)):
            raise ValueError("the program's variables are not the tree "
                             "this runner places the seed's weights into")

        o = traffic["optimizer"]
        if o["name"] != "sgd":
            raise ValueError(f"this runner trains with optax.sgd, not {o}")
        opt = optax.sgd(o["learning_rate"], momentum=o["momentum"])
        loss_fn = resnet.make_loss_fn(
            model, weight_decay=traffic["weight_decay"],
            label_smoothing=traffic["label_smoothing"])

        def train_step(variables, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                variables, batch)
            grads = hvd.allreduce_gradients(grads, compression="none")
            updates, opt_state = opt.update(grads, opt_state, variables)
            variables = optax.apply_updates(variables, updates)
            variables = {
                "params": variables["params"],
                "batch_stats": jax.tree.map(lambda t: hvd.allreduce(t),
                                            aux["batch_stats"]),
            }
            return variables, opt_state, hvd.allreduce(loss)

        self.step = hvd.spmd(train_step, donate_argnums=(0, 1))
        variables = make(key)
        self.vs = hvd.broadcast_global_variables(hvd.replicate(variables),
                                                 root_rank=0)
        self.os = hvd.replicate(jax.jit(opt.init)(variables))
        del variables
        rows = traffic["batch_per_chip"]
        images = jax.jit(seeded.images, static_argnums=(3, 4, 5))
        self.pool = [
            hvd.rank_stack([images(key, r, i, rows, cfg["image_size"],
                                   cfg["num_classes"]) for r in range(n)])
            for i in range(traffic["pool_batches"])]
        if len(self.pool) < FOLLOWED_STEPS:
            raise ValueError("the pool is shorter than the followed steps")
        self.units_per_step = n * rows
        jax.block_until_ready((self.vs, self.os, self.pool))
        mark("weights, placement, broadcast, optimizer state, pool")

        losses = []
        for k in range(FOLLOWED_STEPS):
            losses.append(self.finish(self.dispatch(k)))
            mark(f"step {k + 1} (the first compiles or loads)")
            if k == 0:
                # optax's momentum trace after one step IS the gradient
                # the optimizer was given.
                trace = _by_name(ctx.readings.leaf_norms(
                    self.os[0].trace["params"]), self.names)
                grad_norm = {nm: np.asarray(v).tolist()
                             for nm, v in trace.items()}
        change_norm = ctx.readings.change_norms(
            hvd, seeded, ctx.seed, specs,
            _by_name(self.vs["params"], self.names))
        mark("the followed steps' readings")
        self.observed = {"loss": [np.asarray(l).tolist() for l in losses],
                         "grad_norm": grad_norm,
                         "change_norm": change_norm}
        self.next_batch = FOLLOWED_STEPS

    def dispatch(self, k: int):
        self.vs, self.os, loss = self.step(
            self.vs, self.os, self.pool[k % len(self.pool)])
        return loss

    def finish(self, handle):
        return np.asarray(handle)

    def release(self):
        del self.vs, self.os, self.pool, self.step
        self.hvd.shutdown()


def setup(ctx) -> Session:
    return Session(ctx)


def end_to_end(session: Session, window: dict) -> dict:
    return {"resnet_images_per_s_per_chip": (
        window["steps"] * session.units_per_step / window["seconds"]
        / session.n, "images/s/chip")}
