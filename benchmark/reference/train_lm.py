"""Plain reference of the LM training cells: the decoder's forward, loss,
gradients and AdamW in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision, no kernel, no fusion, no collectives.

It imports nothing of the program and takes nothing the program made: the
weights come from ``seeded.leaves`` over ``leaf_specs`` and the tokens from
``seeded.lm_tokens``, both from the seed.

So that 781 M parameters with float32 moments fit one 16 GB chip, a step
runs layer by layer: the forward keeps each layer's input, the backward
walks down with one ``jax.vjp`` a layer (which computes that layer's
forward again), and each layer's parameters take their AdamW update as soon
as their gradient, summed over all rows, exists. Attention runs in blocks of
query rows against the keys its causal window lets it see; the head in
blocks of rows. A "row" is one sequence; rows beyond one chip's (the other
replicas' of a data-parallel cell) go to the other chips, one each, and
their gradients are summed on the first.

``variant`` puts the reference in the program's place for the control and
the planted faults (``benchmark/tests``): ``fp8`` rounds both operands of
every matmul to float8_e4m3 (per-tensor scale), the nearest precision under
the configuration's bfloat16; ``half_batch`` takes the loss over the first
half of each row's positions; ``no_exchange`` trains on rank 0's rows alone;
``unchanged`` computes each step and leaves the state as it was.
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

VARIANTS = ("reference", "fp8", "half_batch", "no_exchange", "unchanged")


def leaf_specs(cfg: dict):
    """(name, shape, init) of every parameter, in a fixed order."""
    e, h, g = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, m, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    std = ("normal", cfg["initializer_range"])
    specs = [("embed", (v, e), std)]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"l{i}.ln1", (e,), ("ones",)),
                  (f"l{i}.wq", (e, h, d), std),
                  (f"l{i}.wk", (e, g, d), std),
                  (f"l{i}.wv", (e, g, d), std),
                  (f"l{i}.wo", (h, d, e), std),
                  (f"l{i}.ln2", (e,), ("ones",)),
                  (f"l{i}.w1", (e, m), std),
                  (f"l{i}.w2", (m, e), std)]
    specs += [("ln_f", (e,), ("ones",)), ("head", (e, v), std)]
    return specs


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8_e4m3 with one scale a tensor; the gradient passes
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    t, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window, fp8):
    """Causal attention of one sequence, query position t seeing keys
    (t - window, t]. q (T, H, D), k and v (T, G, D); H = G x R."""
    t, h, d = q.shape
    g = k.shape[1]
    w = min(window or t, t)
    qb = min(512, t)
    if t % qb:
        raise ValueError(f"sequence length {t} is not a multiple of {qb}")
    kp = jnp.pad(k, ((w, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((w, 0), (0, 0), (0, 0)))
    qr = q.reshape(t // qb, qb, g, h // g, d)

    @jax.checkpoint
    def block(qblk, i):
        ks = lax.dynamic_slice_in_dim(kp, i * qb, w + qb)
        vs = lax.dynamic_slice_in_dim(vp, i * qb, w + qb)
        s = _mm("qgrd,sgd->grqs", qblk, ks, fp8) / np.sqrt(d)
        qpos = i * qb + jnp.arange(qb)[:, None]
        kpos = i * qb - w + jnp.arange(w + qb)[None, :]
        ok = (kpos <= qpos) & (kpos > qpos - w) & (kpos >= 0)
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return _mm("grqs,sgd->qgrd", p, vs, fp8)

    out = lax.map(lambda a: block(*a), (qr, jnp.arange(t // qb)))
    return out.reshape(t, h, d)


def _block(lp, x, cfg, fp8):
    y = _rmsnorm(x, lp["ln1"], cfg["norm_epsilon"])
    q = _rotary(_mm("te,ehd->thd", y, lp["wq"], fp8), cfg["rope_theta"])
    k = _rotary(_mm("te,egd->tgd", y, lp["wk"], fp8), cfg["rope_theta"])
    v = _mm("te,egd->tgd", y, lp["wv"], fp8)
    a = _attention(q, k, v, cfg["sliding_window"], fp8)
    x = x + _mm("thd,hde->te", a, lp["wo"], fp8)
    y = _rmsnorm(x, lp["ln2"], cfg["norm_epsilon"])
    y = jax.nn.gelu(_mm("te,em->tm", y, lp["w1"], fp8), approximate=True)
    return x + _mm("tm,me->te", y, lp["w2"], fp8)


def _head_loss(hp, x, tokens, cfg, fp8, positions):
    """Mean next-token cross-entropy over the first ``positions``
    transitions of the row, the head taken in blocks of rows."""
    t = x.shape[0]
    rb = min(1024, t)
    y = _rmsnorm(x, hp["ln_f"], cfg["norm_epsilon"])
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    weight = (jnp.arange(t) < positions).astype(jnp.float32)

    @jax.checkpoint
    def rows(yb, tb, wb):
        logits = _mm("te,ev->tv", yb, hp["head"], fp8)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=1)[:, 0]
        return jnp.sum(wb * (lse - picked))

    parts = lax.map(lambda a: rows(*a),
                    (y.reshape(t // rb, rb, -1), targets.reshape(-1, rb),
                     weight.reshape(-1, rb)))
    return jnp.sum(parts) / positions


def _adamw(p, m, v, g, count, opt):
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** count)
    vhat = v / (1.0 - b2 ** count)
    p = p - opt["learning_rate"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                    + opt["weight_decay"] * p)
    return p, m, v


# ---------------------------------------------------------------------------
# one replica of the parameters a device, the state on the first
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, opt_json: str, fp8: bool):
    """The jitted pieces of a step, built once for a configuration."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    block = functools.partial(_block, cfg=cfg, fp8=fp8)

    def block_bwd(lp, x, dy):
        return jax.vjp(block, lp, x)[1](dy)

    def head_bwd(hp, x, toks, positions):
        loss, vjp = jax.vjp(
            lambda hp_, x_: _head_loss(hp_, x_, toks, cfg, fp8, positions),
            hp, x)
        return (loss,) + vjp(jnp.ones((), jnp.float32))

    def update(p, m, v, g, count):
        return _adamw(p, m, v, g, count, opt) + (jnp.sqrt(jnp.sum(g * g)),)

    return types.SimpleNamespace(
        embed=jax.jit(lambda emb, toks: emb[toks]),
        fwd=jax.jit(block), bwd=jax.jit(block_bwd),
        head=jax.jit(head_bwd, static_argnums=(3,)),
        embed_bwd=jax.jit(
            lambda toks, dx, v: jnp.zeros((v, dx.shape[1]),
                                          jnp.float32).at[toks].add(dx),
            static_argnums=(2,)),
        update=jax.jit(update, donate_argnums=(0, 1, 2)),
        change=jax.jit(lambda p, p0: jnp.sqrt(jnp.sum((p - p0) ** 2))))


class Reference:
    """Parameters and AdamW state from the seed; ``step(rows)`` trains one
    step on a list of token rows and returns its loss and the norm of
    every leaf's gradient."""

    def __init__(self, cfg: dict, opt: dict, seed: int, seeded,
                 variant: str = "reference", devices=None, ranks: int = 1):
        if variant not in VARIANTS:
            raise ValueError(f"variant {variant!r} not in {VARIANTS}")
        self.cfg, self.variant, self.ranks = cfg, variant, ranks
        self.fp8 = variant == "fp8"
        self.devices = list(devices or jax.devices()[:1])
        self.specs = leaf_specs(cfg)
        self.count = 0
        d0 = self.devices[0]
        self._key = jax.device_put(seeded.key(seed), d0)
        self._make = jax.jit(seeded.leaf, static_argnums=(2, 3))

        def weights_and_moments(k):
            p = seeded.leaves(k, self.specs)
            zeros = lambda: {n: jnp.zeros_like(a) for n, a in p.items()}
            return p, zeros(), zeros()

        # replicas[d][name]; moments on the first device only.
        with jax.default_device(d0):
            first, self.mu, self.nu = jax.jit(weights_and_moments)(
                self._key)
        self.replicas = [first] + [
            {n: jax.device_put(a, d) for n, a in first.items()}
            for d in self.devices[1:]]

        self._p = _programs(json.dumps(cfg, sort_keys=True),
                            json.dumps(opt, sort_keys=True), self.fp8)

    # -- helpers ------------------------------------------------------------

    def _layer(self, rep, i):
        pre = f"l{i}."
        return {n[len(pre):]: a for n, a in rep.items()
                if n.startswith(pre)}

    def _apply(self, grads: dict, scale: float, norms: dict):
        """Sum ``grads[name]`` (one per row, wherever it was computed) on
        the first device, update that leaf, and hand every replica the new
        value."""
        d0 = self.devices[0]
        for name, parts in grads.items():
            g = None
            for part in parts:
                part = jax.device_put(part, d0)
                g = part if g is None else g + part
            g = g * scale
            if self.variant == "unchanged":
                norms[name] = jnp.sqrt(jnp.sum(g * g))
                continue
            p, m, v, gn = self._p.update(
                self.replicas[0][name], self.mu[name], self.nu[name], g,
                jnp.float32(self.count))
            norms[name] = gn
            self.replicas[0][name], self.mu[name], self.nu[name] = p, m, v
            for rep, d in zip(self.replicas[1:], self.devices[1:]):
                rep[name] = jax.device_put(p, d)

    # -- one step -----------------------------------------------------------

    def step(self, rows):
        """``rows``: list of (T,) int32 token rows, all ranks' in rank
        order. Returns (loss, {leaf: gradient norm})."""
        if self.variant == "no_exchange":
            rows = rows[:len(rows) // self.ranks]
        nl = self.cfg["num_hidden_layers"]
        t = len(rows[0])
        positions = (t // 2) if self.variant == "half_batch" else t - 1
        nd = len(self.devices)
        self.count += 1
        place = [r % nd for r in range(len(rows))]
        toks = [jax.device_put(jnp.asarray(r, jnp.int32), self.devices[d])
                for r, d in zip(rows, place)]
        xs = []
        for tk, d in zip(toks, place):
            rep = self.replicas[d]
            x = [self._p.embed(rep["embed"], tk)]
            for i in range(nl):
                x.append(self._p.fwd(self._layer(rep, i), x[-1]))
            xs.append(x)
        scale = 1.0 / len(rows)
        norms: dict = {}
        losses, dxs = [], []
        grads: dict = {"ln_f": [], "head": []}
        for tk, d, x in zip(toks, place, xs):
            rep = self.replicas[d]
            loss, dhp, dx = self._p.head(
                {"ln_f": rep["ln_f"], "head": rep["head"]}, x.pop(), tk,
                positions)
            losses.append(loss)
            dxs.append(dx)
            for n in grads:
                grads[n].append(dhp[n])
        self._apply(grads, scale, norms)
        for i in reversed(range(nl)):
            grads = {}
            for r, (d, x) in enumerate(zip(place, xs)):
                dlp, dxs[r] = self._p.bwd(self._layer(self.replicas[d], i),
                                        x.pop(), dxs[r])
                for n, gpart in dlp.items():
                    grads.setdefault(f"l{i}.{n}", []).append(gpart)
            self._apply(grads, scale, norms)
        grads = {"embed": [
            self._p.embed_bwd(tk, dx, self.cfg["vocab_size"])
            for tk, dx in zip(toks, dxs)]}
        self._apply(grads, scale, norms)
        loss = float(np.mean([float(np.asarray(l)) for l in losses]))
        return loss, {n: float(np.asarray(v)) for n, v in norms.items()}

    def change_norms(self) -> dict:
        """Norm of every leaf's change since the seed's weights."""
        out = {}
        for i, (name, shape, init) in enumerate(self.specs):
            p0 = self._make(self._key, i, shape, init)
            out[name] = self._p.change(self.replicas[0][name], p0)
        return {n: float(np.asarray(v)) for n, v in out.items()}


def run(cfg: dict, traffic: dict, seed: int, chips: int, seeded,
        steps: int = 3, variant: str = "reference", devices=None,
        log=None) -> dict:
    """Follow the first ``steps`` steps of the cell from the seed. Returns
    ``{"loss": [..], "grad_norm": {leaf: norm at step 1},
    "change_norm": {leaf: norm after the steps}}``."""
    import time

    t0 = time.perf_counter()
    log = log or (lambda msg: None)
    with jax.default_matmul_precision("highest"):
        ref = Reference(cfg, traffic["optimizer"], seed, seeded, variant,
                        devices, ranks=chips)
        jax.block_until_ready(ref.replicas)
        log(f"reference: weights {time.perf_counter() - t0:.1f} s")
        b, t = traffic["batch_per_chip"], traffic["seq_len"]
        losses, grad_norm = [], None
        for s in range(steps):
            rows = [row for r in range(chips)
                    for row in seeded.lm_tokens(
                        seed, r, s, b, t, cfg["vocab_size"])]
            loss, norms = ref.step(rows)
            log(f"reference: step {s + 1} at {time.perf_counter() - t0:.1f} s")
            losses.append(loss)
            if s == 0:
                grad_norm = norms
        change = ref.change_norms()
        log(f"reference: done at {time.perf_counter() - t0:.1f} s")
        return {"loss": losses, "grad_norm": grad_norm,
                "change_norm": change}
