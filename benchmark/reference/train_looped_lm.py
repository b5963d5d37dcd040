"""Plain reference of the looped-LM training cells: a weight-shared stack
applied ``total_ut_steps`` times, the per-token multi-exit loss over all
passes, gradients and AdamW in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision — no kernel, no fused head, no
``jax.checkpoint`` around a block, no collectives.

The model (x of one sequence, (T, E); every norm an RMSNorm):

  block l   a = Attn(norm1(x));  x = x + norm2(a)
            n = norm3(x);  m = (silu(n Wg) * (n Wu)) Wd;  x = x + norm4(m)
  Attn      q, k = rope(n Wq), rope(n Wk) over the whole head, base
            ``rope_theta``; v = n Wv; causal softmax(q k^T / sqrt(d)) v; Wo
  stack     h_0 = Embed(tokens);  h_t = norm_f(block_L(... block_1(h_{t-1})))
            for t = 1..R: the same blocks and the same norm_f every pass
  heads     logits_t = h_t W_head;  lambda_t = sigmoid(h_t w_g + b_g)
  exit      p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j), t < R;
            p_R = prod_{j<R}(1 - lambda_j)
  loss      mean over the T - 1 positions with a target of
            sum_t p_t CE(logits_t, next token) - beta H(p),
            H(p) = -sum_t p_t ln p_t

It imports nothing of the program; what it shares with the plain LM's
reference (the float8 rounding, RMSNorm, rotary, blocked causal attention,
AdamW) it takes from ``reference/train_lm.py``.

So that it fits one 16 GB chip it keeps only each block application's
input ((T, E) float32, R x L of them), walks the R x L applications down
with one ``jax.vjp`` each (which runs that block again), sums every shared
leaf's gradient over the passes, and updates after the last: float32
weights, two moments and a gradient accumulator, 16 bytes a parameter.

``variant`` puts the reference in the program's place for the control and
the planted faults (``benchmark/tests``): ``fp8`` rounds both operands of
every matmul to float8_e4m3; ``half_batch`` takes the loss over the first
half of each row's positions; ``unchanged`` computes each step and leaves
the state as it was; ``last_pass_only`` is the fault this mechanism
invites — the loss and the gradient of the last pass alone, the passes
before it behind a ``stop_gradient``.
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.train_lm import (_adamw, _attention, _mm, _rmsnorm,
                                          _rotary)

VARIANTS = ("reference", "fp8", "half_batch", "unchanged", "last_pass_only")
BLOCK_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "ln3", "wg", "wu",
                "wd", "ln4")
HEAD_LEAVES = ("head", "gate_w", "gate_b")


def leaf_specs(cfg: dict):
    """(name, shape, init) of every parameter, in a fixed order."""
    e, h, g = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, m, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    std, ones = ("normal", cfg["initializer_range"]), ("ones",)
    shapes = {"ln1": (e,), "wq": (e, h, d), "wk": (e, g, d),
              "wv": (e, g, d), "wo": (h, d, e), "ln2": (e,), "ln3": (e,),
              "wg": (e, m), "wu": (e, m), "wd": (m, e), "ln4": (e,)}
    specs = [("embed", (v, e), std)]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"l{i}.{n}", shapes[n], ones if n.startswith("ln")
                   else std) for n in BLOCK_LEAVES]
    specs += [("ln_f", (e,), ones), ("gate_w", (e, 1), std),
              ("gate_b", (1,), ("zeros",)), ("head", (e, v), std)]
    return specs


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def _block(lp, x, cfg, fp8):
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n = _rmsnorm(x, lp["ln1"], eps)
    q = _rotary(_mm("te,ehd->thd", n, lp["wq"], fp8), theta)
    k = _rotary(_mm("te,egd->tgd", n, lp["wk"], fp8), theta)
    v = _mm("te,egd->tgd", n, lp["wv"], fp8)
    a = _attention(q, k, v, cfg["sliding_window"], fp8)
    x = x + _rmsnorm(_mm("thd,hde->te", a, lp["wo"], fp8), lp["ln2"], eps)
    n = _rmsnorm(x, lp["ln3"], eps)
    m = jax.nn.silu(_mm("te,em->tm", n, lp["wg"], fp8)) \
        * _mm("te,em->tm", n, lp["wu"], fp8)
    return x + _rmsnorm(_mm("tm,me->te", m, lp["wd"], fp8), lp["ln4"], eps)


def exit_objective(ce, lam, beta):
    """``sum_t p_t ce_t - beta H(p)`` at every position: ``ce`` (R, n) the
    passes' cross-entropies, ``lam`` (R - 1, n) the gates' probabilities."""
    stay = jnp.cumprod(1.0 - lam, axis=0)              # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = jnp.concatenate([lam * before, stay[-1:]])     # (R, n), sums to 1
    return jnp.sum(p * ce, axis=0) + beta * jnp.sum(p * jnp.log(p), axis=0)


def _heads_loss(hp, hs, tokens, cfg, fp8, positions, last_only):
    """The loss over the first ``positions`` transitions of the row from
    the passes' normed states ``hs`` (R, T, E), the head taken in blocks
    of rows."""
    r, t, _ = hs.shape
    rb = min(256, t)  # R x rb x V float32 logits a block, and their softmax
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    weight = (jnp.arange(t) < positions).astype(jnp.float32)

    @jax.checkpoint
    def rows(hb, tb, wb):
        logits = _mm("rte,ev->rtv", hb, hp["head"], fp8)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.broadcast_to(tb[None, :, None], (r, rb, 1)),
            axis=2)[..., 0]
        ce = lse - picked                               # (R, rb)
        if last_only:
            return jnp.sum(wb * ce[-1])
        z = _mm("rte,eo->rto", hb[:-1], hp["gate_w"], fp8)[..., 0] \
            + hp["gate_b"][0]
        return jnp.sum(wb * exit_objective(ce, jax.nn.sigmoid(z),
                                           cfg["exit_entropy_beta"]))

    blocks = (jnp.moveaxis(hs.reshape(r, t // rb, rb, -1), 1, 0),
              targets.reshape(-1, rb), weight.reshape(-1, rb))
    return jnp.sum(lax.map(lambda a: rows(*a), blocks)) / positions


# ---------------------------------------------------------------------------
# the state on one device; a step walks the R x L applications up and down
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, opt_json: str, fp8: bool, last_only: bool):
    """The jitted pieces of a step, built once for a configuration."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    block = functools.partial(_block, cfg=cfg, fp8=fp8)
    norm = lambda scale, x: _rmsnorm(x, scale, cfg["rms_norm_eps"])

    def heads_bwd(hp, hs, toks, positions):
        loss, vjp = jax.vjp(
            lambda hp_, hs_: _heads_loss(hp_, hs_, toks, cfg, fp8,
                                         positions, last_only), hp, hs)
        return (loss,) + vjp(jnp.ones((), loss.dtype))

    def update(p, m, v, g, count):
        return _adamw(p, m, v, g, count, opt)

    return types.SimpleNamespace(
        embed=jax.jit(lambda emb, toks: emb[toks]),
        fwd=jax.jit(block),
        bwd=jax.jit(lambda lp, x, dy: jax.vjp(block, lp, x)[1](dy)),
        norm=jax.jit(norm),
        norm_bwd=jax.jit(lambda s, x, dy: jax.vjp(norm, s, x)[1](dy)),
        heads=jax.jit(heads_bwd, static_argnums=(3,)),
        embed_bwd=jax.jit(
            lambda toks, dx, v: jnp.zeros((v, dx.shape[1]),
                                          jnp.float32).at[toks].add(dx),
            static_argnums=(2,)),
        update=jax.jit(update, donate_argnums=(0, 1, 2)),
        norm_of=jax.jit(lambda g: jnp.sqrt(jnp.sum(g * g))),
        change=jax.jit(lambda p, p0: jnp.sqrt(jnp.sum((p - p0) ** 2))))


class Reference:
    """Parameters and AdamW state from the seed; ``step(rows)`` trains one
    step on a list of token rows and returns its loss and the norm of
    every leaf's gradient."""

    def __init__(self, cfg: dict, opt: dict, seed: int, seeded,
                 variant: str = "reference", device=None):
        if variant not in VARIANTS:
            raise ValueError(f"variant {variant!r} not in {VARIANTS}")
        self.cfg, self.variant = cfg, variant
        self.device = device or jax.devices()[0]
        self.specs = leaf_specs(cfg)
        self.count = 0
        self._key = jax.device_put(seeded.key(seed), self.device)
        self._make = jax.jit(seeded.leaf, static_argnums=(2, 3))

        def weights_and_moments(k):
            p = seeded.leaves(k, self.specs)
            zeros = lambda: {n: jnp.zeros_like(a) for n, a in p.items()}
            return p, zeros(), zeros()

        with jax.default_device(self.device):
            self.p, self.mu, self.nu = jax.jit(weights_and_moments)(
                self._key)
        self._p = _programs(json.dumps(cfg, sort_keys=True),
                            json.dumps(opt, sort_keys=True),
                            variant == "fp8", variant == "last_pass_only")

    def _layer(self, i):
        return {n: self.p[f"l{i}.{n}"] for n in BLOCK_LEAVES}

    def _gradients(self, toks, positions, acc: dict):
        """One row's loss; its gradients are added into ``acc``."""
        def add(name, g):
            acc[name] = g if name not in acc else acc[name] + g

        nl, passes = self.cfg["num_hidden_layers"], \
            self.cfg["total_ut_steps"]
        x = self._p.embed(self.p["embed"], toks)
        kept, before_norm, states = [], [], []
        for _ in range(passes):
            for i in range(nl):
                kept.append(x)
                x = self._p.fwd(self._layer(i), x)
            before_norm.append(x)
            x = self._p.norm(self.p["ln_f"], x)
            states.append(x)
        loss, dhp, dstates = self._p.heads(
            {n: self.p[n] for n in HEAD_LEAVES}, jnp.stack(states), toks,
            positions)
        del states
        for n, g in dhp.items():
            add(n, g)
        # Down the passes: a pass's state takes the gradient of its own
        # head and gate, and of the pass that took it as its input.
        last_only = self.variant == "last_pass_only"
        dx = None
        for t in reversed(range(passes - 1 if last_only else 0, passes)):
            dh = dstates[t] if dx is None else dstates[t] + dx
            dscale, dx = self._p.norm_bwd(self.p["ln_f"],
                                          before_norm.pop(), dh)
            add("ln_f", dscale)
            for i in reversed(range(nl)):
                dlp, dx = self._p.bwd(self._layer(i), kept.pop(), dx)
                for n, g in dlp.items():
                    add(f"l{i}.{n}", g)
        if not last_only:
            add("embed", self._p.embed_bwd(toks, dx,
                                           self.cfg["vocab_size"]))
        return loss

    def step(self, rows):
        """``rows``: list of (T,) int32 token rows. Returns (loss, {leaf:
        gradient norm})."""
        t = len(rows[0])
        positions = (t // 2) if self.variant == "half_batch" else t - 1
        self.count += 1
        acc: dict = {}
        losses = [self._gradients(
            jax.device_put(jnp.asarray(r, jnp.int32), self.device),
            positions, acc) for r in rows]
        norms = {}
        for name in self.p:
            g = acc.pop(name, None)     # none: no gradient reaches it
            g = jnp.zeros_like(self.p[name]) if g is None \
                else g * (1.0 / len(rows))
            norms[name] = self._p.norm_of(g)
            if self.variant != "unchanged":
                self.p[name], self.mu[name], self.nu[name] = self._p.update(
                    self.p[name], self.mu[name], self.nu[name], g,
                    jnp.float32(self.count))
        loss = float(np.mean([float(np.asarray(l)) for l in losses]))
        return loss, {n: float(np.asarray(v)) for n, v in norms.items()}

    def change_norms(self) -> dict:
        """Norm of every leaf's change since the seed's weights."""
        out = {}
        for i, (name, shape, init) in enumerate(self.specs):
            p0 = self._make(self._key, i, shape, init)
            out[name] = self._p.change(self.p[name], p0)
        return {n: float(np.asarray(v)) for n, v in out.items()}


def run(cfg: dict, traffic: dict, seed: int, chips: int, seeded,
        steps: int = 3, variant: str = "reference", devices=None,
        log=None) -> dict:
    """Follow the first ``steps`` steps of the cell from the seed, every
    rank's rows on the first device. Returns ``{"loss": [..],
    "grad_norm": {leaf: norm at step 1}, "change_norm": {leaf: norm after
    the steps}}``."""
    import time

    t0 = time.perf_counter()
    log = log or (lambda msg: None)
    with jax.default_matmul_precision("highest"):
        ref = Reference(cfg, traffic["optimizer"], seed, seeded, variant,
                        devices[0] if devices else None)
        jax.block_until_ready(ref.p)
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
