"""Plain reference of the convolution-and-attention expert-layer LM
training cells (LFM2-24B-A2B, ``model_type`` ``lfm2_moe``): forward, loss,
gradients and AdamW in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision — no kernel, no fused head, no sorting, no
grouped product, no collectives.

The model (x of one sequence, (T, E); every norm an RMSNorm, eps
``norm_eps`` = 1e-5; no bias anywhere):

  block l   h = x + M_l(norm1(x));   y = h + F_l(norm2(h))     (pre-norm)
            M_l by ``layer_types[l]``: ``conv`` or ``full_attention``
            F_l = dense SwiGLU, width ``intermediate_size``, for
            l < ``num_dense_layers``;  F_l = MoE after them
  conv(n)   [B | C | u] = n W_in              (E -> 3E, in that order)
            z = B * u
            c_t = sum_{j<K} w[:, j] * z_{t-(K-1)+j}   (K = ``conv_L_cache``
                                              taps a channel, z = 0 before
                                              position 0; three shifted
                                              products)
            out = (C * c) W_out               (E -> E)
  attn(n)   q = n W_q -> H heads of D;  k = n W_k, v = n W_v -> G heads
            q = rope(norm_q(q)), k = rope(norm_k(k))    (RMSNorm over each
                                              head's D channels, one scale
                                              vector for the queries and
                                              one for the keys; rope on all
                                              D channels, base
                                              ``rope_parameters.rope_theta``)
            o = causal softmax(q k^T / sqrt(D)) v, each KV head serving
            H / G query heads;  out = o W_o
  MoE(n)    s = sigmoid(n W_r)                (all ``published``
                                              num_experts scores)
            idx = top-k of (s + b)            (k = ``num_experts_per_tok``)
            g_k = ``routed_scaling_factor`` * s[idx_k]
                  / (sum_j s[idx_j] + 1e-20)  (norm_topk_prob)
            out = sum over k with idx_k HELD HERE of g_k * Expert_{idx_k}(n)
            Expert: (silu(n Wg) * (n Wu)) Wd, width
            ``moe_intermediate_size``;  NO shared expert
  stack     x_0 = Embed(tokens);  h = norm_f(block_{L-1}(... block_0(x_0)))
  loss      mean_{i < T-1} CE(h_i W_head, t_{i+1})

Departures from the published model, each also under ``assumed`` in the
configuration's file: ``b`` (the selection bias of ``use_expert_bias``) is
a constant of zeros — its update rule is not in ``config.json``; the gate's
epsilon is 1e-20; the rotary pairing is the half-split one; the head is
not tied to the embedding; the embedding's rows are drawn at
``embedding_std`` and the taps uniformly within ``conv_init_bound``, every
other matrix at ``initializer_range``. The chip's share: this reference
is given the same share as the program — experts ``first .. first +
num_experts`` of the router's ``published`` count, a vocabulary of
``vocab_size`` rows — and, like the program, leaves out what the absent
experts would add: the gate's denominator runs over all k choices, held
here or not, and the partial result goes on to the next layer. Every held
expert is applied to every token and masked by its gate.

It imports nothing of the program; the float8 rounding, RMSNorm, rotary,
blocked causal attention and AdamW it takes from ``reference/train_lm.py``,
the gated expert, the routed part of an expert layer and the blocked head
from ``reference/train_moe_lm.py``.

So that it fits one 16 GB chip it keeps only each block's input, walks
the blocks down with one ``jax.vjp`` each (which runs that block again)
and, where the step has one row, updates a leaf as soon as its gradient
is whole: float32 weights and two moments, 12 bytes a parameter, and the
gradients of one block.

``variant`` puts the reference in the program's place for the control and
the planted faults (``benchmark/tests``): ``fp8`` rounds both operands of
every matmul to float8_e4m3; ``half_batch`` takes the loss over the first
half of each row's positions; ``unchanged`` computes each step and leaves
the state as it was; ``dropped_tokens`` — every expert takes at most 1.0 x
the mean load, tokens x k / experts, in token order, and drops the rest;
and the two faults this architecture invites: ``conv_ahead`` — the short
convolution looks one position AHEAD (its window is t - K + 2 .. t + 1) —
and ``no_qk_norm`` (queries and keys go to the rotary un-normed).
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.train_lm import (_adamw, _attention, _mm, _rmsnorm,
                                          _rotary)
from benchmark.reference.train_moe_lm import _ce_mean, _gated
from benchmark.reference.train_moe_lm import moe as _routed

VARIANTS = ("reference", "fp8", "half_batch", "unchanged", "dropped_tokens",
            "conv_ahead", "no_qk_norm")
MIXER_LEAVES = {"conv": ("win", "taps", "wout"),
                "full_attention": ("wq", "wk", "wv", "lnq", "lnk", "wo")}
FFN_LEAVES = {"dense": ("wg", "wu", "wd"), "moe": ("wr", "eg", "eu", "ed")}


def experts_total(cfg: dict) -> int:
    """The router's width: the published count of routed experts."""
    return cfg["published"]["num_experts"]


def first_expert(cfg: dict) -> int:
    return cfg["expert_share"]["index"] * cfg["num_experts"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict, i: int):
    """(mixer, feed-forward) of layer ``i``."""
    return (cfg["layer_types"][i],
            "dense" if i < cfg["num_dense_layers"] else "moe")


def layer_leaves(cfg: dict, i: int):
    mixer, ffn = layer_kinds(cfg, i)
    return ("ln1",) + MIXER_LEAVES[mixer] + ("ln2",) + FFN_LEAVES[ffn]


def leaf_specs(cfg: dict):
    """(name, shape, init) of every parameter, in a fixed order."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer's mixer")
    e, h, g = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    d, m, f = head_dim(cfg), cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    held, v = cfg["num_experts"], cfg["vocab_size"]
    shapes = {"ln1": (e,), "ln2": (e,), "win": (e, 3 * e),
              "taps": (e, cfg["conv_L_cache"]), "wout": (e, e),
              "wq": (e, h, d), "wk": (e, g, d), "wv": (e, g, d),
              "lnq": (d,), "lnk": (d,), "wo": (h, d, e),
              "wg": (e, m), "wu": (e, m), "wd": (m, e),
              "wr": (e, experts_total(cfg)), "eg": (held, e, f),
              "eu": (held, e, f), "ed": (held, f, e)}
    std, ones = ("normal", cfg["initializer_range"]), ("ones",)
    bound = cfg["conv_init_bound"]
    init = lambda n: ones if n.startswith("ln") else \
        ("uniform", -bound, bound) if n == "taps" else std
    specs = [("embed", (v, e), ("normal", cfg["embedding_std"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"l{i}.{n}", shapes[n], init(n))
                  for n in layer_leaves(cfg, i)]
    return specs + [("ln_f", (e,), ones), ("head", (e, v), std)]


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def _earlier(z, shift: int):
    """Row t holds ``z[t - shift]`` (``z[t + 1]`` for shift -1), zero
    where that is outside the sequence."""
    if shift == 0:
        return z
    if shift > 0:
        return jnp.concatenate([jnp.zeros_like(z[:shift]), z[:-shift]])
    return jnp.concatenate([z[-shift:], jnp.zeros_like(z[:-shift])])


def short_conv(lp, n, fp8, ahead=0):
    """The gated short convolution of one sequence, its taps as shifted
    products; ``ahead`` moves the window that many positions later (the
    planted fault)."""
    e = lp["wout"].shape[0]
    bcu = _mm("te,ec->tc", n, lp["win"], fp8)
    b, c, u = bcu[:, :e], bcu[:, e:2 * e], bcu[:, 2 * e:]
    z = b * u
    taps = lp["taps"].shape[1]
    conv = sum(lp["taps"][:, j] * _earlier(z, taps - 1 - j - ahead)
               for j in range(taps))
    return _mm("te,ef->tf", c * conv, lp["wout"], fp8)


def attention(lp, n, cfg, fp8, qk_norm=True):
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = _mm("te,ehd->thd", n, lp["wq"], fp8)
    k = _mm("te,egd->tgd", n, lp["wk"], fp8)
    v = _mm("te,egd->tgd", n, lp["wv"], fp8)
    if qk_norm:
        q, k = _rmsnorm(q, lp["lnq"], eps), _rmsnorm(k, lp["lnk"], eps)
    o = _attention(_rotary(q, theta), _rotary(k, theta), v, None, fp8)
    return _mm("thd,hde->te", o, lp["wo"], fp8)


def moe(lp, n, cfg, fp8, drop=False, bias=None, first=None):
    """The layer's part that experts ``first .. first + held`` give: the
    expert-layer reference's routed part (``num_experts_per_tok`` and
    ``routed_scaling_factor`` are both files' keys), with no shared
    expert beside it."""
    first = first_expert(cfg) if first is None else first
    return _routed(lp, n, cfg, fp8, drop, bias, first, shared=False)


def _block(lp, x, cfg, fp8, fault):
    eps = cfg["norm_eps"]
    n = _rmsnorm(x, lp["ln1"], eps)
    if "win" in lp:
        h = x + short_conv(lp, n, fp8, ahead=fault == "conv_ahead")
    else:
        h = x + attention(lp, n, cfg, fp8, qk_norm=fault != "no_qk_norm")
    n = _rmsnorm(h, lp["ln2"], eps)
    if "wr" in lp:
        return h + moe(lp, n, cfg, fp8, drop=fault == "dropped_tokens")
    return h + _gated(n, lp["wg"], lp["wu"], lp["wd"], fp8)


def _loss(head, h, tokens, fp8, half):
    """Mean cross-entropy over the positions with a next token (``half``:
    over the first half of the row's positions)."""
    t = h.shape[0]
    count = t // 2 if half else t - 1
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    return _ce_mean(head, h, targets,
                    (jnp.arange(t) < count).astype(jnp.float32), count, fp8)


# ---------------------------------------------------------------------------
# the state on one device; a step walks the blocks up and down
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, opt_json: str, fp8: bool, fault: str):
    """The jitted pieces of a step, built once for a configuration."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    block = functools.partial(_block, cfg=cfg, fp8=fp8, fault=fault)
    norm = lambda scale, x: _rmsnorm(x, scale, cfg["norm_eps"])

    def loss_bwd(head, h, toks, half):
        loss, vjp = jax.vjp(
            lambda hd, h_: _loss(hd, h_, toks, fp8, half), head, h)
        return (loss,) + vjp(jnp.ones((), loss.dtype))

    return types.SimpleNamespace(
        embed=jax.jit(lambda emb, toks: emb[toks]),
        fwd=jax.jit(block),
        bwd=jax.jit(lambda lp, x, dy: jax.vjp(block, lp, x)[1](dy)),
        norm=jax.jit(norm),
        norm_bwd=jax.jit(lambda s, x, dy: jax.vjp(norm, s, x)[1](dy)),
        loss=jax.jit(loss_bwd, static_argnums=(3,)),
        embed_bwd=jax.jit(
            lambda toks, dx, v: jnp.zeros((v, dx.shape[1]),
                                          jnp.float32).at[toks].add(dx),
            static_argnums=(2,)),
        update=jax.jit(lambda p, m, v, g, count: _adamw(p, m, v, g, count,
                                                        opt),
                       donate_argnums=(0, 1, 2)),
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
                            variant == "fp8", variant)

    def _layer(self, i):
        return {n: self.p[f"l{i}.{n}"] for n in layer_leaves(self.cfg, i)}

    def _gradients(self, toks, add):
        """One row's loss; ``add(name, g, whole)`` takes each leaf's
        gradient, ``whole`` once nothing more of this row comes for it."""
        cfg, pr = self.cfg, self._p
        nl = cfg["num_hidden_layers"]
        x = pr.embed(self.p["embed"], toks)
        kept = []
        for i in range(nl):
            kept.append(x)
            x = pr.fwd(self._layer(i), x)
        h = pr.norm(self.p["ln_f"], x)
        loss, dhead, dh = pr.loss(self.p["head"], h, toks,
                                  self.variant == "half_batch")
        add("head", dhead, True)
        dscale, dx = pr.norm_bwd(self.p["ln_f"], x, dh)
        add("ln_f", dscale, True)
        del h, dh, dhead
        for i in reversed(range(nl)):
            dlp, dx = pr.bwd(self._layer(i), kept.pop(), dx)
            for n, g in dlp.items():
                add(f"l{i}.{n}", g, True)
            del dlp
        add("embed", pr.embed_bwd(toks, dx, cfg["vocab_size"]), True)
        return loss

    def step(self, rows):
        """``rows``: list of (T,) int32 token rows. Returns (loss, {leaf:
        gradient norm})."""
        self.count += 1
        acc, norms = {}, {}
        scale = 1.0 / len(rows)

        def finish(name):
            g = acc.pop(name) * scale
            norms[name] = self._p.norm_of(g)
            if self.variant != "unchanged":
                self.p[name], self.mu[name], self.nu[name] = self._p.update(
                    self.p[name], self.mu[name], self.nu[name], g,
                    jnp.float32(self.count))

        losses = []
        for r, row in enumerate(rows):
            last = r == len(rows) - 1

            def add(name, g, whole):
                acc[name] = g if name not in acc else acc[name] + g
                if whole and last:  # nothing more comes: update it now
                    finish(name)

            losses.append(self._gradients(
                jax.device_put(jnp.asarray(row, jnp.int32), self.device),
                add))
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
