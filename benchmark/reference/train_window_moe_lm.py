"""Plain reference of the windowed-and-full-attention expert-layer LM
training cells (Trinity-Mini, ``model_type`` ``afmoe``): forward, loss,
gradients and AdamW in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision — no kernel, no fused head, no sorting, no
grouped product, no collectives.

The model (x of one sequence, (T, E); every norm an RMSNorm, eps
``rms_norm_eps`` = 1e-5; no bias anywhere; H = ``num_attention_heads``
query heads and G = ``num_key_value_heads`` key / value heads, each
D = ``head_dim`` wide, H x D free of E):

  stack     x_0 = Embed(tokens) * sqrt(E)         (``mup_enabled``)
            h = norm_f(block_{L-1}(... block_0(x_0)))
  block l   y = norm_ai(x)
            x = x + norm_ao(A_l(y))               (sandwich norms: four
            z = norm_fi(x)                         RMSNorms a block)
            x = x + norm_fo(F_l(z))
  A_l(y)    q = y W_q -> H heads of D;  k = y W_k, v = y W_v -> G heads
            q, k = norm_q(q), norm_k(k)           (over each head's D
                                                   channels, one scale
                                                   vector for the queries
                                                   and one for the keys)
            ``sliding_attention`` layer: q, k = rope(q), rope(k) (base
              ``rope_theta``, all D channels, half-split pairs); key j
              visible to query i iff i - ``sliding_window`` < j <= i
            ``full_attention`` layer: no rotary; j <= i
            o = softmax(q k^T / sqrt(D) + mask) v, each KV head serving
                H / G query heads
            out = (o * sigmoid(y W_gate)) W_o     (W_gate: E -> H x D)
  F_l(z)    dense SwiGLU, width ``intermediate_size``, for
            l < ``num_dense_layers``; after them
            s = sigmoid(z W_r)                    (all ``published``
                                                   num_experts scores)
            idx = top-k of (s + b)                (k = ``num_experts_per_tok``)
            g_k = ``route_scale`` * s[idx_k] / (sum_j s[idx_j] + 1e-20)
            out = Shared(z) + sum over k with idx_k HELD HERE of
                  g_k * Expert_{idx_k}(z)
            Expert, Shared: (silu(z Wg) * (z Wu)) Wd, width
            ``moe_intermediate_size`` (x ``num_shared_experts``)
  loss      mean_{i < T-1} CE(h_i W_head, t_{i+1})

The layer's parts that the model's public modelling code has and
``config.json`` has no key for (the q/k norms, the output gate, the
sandwich norms, rotary on the sliding layers only, the embedding's
multiplier) are listed under ``assumed`` in the configuration's file, as
are the departures: ``b`` (the selection bias that ``load_balance_coeff``
steps) is a constant of zeros, its rule not run; the head is not tied;
the embedding's rows are drawn at ``embedding_std`` (1.0: the
configuration's file gives the measured reason), every other matrix at
``initializer_range``. Every leaf trains, the routers included. The
chip's share: this reference is given the same share as the program —
experts ``first .. first + num_experts`` of the router's ``published``
count, a vocabulary of
``vocab_size`` rows — and, like the program, leaves out what the absent
experts would add: the gate's denominator runs over all k choices, held
here or not, and the partial result goes on to the next layer. Every held
expert is applied to every token and masked by its gate.

It imports nothing of the program; the float8 rounding, RMSNorm, rotary,
blocked causal attention with a window and AdamW it takes from
``reference/train_lm.py``, the gated expert, the routed part of an expert
layer with its shared expert and the blocked head from
``reference/train_moe_lm.py``.

So that it fits one 16 GB chip it keeps only each block's input, walks
the blocks down with one ``jax.vjp`` each (which runs that block again)
and, where the step has one row, updates a leaf as soon as its gradient
is whole: float32 weights and two moments, 12 bytes a parameter, and the
gradients of one block.

``variant`` puts the reference in the program's place for the control and
the planted faults (``benchmark/tests``): ``fp8`` rounds both operands of
every matmul to float8_e4m3; ``half_batch`` takes the loss over the first
half of each row's positions; ``unchanged`` computes each step and leaves
the state as it was; and one fault for each part this architecture adds:
``sliding_full`` (the sliding layers attend full causal),
``rotary_everywhere`` (the full layers take the rotary embedding too),
``no_gate`` (the output gate left out), ``no_embed_scale`` (the
embedding's multiplier left out) and ``narrow_heads`` (the scores scaled
by 1/sqrt(E / H), the width a head would have were it not decoupled,
in place of 1/sqrt(D)).
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

VARIANTS = ("reference", "fp8", "half_batch", "unchanged", "sliding_full",
            "rotary_everywhere", "no_gate", "no_embed_scale", "narrow_heads")
KINDS = ("sliding_attention", "full_attention")
ATTENTION_LEAVES = ("ln_ai", "wq", "wk", "wv", "lnq", "lnk", "wgate", "wo",
                    "ln_ao")
FFN_LEAVES = {"dense": ("ln_fi", "wg", "wu", "wd", "ln_fo"),
              "moe": ("ln_fi", "wr", "eg", "eu", "ed", "sg", "su", "sd",
                      "ln_fo")}


def experts_total(cfg: dict) -> int:
    """The router's width: the published count of routed experts."""
    return cfg["published"]["num_experts"]


def first_expert(cfg: dict) -> int:
    return cfg["expert_share"]["index"] * cfg["num_experts"]


def embed_scale(cfg: dict) -> float:
    return cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else 1.0


def layer_kinds(cfg: dict, i: int):
    """(attention kind, feed-forward) of layer ``i``."""
    return (cfg["layer_types"][i],
            "dense" if i < cfg["num_dense_layers"] else "moe")


def layer_leaves(cfg: dict, i: int):
    return ATTENTION_LEAVES + FFN_LEAVES[layer_kinds(cfg, i)[1]]


def check(cfg: dict) -> None:
    """What this reference's model is; raises for another."""
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["n_group"] != 1 or cfg["hidden_act"] != "silu" \
            or cfg["rope_scaling"] is not None or cfg["tie_word_embeddings"] \
            or not cfg["attention_output_gate"]:
        raise ValueError("not a configuration this reference computes")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"] \
            or not set(cfg["layer_types"]) <= set(KINDS):
        raise ValueError("layer_types does not name every layer's kind")


def leaf_specs(cfg: dict):
    """(name, shape, init) of every parameter, in a fixed order."""
    check(cfg)
    e, h, g, d = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    m, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    s = cfg["num_shared_experts"] * f
    held, v = cfg["num_experts"], cfg["vocab_size"]
    shapes = {"wq": (e, h, d), "wk": (e, g, d), "wv": (e, g, d),
              "lnq": (d,), "lnk": (d,), "wgate": (e, h, d), "wo": (h, d, e),
              "wg": (e, m), "wu": (e, m), "wd": (m, e),
              "wr": (e, experts_total(cfg)), "eg": (held, e, f),
              "eu": (held, e, f), "ed": (held, f, e), "sg": (e, s),
              "su": (e, s), "sd": (s, e)}
    std, ones = ("normal", cfg["initializer_range"]), ("ones",)
    specs = [("embed", (v, e), ("normal", cfg["embedding_std"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"l{i}.{n}", shapes.get(n, (e,)),
                   ones if n.startswith("ln") else std)
                  for n in layer_leaves(cfg, i)]
    return specs + [("ln_f", (e,), ones), ("head", (e, v), std)]


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def attention(lp, y, cfg, kind, fp8, fault="reference"):
    """One attention mixer of ``kind`` on one sequence's normed input."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _rmsnorm(_mm("te,ehd->thd", y, lp["wq"], fp8), lp["lnq"], eps)
    k = _rmsnorm(_mm("te,egd->tgd", y, lp["wk"], fp8), lp["lnk"], eps)
    v = _mm("te,egd->tgd", y, lp["wv"], fp8)
    sliding = kind == "sliding_attention"
    if sliding or fault == "rotary_everywhere":
        q, k = _rotary(q, theta), _rotary(k, theta)
    if fault == "narrow_heads":  # (_attention divides by sqrt(D))
        q = q * (q.shape[-1] * cfg["num_attention_heads"]
                 / cfg["hidden_size"]) ** 0.5
    window = cfg["sliding_window"] if sliding and fault != "sliding_full" \
        else None
    o = _attention(q, k, v, window, fp8)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(_mm("te,ehd->thd", y, lp["wgate"], fp8))
    return _mm("thd,hde->te", o, lp["wo"], fp8)


def moe(lp, z, cfg, fp8, first=None):
    """The layer's part that experts ``first .. first + held`` give, and
    the shared expert's: the expert-layer reference's, with this file's
    keys."""
    keys = {"num_experts_per_tok": cfg["num_experts_per_tok"],
            "routed_scaling_factor": cfg["route_scale"]}
    first = first_expert(cfg) if first is None else first
    return _routed(lp, z, keys, fp8, first=first,
                   shared=cfg["num_shared_experts"] > 0)


def _block(lp, x, cfg, kind, fp8, fault):
    eps = cfg["rms_norm_eps"]
    norm = lambda a, name: _rmsnorm(a, lp[name], eps)
    x = x + norm(attention(lp, norm(x, "ln_ai"), cfg, kind, fp8, fault),
                 "ln_ao")
    z = norm(x, "ln_fi")
    if "wr" in lp:
        f = moe(lp, z, cfg, fp8)
    else:
        f = _gated(z, lp["wg"], lp["wu"], lp["wd"], fp8)
    return x + norm(f, "ln_fo")


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
    """The jitted pieces of a step, built once for a configuration: a
    block's forward and backward for each attention kind."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    scale = 1.0 if fault == "no_embed_scale" else embed_scale(cfg)
    block = {kind: functools.partial(_block, cfg=cfg, kind=kind, fp8=fp8,
                                     fault=fault) for kind in KINDS}
    norm = lambda s, x: _rmsnorm(x, s, cfg["rms_norm_eps"])

    def loss_bwd(head, h, toks, half):
        loss, vjp = jax.vjp(
            lambda hd, h_: _loss(hd, h_, toks, fp8, half), head, h)
        return (loss,) + vjp(jnp.ones((), loss.dtype))

    return types.SimpleNamespace(
        embed=jax.jit(lambda emb, toks: emb[toks] * scale),
        fwd={kind: jax.jit(b) for kind, b in block.items()},
        bwd={kind: jax.jit(lambda lp, x, dy, b=b: jax.vjp(b, lp, x)[1](dy))
             for kind, b in block.items()},
        norm=jax.jit(norm),
        norm_bwd=jax.jit(lambda s, x, dy: jax.vjp(norm, s, x)[1](dy)),
        loss=jax.jit(loss_bwd, static_argnums=(3,)),
        embed_bwd=jax.jit(
            lambda toks, dx, v: jnp.zeros((v, dx.shape[1]), jnp.float32)
            .at[toks].add(dx * scale), static_argnums=(2,)),
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
        kind = lambda i: layer_kinds(cfg, i)[0]
        x = pr.embed(self.p["embed"], toks)
        kept = []
        for i in range(nl):
            kept.append(x)
            x = pr.fwd[kind(i)](self._layer(i), x)
        h = pr.norm(self.p["ln_f"], x)
        loss, dhead, dh = pr.loss(self.p["head"], h, toks,
                                  self.variant == "half_batch")
        add("head", dhead, True)
        dscale, dx = pr.norm_bwd(self.p["ln_f"], x, dh)
        add("ln_f", dscale, True)
        del h, dh, dhead
        for i in reversed(range(nl)):
            dlp, dx = pr.bwd[kind(i)](self._layer(i), kept.pop(), dx)
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
