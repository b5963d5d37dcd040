"""Plain reference of the latent-attention / expert-layer LM training
cells (GLM-4.7-Flash, ``model_type`` ``glm4_moe_lite``): forward, loss,
gradients and AdamW in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision — no kernel, no fused head, no sorting, no
grouped product, no collectives.

The model (x of one sequence, (T, E); every norm an RMSNorm, eps
``rms_norm_eps`` = 1e-5; no bias anywhere):

  block l   h = x + MLA(norm1(x));   y = h + F_l(norm2(h))     (pre-norm)
            F_l = dense SwiGLU, width ``intermediate_size``, for
            l < ``first_k_dense_replace``;  F_l = MoE after them
  MLA(n)    c_q = norm_q(n W_qa)              (``q_lora_rank``)
            q = c_q W_qb  -> heads x (nope | rope)
            [c_kv | k_r] = n W_kva            (``kv_lora_rank`` | rope)
            [k_nope | v] = norm_kv(c_kv) W_kvb -> heads x (nope | v)
            q = [q_nope | rope(q_rope)]
            k = [k_nope | rope(k_r) broadcast to every head]
            o = causal softmax(q k^T / sqrt(nope + rope)) v   (full causal)
            out = o W_o;  rope: base ``rope_theta``, on the rope part only
  MoE(n)    s = sigmoid(n W_r)                (all ``published``
                                              n_routed_experts scores)
            idx = top-k of (s + b)            (k = ``num_experts_per_tok``;
                                              n_group = topk_group = 1)
            g_k = ``routed_scaling_factor`` * s[idx_k]
                  / (sum_j s[idx_j] + 1e-20)  (norm_topk_prob)
            out = Shared(n) + sum over k with idx_k HELD HERE of
                  g_k * Expert_{idx_k}(n)
            Expert, Shared: (silu(n Wg) * (n Wu)) Wd, width
            ``moe_intermediate_size`` (x ``n_shared_experts``)
  stack     x_0 = Embed(tokens);  h = norm_f(block_{L-1}(... block_0(x_0)))
  MTP       m_i = [norm_h(h_i) | norm_e(Embed(t_{i+1}))] W_eh   (2E -> E)
            z = norm_mtp(block_mtp(m))   one more MLA + MoE block of its
            own leaves, on all T positions (a pad id where t_{i+1} does
            not exist); the SAME embedding and the SAME head
  loss      mean_{i < T-1} CE(h_i W_head, t_{i+1})
            + lambda * mean_{i < T-2} CE(z_i W_head, t_{i+2})

Departures from the published model, each also under ``assumed`` in the
configuration's file: ``b`` (``e_score_correction_bias``) is a constant of
zeros — its sign-update rule and rate are not in ``config.json``, so it is
not run; the rotary pairing is the half-split one (an interleaved pairing
is a fixed permutation of W_qb / W_kva columns: the same model under
seeded weights); in W_eh the hidden half comes first; MTP reads ``h``
AFTER ``norm_f``; lambda = ``mtp_loss_weight``; the embedding's rows are
drawn at ``embedding_std`` (1.0, a departure the configuration's file
argues: the token's own part then dominates the state and a seed's router
spreads its load), every other matrix at ``initializer_range``. The chip's share: this
reference is given the same share as the program — experts
``first .. first + n_routed_experts`` of the router's ``published``
count, a vocabulary of ``vocab_size`` rows — and, like the program,
leaves out what the absent experts would add: the gate's denominator runs
over all k choices, held here or not, and the partial result goes on to
the next layer. Every held expert is applied to every token and masked by
its gate.

It imports nothing of the program; the float8 rounding, RMSNorm, rotary
and AdamW it takes from ``reference/train_lm.py``.

So that it fits one 16 GB chip it keeps only each block's input, walks
the blocks down with one ``jax.vjp`` each (which runs that block again)
and, where the step has one row, updates a leaf as soon as its gradient
is whole: float32 weights and two moments, 12 bytes a parameter, and the
gradients of one block.

``variant`` puts the reference in the program's place for the control and
the planted faults (``benchmark/tests``): ``fp8`` rounds both operands of
every matmul to float8_e4m3; ``half_batch`` takes both losses over the
first half of each row's positions; ``unchanged`` computes each step and
leaves the state as it was; and the two faults this architecture invites:
``dropped_tokens`` — every expert takes at most 1.0 x the mean load,
tokens x k / experts, in token order, and drops the rest — and ``no_mtp``
(lambda = 0).
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.train_lm import _adamw, _mm, _rmsnorm, _rotary

VARIANTS = ("reference", "fp8", "half_batch", "unchanged", "dropped_tokens",
            "no_mtp")
MLA_LEAVES = ("ln1", "wqa", "lnq", "wqb", "wkva", "lnkv", "wkvb", "wo",
              "ln2")
DENSE_LEAVES = MLA_LEAVES + ("wg", "wu", "wd")
MOE_LEAVES = MLA_LEAVES + ("wr", "eg", "eu", "ed", "sg", "su", "sd")
PAD_ID = 0


def experts_total(cfg: dict) -> int:
    """The router's width: the published count of routed experts."""
    return cfg["published"]["n_routed_experts"]


def first_expert(cfg: dict) -> int:
    return cfg["expert_share"]["index"] * cfg["n_routed_experts"]


def layer_kind(cfg: dict, i: int) -> str:
    return "dense" if i < cfg["first_k_dense_replace"] else "moe"


def _block_specs(cfg: dict, prefix: str, kind: str):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    m, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, s = cfg["n_routed_experts"], \
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    shapes = {"ln1": (e,), "wqa": (e, qr), "lnq": (qr,),
              "wqb": (qr, h, nope + rope), "wkva": (e, kvr + rope),
              "lnkv": (kvr,), "wkvb": (kvr, h, nope + vd), "wo": (h, vd, e),
              "ln2": (e,), "wg": (e, m), "wu": (e, m), "wd": (m, e),
              "wr": (e, experts_total(cfg)), "eg": (held, e, f),
              "eu": (held, e, f), "ed": (held, f, e), "sg": (e, s),
              "su": (e, s), "sd": (s, e)}
    std, ones = ("normal", cfg["initializer_range"]), ("ones",)
    names = DENSE_LEAVES if kind == "dense" else MOE_LEAVES
    return [(f"{prefix}.{n}", shapes[n], ones if n.startswith("ln") else std)
            for n in names]


def leaf_specs(cfg: dict):
    """(name, shape, init) of every parameter, in a fixed order."""
    if cfg["num_nextn_predict_layers"] != 1 or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1:
        raise ValueError("this reference has one MTP module and one "
                         "routing group")
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    std, ones = ("normal", cfg["initializer_range"]), ("ones",)
    specs = [("embed", (v, e), ("normal", cfg["embedding_std"]))]
    for i in range(cfg["num_hidden_layers"]):
        specs += _block_specs(cfg, f"l{i}", layer_kind(cfg, i))
    specs += [("ln_f", (e,), ones), ("head", (e, v), std),
              ("mtp.lnh", (e,), ones), ("mtp.lne", (e,), ones),
              ("mtp.weh", (2 * e, e), std)]
    specs += _block_specs(cfg, "mtp", "moe")
    specs += [("mtp.ln", (e,), ones)]
    return specs


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def _causal_attention(q, k, v, fp8):
    """Full causal attention of one sequence: q, k (T, H, D), v (T, H, Dv),
    scores over sqrt(D); blocks of query rows against every key."""
    t, h, d = q.shape
    qb = min(512, t)
    if t % qb:
        raise ValueError(f"sequence length {t} is not a multiple of {qb}")

    @jax.checkpoint
    def block(qblk, i):
        s = _mm("qhd,shd->hqs", qblk, k, fp8) / np.sqrt(d)
        qpos = i * qb + jnp.arange(qb)[:, None]
        ok = jnp.arange(t)[None, :] <= qpos
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return _mm("hqs,shd->qhd", p, v, fp8)

    out = lax.map(lambda a: block(*a),
                  (q.reshape(t // qb, qb, h, d), jnp.arange(t // qb)))
    return out.reshape(t, h, v.shape[-1])


def mla(lp, n, cfg, fp8):
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nope, kvr = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    cq = _rmsnorm(_mm("te,er->tr", n, lp["wqa"], fp8), lp["lnq"], eps)
    q = _mm("tr,rhd->thd", cq, lp["wqb"], fp8)
    latent = _mm("te,ec->tc", n, lp["wkva"], fp8)
    ckv = _rmsnorm(latent[:, :kvr], lp["lnkv"], eps)
    kv = _mm("tc,chd->thd", ckv, lp["wkvb"], fp8)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], -1)
    kr = _rotary(latent[:, None, kvr:], theta)             # (T, 1, rope)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr, (kr.shape[0], q.shape[1], kr.shape[2]))], -1)
    o = _causal_attention(q, k, kv[..., nope:], fp8)
    return _mm("thd,hde->te", o, lp["wo"], fp8)


def _gated(n, wg, wu, wd, fp8):
    return _mm("tm,me->te", jax.nn.silu(_mm("te,em->tm", n, wg, fp8))
               * _mm("te,em->tm", n, wu, fp8), wd, fp8)


def gates_of(scores, bias, cfg):
    """(T, total) gates: zero but at each token's top-k of ``scores +
    bias``, there ``routed_scaling_factor * s / (sum of the k + 1e-20)``."""
    _, idx = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[1],
                                    dtype=scores.dtype), axis=1)
    picked = scores * chosen
    return cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, axis=1, keepdims=True) + 1e-20)


def moe(lp, n, cfg, fp8, drop=False, bias=None, first=None, shared=True):
    """The layer's part that experts ``first .. first + held`` give (and,
    with ``shared``, the shared expert's)."""
    held = lp["eg"].shape[0]
    first = first_expert(cfg) if first is None else first
    scores = jax.nn.sigmoid(_mm("te,ex->tx", n, lp["wr"], fp8))
    bias = jnp.zeros((scores.shape[1],), scores.dtype) if bias is None \
        else bias
    gates = lax.dynamic_slice_in_dim(gates_of(scores, bias, cfg), first,
                                     held, axis=1)           # (T, held)
    if drop:  # the planted fault: a capacity of 1.0 x the mean load
        cap = n.shape[0] * cfg["num_experts_per_tok"] // scores.shape[1]
        taken = jnp.cumsum((gates > 0).astype(jnp.int32), axis=0)
        gates = jnp.where(taken <= cap, gates, 0.0)

    @jax.checkpoint
    def one(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * _gated(n, wg, wu, wd, fp8), None

    out, _ = lax.scan(one, jnp.zeros_like(n),
                      (lp["eg"], lp["eu"], lp["ed"], gates.T))
    if shared:
        out = out + _gated(n, lp["sg"], lp["su"], lp["sd"], fp8)
    return out


def _block(lp, x, cfg, fp8, drop):
    eps = cfg["rms_norm_eps"]
    h = x + mla(lp, _rmsnorm(x, lp["ln1"], eps), cfg, fp8)
    n = _rmsnorm(h, lp["ln2"], eps)
    if "wr" in lp:
        return h + moe(lp, n, cfg, fp8, drop)
    return h + _gated(n, lp["wg"], lp["wu"], lp["wd"], fp8)


def _mtp_input(mp, h, after, cfg, fp8):
    """m = [norm_h(h) | norm_e(Embed(t_{i+1}))] W_eh, the hidden half
    first; ``after`` the embedding rows of the next tokens."""
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate([_rmsnorm(h, mp["lnh"], eps),
                            _rmsnorm(after, mp["lne"], eps)], -1)
    return _mm("tc,ce->te", both, mp["weh"], fp8)


def _ce_mean(head, y, targets, weight, count, fp8):
    """Sum of ``weight`` x CE(y W_head, targets) over the rows, over
    ``count``; the head in blocks of rows."""
    t = y.shape[0]
    rb = min(1024, t)

    @jax.checkpoint
    def rows(yb, tb, wb):
        logits = _mm("te,ev->tv", yb, head, fp8)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=1)[:, 0]
        return jnp.sum(wb * (lse - picked))

    parts = lax.map(lambda a: rows(*a),
                    (y.reshape(t // rb, rb, -1), targets.reshape(-1, rb),
                     weight.reshape(-1, rb)))
    return jnp.sum(parts) / count


def _loss(head, h, z, tokens, lam, fp8, half):
    """Main loss over the positions with a next token plus ``lam`` x the
    MTP module's over those with a token after it (``half``: both over the
    first half of the row's positions)."""
    t = h.shape[0]
    pos = jnp.arange(t)
    n1, n2 = (t // 2, t // 2) if half else (t - 1, t - 2)
    roll = lambda k: jnp.concatenate([tokens[k:], tokens[:k]])
    main = _ce_mean(head, h, roll(1), (pos < n1).astype(jnp.float32), n1,
                    fp8)
    ahead = _ce_mean(head, z, roll(2), (pos < n2).astype(jnp.float32), n2,
                     fp8)
    return main + lam * ahead


# ---------------------------------------------------------------------------
# the state on one device; a step walks the blocks up and down
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, opt_json: str, fp8: bool, drop: bool):
    """The jitted pieces of a step, built once for a configuration."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    block = functools.partial(_block, cfg=cfg, fp8=fp8, drop=drop)
    norm = lambda scale, x: _rmsnorm(x, scale, cfg["rms_norm_eps"])
    mtp_in = functools.partial(_mtp_input, cfg=cfg, fp8=fp8)

    def loss_bwd(head, h, z, toks, lam, half):
        loss, vjp = jax.vjp(
            lambda hd, h_, z_: _loss(hd, h_, z_, toks, lam, fp8, half),
            head, h, z)
        return (loss,) + vjp(jnp.ones((), loss.dtype))

    def update(p, m, v, g, count):
        return _adamw(p, m, v, g, count, opt)

    return types.SimpleNamespace(
        embed=jax.jit(lambda emb, toks: emb[toks]),
        fwd=jax.jit(block),
        bwd=jax.jit(lambda lp, x, dy: jax.vjp(block, lp, x)[1](dy)),
        norm=jax.jit(norm),
        norm_bwd=jax.jit(lambda s, x, dy: jax.vjp(norm, s, x)[1](dy)),
        mtp_in=jax.jit(mtp_in),
        mtp_in_bwd=jax.jit(
            lambda mp, h, after, dy: jax.vjp(mtp_in, mp, h, after)[1](dy)),
        loss=jax.jit(loss_bwd, static_argnums=(5,)),
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
        self.lam = 0.0 if variant == "no_mtp" else cfg["mtp_loss_weight"]
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
                            variant == "fp8", variant == "dropped_tokens")

    def _layer(self, prefix, kind):
        names = DENSE_LEAVES if kind == "dense" else MOE_LEAVES
        return {n: self.p[f"{prefix}.{n}"] for n in names}

    def _gradients(self, toks, add):
        """One row's loss; ``add(name, g, whole)`` takes each leaf's
        gradient, ``whole`` once nothing more of this row comes for it."""
        cfg, pr = self.cfg, self._p
        nl, v = cfg["num_hidden_layers"], cfg["vocab_size"]
        after_ids = jnp.concatenate(
            [toks[1:], jnp.full((1,), PAD_ID, toks.dtype)])
        x = pr.embed(self.p["embed"], toks)
        kept = []
        for i in range(nl):
            kept.append(x)
            x = pr.fwd(self._layer(f"l{i}", layer_kind(cfg, i)), x)
        h = pr.norm(self.p["ln_f"], x)
        mp = {n: self.p[f"mtp.{n}"] for n in ("lnh", "lne", "weh")}
        after = pr.embed(self.p["embed"], after_ids)
        m = pr.mtp_in(mp, h, after)
        zpre = pr.fwd(self._layer("mtp", "moe"), m)
        z = pr.norm(self.p["mtp.ln"], zpre)
        loss, dhead, dh, dz = pr.loss(self.p["head"], h, z, toks,
                                      jnp.float32(self.lam),
                                      self.variant == "half_batch")
        del z
        add("head", dhead, True)
        # Down the MTP module, whose input's gradient joins the main
        # state's and the next tokens' embedding rows'.
        dscale, dzpre = pr.norm_bwd(self.p["mtp.ln"], zpre, dz)
        add("mtp.ln", dscale, True)
        del zpre, dz
        dlp, dm = pr.bwd(self._layer("mtp", "moe"), m, dzpre)
        for n, g in dlp.items():
            add(f"mtp.{n}", g, True)
        del dlp, m, dzpre
        dmp, dh2, dafter = pr.mtp_in_bwd(mp, h, after, dm)
        for n, g in dmp.items():
            add(f"mtp.{n}", g, True)
        add("embed", pr.embed_bwd(after_ids, dafter, v), False)
        del dmp, dafter, after, dm
        dscale, dx = pr.norm_bwd(self.p["ln_f"], x, dh + dh2)
        add("ln_f", dscale, True)
        for i in reversed(range(nl)):
            dlp, dx = pr.bwd(self._layer(f"l{i}", layer_kind(cfg, i)),
                             kept.pop(), dx)
            for n, g in dlp.items():
                add(f"l{i}.{n}", g, True)
            del dlp
        add("embed", pr.embed_bwd(toks, dx, v), True)
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
