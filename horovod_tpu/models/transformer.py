"""Transformer (GPT-style causal LM) — the long-context model family.

No reference analog (the reference's models are CNNs and word2vec —
SURVEY §5.7); this family exists because long-context training is first-class
in the TPU rebuild. Designed for the MXU: bf16 compute / fp32 params, rotary
position embeddings, pre-norm blocks, and a pluggable attention strategy:

* ``attention='local'``  — every rank sees the full sequence (plain DP),
* ``attention='ring'``   — sequence sharded over a context-parallel group,
  exact attention via :func:`horovod_tpu.ring_attention`,
* ``attention='ulysses'`` — sequence sharded, all-to-all head-parallel
  attention via :func:`horovod_tpu.ulysses_attention`.

With 'ring'/'ulysses' the model consumes the LOCAL sequence shard and rotary
phases are computed from global positions (shard offset), so DP×SP meshes
compose through the group machinery: gradients allreduce over group 0 while
attention rides the SP group's ring.

``num_kv_heads`` enables grouped-query attention (fewer K/V heads; the
ring then carries only the Hkv heads), and ``segment_ids`` masks packed
documents apart — both lower to the flash kernel's native GQA/segment
support on every attention strategy.

The block is a slot, not one block: ``ffn`` chooses the feed-forward
(``'gelu'``: two matrices; ``'swiglu'``: three, gated), ``sandwich_norm``
norms each branch's output as well as its input, ``rope_theta`` is the
rotary base. ``recurrent_steps=R`` makes the model LOOPED: the same
``num_layers`` blocks and the same final norm are applied R times over the
same parameter leaves (one ``lax.scan`` over the passes), each pass's
normed state feeding the next; with ``exit_gate`` every pass but the last
also gives an exit probability and :func:`make_loss_fn` trains all R
passes (``exit_loss``).

Three more kinds live in the same slots (PR 31; a DeepSeek-V3-style model
such as GLM-4.7-Flash uses all three, ``benchmark/configs``):

* ``mla=MLAConfig(...)`` makes :class:`Attention` LATENT (MLA): low-rank
  query and key/value paths with RMSNorms of their own, a rotary part
  beside a no-position part in every query and key head, ONE rotary key
  shared by all heads, value heads of their own width, ``heads x v_dim``
  free of ``embed_dim``. Training and the plain forward only.
* ``moe=MoEConfig(...)`` makes the feed-forward of every layer after the
  first ``dense_layers`` (which keep ``ffn`` at ``mlp_dim``) the
  ``'moe'`` entry of :data:`FFN`: a sigmoid-routed top-k over ``total``
  experts of which this chip holds ``held`` from ``first``
  (``ops/moe.py``: no token dropped, no exchange), beside a shared expert.
* ``mtp=MTPConfig(...)`` adds the multi-token-prediction module: one more
  block of its own leaves over ``[norm(h_i) | norm(Embed(t_{i+1}))]``,
  through the SAME embedding and the SAME head, trained by
  :func:`make_loss_fn` on ``t_{i+2}`` with weight ``mtp.weight``.

**The mixer is a slot too** (PR 33; an LFM2-style hybrid uses it,
``benchmark/configs/lfm2_24b_a2b.json``): ``layer_types`` names each
layer's kind in :data:`MIXER` — ``'attention'`` (:class:`Attention`) or
``'conv'`` (:class:`ShortConv`: a gated depthwise causal convolution of
``conv_taps`` taps between two projections, ``ops/short_conv.py``) — as
DATA of the configuration, read by :func:`mixer_of_layer` as
:func:`ffn_of_layer` reads the expert configuration; ``None`` is attention
everywhere. ``qk_norm`` RMSNorms every head's query and key over its own
channels before the rotary embedding, one learned scale for the queries
and one for the keys (the GQA path; latent attention has norms of its
own). A ``'conv'`` layer RAISES with ``decode=True`` (its state is the
last ``conv_taps - 1`` gated inputs, not a KV cache: serving it is
ROADMAP M6) and with ``attention='ring'`` / ``'ulysses'`` (a shard's first
positions would need a halo of ``conv_taps - 1`` from the shard before).

**Attention kinds by layer** (an AFMoE-style model such as Trinity-Mini
uses them, ``benchmark/configs/trinity_mini.json``): three entries of
:data:`MIXER` are attention, each in :data:`ATTENTION_KINDS` —
``'attention'`` (``window``, which may be None, and rotary: what every
layer was before kinds existed), ``'sliding'`` (``window``, which it
needs, and rotary) and ``'full'`` (full causal and NO position encoding).
All three build :class:`Attention` under ``attn``; the two new ones sit
under a named scope of their own, ``sliding_attn`` / ``full_attn``.
``head_dim`` frees a GQA head's width from ``embed_dim / num_heads``,
``attn_gate`` multiplies every head's output by a sigmoid of the layer's
input before the output projection, and ``embed_scale`` multiplies the
embedding's output. Such a layer, with ``qk_norm``, ``sandwich_norm`` and
an expert layer beside a shared expert, reads (x: (T, E); RMSNorm
everywhere; no bias)::

    h^0        = Embed(t) * embed_scale               (sqrt(E) under muP)
    y          = RMSNorm(x)
    q          = y W_q  (H heads of D);  k = y W_k,  v = y W_v  (G heads)
    q, k      <- RMSNorm over each head's D channels, one scale vector
                 for the queries and one for the keys
    'sliding': q, k <- rope(q), rope(k);  key j visible to query i iff
                 i - window < j <= i
    'full':    no rotary;  j <= i
    o          = softmax(q k^T / sqrt(D) + mask) v   (a KV head serves
                 H / G query heads)
    x         <- x + RMSNorm((o * sigmoid(y W_gate)) W_o)    (W_gate: E->HD)
    z          = RMSNorm(x)
    f          = SwiGLU(z) in the leading dense layers; else
                 s = sigmoid(z W_r) over all experts (float32),
                 S = top-k of s,  w_e = scale * s_e / sum_S s,
                 f = sum_{e in S, held here} w_e SwiGLU_e(z) + SwiGLU_sh(z)
    x         <- x + RMSNorm(f)

The new kinds, ``head_dim`` other than ``embed_dim / num_heads`` and
``attn_gate`` RAISE with ``decode=True`` and under ``'ulysses'`` (a cache
that knows a layer's kind is ROADMAP M4 (b)); under ``'ring'`` a
``'sliding'`` layer passes its window to the ring.

``norm_eps`` is every RMSNorm's epsilon. Combinations that RAISE, each
where it is first seen: ``mla`` with ``decode=True`` (caching the latent
is serving's work and waits for a serving metric: ROADMAP M5), with
``attention='ring'`` / ``'ulysses'`` (one shared rotary key and unequal
widths are not plumbed through the exchanges) and with ``window``;
``moe`` and ``mtp`` with ``recurrent_steps > 1`` (a pass's expert counts
and a looped MTP have no defined meaning here); ``mtp`` under sequence
parallelism (its shifted tokens cross the shards).

**Recomputation is a rule, not a switch**: a stack run more than once
(``recurrent_steps > 1``) keeps, of each block application, its INPUT and
WHAT ITS ATTENTION KERNEL WROTE for the backward (``nn.remat`` under a
policy that saves the two residuals the flash kernel's VJP names,
``ops/flash_attention.OUT_RESIDUAL`` and ``LSE_RESIDUAL``): the block's
forward runs again there, the Pallas forward kernel does not. A stack run
once keeps what it always kept and reads no name; attention that does not
go through the kernel (the CPU's blockwise path) names nothing and is
recomputed whole. R passes hold R x the activations of one for the same
parameters, and those activations, not the parameters, decide the depth
that fits a chip: at the benchmark's looped configuration, 6 layers x 4
passes at T=8192, the step without the rule needs 19.0 GiB of a v5e's
15.75 and does not compile (PERF.md, PR 27). What the kernel wrote is the
dearest thing in the block by the byte: at 8 layers x 4 passes, 16 heads
of 128 and T=8192 the 32 kept outputs (bfloat16, 33.5 MB each) and
log-sum-exps (float32, 0.5 MB) are 1.07 GB more for 32 kernel calls of
2.6 ms fewer, and the step still compiles for a v5e: a peak of 15.71 GB
of its 16.91, for 14.99 without them, and XLA's own rematerialization
pass already runs six fusions twice to hold it there (PERF.md, PR 28).
Keeping more (q, k, v at 100 MB a block application, the MLP's products
at 184 MB) does not fit.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
import optax

from horovod_tpu.core import state as _state
from horovod_tpu.core import timeline as _timeline
from horovod_tpu.ops import flash_attention as _flash
from horovod_tpu.ops import moe as _moe
from horovod_tpu.ops import short_conv as _short_conv
from horovod_tpu.ops.flash_attention import LSE_RESIDUAL, OUT_RESIDUAL


class MLAConfig(NamedTuple):
    """Latent attention's widths (a head's query and key are
    ``nope_dim + rope_dim`` wide, its value ``v_dim``)."""
    q_rank: int               # the query's latent width
    kv_rank: int              # the shared key/value latent's width
    nope_dim: int             # a head's part that carries no position
    rope_dim: int             # a head's rotary part; ONE key for all heads
    v_dim: int                # a head's value width


class MoEConfig(NamedTuple):
    """The expert layer and this chip's share of it (``ops/moe.py``)."""
    total: int                # experts the router scores: its width
    held: int                 # of them, held here ...
    first: int = 0            # ... from this one on
    top_k: int = 1            # experts a token
    expert_dim: int = 2048    # a gated expert's width
    shared_experts: int = 0   # shared expert's width, in experts
    scale: float = 1.0        # routed_scaling_factor on the gates
    dense_layers: int = 0     # leading layers that keep ``ffn``


class MTPConfig(NamedTuple):
    """The multi-token-prediction module (one: t_{i+2} from position i)."""
    weight: float = 0.3       # its loss's weight beside the main one
    pad_id: int = 0           # stands for t_{i+1} where there is none


class TransformerConfig(NamedTuple):
    vocab_size: int = 32_000
    num_layers: int = 4
    num_heads: int = 8
    embed_dim: int = 512
    mlp_dim: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention: str = "local"      # 'local' | 'ring' | 'ulysses'
    sp_group: int = 0             # context-parallel group for ring/ulysses
    num_kv_heads: int | None = None  # GQA/MQA: fewer K/V heads (None = MHA)
    sp_layout: str = "contiguous"    # ring only: 'contiguous' | 'zigzag'
    decode: bool = False          # one-token KV-cache decoding (generate())
    window: int | None = None     # sliding-window attention (causal SWA)
    kv_dtype: str = "model"       # paged-KV pool format ('model' = dtype;
                                  # fp32|bf16|int8_block|int4 — serving)
    ffn: str = "gelu"             # 'gelu' (2 matrices) | 'swiglu' (3, gated)
    sandwich_norm: bool = False   # RMSNorm on each branch's output too
    rope_theta: float = 10000.0   # rotary base
    recurrent_steps: int = 1      # passes of the one weight-shared stack
    exit_gate: bool = False       # per-pass exit probability (looped only)
    norm_eps: float = 1e-6        # every RMSNorm's epsilon
    mla: MLAConfig | None = None  # latent attention; raises with decode,
                                  # 'ring' / 'ulysses' and window
    moe: MoEConfig | None = None  # expert layers after moe.dense_layers;
                                  # raises with recurrent_steps > 1
    mtp: MTPConfig | None = None  # multi-token prediction; raises with
                                  # recurrent_steps > 1 and under SP
    layer_types: tuple | None = None  # each layer's mixer, a kind of MIXER
                                      # ('attention' | 'conv'); None: all
                                      # attention. 'conv' raises with decode
                                      # and 'ring' / 'ulysses'
    conv_taps: int = 3            # a 'conv' layer's taps (conv_L_cache)
    qk_norm: bool = False         # RMSNorm on each head's q and k (GQA)
    head_dim: int | None = None   # a GQA head's width; None: embed_dim /
                                  # num_heads. Another raises with decode
                                  # and 'ulysses'
    attn_gate: bool = False       # sigmoid gate on the heads' output
                                  # (raises with decode and 'ulysses')
    embed_scale: float | None = None  # the embedding's output times this


def _rotary(x, positions, theta=10000.0):
    """Rotary position embedding on (B, T, H, D), base ``theta``.

    ``positions`` is (T,) global positions shared across the batch, or
    (B, T) per-row positions — the paged decode path serves ragged
    requests whose current indices differ per batch slot. The (T,) case
    computes exactly what it always did; (B, T) broadcasts per row.
    The head is rotated whole (:func:`_rotate`), never split in halves.
    """
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    cos2 = jnp.concatenate([cos, cos], -1)[..., None, :]        # (..., T, 1, D)
    sin2 = jnp.concatenate([sin, sin], -1)[..., None, :]
    if angles.ndim == 2:            # (T, half): shared positions
        cos2, sin2 = cos2[None], sin2[None]
    return _rotate(x, cos2, sin2)


def _rotate_by(x, cos2, sin2):
    """``x·[cos|cos] + (x P)·[sin|sin]`` in float32, cast back to x's dtype.

    P is the (D, D) signed permutation that maps ``[x1 | x2]`` to
    ``[-x2 | x1]``, a product on the MXU in x's own dtype: each of its
    outputs is one input times ±1, so it is exact, and op by op the sums
    are those of the half-split form ``[x1 cos - x2 sin | x1 sin + x2
    cos]``. No ``D/2``-wide half of a head is ever a value, which XLA
    would keep in HBM at twice its bytes in 128-lane tiles and in passes
    of its own between the q/k projection and the attention kernel
    (``jnp.roll`` lowers to slices and a concatenate: the halves again).
    """
    d = x.shape[-1]
    perm = np.zeros((d, d), np.float32)
    perm[d // 2:, :d // 2] = -np.eye(d // 2, dtype=np.float32)  # -x2 first
    perm[:d // 2, d // 2:] = np.eye(d // 2, dtype=np.float32)   # x1 second
    swap = jnp.einsum(
        "...d,de->...e", x, jnp.asarray(perm, x.dtype),
        preferred_element_type=jnp.float32,
        precision=(None if x.dtype == jnp.bfloat16
                   else jax.lax.Precision.HIGHEST))
    return (x.astype(jnp.float32) * cos2 + swap * sin2).astype(x.dtype)


@jax.custom_vjp
def _rotate(x, cos2, sin2):
    return _rotate_by(x, cos2, sin2)


def _rotate_fwd(x, cos2, sin2):
    return _rotate_by(x, cos2, sin2), (cos2, sin2)


def _rotate_bwd(tables, g):
    """The rotation's transpose is the rotation by the opposite angle
    (``Pᵀ = -P``, and swapping the halves leaves ``[sin|sin]`` as it is),
    so it is the same pass on the cotangent in its own dtype: one
    rounding, as the half-split form's. Autodiff would put the float32
    ``g·sin`` through the product instead, which the MXU rounds to
    bfloat16, and add the two terms' bfloat16 casts in bfloat16."""
    cos2, sin2 = tables
    return _rotate_by(g, cos2, -sin2), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def _mla_attention(cfg, x, positions, segs):
    """Latent attention (MLA), built in the calling :class:`Attention`'s
    scope: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` (heads of nope | rope);
    ``[c_kv | k_r] = x W_kva``, ``[k_nope | v] = norm(c_kv) W_kvb``; the
    rotary embedding on the rope parts only, ``k_r`` one key for all
    heads; causal softmax over ``q k^T / sqrt(nope + rope)``; the heads'
    values through ``out``."""
    import horovod_tpu as hvd

    m, h = cfg.mla, cfg.num_heads
    if cfg.decode:
        raise ValueError(
            "decode=True does not run latent attention (mla=): the cache "
            "would hold the kv_rank + rope_dim latent a token, not K and V "
            "— the latent cache, this decode branch and the page layout "
            "are serving's work and wait for a serving metric (ROADMAP "
            "M5).")
    if cfg.attention != "local":
        raise ValueError(
            f"latent attention (mla=) runs attention='local' only, not "
            f"{cfg.attention!r}: one rotary key shared by all heads and "
            f"unequal key / value widths are not plumbed through the "
            f"sequence-parallel exchanges.")
    if cfg.window is not None:
        raise ValueError("latent attention (mla=) is full causal: window "
                         "is not supported with it.")
    if m.rope_dim % 2 != 0:
        raise ValueError(f"mla.rope_dim ({m.rope_dim}) must be even for "
                         f"rotary embeddings.")
    dense = lambda width, name: nn.Dense(width, dtype=cfg.dtype,
                                         use_bias=False, name=name)
    heads = lambda width, name: nn.DenseGeneral(
        (h, width), axis=-1, dtype=cfg.dtype, use_bias=False, name=name)
    norm = lambda name: nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                                   name=name)
    with jax.named_scope("mla"):
        q = heads(m.nope_dim + m.rope_dim, "q_b")(
            norm("q_norm")(dense(m.q_rank, "q_a")(x)))
        latent = dense(m.kv_rank + m.rope_dim, "kv_a")(x)
        kv = heads(m.nope_dim + m.v_dim, "kv_b")(
            norm("kv_norm")(latent[..., :m.kv_rank]))
        k_rope = _rotary(latent[..., None, m.kv_rank:], positions,
                         cfg.rope_theta)                   # (B, T, 1, rope)
        q = jnp.concatenate(
            [q[..., :m.nope_dim],
             _rotary(q[..., m.nope_dim:], positions, cfg.rope_theta)], -1)
        k = jnp.concatenate(
            [kv[..., :m.nope_dim],
             jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, m.rope_dim))],
            -1)
        v = kv[..., m.nope_dim:]
        # The kernels take one head width: the narrower of (q, k) and v is
        # padded with zeros, which add nothing to a score or an output.
        qk_dim = m.nope_dim + m.rope_dim
        width = max(qk_dim, m.v_dim)
        pad = lambda a: a if a.shape[-1] == width else jnp.pad(
            a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))
        out = hvd.local_attention(pad(q), pad(k), pad(v), causal=True,
                                  sm_scale=qk_dim ** -0.5, **segs)
        return nn.DenseGeneral(cfg.embed_dim, axis=(-2, -1),
                               dtype=cfg.dtype, use_bias=False,
                               name="out")(out[..., :m.v_dim])


class AttentionKind(NamedTuple):
    """What an attention kind of :data:`MIXER` does beside attending."""
    windowed: bool            # takes ``cfg.window`` (None: full causal)
    rotary: bool              # the rotary embedding on q and k
    scope: str | None         # its mixer's named scope (None: none)


# ``'attention'`` is the kind every configuration had before kinds existed
# (``cfg.window``, which may be None, and rotary). ``'sliding'`` computes
# what ``'attention'`` computes and differs in two things only: it raises
# where the configuration has no window (a published windowed layer never
# runs full causal unnoticed), and its own scope parts windowed time from
# full time, where giving ``'attention'`` a scope would rename the older
# configurations' ops. ``'full'`` takes neither window nor rotary (no
# position encoding at all: what such a layer knows of order is the causal
# mask).
ATTENTION_KINDS = {
    "attention": AttentionKind(windowed=True, rotary=True, scope=None),
    "sliding": AttentionKind(windowed=True, rotary=True,
                             scope="sliding_attn"),
    "full": AttentionKind(windowed=False, rotary=False, scope="full_attn"),
}


def window_of(cfg: TransformerConfig, kind: str) -> int | None:
    """The window an attention layer of ``kind`` attends through."""
    return cfg.window if ATTENTION_KINDS[kind].windowed else None


def head_width(cfg: TransformerConfig) -> int:
    """The width of a head as the attention kernels see it."""
    if cfg.mla is not None:  # the narrower of (q, k) and v is padded
        return max(cfg.mla.nope_dim + cfg.mla.rope_dim, cfg.mla.v_dim)
    return cfg.head_dim or cfg.embed_dim // cfg.num_heads


class Attention(nn.Module):
    config: TransformerConfig
    kind: str = "attention"       # a kind of ATTENTION_KINDS

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, kv_view=None):
        cfg = self.config
        kind = ATTENTION_KINDS[self.kind]
        window = window_of(cfg, self.kind)
        decoupled = cfg.head_dim is not None \
            and cfg.head_dim * cfg.num_heads != cfg.embed_dim
        novel = [what for what, on in (
            (f"a {self.kind!r} layer", self.kind != "attention"),
            ("the output gate (attn_gate)", cfg.attn_gate),
            (f"heads of head_dim={cfg.head_dim} beside embed_dim / "
             f"num_heads", decoupled)) if on]
        if cfg.mla is not None:
            segs = {} if segment_ids is None else dict(
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids)
            if cfg.qk_norm:
                raise ValueError(
                    "qk_norm is the GQA path's: latent attention (mla=) "
                    "norms its query and key/value latents itself.")
            if novel or cfg.head_dim is not None:
                raise ValueError(
                    "latent attention (mla=) is an attention kind of its "
                    "own: layer kinds, head_dim and attn_gate are the GQA "
                    "path's.")
            return _mla_attention(cfg, x, positions, segs)
        if novel and cfg.decode:
            raise ValueError(
                f"decode=True does not run {', '.join(novel)}: a cache "
                f"that knows a layer's kind (a windowed layer holding a "
                f"window of pages, a full one all of them) and the decode "
                f"branch's head width and gate are serving's work (ROADMAP "
                f"M4 (b)).")
        if novel and cfg.attention == "ulysses":
            raise ValueError(
                f"attention='ulysses' does not run {', '.join(novel)}: its "
                f"all-to-all exchanges assume every layer's one attention "
                f"path at embed_dim / num_heads (ROADMAP M4 (b)); use "
                f"'local' or 'ring'.")
        if self.kind == "sliding" and window is None:
            raise ValueError("a 'sliding' layer (layer_types) needs the "
                             "configuration's window.")
        if cfg.head_dim is None and cfg.embed_dim % cfg.num_heads != 0:
            raise ValueError(
                f"embed_dim ({cfg.embed_dim}) must be divisible by num_heads "
                f"({cfg.num_heads}).")
        h, d = cfg.num_heads, head_width(cfg)
        hkv = cfg.num_kv_heads or h
        if h % hkv != 0:
            raise ValueError(
                f"num_heads ({h}) must be a multiple of num_kv_heads "
                f"({hkv}) for grouped-query attention.")
        if kind.rotary and d % 2 != 0:
            raise ValueError(
                f"head_dim ({d}) must be even for rotary embeddings.")
        dense = lambda name, heads: nn.DenseGeneral(
            (heads, d), axis=-1, dtype=cfg.dtype, use_bias=False, name=name)
        if kv_view is not None and not cfg.decode:
            raise ValueError(
                "kv_view= (paged KV cache) is only meaningful with "
                "decode=True — the serving engine's one-token step.")
        q, k, v = dense("query", h)(x), dense("key", hkv)(x), \
            dense("value", hkv)(x)
        if cfg.qk_norm:
            # Over each head's own channels, one scale vector for the
            # queries and one for the keys, before the rotary embedding.
            with jax.named_scope("qk_norm"):
                norm = lambda name: nn.RMSNorm(
                    epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
                q, k = norm("q_norm")(q), norm("k_norm")(k)
        if kind.rotary:
            q = _rotary(q, positions, cfg.rope_theta)
            k = _rotary(k, positions, cfg.rope_theta)

        import horovod_tpu as hvd

        segs = {}
        if segment_ids is not None:
            segs = dict(q_segment_ids=segment_ids,
                        kv_segment_ids=segment_ids)
        if cfg.decode:
            # One-token autoregressive step against a KV cache. Two cache
            # carriers share ONE attend computation (the serving engine
            # and generate() must be bit-identical — docs/inference.md):
            #   * flax 'cache' collection — dense (b, max_seq_len) cache,
            #     one shared write index (generate()'s path);
            #   * kv_view=(k_view, v_view) — a gathered paged-cache view
            #     (serving/kv_cache.py block pool), per-row positions, the
            #     fresh K/V sown to 'paged_kv' so the engine can scatter
            #     them back into the pool.
            # GQA cache: Hkv heads — grouped heads shrink cache memory AND
            # per-step bandwidth by H/Hkv; the einsum groups q rather than
            # expanding the cache.
            if cfg.attention != "local":
                raise ValueError(
                    "decode=True supports attention='local' (generation "
                    "runs on the full cached sequence per chip).")
            if x.shape[1] != 1 and kv_view is None:
                raise ValueError(
                    f"decode=True processes ONE token per call (got "
                    f"{x.shape[1]}); feed the prompt token-by-token as "
                    f"generate() does. (Multi-token windows need the "
                    f"paged kv_view= carrier — the engine's speculative "
                    f"verify step.)")
            if segment_ids is not None:
                raise ValueError(
                    "decode=True does not support segment_ids (serve "
                    "one document per batch row).")
            b = x.shape[0]
            if kv_view is not None:
                # Two carrier layouts: (k, v) — raw pages in the pool
                # dtype (fp32/bf16) — or (k, v, k_scale, v_scale) when
                # cfg.kv_dtype is a quantized format (int8/int4 payloads
                # plus per-(token, head) bf16 scale planes,
                # serving/kv_cache.py). Quantization happens HERE, on
                # the fresh K/V of this one token (deterministic
                # round-to-nearest — recompute/prefix-sharing
                # bit-identity), and the whole view dequantizes to fp32
                # before the attend below, so the attention math is the
                # same on every format.
                from horovod_tpu.serving import kv_cache as _paged

                quant = _paged.kv_quantized(
                    _paged.resolve_kv_dtype(cfg.kv_dtype, cfg.dtype))
                if quant and len(kv_view) != 4:
                    raise ValueError(
                        f"kv_dtype={cfg.kv_dtype!r} pages carry scale "
                        f"planes: kv_view must be (k, v, k_scale, "
                        f"v_scale), got a {len(kv_view)}-tuple.")
                if not quant and len(kv_view) != 2:
                    raise ValueError(
                        f"kv_dtype={cfg.kv_dtype!r} pages are raw (k, v) "
                        f"— a {len(kv_view)}-tuple kv_view looks like "
                        f"quantized pools passed to an unquantized "
                        f"config (fresh K/V would be written into the "
                        f"int8 payload view as garbage).")
                if quant:
                    kview, vview, kscale, vscale = kv_view
                else:
                    kview, vview = kv_view
                w = x.shape[1]
                if positions.ndim != 2 or positions.shape[:2] != (b, w):
                    raise ValueError(
                        "paged decode (kv_view=) needs per-row positions "
                        f"shaped (B, W) matching the tokens, got "
                        f"{positions.shape} for (B, W)=({b}, {w}).")
                # (b, w) write positions — w == 1 is the plain decode
                # step, w == k+1 the speculative verify window (all
                # fresh K/V land in the view BEFORE the attend, and the
                # causal visibility below keeps each query blind to the
                # window positions after it).
                pos = positions.astype(jnp.int32)
                bidx = jnp.arange(b)[:, None]
                if quant:
                    kvd = cfg.kv_dtype
                    kw, ku = _paged.quantize_kv(k, kvd)
                    vw, vu = _paged.quantize_kv(v, kvd)
                    kview = kview.at[bidx, pos].set(kw)
                    vview = vview.at[bidx, pos].set(vw)
                    kscale = kscale.at[bidx, pos].set(ku)
                    vscale = vscale.at[bidx, pos].set(vu)
                    # QUANTIZED fresh K/V out to the engine's pool
                    # scatter — the pool and this step's view hold the
                    # identical bits (quantize once, never twice).
                    # Sown squeezed for w == 1 (the plain decode step's
                    # layout), full (b, w, ...) for a verify window.
                    self.sow("paged_kv", "k", kw[:, 0] if w == 1 else kw)
                    self.sow("paged_kv", "v", vw[:, 0] if w == 1 else vw)
                    self.sow("paged_kv", "k_scale",
                             ku[:, 0] if w == 1 else ku)
                    self.sow("paged_kv", "v_scale",
                             vu[:, 0] if w == 1 else vu)
                    kc = _paged.dequantize_kv(kview, kscale, kvd)
                    vc = _paged.dequantize_kv(vview, vscale, kvd)
                else:
                    kw = k.astype(kview.dtype)
                    vw = v.astype(vview.dtype)
                    kview = kview.at[bidx, pos].set(kw)
                    vview = vview.at[bidx, pos].set(vw)
                    # Fresh K/V out to the engine (it owns the pool
                    # scatter; rewriting the whole view back would copy
                    # the entire cache every step).
                    self.sow("paged_kv", "k", kw[:, 0] if w == 1 else kw)
                    self.sow("paged_kv", "v", vw[:, 0] if w == 1 else vw)
                    kc, vc = kview, vview
                ivec = pos  # (b, w) per-query visibility frontiers
            else:
                ck = self.variable("cache", "k", jnp.zeros,
                                   (b, cfg.max_seq_len, hkv, d), cfg.dtype)
                cv = self.variable("cache", "v", jnp.zeros,
                                   (b, cfg.max_seq_len, hkv, d), cfg.dtype)
                idx = self.variable("cache", "idx",
                                    lambda: jnp.zeros((), jnp.int32))
                i = idx.value
                zero = jnp.zeros((), jnp.int32)
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(cfg.dtype), (zero, i, zero, zero))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(cfg.dtype), (zero, i, zero, zero))
                idx.value = i + 1
                kc, vc = ck.value, cv.value
                ivec = jnp.full((b, 1), i, jnp.int32)
            w = x.shape[1]
            qg = q.reshape(b, w, hkv, h // hkv, d).astype(jnp.float32)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                           kc.astype(jnp.float32)) * (1.0 / d ** 0.5)
            kpos = jnp.arange(kc.shape[1])
            vis = kpos[None, None, :] <= ivec[:, :, None]  # (b, w, K)
            if window is not None:
                vis = vis & (kpos[None, None, :] > ivec[:, :, None]
                             - window)
            s = jnp.where(vis[:, None, None, :, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                             vc.astype(jnp.float32))
            out = out.reshape(b, w, h, d).astype(cfg.dtype)
        elif cfg.attention == "ring":
            out = hvd.ring_attention(q, k, v, group=cfg.sp_group,
                                     causal=True, layout=cfg.sp_layout,
                                     window=window, **segs)
        elif cfg.attention == "ulysses":
            if hkv != h:
                # Ulysses all-to-alls the head axis against the sequence
                # axis, which needs equal head counts: expand the grouped
                # KV heads locally. (GQA still saves K/V projection
                # parameters; the ring strategy also saves wire traffic.)
                k = jnp.repeat(k, h // hkv, axis=2)
                v = jnp.repeat(v, h // hkv, axis=2)
            if window is not None:
                raise ValueError(
                    "window is not supported with attention='ulysses'; "
                    "use 'local' or 'ring'.")
            out = hvd.ulysses_attention(q, k, v, group=cfg.sp_group,
                                        causal=True, **segs)
        elif cfg.attention == "local":
            out = hvd.local_attention(q, k, v, causal=True,
                                      window=window, **segs)
        else:
            raise ValueError(f"Unknown attention strategy {cfg.attention!r}.")
        if cfg.attn_gate:
            # Every head's output times a sigmoid of the layer's input.
            gate = jax.nn.sigmoid(dense("gate", h)(x).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate).astype(cfg.dtype)
        return nn.DenseGeneral(cfg.embed_dim, axis=(-2, -1), dtype=cfg.dtype,
                               use_bias=False, name="out")(out)


class ShortConv(nn.Module):
    """The ``'conv'`` mixer: ``[B | C | u] = y W_in`` (three streams of
    the model's width), ``out = (C * taps(B * u)) W_out`` with ``taps`` a
    depthwise causal convolution of ``conv_taps`` taps
    (``ops/short_conv.py``); no bias, no activation. The ``gate`` scope is
    everything between the two projections."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, y, segment_ids=None):
        cfg = self.config
        if cfg.decode:
            raise ValueError(
                "decode=True does not run a 'conv' layer (layer_types): "
                "its state is the last conv_taps - 1 gated inputs of the "
                "short convolution, not a KV cache — that state, its "
                "decode branch and a cache that knows a layer's kind are "
                "serving's work (ROADMAP M6).")
        if cfg.attention != "local":
            raise ValueError(
                f"a 'conv' layer (layer_types) runs attention='local' "
                f"only, not {cfg.attention!r}: the short convolution of a "
                f"sequence shard's first positions would need a halo of "
                f"conv_taps - 1 = {cfg.conv_taps - 1} positions from the "
                f"shard before, which no exchange brings.")
        dense = lambda width, name: nn.Dense(width, dtype=cfg.dtype,
                                             use_bias=False, name=name)
        taps = self.param(  # (a channel's fan-in is its own taps)
            "taps", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
            (cfg.embed_dim, cfg.conv_taps))
        bcu = dense(3 * cfg.embed_dim, "in_proj")(y)
        with jax.named_scope("gate"):
            out = _short_conv.gated_short_conv(bcu, taps, segment_ids)
        return dense(cfg.embed_dim, "out_proj")(out)


def _attention_mixer(kind: str):
    scope = ATTENTION_KINDS[kind].scope

    def mixer(cfg, y, positions, segment_ids, kv_view):
        attend = lambda: Attention(cfg, kind=kind, name="attn")(
            y, positions, segment_ids, kv_view=kv_view)
        if scope is None:
            return attend()
        with jax.named_scope(scope):
            return attend()
    return mixer


def _conv_mixer(cfg, y, positions, segment_ids, kv_view):
    return ShortConv(cfg, name="conv")(y, segment_ids)


# The block's mixer slot: a kind -> (cfg, y, positions, segment_ids,
# kv_view) -> y, building its module in the calling Block's scope under the
# kind's own name (``attn``, ``conv``: the scopes the tracing reads; an
# attention kind's own scope, ``sliding_attn`` / ``full_attn``, holds it).
MIXER = {**{kind: _attention_mixer(kind) for kind in ATTENTION_KINDS},
         "conv": _conv_mixer}


def mixer_of_layer(cfg: TransformerConfig, i: int) -> str:
    """The mixer kind of layer ``i``: what ``cfg.layer_types`` says, and
    attention where it says nothing (no pattern; the MTP module's block,
    which is one past the stack)."""
    if cfg.layer_types is None or i >= cfg.num_layers:
        return "attention"
    return cfg.layer_types[i]


def _gelu_ffn(cfg, y):
    y = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, use_bias=False)(y)
    y = nn.gelu(y)
    return nn.Dense(cfg.embed_dim, dtype=cfg.dtype, use_bias=False)(y)


def _swiglu_ffn(cfg, y, width=None, prefix=""):
    dense = lambda width, name: nn.Dense(width, dtype=cfg.dtype,
                                         use_bias=False, name=prefix + name)
    width = width or cfg.mlp_dim
    y = nn.silu(dense(width, "gate")(y)) * dense(width, "up")(y)
    return dense(cfg.embed_dim, "down")(y)


EXPERT_PAIRS = "expert_pairs"  # the collection an expert layer sows into
EXPERT_CHOICES = "expert_choices"  # ... and its tokens' top-k, if asked


class MoE(nn.Module):
    """The expert layer as this chip holds it (``ops/moe.py``): the
    router over all ``moe.total`` experts, this chip's ``moe.held`` gated
    experts for the tokens routed to them, and the shared expert. The
    score-correction bias is a constant of zeros, not a parameter (its
    update rule is no gradient's: ROADMAP M3). Sows, into
    :data:`EXPERT_PAIRS`, how many (token, choice) pairs each held expert
    took and, into :data:`EXPERT_CHOICES`, every token's top-k (nothing
    where the collection is not asked for)."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, y):
        cfg, m = self.config, self.config.moe
        _moe.check_share(m.total, m.held, m.first, m.top_k)
        e, f = cfg.embed_dim, m.expert_dim
        experts = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (e, m.total))
        wg = self.param("wg", experts, (m.held, e, f))
        wu = self.param("wu", experts, (m.held, e, f))
        wd = self.param("wd", experts, (m.held, f, e))
        tokens = y.reshape(-1, e)
        with jax.named_scope("router"):
            idx, gates = _moe.route(tokens, router, jnp.zeros((m.total,)),
                                    m.top_k, m.scale)
        out, pairs = _moe.routed_experts(tokens, idx, gates, wg, wu, wd,
                                         first=m.first)
        self.sow(EXPERT_PAIRS, "pairs", pairs)
        self.sow(EXPERT_CHOICES, "idx", idx)
        out = out.reshape(y.shape)
        if m.shared_experts:
            with jax.named_scope("shared_expert"):
                out = out + _swiglu_ffn(cfg, y, m.shared_experts * f,
                                        prefix="shared_")
        return out


def _moe_ffn(cfg, y):
    return MoE(cfg, name="moe")(y)


# The block's feed-forward slot: a kind -> (cfg, y) -> y, building its
# matrices in the calling Block's scope. ``cfg.ffn`` names the dense
# kind; ``'moe'`` is what the layers after ``cfg.moe.dense_layers`` take.
FFN = {"gelu": _gelu_ffn, "swiglu": _swiglu_ffn, "moe": _moe_ffn}


def ffn_of_layer(cfg: TransformerConfig, i: int) -> str:
    """The feed-forward kind of layer ``i``: ``cfg.ffn`` but ``'moe'``
    after an expert configuration's leading dense layers."""
    if cfg.moe is not None and i >= cfg.moe.dense_layers:
        return "moe"
    return cfg.ffn


class Block(nn.Module):
    config: TransformerConfig
    ffn: str | None = None        # this layer's kind; None: ``config.ffn``
    mixer: str = "attention"      # this layer's mixer, a kind of MIXER

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, kv_view=None):
        cfg = self.config
        ffn = self.ffn or cfg.ffn
        if ffn not in FFN:
            raise ValueError(f"Unknown ffn {ffn!r}; one of "
                             f"{sorted(FFN)}.")
        if self.mixer not in MIXER:
            raise ValueError(f"Unknown mixer {self.mixer!r} in "
                             f"layer_types; one of {sorted(MIXER)}.")
        norm = lambda: nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)
        # Sandwich norms: a branch's output is normed before it joins the
        # residual stream, so four RMSNorms a block for two.
        post = (lambda y: norm()(y)) if cfg.sandwich_norm else (lambda y: y)
        y = norm()(x)
        x = x + post(MIXER[self.mixer](cfg, y, positions, segment_ids,
                                       kv_view))
        y = norm()(x)
        with jax.named_scope("moe" if ffn == "moe" else "mlp"):
            y = FFN[ffn](cfg, y)
        return x + post(y)


def _attends_through_the_kernel(cfg: TransformerConfig, t_local: int) -> bool:
    """Whether an ATTENTION block's mixer is a call of the Pallas flash
    kernel that the block's ``nn.remat`` sees, so that its policy finds
    the kernel's two named residuals (a ``'conv'`` block has no kernel and
    names nothing: it is recomputed whole). Ring attention's steps sit
    under a bare ``jax.checkpoint`` of their own, which saves nothing,
    named or not."""
    from horovod_tpu.parallel.sequence import local_attention_impl

    t = _kernel_tokens(cfg, t_local)
    return t is not None and local_attention_impl(t) == "flash"


def _kernel_tokens(cfg: TransformerConfig, t_local: int) -> int | None:
    """The sequence length a block's ``local_attention`` call sees, None
    where the block makes no such call under its own ``nn.remat``."""
    if cfg.attention == "local":
        return t_local
    if cfg.attention == "ulysses":  # the full sequence, H/g heads
        return t_local * _state.get_group(cfg.sp_group).size
    return None


class Transformer(nn.Module):
    """Decoder-only LM over the LOCAL sequence shard.

    ``shard_offset``: global position of this rank's first token (0 for
    'local'; ``sp_rank * T_local`` under sequence parallelism — pass
    ``hvd.rank(sp_group) * t_local`` from inside the step function).
    ``positions``: explicit (T_local,) global positions, overriding
    ``shard_offset`` — required for ``sp_layout='zigzag'`` shards (use
    :func:`horovod_tpu.zigzag_positions`).
    ``return_passes``: every pass's output and the exit gates' logits, as
    two tuples (R and R - 1 long; one and none for a plain model) — logits,
    or with ``return_hidden`` the normed states the head reads. Without
    it a looped model returns its last pass's.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, shard_offset=0, segment_ids=None,
                 positions=None, return_hidden=False, kv_views=None,
                 return_passes=False):
        cfg = self.config
        t_local = tokens.shape[1]
        if kv_views is not None:
            if not cfg.decode:
                raise ValueError(
                    "kv_views= (paged KV cache) requires decode=True — "
                    "it is the serving engine's one-token step interface.")
            if len(kv_views) != cfg.num_layers:
                raise ValueError(
                    f"kv_views must carry one (k_view, v_view) pair per "
                    f"layer: got {len(kv_views)} for num_layers="
                    f"{cfg.num_layers}.")
        if cfg.sp_layout == "zigzag" and cfg.attention != "ring":
            raise ValueError(
                "sp_layout='zigzag' only applies to attention='ring' "
                f"(got {cfg.attention!r}); zigzag-sharded data under any "
                "other strategy would silently misplace positions.")
        if positions is None:
            if cfg.sp_layout == "zigzag":
                raise ValueError(
                    "sp_layout='zigzag' shards are not contiguous: pass "
                    "positions=hvd.zigzag_positions(hvd.rank(sp_group), "
                    "t_local, group_size) from inside the step function.")
            positions = shard_offset + jnp.arange(t_local)
        if cfg.recurrent_steps < 1:
            raise ValueError(
                f"recurrent_steps must be >= 1, got {cfg.recurrent_steps}.")
        if cfg.layer_types is not None \
                and len(cfg.layer_types) != cfg.num_layers:
            raise ValueError(
                f"layer_types names {len(cfg.layer_types)} layers' mixers "
                f"for num_layers={cfg.num_layers}.")
        looped = cfg.recurrent_steps > 1
        if cfg.exit_gate and not looped:
            raise ValueError(
                "exit_gate needs recurrent_steps > 1: with one pass there "
                "is nothing to exit from.")
        if looped and cfg.decode:
            raise ValueError(
                "decode=True does not run a looped model (recurrent_steps="
                f"{cfg.recurrent_steps}): the KV cache holds one entry a "
                "layer, a looped model needs one a pass and layer.")
        if looped and (cfg.moe is not None or cfg.mtp is not None):
            raise ValueError(
                "expert layers (moe=) and the multi-token-prediction "
                "module (mtp=) do not run in a looped model "
                f"(recurrent_steps={cfg.recurrent_steps}): a pass's expert "
                "counts and a looped MTP have no defined meaning here.")
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype,
                         embedding_init=nn.initializers.normal(0.02))
        x = embed(tokens)
        if cfg.embed_scale is not None:
            x = x * cfg.embed_scale
        # Into the record of the hvd.spmd program being traced, a step, a
        # rank (core/timeline.py count_plan; dropped where none is). The
        # mixer kind of every block application, the MTP module's last.
        applied_kinds = [mixer_of_layer(cfg, i)
                         for i in range(cfg.num_layers)] \
            * cfg.recurrent_steps + ["attention"] * (cfg.mtp is not None)
        attends = [k for k in applied_kinds if k in ATTENTION_KINDS]
        windows = [window_of(cfg, k) for k in attends]
        applied = len(applied_kinds)
        convs = applied - len(attends)
        tl = _timeline.session()
        tl.count_plan("model.block_applications", applied)
        tl.count_plan("model.attention_layers", len(attends))
        tl.count_plan("model.conv_layers", convs)
        # The attention block applications by what their kind does: with a
        # window, full causal (rotary or not), rotary, gated.
        tl.count_plan("model.windowed_attention_layers",
                      sum(w is not None for w in windows))
        tl.count_plan("model.full_attention_layers",
                      sum(w is None for w in windows))
        tl.count_plan("model.rotary_attention_layers",
                      sum(ATTENTION_KINDS[k].rotary for k in attends))
        tl.count_plan("model.gated_attention_layers",
                      len(attends) if cfg.attn_gate else 0)
        # Of them, those whose gate-and-tap pass is the Pallas kernel pair
        # (ops/short_conv.py runs_kernels: a TPU, bfloat16, whole lanes).
        tl.count_plan("model.conv_kernel_layers", convs if convs and (
            _short_conv.runs_kernels(t_local, cfg.embed_dim, cfg.conv_taps,
                                     cfg.dtype)) else 0)
        tl.count_plan("model.recomputed_blocks", applied if looped else 0)
        # Whether an attention block's mixer is a call of the Pallas kernel:
        # asked for a looped stack (whose policy keeps the kernel's
        # residuals) and for an open record (hvd.spmd's: a world exists
        # there, which the 'ulysses' answer needs).
        kernel = (looped or tl.building is not None) \
            and _attends_through_the_kernel(cfg, t_local)
        # Of the attention blocks, the ones whose backward reads the
        # kernel's output and log-sum-exp back and does not run it again.
        tl.count_plan("model.kept_attention_outputs",
                      len(attends) if looped and kernel else 0)
        if kernel:
            # What the mask leaves visible of the kernels' scores and what
            # they compute (ops/flash_attention.score_counts, the kernels'
            # own classification): a forward and a backward call a block
            # application, every batch row and head, each application at
            # its own window and the heads' width. Segment ids are data
            # and not counted.
            t = _kernel_tokens(cfg, t_local)  # ulysses: g x the tokens,
            heads = cfg.num_heads * t_local // t  # a g-th of the heads
            counts = {w: _flash.score_counts(t, t, head_width(cfg), window=w)
                      for w in set(windows)}
            calls = tokens.shape[0] * heads
            tl.count_plan("flash.scores_visible",
                          calls * sum(counts[w][0] for w in windows))
            tl.count_plan("flash.scores_computed",
                          calls * sum(counts[w][1] for w in windows))
        moe_layers = 0 if cfg.moe is None else max(
            cfg.num_layers - cfg.moe.dense_layers, 0) + (cfg.mtp is not None)
        # Of the expert layers, those whose backward reads back the
        # gate-and-up product its forward kept and runs no grouped product
        # a second time (ops/moe.py routed_experts): every one.
        tl.count_plan("model.moe_kept_products", moe_layers)
        if cfg.moe is not None:
            # The plan of the expert layers (the MTP module's is one more);
            # what the experts TOOK is the step's output, not the plan's.
            tl.count_plan("model.moe_layers", moe_layers)
            tl.count_plan("model.experts_held", cfg.moe.held)
            tl.count_plan("model.experts_total", cfg.moe.total)
            tl.count_plan("model.moe_pair_capacity",
                          tokens.size * cfg.moe.top_k)
            # The rows a round of the layer's device-sized passes takes:
            # a pass visits the routed rows and less than one block more.
            tl.count_plan("model.moe_row_block",
                          _moe.row_block(tokens.size * cfg.moe.top_k))

        def stack(block, x):
            """One pass: the blocks and the final norm."""
            for i in range(cfg.num_layers):
                x = block(cfg, ffn=ffn_of_layer(cfg, i),
                          mixer=mixer_of_layer(cfg, i), name=f"block_{i}")(
                    x, positions, segment_ids,
                    None if kv_views is None else kv_views[i])
            return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                              name="RMSNorm_0")(x)

        if looped:
            # The R passes are ONE lax.scan whose body is the stack, its
            # parameters broadcast to every iteration: the same leaves,
            # their gradient the sum over the passes. THE RECOMPUTATION
            # RULE (module docstring): each block application keeps its
            # input and what its attention kernel wrote. The normed state
            # is what the next pass takes.
            kept = jax.checkpoint_policies.save_only_these_names(
                OUT_RESIDUAL, LSE_RESIDUAL)

            def one_pass(_, x, __):
                x = stack(nn.remat(Block, policy=kept), x)
                return x, x

            with jax.named_scope("loop"):
                x, states = nn.scan(
                    one_pass, variable_broadcast="params",
                    split_rngs={"params": False},
                    length=cfg.recurrent_steps)(self, x, None)
            hidden = tuple(states)
        else:
            x = stack(Block, x)
            hidden = (x,)
        if cfg.mtp is not None and (return_passes or self.is_initializing()):
            # Multi-token prediction: position i's state beside the
            # embedding of t_{i+1} (a pad id where there is none), through
            # one more block of its own; the loss reads it for t_{i+2}
            # through the same head. Its state rides behind the main one.
            with jax.named_scope("mtp"):
                norm = lambda name: nn.RMSNorm(
                    epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
                after = jnp.concatenate(
                    [tokens[:, 1:],
                     jnp.full_like(tokens[:, :1], cfg.mtp.pad_id)], axis=1)
                z = jnp.concatenate([norm("mtp_norm_h")(x),
                                     norm("mtp_norm_e")(embed(after))], -1)
                z = nn.Dense(cfg.embed_dim, dtype=cfg.dtype, use_bias=False,
                             name="mtp_proj")(z)
                z = Block(cfg, ffn=ffn_of_layer(cfg, cfg.num_layers),
                          name="mtp_block")(z, positions, segment_ids)
                hidden += (norm("mtp_norm")(z),)
        exits = ()
        if cfg.exit_gate:
            # Every pass but the last has an exit probability; the last
            # takes what is left.
            with jax.named_scope("exit_gate"):
                exits = tuple(nn.Dense(1, dtype=jnp.float32,
                                       name="exit_gate")(states[:-1])[..., 0])
        if return_hidden and not return_passes:
            # Pre-head activations for the fused (chunked-vocab) loss —
            # the lm_head matmul then runs inside fused_cross_entropy
            # without materializing (N, V) logits (ops/losses.py).
            return x
        if return_hidden:
            # What a loss over every pass takes (make_loss_fn): each
            # pass's normed state and the gates' logits, tuples of R and
            # R - 1 (one and none for a plain model).
            return hidden, exits
        lm_head = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=False,
                           name="lm_head")
        if return_passes:
            return (tuple(lm_head(h).astype(jnp.float32) for h in hidden),
                    exits)
        # A looped model's logits are its last pass's (no early exit).
        return lm_head(x).astype(jnp.float32)


def init_params(config: TransformerConfig, seed: int = 0):
    # Init traces eagerly (no mesh program), where ring/ulysses attention
    # cannot run; a local-attention clone (contiguous layout — zigzag only
    # modifies the ring schedule, not parameter structure) has identical
    # parameter structure.
    model = Transformer(config._replace(attention="local",
                                        sp_layout="contiguous"))
    dummy = jnp.zeros((1, min(8, config.max_seq_len)), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), dummy)["params"]


def exit_log_probs(gate_logits):
    """Log of the exit distribution of a looped model, from its gates.

    ``gate_logits``: (R - 1, ...) — pass t's gate at every position,
    ``lambda_t = sigmoid(z_t)``. Returns (R, ...):
    ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)`` for t < R and
    ``p_R = prod_{j<R}(1 - lambda_j)`` — the last pass takes what is
    left, so the R probabilities sum to 1 whatever the gates say."""
    z = jnp.asarray(gate_logits, jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)   # sum_{j<=t} log(1-l_j)
    stayed = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([jax.nn.log_sigmoid(z) + stayed, stay[-1:]])


def exit_loss(pass_losses, gate_logits, beta: float):
    """The looped LM's entropy-regularised multi-exit objective
    (arXiv:2510.25741, first training stage): over all positions, the mean
    of ``sum_t p_t * loss_t - beta * H(p)``, with ``p`` the positions'
    exit distributions (:func:`exit_log_probs`) and ``H`` their entropy.
    ``pass_losses``: (R, ...) per-position losses of every pass;
    ``gate_logits``: (R - 1, ...)."""
    logp = exit_log_probs(gate_logits)
    p = jnp.exp(logp)
    return jnp.mean(jnp.sum(p * (pass_losses + beta * logp), axis=0))


def expert_pairs(config: TransformerConfig, sown) -> jax.Array:
    """(expert layers, held) int32 from what the expert layers sowed into
    :data:`EXPERT_PAIRS`, in layer order, the MTP module's layer last."""
    blocks = [f"block_{i}" for i in range(config.num_layers)
              if ffn_of_layer(config, i) == "moe"]
    if config.mtp is not None:
        blocks.append("mtp_block")
    return jnp.stack([sown[b]["moe"]["pairs"][0] for b in blocks])


def make_loss_fn(config: TransformerConfig, sp_rank=None,
                 fused_head: bool = False, exit_beta: float = 0.1,
                 with_expert_pairs: bool = False):
    """Next-token cross-entropy over the local shard.

    ``fused_head=True`` routes the lm_head matmul through
    :func:`horovod_tpu.ops.losses.fused_cross_entropy_per_position`
    (chunked-vocab log-sum-exp): the (N, V) logits never materialize in
    HBM in either direction — peak memory drops by that footprint (1 GB
    fp32 at T=8k, V=32k) at the cost of one extra head-matmul recompute
    in backward — the right trade when the logits tensor threatens HBM.
    Contiguous layouts only.

    A looped model (``recurrent_steps > 1``) with an ``exit_gate`` trains
    every pass: the one head is applied to each pass's state, and the
    loss is :func:`exit_loss` over the passes' per-position losses with
    ``exit_beta`` on the exit distribution's entropy. Without a gate only
    the last pass's loss counts (its gradient still reaches every shared
    leaf through all the passes).

    With ``mtp=`` the loss is the main one over the T - 1 positions with a
    target plus ``mtp.weight`` times the MTP module's over the T - 2
    positions whose ``t_{i+2}`` exists, both through the one head.

    ``with_expert_pairs`` (``moe=`` only) makes the loss function return
    ``(loss, pairs)`` for ``jax.value_and_grad(..., has_aux=True)``:
    ``pairs`` (expert layers, held) int32, how many (token, choice) pairs
    each held expert took in each expert layer (:func:`expert_pairs`).

    ``sp_rank``: traced group rank when sequence-parallel (compute it inside
    the hvd.spmd step: ``hvd.rank(cfg.sp_group)``); None for plain DP.
    Under SP the boundary token between shards is predicted from the previous
    shard's last position — that logit lives on the previous rank, so each
    shard trains on its own T_local - 1 transitions plus the ring makes all
    attention context available; losses are averaged per-token.

    With ``sp_layout='zigzag'`` the local shard is TWO non-adjacent chunks:
    positions come from :func:`horovod_tpu.zigzag_positions` and each chunk
    trains on its own c-1 transitions (the pair straddling the chunk
    boundary in the middle of the shard is not a real next-token
    transition and is excluded, like the shard boundary above).
    """
    model = Transformer(config)
    zigzag = (config.sp_layout == "zigzag"
              and config.attention == "ring")
    if with_expert_pairs and config.moe is None:
        raise ValueError("with_expert_pairs needs expert layers (moe=).")
    if config.mtp is not None and (sp_rank is not None or zigzag):
        raise ValueError(
            "the multi-token-prediction module (mtp=) is not supported "
            "under sequence parallelism: its shifted tokens cross the "
            "shards.")

    def _loss(params, batch):
        tokens = batch  # (B, T_local) int32
        t_local = tokens.shape[1]
        if zigzag:
            if fused_head:
                raise ValueError(
                    "fused_head=True is not supported with "
                    "sp_layout='zigzag' (the cross-chunk loss masking is "
                    "not plumbed through the fused path).")
            if config.recurrent_steps > 1:
                raise ValueError(
                    "a looped model (recurrent_steps > 1) is not supported "
                    "with sp_layout='zigzag' (the cross-chunk loss masking "
                    "is not plumbed through the multi-exit loss).")
            if sp_rank is None:
                raise ValueError(
                    "sp_layout='zigzag' needs sp_rank (the SP group rank "
                    "determines the shard's chunk positions).")
            from horovod_tpu.core import state as _state
            from horovod_tpu.parallel.sequence import zigzag_positions

            gsize = _state.get_group(config.sp_group).size
            pos = zigzag_positions(sp_rank(), t_local, gsize)
            logits = model.apply({"params": params}, tokens, positions=pos)
            c = t_local // 2
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:])      # (B, T_local - 1)
            # Transition c-1 -> c crosses the non-adjacent chunk boundary.
            valid = jnp.arange(t_local - 1) != (c - 1)
            return (per_tok * valid[None]).sum() / valid.sum()
        offset = 0 if sp_rank is None else sp_rank() * t_local
        # Every pass's state (or logits) and the exit gates' logits: one
        # and none for a plain model, R and R - 1 for a gated looped one.
        if with_expert_pairs:
            (passes, exits), sown = model.apply(
                {"params": params}, tokens, shard_offset=offset,
                return_hidden=fused_head, return_passes=True,
                mutable=[EXPERT_PAIRS])
            pairs = expert_pairs(config, sown[EXPERT_PAIRS])
            return _passes_loss(params, tokens, passes, exits), pairs
        passes, exits = model.apply(
            {"params": params}, tokens, shard_offset=offset,
            return_hidden=fused_head, return_passes=True)
        return _passes_loss(params, tokens, passes, exits)

    def _mtp_loss(params, tokens, passes):
        """Main loss + ``mtp.weight`` x the MTP module's, from the two
        states ``passes`` holds (logits where the head is not fused)."""
        main, ahead = passes
        with jax.named_scope("head"):
            if fused_head:
                from horovod_tpu.ops.losses import (
                    default_chunk, fused_cross_entropy_per_position)

                w = params["lm_head"]["kernel"].astype(config.dtype)
                ce = lambda h, shift: fused_cross_entropy_per_position(
                    h[:, :-shift].reshape(-1, h.shape[-1]), w,
                    tokens[:, shift:].reshape(-1),
                    chunk=default_chunk(w.shape[1]))
            else:
                ce = lambda logits, shift: \
                    optax.softmax_cross_entropy_with_integer_labels(
                        logits[:, :-shift], tokens[:, shift:])
            return ce(main, 1).mean() + config.mtp.weight * ce(ahead,
                                                               2).mean()

    def _passes_loss(params, tokens, passes, exits):
        if config.mtp is not None:
            _timeline.session().count_plan("model.head_applications", 2)
            return _mtp_loss(params, tokens, passes)
        if not config.exit_gate:
            passes = passes[-1:]  # only the last pass is trained
        _timeline.session().count_plan("model.head_applications",
                                       len(passes))
        with jax.named_scope("head"):
            # Shift within the shard: predict token[t+1] from position t.
            if fused_head:
                from horovod_tpu.ops.losses import (
                    default_chunk, fused_cross_entropy_per_position)

                w = params["lm_head"]["kernel"].astype(config.dtype)
                rows = [hidden[:, :-1].reshape(-1, hidden.shape[-1])
                        for hidden in passes]
                tgt = tokens[:, 1:].reshape(-1)
                per_pass = [fused_cross_entropy_per_position(
                    x2, w, tgt, chunk=default_chunk(w.shape[1]))
                    for x2 in rows]
            else:
                targets = tokens[:, 1:]
                per_pass = [optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], targets) for logits in passes]
            if not config.exit_gate:
                return per_pass[0].mean()
        with jax.named_scope("exit_gate"):
            gates = jnp.stack([z[:, :-1].reshape(per_pass[0].shape)
                               for z in exits])
            return exit_loss(jnp.stack(per_pass), gates, exit_beta)

    def loss_fn(params, batch):
        # The root of every op_name of the loss, whatever flax calls its
        # modules: under value_and_grad JAX makes it jvp(hvd.model) for the
        # forward and transpose(jvp(hvd.model)) for the backward.
        with jax.named_scope("hvd.model"):
            return _loss(params, batch)

    return loss_fn


def synthetic_tokens(batch_size: int, seq_len: int,
                     vocab_size: int = 32_000, seed: int = 0):
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (batch_size, seq_len), 0, vocab_size,
                              dtype=jnp.int32)


def decode_config(config: TransformerConfig) -> TransformerConfig:
    """The cached-decode variant of a training config: one-token steps,
    local attention, contiguous layout — what ``generate``, the public
    ``prefill``/``decode_step`` pair, and the serving engine all run."""
    return config._replace(decode=True, attention="local",
                           sp_layout="contiguous")


def draft_config(config: TransformerConfig, num_layers: int = 1,
                 mlp_dim: int | None = None) -> TransformerConfig:
    """A small DRAFT-model config for speculative decoding
    (serving/engine.py ``speculate=k``): same vocab (proposals are
    target token ids), same heads/embed/max_seq_len (its paged cache
    rides the target's block tables and positions), fewer layers — the
    draft only has to guess, the target re-scores every emitted token.
    Train it separately (or distill from the target) and pass its
    params as ``draft_params``."""
    if num_layers < 1:
        raise ValueError(f"draft num_layers must be >= 1, got {num_layers}")
    return config._replace(
        num_layers=num_layers,
        mlp_dim=config.mlp_dim if mlp_dim is None else mlp_dim)


def init_cache(config: TransformerConfig, batch_size: int):
    """A zeroed dense KV cache (the flax 'cache' collection pytree) for
    ``batch_size`` rows — shapes via eval_shape, no parameter
    materialization. Feed it to :func:`decode_step`."""
    model = Transformer(decode_config(config))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch_size, 1), jnp.int32)))["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _decode_apply(model, params, cache, token2d, t):
    """The single shared one-token cached-decode apply: (B, 1) tokens at
    position ``t`` against the dense cache → ((B, V) logits, cache')."""
    logits, upd = model.apply({"params": params, "cache": cache},
                              token2d, shard_offset=t, mutable=["cache"])
    return logits[:, 0], upd["cache"]


def _cache_index(cache):
    """Current write position of a dense decode cache (its 'idx' entry —
    every layer carries the same value)."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if getattr(path[-1], "key", None) == "idx":
            return leaf
    raise ValueError("not a decode cache: no 'idx' entry (build one with "
                     "init_cache()).")


def decode_step(config: TransformerConfig, params, cache, token, t=None):
    """One cached decode step: ``token`` (B,) or (B, 1) int32 at position
    ``t`` (default: the cache's own write index) → ((B, V) fp32 logits,
    updated cache). This is the piece :func:`generate` runs in its scan;
    the serving engine runs the same model path against a paged cache
    (serving/engine.py)."""
    model = Transformer(decode_config(config))
    token = jnp.asarray(token, jnp.int32)
    if token.ndim == 1:
        token = token[:, None]
    if t is None:
        t = _cache_index(cache)
    return _decode_apply(model, params, cache, token, t)


def prefill(config: TransformerConfig, params, tokens):
    """Ingest a whole prompt through the cached decode path in ONE
    compiled call: ``tokens`` (B, P) int32 → (cache, (B, V) logits at the
    last prompt position — sample the first generated token from them).

    Internally a ``lax.scan`` of the same one-token apply that
    :func:`decode_step` runs, so prefill-then-decode is numerically
    IDENTICAL to feeding the prompt token-by-token (the property the
    serving engine's bit-exactness guarantee rests on)."""
    from jax import lax

    model = Transformer(decode_config(config))
    tokens = jnp.asarray(tokens, jnp.int32)
    b, plen = tokens.shape
    if plen > config.max_seq_len:
        raise ValueError(
            f"prompt ({plen}) exceeds max_seq_len ({config.max_seq_len}) "
            f"— the KV cache's capacity.")
    cache = init_cache(config, b)

    def step(cache, xs):
        tok, t = xs
        logits, cache = _decode_apply(model, params, cache, tok[:, None], t)
        return cache, logits

    cache, logits = lax.scan(step, cache,
                             (tokens.T, jnp.arange(plen)))
    return cache, logits[-1]


def generate(config: TransformerConfig, params, prompt,
             max_new_tokens: int, temperature: float = 0.0,
             seed: int = 0):
    """Autoregressive generation with a KV cache (greedy or sampled).

    ``prompt``: (B, P) int32; returns (B, P + max_new_tokens) — the prompt
    followed by generated tokens. One token per step against the flax
    'cache' collection (the decode path in :class:`Attention`), so each
    step costs O(T) attention instead of O(T²) recompute; the cache holds
    Hkv heads, so GQA shrinks it by H/Hkv. ``temperature=0`` is greedy;
    otherwise softmax sampling at the given temperature.

    This is the one-shot single-chip serving path; a request-lifecycle
    service (continuous batching, paged cache, admission control) is
    :class:`horovod_tpu.serving.Engine` (docs/inference.md) — training
    state restores into both directly (the parameter tree is identical).
    """
    from jax import lax

    cfg = decode_config(config)
    model = Transformer(cfg)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, plen = prompt.shape
    total = plen + max_new_tokens
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({plen}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len}) — the KV cache's capacity.")

    cache = init_cache(config, b)

    def step(carry, t):
        cache, tok, rng = carry
        logits, cache = _decode_apply(model, params, cache, tok[:, None], t)
        rng, sub = jax.random.split(rng)
        if temperature == 0.0:
            sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            sampled = jax.random.categorical(
                sub, logits / temperature).astype(jnp.int32)
        # While inside the prompt, teacher-force the next prompt token.
        nxt = jnp.where(t + 1 < plen,
                        prompt[:, jnp.minimum(t + 1, plen - 1)], sampled)
        return (cache, nxt, rng), nxt

    carry = (cache, prompt[:, 0], jax.random.PRNGKey(seed))
    _, toks = lax.scan(step, carry, jnp.arange(total - 1))
    return jnp.concatenate([prompt[:, :1], jnp.swapaxes(toks, 0, 1)],
                           axis=1)
