"""Fused (chunked-vocab) softmax cross-entropy — the LM-head hot loss.

A causal LM's loss materializes logits of shape (N, V): at T=8k and
V=32k that is a 1 GB fp32 tensor written by the head matmul, read by the
log-sum-exp, saved for backward, and turned into an equally large dlogits
— several GB of HBM traffic that dwarfs the loss math itself. This module
computes ``CE(x @ W, targets)`` WITHOUT ever materializing the full
logits: a ``lax.scan`` over vocabulary chunks keeps a running
log-sum-exp (the flash-attention trick applied to the vocab axis), and a
custom VJP recomputes each chunk's logits during backward, emitting the
``softmax - onehot`` cotangent chunk-by-chunk straight into the dx/dW
matmuls. Peak memory is O(N · chunk) and logits never round-trip HBM.
Vocabularies that do not divide the chunk (GPT-2's prime 50257, say) get
a single remainder chunk — no padding, no divisibility requirement.

The same decomposition ships as fused linear-cross-entropy kernels in
GPU stacks (Liger et al.); on TPU the scan + remat formulation lets XLA
keep every chunk's matmul on the MXU with fp32 accumulation.

Measured (v5e, T=8k, V=32k, E=1024 — r4 device profile,
tools/profile_lm.py): a clean WIN on both axes. Peak HBM drops by the
logits' footprint (>1 GB fp32 there), AND the step gets faster — the
unfused path spends ~10 ms/step materializing/converting fp32 logits,
more than the ONE extra head-matmul recompute the chunked backward
costs (86.8 → 82.0 ms/step on the bench.py LM, which uses this path by
default).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Default vocabulary chunk width. 8192 measured best on v5e (r5 sweep,
# tools/lm_exp.py: 4096 → 8192 is -1.3 ms/step on the bench LM; 16384 is
# only marginally better while doubling the live chunk footprint);
# callers (model-level loss, bench FLOP accounting) import this rather
# than re-hardcoding it.
DEFAULT_CHUNK = 8192

# Chunk counts up to this bound run as a Python-unrolled loop instead of
# ``lax.scan``. Measured on v5e (r5, tools/profile_lm.py): the scan
# formulation cost ~6 ms/step of pure machinery on the bench LM — the
# backward accumulated dW chunks through a loop-carried stacked buffer
# (dynamic-update-slice ~3 ms + a moveaxis relayout ~0.8 ms) and the
# forward paid ~2 ms of loop-carry shuffling — all of which vanishes
# when the chunks are separate traced ops XLA can schedule freely.
# Scan remains the fallback so a huge vocabulary (V/chunk beyond the
# bound) cannot blow up program size / compile time.
UNROLL_MAX_CHUNKS = 16


def default_chunk(vocab_size: int) -> int:
    """The chunk :func:`fused_cross_entropy` callers use by default —
    shared so FLOP accounting (bench.py) can never diverge from the
    chunk the model-level loss (models/transformer.py) actually runs."""
    return min(DEFAULT_CHUNK, vocab_size)


def scan_counted_once_flops(n_tok: int, embed: int, vocab: int,
                            chunk: int) -> int:
    """Head-matmul FLOPs that XLA's cost analysis does NOT count for one
    :func:`fused_cross_entropy` call — the bench.py MFU correction.

    XLA counts a ``lax.scan`` body once; the unrolled path (``V/chunk <=
    UNROLL_MAX_CHUNKS``) has no scan, so everything is counted and the
    correction is zero. On the scan path the (nfull − 1) uncounted full
    chunks each run 4 matmuls of 2·N·E·chunk (fwd logits; bwd recompute +
    dx + dW). Kept next to the implementation so the accounting can never
    silently diverge from the code path actually taken."""
    nfull = vocab // chunk
    if nfull <= UNROLL_MAX_CHUNKS:
        return 0
    return 4 * 2 * n_tok * embed * max(0, nfull - 1) * chunk


def _split(w, chunk):
    """W -> (scan-major full chunks (n, E, chunk), remainder (E, r) or None)."""
    e, v = w.shape
    nfull = v // chunk
    w_full = jnp.moveaxis(w[:, :nfull * chunk].reshape(e, nfull, chunk),
                          1, 0)
    w_rem = w[:, nfull * chunk:] if v % chunk else None
    return w_full, w_rem


def _lse_update(m, s, tl, logits, base, targets):
    """Fold one chunk's logits into the running (max, sumexp, target)."""
    width = logits.shape[1]
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    s = s * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1)
    local = targets - base
    in_chunk = (local >= 0) & (local < width)
    picked = jnp.take_along_axis(
        logits, jnp.clip(local, 0, width - 1)[:, None], axis=1)[:, 0]
    tl = jnp.where(in_chunk, picked, tl)
    return m_new, s, tl


def _fwd_scan(x, w, targets, chunk):
    """Running (log-sum-exp, target_logit) over vocab chunks, each (N,).

    Chunk counts ≤ :data:`UNROLL_MAX_CHUNKS` unroll in Python (see the
    constant's rationale); larger vocabularies take the ``lax.scan``
    formulation with identical math."""
    n = x.shape[0]
    e, v = w.shape
    nfull = v // chunk
    m = jnp.full((n,), -jnp.inf, jnp.float32)
    s = jnp.zeros((n,), jnp.float32)
    tl = jnp.zeros((n,), jnp.float32)

    if nfull <= UNROLL_MAX_CHUNKS:
        for i in range(nfull):
            logits = jnp.dot(x, w[:, i * chunk:(i + 1) * chunk],
                             preferred_element_type=jnp.float32)
            m, s, tl = _lse_update(m, s, tl, logits, i * chunk, targets)
        if v % chunk:
            logits = jnp.dot(x, w[:, nfull * chunk:],
                             preferred_element_type=jnp.float32)
            m, s, tl = _lse_update(m, s, tl, logits, nfull * chunk,
                                   targets)
        return m + jnp.log(s), tl

    w_full, w_rem = _split(w, chunk)

    def step(carry, wc_i):
        m, s, tl, i = carry
        wc, = wc_i
        logits = jnp.dot(x, wc, preferred_element_type=jnp.float32)
        m, s, tl = _lse_update(m, s, tl, logits, i * chunk, targets)
        return (m, s, tl, i + 1), None

    (m, s, tl, _), _ = lax.scan(step, (m, s, tl, jnp.int32(0)),
                                (w_full,))
    if w_rem is not None:
        logits = jnp.dot(x, w_rem, preferred_element_type=jnp.float32)
        m, s, tl = _lse_update(m, s, tl, logits,
                               w_full.shape[0] * chunk, targets)
    return m + jnp.log(s), tl


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_cross_entropy_per_position(x, w, targets,
                                     chunk: int = DEFAULT_CHUNK):
    """Cross-entropy of ``x @ w`` against integer ``targets``, one loss a
    row: ``lse - target logit``, shape (N,), float32.

    ``x``: (N, E) activations (any float dtype; matmuls run in its dtype
    with fp32 accumulation); ``w``: (E, V) vocabulary projection;
    ``targets``: (N,) int32 class ids. Equivalent to
    ``optax.softmax_cross_entropy_with_integer_labels(x @ w, targets)``
    without materializing the (N, V) logits in either direction; any
    vocabulary size works (a trailing remainder chunk handles V % chunk).
    The backward takes an (N,) cotangent — a loss that weights every
    position by itself (the looped LM's exit probabilities,
    models/transformer.py) — and scales each row's ``softmax - onehot``
    by it.
    """
    lse, tl = _fwd_scan(x, w, targets, chunk)
    return lse - tl


def fused_cross_entropy(x, w, targets, chunk: int = DEFAULT_CHUNK):
    """Mean cross-entropy of ``x @ w`` against integer ``targets``:
    :func:`fused_cross_entropy_per_position` with a mean on top (its
    backward hands every row the cotangent ``g / N``)."""
    return jnp.mean(fused_cross_entropy_per_position(x, w, targets, chunk))


def _fce_fwd(x, w, targets, chunk):
    lse, tl = _fwd_scan(x, w, targets, chunk)
    return lse - tl, (x, w, targets, lse)


def _dchunk(x, wc, base, targets, lse, scale):
    """Recompute one chunk's softmax-minus-onehot cotangent, each row
    scaled by its ``scale`` (N, 1); return (dx contribution, dW chunk)."""
    width = wc.shape[1]
    logits = jnp.dot(x, wc, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse[:, None])
    local = targets - base
    onehot = ((local[:, None] == jnp.arange(width)[None, :])
              .astype(jnp.float32))
    dlogits = ((p - onehot) * scale).astype(x.dtype)
    dx = jnp.dot(dlogits, wc.T, preferred_element_type=jnp.float32)
    dwc = jnp.dot(x.T, dlogits, preferred_element_type=jnp.float32)
    return dx, dwc


def _fce_bwd(chunk, res, g):
    x, w, targets, lse = res
    n, e = x.shape
    v = w.shape[1]
    nfull = v // chunk
    scale = g.astype(jnp.float32)[:, None]         # a row's own cotangent

    if nfull <= UNROLL_MAX_CHUNKS:
        # Unrolled: each chunk's dW is its own tensor and one concatenate
        # assembles (E, V) — no loop-carried stacked buffer to
        # dynamic-update-slice through, no relayout (the scan path's two
        # big data-movement costs; see UNROLL_MAX_CHUNKS).
        dx = jnp.zeros((n, e), jnp.float32)
        dws = []
        for i in range(nfull):
            dxc, dwc = _dchunk(x, w[:, i * chunk:(i + 1) * chunk],
                               i * chunk, targets, lse, scale)
            dx = dx + dxc
            dws.append(dwc)
        if v % chunk:
            dxr, dwr = _dchunk(x, w[:, nfull * chunk:], nfull * chunk,
                               targets, lse, scale)
            dx = dx + dxr
            dws.append(dwr)
        dw = dws[0] if len(dws) == 1 else jnp.concatenate(dws, axis=1)
        return dx.astype(x.dtype), dw.astype(w.dtype), None

    w_full, w_rem = _split(w, chunk)

    def step(carry, wc_i):
        dx, i = carry
        wc, = wc_i
        dxc, dwc = _dchunk(x, wc, i * chunk, targets, lse, scale)
        return (dx + dxc, i + 1), dwc

    dx0 = jnp.zeros((n, e), jnp.float32)
    (dx, _), dw_chunks = lax.scan(step, (dx0, jnp.int32(0)), (w_full,))
    dw = jnp.moveaxis(dw_chunks, 0, 1).reshape(e, w_full.shape[0] * chunk)
    if w_rem is not None:
        dxr, dwr = _dchunk(x, w_rem, w_full.shape[0] * chunk, targets,
                           lse, scale)
        dx = dx + dxr
        dw = jnp.concatenate([dw, dwr], axis=1)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


fused_cross_entropy_per_position.defvjp(_fce_fwd, _fce_bwd)
