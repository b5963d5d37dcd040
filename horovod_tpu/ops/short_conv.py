"""The gated short convolution: the mixer of an LFM2-style ``conv`` layer
between its two projections.

A ``conv`` layer mixes positions with no attention at all: its input
projection gives three streams ``[B | C | u]`` of the model's width each,
and what stands between that projection and the output one is

    z_t = B_t * u_t
    c_t = sum_{j < K} w[:, j] * z_{t - (K - 1) + j}      (z = 0 before 0)
    out_t = C_t * c_t

— a depthwise causal convolution of ``K`` taps (``conv_L_cache``; 3 in the
published models) over every channel alone, gated on both sides, with no
bias and no activation. Position ``t`` sees ``t - K + 1 .. t`` and nothing
later; with ``segment_ids`` (packed documents) a tap that would reach into
another document contributes nothing, as if each document began after
``K - 1`` zeros.

The whole pass is elementwise but for the shifts, so its time is its HBM
traffic: it is written as ``K`` shifted products for XLA to fuse into one
pass over ``[B | C | u]``, computed in float32 between the bfloat16 it
reads and writes, and wrapped in a ``jax.checkpoint`` so that the backward
keeps the projection's output alone (the three streams, which it needs
anyway) and not ``z`` and ``c`` in float32 beside it: they are two
multiplications away.

Plain traced code: it runs inside or outside ``hvd.spmd`` and its
transposes are JAX's. Under sequence parallelism a shard's first ``K - 1``
positions would need the previous shard's last ones (a halo exchange):
``models/transformer.py`` raises there, and for ``decode=True``, whose
state would be the last ``K - 1`` values of ``z`` and not a KV cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _back(a, shift: int, fill=0):
    """``a`` (B, T, ...) moved ``shift`` positions later along T: row ``t``
    holds ``a[t - shift]``, and ``fill`` where that is before 0."""
    if shift == 0:
        return a
    pad = [(0, 0), (shift, 0)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad, constant_values=fill)[:, :a.shape[1]]


def causal_taps(z, w, segment_ids=None):
    """``c_t = sum_j w[:, j] * z_{t - (K - 1) + j}``: ``z`` (B, T, E),
    ``w`` (E, K) — tap ``K - 1`` weighs the position itself, tap 0 the one
    ``K - 1`` before. ``segment_ids`` (B, T): a tap from another segment
    contributes nothing."""
    taps = w.shape[1]
    out = z * w[:, taps - 1]
    for j in range(taps - 1):
        shift = taps - 1 - j
        earlier = _back(z, shift)
        if segment_ids is not None:
            same = segment_ids == _back(segment_ids, shift, fill=-1)
            earlier = jnp.where(same[..., None], earlier, 0)
        out = out + earlier * w[:, j]
    return out


@jax.checkpoint
def gated_short_conv(bcu, w, segment_ids=None):
    """``C * taps(B * u)`` of ``bcu`` = ``[B | C | u]`` (B, T, 3E) with the
    taps ``w`` (E, K); (B, T, E) in ``bcu``'s dtype, float32 inside."""
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    conv = causal_taps(b * u, w.astype(jnp.float32), segment_ids)
    return (c * conv).astype(bcu.dtype)
