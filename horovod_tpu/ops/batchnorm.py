"""Fused BatchNorm — pallas channel reductions, bf16 reads, fp32 accumulation.

Why this exists: the profile in ``docs/profiles/resnet50_v5e.md`` shows the
ResNet-50 training step spending ≈23% of its device time in XLA's
convert+reduce fusions — BatchNorm statistics and their gradients computed
by upcasting every bf16 activation element to fp32 on the VPU before a
cross-sublane reduction, fused into the convolutions' epilogues where they
serialize against the MXU. (The reference feeds its BN to cuDNN's fused
batchnorm and never sees this cost; there is no reference code to port —
tf_cnn_benchmarks simply calls ``fused_batch_norm``.)

The TPU-native fix: channel sums are a **matvec** — ``ones @ X`` contracts
the (batch·spatial) dimension on the MXU, which reads bf16 natively and
accumulates in fp32 for free. One pallas kernel computes Σx and Σx² in a
single HBM pass (1 VPU multiply per element for the square, 2 MAC/element
on the otherwise-idle MXU); a second computes the backward's Σdy and
Σ(dy·x̂) the same way. The elementwise normalize/scale stays in plain JAX
(XLA fuses it into neighbours). A ``jax.custom_vjp`` ties the two kernels
into a training-mode batch-norm whose only fp32 traffic is (C,)-sized.

Cross-replica statistics (the reference's synced-BN analog) ride
``axis_name`` psums over the per-device partial sums, exactly like flax's
``nn.BatchNorm(axis_name=...)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.core import state as _state



def _pick_block(n: int, c: int) -> int:
    """Rows per grid step: keep the bf16 tile ≲ 1 MB and sublane-aligned
    (the grad kernel holds two tiles + a same-size product intermediate,
    double-buffered — the budget below keeps that inside scoped VMEM)."""
    target = max(1, (1024 * 1024) // max(2 * c, 1))
    bn = 1 << min(13, max(3, target.bit_length() - 1))
    return min(bn, max(8, 1 << (n - 1).bit_length()))


_VMEM_LIMIT = 48 * 1024 * 1024


def _sums_kernel(x_ref, s1_ref, s2_ref, acc_ref, *, nsteps):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # (bn, C) bf16
    ones = jnp.ones((1, x.shape[0]), dtype=x.dtype)
    dims = (((1,), (0,)), ((), ()))
    s1 = lax.dot_general(ones, x, dims, preferred_element_type=jnp.float32)
    s2 = lax.dot_general(ones, x * x, dims,
                         preferred_element_type=jnp.float32)
    acc_ref[0:1] += s1
    acc_ref[1:2] += s2

    @pl.when(i == nsteps - 1)
    def _out():
        s1_ref[...] = acc_ref[0:1]
        s2_ref[...] = acc_ref[1:2]


def channel_sums(x, interpret: bool | None = None):
    """(Σx, Σx²) over all leading dims, fp32, shape (C,) each — one HBM pass.

    ``x``: any-rank bf16/fp32 array, channels last. The reduction runs as
    two MXU matvecs per tile (ones·x, ones·x²) with fp32 accumulators, so
    bf16 inputs are never upcast elementwise in HBM. ``interpret=None``
    auto-selects: compiled pallas on TPU, a plain-JAX fallback elsewhere;
    ``True`` forces the pallas interpreter (kernel-logic tests).
    """
    c = x.shape[-1]
    n = int(np.prod(x.shape[:-1]))
    x2 = x.reshape(n, c)
    bn = _pick_block(n, c)
    nsteps = -(-n // bn)
    pad = nsteps * bn - n
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if interpret is None:
        interpret = _state.target_platform() != "tpu"
        if interpret:
            # Interpreter is too slow for real sizes; the math is 2 reduces.
            xf = x2.astype(jnp.float32)
            return jnp.sum(xf, axis=0), jnp.sum(xf * xf, axis=0)
    s1, s2 = pl.pallas_call(
        functools.partial(_sums_kernel, nsteps=nsteps),
        name="hvd_bn_sums",
        grid=(nsteps,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x2)
    return s1[0], s2[0]


def _grad_sums_kernel(dy_ref, x_ref, mean_ref, rstd_ref, sdy_ref, sdx_ref,
                      acc_ref, *, nsteps):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...]                    # (bn, C) bf16
    x = x_ref[...]
    # x̂ in the input dtype: keeps the tile-sized intermediate at bf16
    # width (a full-tile fp32 x̂ was what blew the scoped-VMEM budget),
    # and the dy·x̂ product feeds the MXU at bf16 anyway.
    xhat = ((x - mean_ref[...].astype(x.dtype)) *
            rstd_ref[...].astype(x.dtype))
    ones = jnp.ones((1, dy.shape[0]), dtype=dy.dtype)
    dims = (((1,), (0,)), ((), ()))
    sdy = lax.dot_general(ones, dy, dims, preferred_element_type=jnp.float32)
    sdx = lax.dot_general(ones, dy * xhat, dims,
                          preferred_element_type=jnp.float32)
    acc_ref[0:1] += sdy
    acc_ref[1:2] += sdx

    @pl.when(i == nsteps - 1)
    def _out():
        sdy_ref[...] = acc_ref[0:1]
        sdx_ref[...] = acc_ref[1:2]


def channel_grad_sums(dy, x, mean, rstd, interpret: bool | None = None):
    """(Σdy, Σdy·x̂) over leading dims, fp32 (C,) — the BN backward sums.

    ``mean``/``rstd``: (C,) fp32. x̂ is recomputed tile-locally in VMEM, so
    the normalized activation is never materialized in HBM.
    """
    c = x.shape[-1]
    n = int(np.prod(x.shape[:-1]))
    dy2, x2 = dy.reshape(n, c), x.reshape(n, c)
    bn = _pick_block(n, c)
    nsteps = -(-n // bn)
    pad = nsteps * bn - n
    if pad:
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if interpret is None:
        interpret = _state.target_platform() != "tpu"
        if interpret:
            dyf = dy2.astype(jnp.float32)
            xhat = (x2.astype(jnp.float32) - mean) * rstd
            return jnp.sum(dyf, axis=0), jnp.sum(dyf * xhat, axis=0)
    sdy, sdx = pl.pallas_call(
        functools.partial(_grad_sums_kernel, nsteps=nsteps),
        name="hvd_bn_grad_sums",
        grid=(nsteps,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(dy2, x2, mean.reshape(1, c), rstd.reshape(1, c))
    return sdy[0], sdx[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def batch_norm_train(x, gamma, beta, eps: float = 1e-5,
                     axis_name: str | None = None):
    """Training-mode batch norm; returns ``(y, mean, var)``.

    ``x``: (..., C) bf16/fp32; ``gamma``/``beta``: (C,) fp32. ``mean``/
    ``var`` are the fp32 batch statistics (biased variance, like flax) for
    the caller's running-average update. With ``axis_name`` the statistics
    (and backward sums) are psummed across that mesh axis — synced BN.
    """
    y, mean, var, _ = _bn_fwd_impl(x, gamma, beta, eps, axis_name)
    return y, mean, var


def _bn_fwd_impl(x, gamma, beta, eps, axis_name):
    n = float(np.prod(x.shape[:-1]))
    s1, s2 = channel_sums(x)
    if axis_name is not None:
        s1 = lax.psum(s1, axis_name)
        s2 = lax.psum(s2, axis_name)
        n = n * lax.psum(1, axis_name)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    # One fused multiply-add pass in x's dtype: y = x·a + b.
    a = (gamma * rstd).astype(x.dtype)
    b = (beta - gamma * rstd * mean).astype(x.dtype)
    y = x * a + b
    return y, mean, var, rstd


def _bn_fwd(x, gamma, beta, eps, axis_name):
    y, mean, var, rstd = _bn_fwd_impl(x, gamma, beta, eps, axis_name)
    return (y, mean, var), (x, gamma, mean, rstd)


def _bn_bwd(eps, axis_name, res, cts):
    dy, _, _ = cts  # mean/var cotangents: running-average updates are
    x, gamma, mean, rstd = res  # stop-gradiented by the module below.
    n = float(np.prod(x.shape[:-1]))
    sdy, sdx = channel_grad_sums(dy, x, mean, rstd)
    if axis_name is not None:
        sdy = lax.psum(sdy, axis_name)
        sdx = lax.psum(sdx, axis_name)
        n = n * lax.psum(1, axis_name)
    dgamma = sdx
    dbeta = sdy
    # dx = γ·rstd·(dy - Σdy/n - x̂·Σ(dy·x̂)/n), one fused elementwise pass.
    a = (gamma * rstd).astype(x.dtype)
    c1 = (sdy / n).astype(x.dtype)
    c2 = (gamma * rstd * rstd * (sdx / n)).astype(x.dtype)
    # dx = a·dy - a·Σdy/n - (x-μ)·rstd·(γ·rstd·Σ(dy·x̂)/n)
    dx = a * dy - a * c1 - (x - mean.astype(x.dtype)) * c2
    return dx, dgamma, dbeta


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)
