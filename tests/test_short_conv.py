"""The gate-and-tap pass's kernel pair (``ops/short_conv.py``): the
forward and backward Pallas kernels, interpreted, against the plain form
computed in float32 — the output, the three streams' cotangents and the
taps' gradient — over tiles that split T into three and E into two, with
and without packed documents; causality bit for bit on the kernel path,
both ways; which path runs where; and one ``pallas_call`` built a
geometry however many call sites there are."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import timeline
from horovod_tpu.models import transformer
from horovod_tpu.ops import short_conv

B, T, E = 2, 96, 256
TILES = (32, 128)  # three tiles of T of two strips each, two tiles of E
SEGMENTS = {
    "none": None,
    "inside_a_strip": [0] * 5 + [1] * 20 + [2] * 71,
    "on_a_tile_edge": [0] * 32 + [1] * 16 + [2] * 48,  # and a strip's
    "one_token": [0] * 47 + [1] + [2] * 48,  # alone at a strip's last row
}


def _inputs(taps, seed=0):
    ka, kw, kg = jax.random.split(jax.random.PRNGKey(seed + taps), 3)
    bcu = jax.random.normal(ka, (B, T, 3 * E), jnp.float32)
    w = jax.random.uniform(kw, (E, taps), jnp.float32, -0.58, 0.58)
    g = jax.random.normal(kg, (B, T, E), jnp.float32)
    return bcu.astype(jnp.bfloat16), w, g.astype(jnp.bfloat16)


def _segs(kind):
    row = SEGMENTS[kind]
    return None if row is None else jnp.asarray([row, row[::-1]], jnp.int32)


def _kernel(taps, segs):
    keep = None if segs is None or taps == 1 else short_conv._keep(segs,
                                                                   taps)
    return lambda x, w: short_conv._kernels(x, w, keep, TILES, True)


def _close_in_bf16(got, want):
    """A bfloat16 result of float32 arithmetic: one rounding (half an ulp,
    2^-9 of the value) and the summation order."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=1e-5)


@pytest.mark.parametrize("segments", list(SEGMENTS))
@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_kernels_are_the_plain_form_in_float32(taps, segments):
    bcu, w, g = _inputs(taps)
    segs = _segs(segments)
    plain = lambda x, w: short_conv._plain(x, w, segs)
    want, vjp = jax.vjp(plain, bcu.astype(jnp.float32), w)
    d_want, dw_want = vjp(g.astype(jnp.float32))
    got, vjp = jax.vjp(_kernel(taps, segs), bcu, w)
    d_got, dw_got = vjp(g)
    assert got.dtype == d_got.dtype == jnp.bfloat16
    assert got.shape == (B, T, E) and d_got.shape == bcu.shape
    assert dw_got.shape == w.shape and dw_got.dtype == jnp.float32
    _close_in_bf16(got, want)
    for k in range(3):  # d[B], d[C], d[u]: each in its own column slab
        sl = slice(k * E, (k + 1) * E)
        _close_in_bf16(d_got[..., sl], d_want[..., sl])
    np.testing.assert_allclose(dw_got, dw_want, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(dw_want))))


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_kernels_are_causal_bit_for_bit(taps):
    """Forward: changing position t leaves every output before t, and
    from t + taps on, bit-identical. Backward: the cotangent of position
    t's output reaches the streams at t - taps + 1 .. t (the taps' reach
    on z, and C at t itself) and nothing else — across strip edges (15 |
    16) and tile edges (31 | 32, 63 | 64) both ways."""
    bcu, w, _ = _inputs(taps)
    conv = _kernel(taps, None)
    out = conv(bcu, w)
    for t in (0, 15, 16, 31, 32, 63, 64, T - 1):
        moved = conv(bcu.at[:, t].add(1.0), w)
        np.testing.assert_array_equal(out[:, :t], moved[:, :t])
        assert not np.array_equal(out[:, t], moved[:, t])
        np.testing.assert_array_equal(out[:, t + taps:], moved[:, t + taps:])
        d_bcu, _ = jax.grad(lambda x, w: jnp.sum(
            conv(x, w)[:, t].astype(jnp.float32)), argnums=(0, 1))(bcu, w)
        reach = np.zeros(T, bool)
        reach[max(0, t - taps + 1):t + 1] = True
        assert not np.any(np.asarray(d_bcu, np.float32)[:, ~reach])
        assert np.all(np.any(np.asarray(d_bcu, np.float32) != 0,
                             axis=(0, 2))[reach])


def test_off_the_chip_and_in_float32_the_plain_form_runs(monkeypatch):
    """The rule (``runs_kernels``): a TPU, bfloat16, whole lanes. Here (a
    CPU) the pass is the plain form for bfloat16 too — no Pallas call in
    its program — and on a TPU for float32, for a width that is not whole
    lanes, and for a long sequence no multiple of 16 divides."""
    bcu, w, _ = _inputs(3)
    assert not short_conv.runs_kernels(T, E, 3, jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(short_conv.gated_short_conv)(bcu, w))
    assert "pallas_call" not in jaxpr and "remat" in jaxpr  # checkpoint
    np.testing.assert_array_equal(short_conv.gated_short_conv(bcu, w),
                                  short_conv._plain(bcu, w, None))
    monkeypatch.setattr(short_conv._state, "target_platform", lambda: "tpu")
    assert short_conv.runs_kernels(8192, 2048, 3, jnp.bfloat16)
    assert short_conv._tiles(8192, 2048, 3) == (512, 512)
    assert short_conv._tiles(T, 640, 3) == (T, 128)  # the whole of T
    assert not short_conv.runs_kernels(8192, 2048, 3, jnp.float32)
    assert not short_conv.runs_kernels(8192, 2000, 3, jnp.bfloat16)
    assert not short_conv.runs_kernels(8200, 2048, 3, jnp.bfloat16)
    assert not short_conv.runs_kernels(8192, 2048, 18, jnp.bfloat16)


@pytest.fixture
def fresh_calls():
    short_conv._fwd_call.cache_clear()
    short_conv._bwd_call.cache_clear()
    yield
    short_conv._fwd_call.cache_clear()  # nothing keeps a patched body
    short_conv._bwd_call.cache_clear()


def test_a_geometry_is_traced_once_whatever_the_call_sites(monkeypatch,
                                                           fresh_calls):
    """Four call sites of one geometry (the cell's four conv layers),
    forward and backward: each kernel's body is traced once or twice a
    process (``_fwd_call`` / ``_bwd_call``: a ``pallas_call`` built a
    call site is a fresh ``jax.jit`` whose cache never hits: PERF.md,
    PR 35), and a second geometry is a second tracing."""
    traced = collections.Counter()

    def counting(kernel):
        def counted(*args, **kwargs):
            traced[kernel.__name__] += 1
            return kernel(*args, **kwargs)
        return counted

    monkeypatch.setattr(short_conv, "_fwd_kernel",
                        counting(short_conv._fwd_kernel))
    monkeypatch.setattr(short_conv, "_bwd_kernel",
                        counting(short_conv._bwd_kernel))
    bcu, w, _ = _inputs(3)
    conv = _kernel(3, None)
    layers = lambda x, w: sum(jnp.sum(conv(x * (i + 1), w).astype(
        jnp.float32)) for i in range(4))
    jaxpr = str(jax.make_jaxpr(jax.grad(layers, argnums=(0, 1)))(bcu, w))
    assert jaxpr.count("name=hvd_conv_fwd") == 4
    assert jaxpr.count("name=hvd_conv_bwd") == 4
    assert traced["_bwd_kernel"] == 1
    assert 1 <= traced["_fwd_kernel"] <= 2
    jax.make_jaxpr(conv)(bcu[:, :64], w)  # another T: another geometry
    assert short_conv._fwd_call.cache_info().currsize == 2
    assert short_conv._bwd_call.cache_info().currsize == 1


CFG = transformer.TransformerConfig(
    vocab_size=64, num_layers=3, num_heads=2, embed_dim=128, mlp_dim=128,
    max_seq_len=32, ffn="swiglu", layer_types=("conv", "attention", "conv"))


def _grad_and_counters(cfg):
    """A step's loss and gradients through ``hvd.spmd`` on one device,
    and its record's counters."""
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])  # a fresh record
    params = transformer.init_params(cfg, seed=3)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (1, 1, 32),
                                         0, cfg.vocab_size))
    loss_fn = transformer.make_loss_fn(cfg)
    step = hvd.spmd(lambda p, t: jax.value_and_grad(loss_fn)(p, t))
    loss, grads = step(hvd.replicate(params), toks)
    [program] = timeline.record()["programs"].values()
    hvd.shutdown()
    return loss[0], jax.tree.map(lambda a: a[0], grads), program["counters"]


def test_off_the_chip_the_record_counts_no_kernel_layer():
    _, _, counters = _grad_and_counters(CFG)
    assert counters["model.conv_layers"] == 2
    assert counters["model.conv_kernel_layers"] == 0


def test_where_the_rule_holds_the_model_takes_the_kernels(monkeypatch):
    """With the platform granted here (the kernels then run interpreted;
    ``init_params``' eight positions are no whole strip and stay plain)
    the record counts both conv layers, and the step's loss and taps'
    gradients are the plain form's within bfloat16."""
    plain_loss, plain_grads, _ = _grad_and_counters(CFG)
    monkeypatch.setattr(short_conv, "runs_kernels", lambda t, e, taps, dtype:
                        short_conv._tiles(t, e, taps) is not None)
    loss, grads, counters = _grad_and_counters(CFG)
    assert counters["model.conv_kernel_layers"] == 2
    np.testing.assert_allclose(loss, plain_loss, rtol=1e-2)
    for name in ("block_0", "block_2"):
        got, want = (g[name]["conv"]["taps"] for g in (grads, plain_grads))
        np.testing.assert_allclose(got, want, rtol=0.05,
                                   atol=0.05 * float(jnp.max(jnp.abs(want))))
