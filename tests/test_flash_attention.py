"""The flash kernel's named residuals (``ops/flash_attention.OUT_RESIDUAL``,
``LSE_RESIDUAL``): under a ``jax.checkpoint`` whose policy saves the two,
the backward reads the kernel's output and log-sum-exp back and the
forward kernel is in the gradient once; under a bare ``jax.checkpoint``,
or a policy of other names, it is there twice, as before. The gradients
are the same bits either way. Then the kernels that follow the mask (PR
35): forward and all three gradients against ``blockwise_attention`` over
the masks, widths and offsets the callers bring; the classification and
the index maps against a dense mask; ``score_counts`` against a brute-force
count; and two guards of set-up a CPU can keep: the kernels' size in
equations, and a lowering that is the same text in every process. (The
kernels inside the sequence-parallel strategies: ``tests/test_sequence.py``.)"""

import collections
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa

B, T, H, D = 1, 64, 4, 16
BLOCKS = dict(block_q=32, block_k=32, block_q_bwd=32, block_k_bwd=32,
              block_kv_mem=64, interpret=True)
SEGMENTS = np.repeat(np.arange(2, dtype=np.int32), T // 2)[None]
CASES = {
    "causal": {},
    "window": {"window": 24},
    "gqa": {},  # two K/V heads for four
    "segments": {"q_segment_ids": SEGMENTS, "kv_segment_ids": SEGMENTS},
}
KEPT = jax.checkpoint_policies.save_only_these_names(fa.OUT_RESIDUAL,
                                                     fa.LSE_RESIDUAL)


def equations(jaxpr):
    """The equations of ``jaxpr`` and of its sub-jaxprs (a scan's body, a
    checkpoint's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def kernel_calls(jaxpr) -> collections.Counter:
    """The ``pallas_call`` equations of ``jaxpr``, counted by the kernel's
    name."""
    return collections.Counter(
        eqn.params["name"] for eqn in equations(jaxpr)
        if eqn.primitive.name == "pallas_call")


def _inputs(case):
    hkv = 2 if case == "gqa" else H
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, T, hkv, D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, T, hkv, D), jnp.bfloat16)
    w = jax.random.normal(keys[3], (B, T, H, D), jnp.float32)
    return q, k, v, w


def _loss(case, with_lse):
    """A scalar of the attention's output (and of its log-sum-exp, where
    the function gives one), so that every cotangent is busy."""
    kwargs = dict(CASES[case], **BLOCKS)

    def loss(q, k, v, w):
        if not with_lse:
            out = fa.flash_attention(q, k, v, **kwargs)
            return jnp.sum(out.astype(jnp.float32) * w)
        out, lse = fa.flash_attention_lse(q, k, v, **kwargs)
        return jnp.sum(out.astype(jnp.float32) * w) \
            + jnp.sum(lse * w[..., 0])

    return loss


@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_policy_of_the_two_names_keeps_what_the_kernel_wrote(case,
                                                               with_lse):
    args = _inputs(case)
    loss = _loss(case, with_lse)
    grads, calls = {}, {}
    for name, policy in [("bare", None), ("kept", KEPT)]:
        g = jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2))
        calls[name] = kernel_calls(jax.make_jaxpr(g)(*args).jaxpr)
        grads[name] = jax.jit(g)(*args)
    assert calls["bare"] == {"hvd_flash_fwd": 2, "hvd_flash_bwd": 1}
    assert calls["kept"] == {"hvd_flash_fwd": 1, "hvd_flash_bwd": 1}
    for a, b in zip(grads["bare"], grads["kept"]):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)))) > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_without_a_checkpoint_the_names_change_nothing():
    """No ``jax.checkpoint``: one forward kernel, one backward, and what
    the backward reads is the kernel's own bfloat16 output and float32
    log-sum-exp, neither cast."""
    args = _inputs("causal")
    jaxpr = jax.make_jaxpr(jax.grad(_loss("causal", False),
                                    argnums=(0, 1, 2)))(*args).jaxpr
    assert kernel_calls(jaxpr) == {"hvd_flash_fwd": 1, "hvd_flash_bwd": 1}
    named = {eqn.params["name"]: eqn.outvars[0].aval for eqn in jaxpr.eqns
             if eqn.primitive.name == "name"}
    assert named[fa.OUT_RESIDUAL].dtype == jnp.bfloat16
    assert named[fa.OUT_RESIDUAL].shape == (B, T, H, D)
    assert named[fa.LSE_RESIDUAL].dtype == jnp.float32
    assert named[fa.LSE_RESIDUAL].shape[:2] == (B, H)


@pytest.mark.parametrize("policy", [
    jax.checkpoint_policies.save_only_these_names("somebody_elses"),
    jax.checkpoint_policies.save_only_these_names(fa.OUT_RESIDUAL),
], ids=["other_names", "out_alone"])
def test_a_policy_without_both_names_runs_the_kernel_again(policy):
    """A user's own policy reads its own names; and the output alone does
    not do: the kernel runs again for the log-sum-exp."""
    args = _inputs("causal")
    g = jax.grad(jax.checkpoint(_loss("causal", False), policy=policy),
                 argnums=(0, 1, 2))
    assert kernel_calls(jax.make_jaxpr(g)(*args).jaxpr) == {
        "hvd_flash_fwd": 2, "hvd_flash_bwd": 1}


# ---------------------------------------------------------------------------
# the kernels that follow the mask, against blockwise_attention
# ---------------------------------------------------------------------------

# Blocks of 1024 (sub-tiles of 512), the defaults at the cells' T = 8192.
BIG = dict(block_q=1024, block_k=1024, block_q_bwd=512, block_k_bwd=1024,
           block_kv_mem=2048)
# The same geometry at a quarter of the size: blocks of 256, sub-tiles of 128.
SMALL = dict(block_q=256, block_k=256, block_q_bwd=128, block_k_bwd=256,
             block_kv_mem=512)
# MLA's blocks (D > 128): 256 q lanes x 1024 keys, at a quarter.
SMALL_MLA = dict(block_q=256, block_k=256, block_q_bwd=64, block_k_bwd=256,
                 block_kv_mem=1024)
_TWO_SEGMENTS = lambda t: np.repeat(np.arange(2, dtype=np.int32),
                                    [t // 3, t - t // 3])[None]
MATH = {
    # name: (T, H, Hkv, D, blocks, keyword arguments)
    "causal": (1024, 2, 2, 64, SMALL, {}),
    "causal_block_1024": (2048, 1, 1, 64, BIG, {}),
    "window_1": (2048, 1, 1, 64, BIG, {"window": 1}),
    "window_300_off_a_sub_tile": (2048, 1, 1, 64, BIG, {"window": 300}),
    "window_512_on_a_sub_tile": (2048, 1, 1, 64, BIG, {"window": 512}),
    "window_4096_on_a_block": (6144, 1, 1, 64, BIG, {"window": 4096}),
    "window_75_small": (1024, 2, 1, 64, SMALL, {"window": 75}),
    "gqa_24_over_2": (512, 24, 2, 128, SMALL, {"window": 256}),
    "gqa_32_over_8": (512, 32, 8, 64, SMALL, {}),
    "d128": (768, 2, 2, 128, SMALL, {}),
    "d256_mla_blocks": (1024, 2, 2, 256, SMALL_MLA, {}),
    "segments": (1024, 2, 2, 64, SMALL,
                 {"q_segment_ids": _TWO_SEGMENTS(1024),
                  "kv_segment_ids": _TWO_SEGMENTS(1024)}),
    "segments_window": (768, 2, 1, 64, SMALL,
                        {"window": 200,
                         "q_segment_ids": _TWO_SEGMENTS(768),
                         "kv_segment_ids": _TWO_SEGMENTS(768)}),
    "t_no_multiple_of_the_block": (700, 2, 2, 64, SMALL, {}),
    "t_no_multiple_window": (1000, 2, 1, 64, SMALL, {"window": 333}),
    "non_causal": (512, 2, 2, 64, SMALL, {"causal": False}),
    "default_blocks_short": (320, 2, 2, 64, {}, {}),
}


def _qkvw(t, h, hkv, d, tk=None, dv=None):
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (1, t, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, tk or t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, tk or t, hkv, dv or d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (1, t, h, dv or d), jnp.float32)
    return q, k, v, w


def _close(got, want, what):
    """bfloat16 operands, float32 accumulation on both sides: the two
    differ by the rounding of p to bfloat16 at other block boundaries."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-3)
    assert float(np.max(np.abs(got - want))) <= 0.02 * scale, what


def _against_blockwise(attn, reference, args):
    for fn, sink in ((attn, got := []), (reference, want := [])):
        loss = lambda q, k, v, w, fn=fn: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w)
        sink.append(jax.jit(fn)(*args[:3]))
        sink.extend(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        _close(a, b, name)
    assert float(jnp.max(jnp.abs(got[0].astype(jnp.float32)))) > 0


@pytest.mark.parametrize("case", list(MATH))
def test_forward_and_gradients_match_blockwise(case):
    t, h, hkv, d, blocks, kwargs = MATH[case]
    _against_blockwise(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True,
                                           **blocks, **kwargs),
        lambda q, k, v: fa.blockwise_attention(q, k, v, **kwargs),
        _qkvw(t, h, hkv, d))


def test_mlas_unequal_widths_padded_as_the_model_pads_them():
    """Latent attention's query and key are 192 wide here and its value
    256: the narrower side is padded with zeros to the kernels' one
    width, which adds nothing to a score or an output."""
    t, h, qk, dv = 768, 2, 192, 256
    q, k, _, _ = _qkvw(t, h, h, qk)
    _, _, v, w = _qkvw(t, h, h, dv)
    pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, dv - a.shape[-1]),))
    _against_blockwise(
        lambda q, k, v: fa.flash_attention(
            pad(q), pad(k), v, sm_scale=qk ** -0.5, interpret=True,
            **SMALL_MLA),
        lambda q, k, v: fa.blockwise_attention(
            pad(q), pad(k), v, sm_scale=qk ** -0.5),
        (q, k, v, w))


# A ring step's call: the offsets traced, the K/V shard another's.
RING = {"kv_wholly_in_the_future": (0, 512), "kv_in_the_past": (1024, 256),
        "kv_the_same_shard": (512, 512), "kv_straddles": (300, 0)}


@pytest.mark.parametrize("case", list(RING))
def test_traced_offsets_match_blockwise(case):
    """Traced ``q_offset`` / ``kv_offset`` (the index maps read the
    prefetched scalars): a shard wholly in every row's future gives zeros
    and a very negative log-sum-exp, and zero gradients; a cotangent on
    the log-sum-exp is carried."""
    q, k, v, w = _qkvw(512, 2, 2, 64)
    offsets = tuple(jnp.int32(o) for o in RING[case])

    def loss(fn, q, k, v, qo, ko):
        out, lse = fn(q, k, v, qo, ko)
        return jnp.sum(out.astype(jnp.float32) * w) \
            + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) * w[..., 0])

    def kernel(q, k, v, qo, ko):
        return fa.flash_attention_lse(q, k, v, q_offset=qo, kv_offset=ko,
                                      interpret=True, **SMALL)

    def reference(q, k, v, qo, ko):
        s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * q.shape[-1] ** -0.5
        seen = (ko + jnp.arange(k.shape[1]))[None, :] \
            <= (qo + jnp.arange(q.shape[1]))[:, None]
        s = jnp.where(seen[None, :, None, :], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(seen[None, :, None, :], jnp.exp(s - lse[..., None]), 0)
        return jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32)), lse

    got = jax.jit(jax.value_and_grad(
        lambda *a: loss(kernel, *a), argnums=(0, 1, 2)))(q, k, v, *offsets)
    want = jax.jit(jax.value_and_grad(
        lambda *a: loss(reference, *a), argnums=(0, 1, 2)))(
            q, k, v, *offsets)
    _close(got[0], want[0], "loss")
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        _close(a, b, name)
    if case == "kv_wholly_in_the_future":
        out, lse = jax.jit(kernel)(q, k, v, *offsets)
        assert float(jnp.max(jnp.abs(out.astype(jnp.float32)))) == 0
        assert float(jnp.max(lse)) < -1e29
        assert all(float(jnp.max(jnp.abs(g.astype(jnp.float32)))) == 0
                   for g in got[1])


# ---------------------------------------------------------------------------
# the classification, the index maps and the counter against a dense mask
# ---------------------------------------------------------------------------

GEOMETRIES = {
    # name: (tq, tk, q_offset, kv_offset, causal, window, block_q, block_k)
    "causal": (2048, 2048, 0, 0, True, None, 512, 512),
    "window_off_everything": (2048, 2048, 0, 0, True, 300, 512, 256),
    "window_a_block": (4096, 4096, 0, 0, True, 1024, 512, 512),
    "window_1": (1024, 1024, 0, 0, True, 1, 256, 256),
    "padded_keys": (1000, 900, 0, 0, True, None, 256, 256),
    "shard_in_the_future": (512, 512, 0, 512, True, None, 256, 256),
    "shard_in_the_past": (512, 512, 1024, 0, True, 700, 256, 128),
    "non_causal": (512, 700, 0, 0, False, None, 256, 256),
}


def _dense(tq, tk, q_offset, kv_offset, causal, window):
    """The mask itself, (tq, tk) booleans."""
    qpos = q_offset + np.arange(tq)[:, None]
    kpos = kv_offset + np.arange(tk)[None, :]
    seen = np.ones((tq, tk), bool)
    if causal:
        seen &= kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    return seen


@pytest.mark.parametrize("case", list(GEOMETRIES))
def test_classification_and_index_maps_agree_with_the_mask(case):
    """``skip`` is "nothing of the rectangle is visible", ``interior``
    "all of it is, and none of it padding"; and the index a skipped pair's
    K/V (forward) or q-side (backward) spec names is the index of a
    visible pair, the nearest one."""
    tq, tk, qo, ko, causal, window, bq, bk = GEOMETRIES[case]
    nq, nk = -(-tq // bq), -(-tk // bk)
    seen = np.zeros((nq * bq, nk * bk), bool)
    seen[:tq, :tk] = _dense(tq, tk, qo, ko, causal, window)
    real = np.zeros_like(seen)
    real[:, :tk] = True
    for iq in range(nq):
        rows = slice(iq * bq, (iq + 1) * bq)
        visible = []
        for ik in range(nk):
            cols = slice(ik * bk, (ik + 1) * bk)
            # Padded ROWS are computed like real ones and sliced away:
            # the classification knows padded keys only.
            rect = _dense(bq, bk, qo + iq * bq, ko + ik * bk, causal,
                          window) & real[rows, cols]
            skip, interior = fa._block_visibility(
                qo + iq * bq, bq, ko + ik * bk, bk, ko + tk, causal, window)
            assert bool(skip) == (not rect.any()), (iq, ik)
            assert bool(interior) == bool(rect.all()), (iq, ik)
            if not skip:
                visible.append(ik)
        for ik in range(nk):
            named = int(fa._block_range(
                ik, None if window is None else qo + iq * bq - (window - 1),
                qo + (iq + 1) * bq - 1 if causal else None, ko, bk, nk))
            if ik in visible:
                assert named == ik
            elif visible:  # the nearest visible one: no fetch of its own
                assert named == min(visible, key=lambda j: abs(j - ik))
            else:
                assert 0 <= named < nk
    for ik in range(nk):  # the backward's q-side blocks of a memory block
        active = [iq for iq in range(nq) if not fa._block_visibility(
            qo + iq * bq, bq, ko + ik * bk, bk, ko + nk * bk, causal,
            window)[0]]
        for iq in range(nq):
            named = int(fa._block_range(
                iq, ko + ik * bk if causal else None,
                None if window is None
                else ko + (ik + 1) * bk - 1 + (window - 1), qo, bq, nq))
            if iq in active:
                assert named == iq
            elif active:
                assert named == min(active, key=lambda j: abs(j - iq))


COUNTS = {
    # name: (T, D, window): the cells' calls, and the same at a quarter
    "full_causal_t8k": (8192, 128, None),
    "mla_t8k": (8192, 256, None),
    "gqa64_t8k": (8192, 64, None),
    "window_4096_t8k": (8192, 128, 4096),
    "window_300_t4k": (4096, 128, 300),
    "full_causal_t16k_blocks_of_2048": (16384, 128, None),
    "no_multiple_of_a_block": (5000, 128, 1500),
}


def _brute_force_counts(t, d, window):
    """The mask counted row by row, and the sub-tiles an independent walk
    over the dense mask would visit: a pair wholly visible is computed
    whole, a pair partly visible by its sub-tiles that hold anything."""
    bq, bk, bq_b, bk_b, mem = fa._default_blocks(d, t, *(None,) * 5)
    half = lambda b: b // 2 if b % 256 == 0 else b
    qpos, kpos = np.arange(t)[:, None], np.arange(t)[None, :]
    row_counts = np.zeros(t, np.int64)
    computed = 0
    lanes = min(bq_b, t)  # the backward's q block: its sub-tiles of keys
    keys = half(min(bk_b, t))  # are no taller than it is wide
    if lanes % 128 == 0 and keys % lanes == 0:
        keys = lanes
    for (block_q, sub_q), (block_k, sub_k) in (
            ((min(bq, t), half(min(bq, t))), (min(bk, t), half(min(bk, t)))),
            ((lanes, lanes), (min(bk_b, t), keys))):
        for q0 in range(0, t, block_q):
            for k0 in range(0, t, block_k):
                rows = qpos[q0:q0 + block_q]
                cols = kpos[:, k0:k0 + block_k]
                rect = (cols <= rows) & (cols > rows - (window or 2 * t))
                full = rect.shape == (block_q, block_k)
                if block_q == min(bq, t):
                    row_counts[q0:q0 + block_q] += rect.sum(axis=1)
                if rect.all() and full:
                    computed += block_q * block_k
                elif rect.any():
                    for r in range(0, block_q, sub_q):
                        for c in range(0, block_k, sub_k):
                            if rect[r:r + sub_q, c:c + sub_k].any():
                                computed += sub_q * sub_k
    return 2 * int(row_counts.sum()), computed


@pytest.mark.parametrize("case", list(COUNTS))
def test_score_counts_equal_a_brute_force_count(case):
    t, d, window = COUNTS[case]
    visible, computed = fa.score_counts(t, t, d, window=window)
    assert (visible, computed) == _brute_force_counts(t, d, window)
    if window is None and t == 8192:
        # An edge pair by halves: one sub-tile of four (of two) left out,
        # 34 blocks of 1024 x 1024 for 32.0 visible (36 computed whole);
        # the backward at D = 256 by quarters, 33 for 32.0.
        assert 100 * computed / visible == pytest.approx(
            104.67 if d > 128 else 106.24, abs=0.01)


# ---------------------------------------------------------------------------
# two guards of set-up
# ---------------------------------------------------------------------------

# The kernels' equations at the cells' geometries (B = 1, T = 8192), an
# unrolled loop's body counted once a copy: what Pallas lowers to Mosaic a
# call site, and Mosaic compiles. The parent of PR 35 counted 124 + 457
# (133 + 499 windowed: its backward unrolled four compute blocks of two
# bodies each); PR 35's kernels 214 + 179 (238 + 200 windowed).
SIZES = {
    # name: (H, Hkv, D, window, forward bound, backward bound)
    "looped_16_heads_of_128": (16, 16, 128, None, 240, 205),
    "windowed_24_over_2_of_128": (24, 2, 128, 4096, 265, 225),
    "mla_20_heads_of_256": (20, 20, 256, None, 240, 205),
    "gqa_32_over_8_of_64": (32, 8, 64, None, 240, 205),
}


def kernel_size(jaxpr) -> int:
    """Equations of ``jaxpr`` and its sub-jaxprs, a scan's body once a
    copy its ``unroll`` makes."""
    total = 0
    for eqn in jaxpr.eqns:
        copies = 1
        if eqn.primitive.name == "scan":
            unroll, length = eqn.params["unroll"], eqn.params["length"]
            copies = length if unroll is True else min(int(unroll), length)
        total += 1 + copies * sum(
            kernel_size(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
    return total


@pytest.mark.parametrize("case", list(SIZES))
def test_the_kernels_stay_small(case):
    h, hkv, d, window, fwd_bound, bwd_bound = SIZES[case]
    t = 8192
    shapes = [jax.ShapeDtypeStruct((1, t, n, d), jnp.bfloat16)
              for n in (h, hkv, hkv)]
    grad = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, window=window, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2))
    sizes = {eqn.params["name"]: kernel_size(eqn.params["jaxpr"])
             for eqn in equations(jax.make_jaxpr(grad)(*shapes).jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert sizes["hvd_flash_fwd"] <= fwd_bound, (
        f"forward kernel: {sizes['hvd_flash_fwd']} equations "
        f"(PR 35: 214, 238 windowed; its parent: 124, 133)")
    assert sizes["hvd_flash_bwd"] <= bwd_bound, (
        f"backward kernel: {sizes['hvd_flash_bwd']} equations "
        f"(PR 35: 179, 200 windowed; its parent: 457, 499)")


_LOWER_THE_LOOPED_STEP = """
    import hashlib, sys
    sys.path[:0] = [{tests!r}, {root!r}]
    import jax, jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    import test_looped_lm as looped
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel import sequence
    sequence.local_attention_impl = lambda t: "flash"
    hvd.init(devices=jax.devices()[:1])
    loss = transformer.make_loss_fn(looped.CFG, fused_head=True,
                                    exit_beta=looped.BETA)
    step = hvd.spmd(lambda p, toks: hvd.allreduce(
        jax.tree.reduce(lambda a, b: a + jnp.sum(b),
                        jax.grad(loss)(p, toks), 0.0)))
    params = hvd.replicate(looped._tree(looped._weights()))
    toks = hvd.rank_stack([looped._tokens(4, rows=2)])
    print(hashlib.sha256(
        step.lower(params, toks).as_text().encode()).hexdigest())
"""


def test_the_looped_step_lowers_to_the_same_text_in_every_process():
    """The compile cache's key is made of the lowered text: a kernel whose
    lowering held anything of the process (an address, a dictionary's
    order, a table made on the device) would miss in every new one."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = textwrap.dedent(_LOWER_THE_LOOPED_STEP).format(
        tests=here, root=os.path.dirname(here))
    digests = [subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, env=dict(os.environ, PYTHONHASHSEED=str(seed)),
    ).stdout.split()[-1] for seed in (1, 2)]
    assert len(digests[0]) == 64 and digests[0] == digests[1]
