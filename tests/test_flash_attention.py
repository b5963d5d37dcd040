"""The flash kernel's named residuals (``ops/flash_attention.OUT_RESIDUAL``,
``LSE_RESIDUAL``): under a ``jax.checkpoint`` whose policy saves the two,
the backward reads the kernel's output and log-sum-exp back and the
forward kernel is in the gradient once; under a bare ``jax.checkpoint``,
or a policy of other names, it is there twice, as before. The gradients
are the same bits either way. (The kernels' mathematics against the
references: ``tests/test_sequence.py``.)"""

import collections

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
