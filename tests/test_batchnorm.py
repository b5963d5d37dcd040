"""Fused BatchNorm (ops/batchnorm.py + models/layers.py).

Parity standard: flax ``nn.BatchNorm`` — same variable collections, same
outputs/gradients/running statistics to mixed-precision tolerance. The
pallas kernels' logic runs under the interpreter here (the compiled path
is exercised on the real chip by bench.py / tools/bn_exp.py).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models.layers import FusedBatchNorm
from horovod_tpu.ops import batchnorm as bnops


class TestChannelSumKernels:
    @pytest.mark.parametrize("shape,c", [((37,), 96), ((5, 11), 128),
                                         ((3, 6, 7), 64)])
    def test_channel_sums(self, shape, c):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(*shape, c) * 3 + 2, jnp.bfloat16)
        s1, s2 = bnops.channel_sums(x, interpret=True)
        xf = np.asarray(x, np.float32).reshape(-1, c)
        np.testing.assert_allclose(np.asarray(s1), xf.sum(0),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(s2), (xf * xf).sum(0),
                                   rtol=2e-2, atol=2e-1)

    def test_channel_grad_sums(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(37, 96) * 3 + 2, jnp.bfloat16)
        dy = jnp.asarray(rng.randn(37, 96), jnp.bfloat16)
        xf = np.asarray(x, np.float32)
        mean, rstd = xf.mean(0), 1.0 / np.sqrt(xf.var(0) + 1e-5)
        sdy, sdx = bnops.channel_grad_sums(
            dy, x, jnp.asarray(mean), jnp.asarray(rstd), interpret=True)
        dyf = np.asarray(dy, np.float32)
        np.testing.assert_allclose(np.asarray(sdy), dyf.sum(0),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(sdx), (dyf * ((xf - mean) * rstd)).sum(0),
            rtol=3e-2, atol=3e-1)


class TestFusedBatchNormModule:
    def _mods(self, dtype):
        kw = dict(use_running_average=False, momentum=0.9, epsilon=1e-5,
                  dtype=dtype, param_dtype=jnp.float32)
        return nn.BatchNorm(**kw), FusedBatchNorm(**kw)

    def test_variable_structure_matches_flax(self):
        ref, fus = self._mods(jnp.float32)
        x = jnp.ones((2, 4, 4, 8))
        vr = ref.init(jax.random.PRNGKey(0), x)
        vf = fus.init(jax.random.PRNGKey(0), x)
        assert jax.tree.structure(vr) == jax.tree.structure(vf)

    def test_fp32_parity_with_flax(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 6, 6, 16) * 2 + 1.5, jnp.float32)
        ref, fus = self._mods(jnp.float32)
        params = {"scale": jnp.asarray(rng.rand(16) + 0.5, jnp.float32),
                  "bias": jnp.asarray(rng.randn(16), jnp.float32)}
        bs = ref.init(jax.random.PRNGKey(0), x)["batch_stats"]

        def run(mod):
            def f(p, xx):
                y, mut = mod.apply({"params": p, "batch_stats": bs}, xx,
                                   mutable=["batch_stats"])
                return jnp.sum(y ** 2), (y, mut)
            (_, (y, mut)), grads = jax.value_and_grad(
                f, argnums=(0, 1), has_aux=True)(params, x)
            return y, mut["batch_stats"], grads

        yr, bsr, gr = run(ref)
        yf, bsf, gf = run(fus)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        for k in ("mean", "var"):
            np.testing.assert_allclose(np.asarray(bsf[k]),
                                       np.asarray(bsr[k]),
                                       rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_bf16_dx_matches_fp32_truth(self):
        """bf16 dx must sit within bf16 noise of the fp32 reference —
        the fused backward formula is checked against autodiff truth."""
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 6, 6, 16) * 2 + 1.5, jnp.float32)
        w = jnp.asarray(rng.randn(4, 6, 6, 16), jnp.float32)
        params = {"scale": jnp.asarray(rng.rand(16) + 0.5, jnp.float32),
                  "bias": jnp.asarray(rng.randn(16), jnp.float32)}

        def make(mod):
            bs = mod.init(jax.random.PRNGKey(0), x)["batch_stats"]

            def f(p, xx):
                y, _ = mod.apply({"params": p, "batch_stats": bs}, xx,
                                 mutable=["batch_stats"])
                return jnp.sum(y.astype(jnp.float32) * w)
            return f

        ref32, _ = self._mods(jnp.float32)
        truth = np.asarray(jax.grad(make(ref32), argnums=1)(params, x))
        _, fus16 = self._mods(jnp.bfloat16)
        got = np.asarray(jax.grad(make(fus16), argnums=1)(params, x),
                         np.float32)
        assert np.abs(got - truth).max() < 0.05 * np.abs(truth).max()

    def test_eval_mode_matches_flax(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(2, 5, 5, 8), jnp.float32)
        kw = dict(use_running_average=True, epsilon=1e-5,
                  dtype=jnp.float32, param_dtype=jnp.float32)
        ref, fus = nn.BatchNorm(**kw), FusedBatchNorm(**kw)
        v = {"params": {"scale": jnp.asarray(rng.rand(8) + 0.5,
                                             jnp.float32),
                        "bias": jnp.asarray(rng.randn(8), jnp.float32)},
             "batch_stats": {"mean": jnp.asarray(rng.randn(8), jnp.float32),
                             "var": jnp.asarray(rng.rand(8) + 0.3,
                                                jnp.float32)}}
        np.testing.assert_allclose(np.asarray(fus.apply(v, x)),
                                   np.asarray(ref.apply(v, x)),
                                   rtol=1e-5, atol=1e-5)

    def test_synced_bn_matches_global_batch(self, world):
        """axis_name statistics: per-device batches with cross-replica
        psum must equal one global-batch BN."""
        rng = np.random.RandomState(4)
        xs = rng.randn(8, 4, 3, 3, 8).astype(np.float32) * 2 + 1
        mod = FusedBatchNorm(use_running_average=False, axis_name="hvd",
                             dtype=jnp.float32)
        local = FusedBatchNorm(use_running_average=False, dtype=jnp.float32)
        v = local.init(jax.random.PRNGKey(0), jnp.asarray(xs[0]))

        @hvd.spmd
        def f(x):
            y, _ = mod.apply(v, x, mutable=["batch_stats"])
            return y

        got = np.asarray(f(jnp.asarray(xs)))
        want, _ = local.apply(
            v, jnp.asarray(xs.reshape(32, 3, 3, 8)),
            mutable=["batch_stats"])
        np.testing.assert_allclose(got.reshape(32, 3, 3, 8),
                                   np.asarray(want), rtol=1e-4, atol=1e-4)


class TestResNetNormImpl:
    def test_fused_and_flax_agree(self, world):
        """The model-level switch: one ResNet18 step under each impl from
        identical init produces matching loss and near-matching grads."""
        from horovod_tpu.models import resnet

        results = {}
        for impl in ("fused", "flax"):
            model = resnet.ResNet18(num_classes=10, dtype=jnp.float32,
                                    norm_impl=impl)
            variables = resnet.init_variables(model, image_size=32, seed=0)
            loss_fn = resnet.make_loss_fn(model)
            imgs, labels = resnet.synthetic_imagenet(4, 32, num_classes=10)
            # jitted: op-by-op dispatch of a ResNet18 fwd+bwd is a minute
            # of one-primitive compiles on the CPU.
            (loss, _), grads = jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True))(variables, (imgs, labels))
            # Key by path with the module-class name normalized, so the
            # two trees align (FusedBatchNorm_i vs BatchNorm_i).
            flat = {
                jax.tree_util.keystr(path).replace("FusedBatchNorm",
                                                   "BatchNorm"): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(grads)
            }
            results[impl] = (float(loss), flat)
        assert abs(results["fused"][0] - results["flax"][0]) < 1e-3
        assert results["fused"][1].keys() == results["flax"][1].keys()
        for k, a in results["fused"][1].items():
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(results["flax"][1][k]),
                rtol=5e-2, atol=5e-2, err_msg=k)
