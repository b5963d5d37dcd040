"""DistributedOptimizer / fusion / broadcast-variables / sparse tests.

Covers the reference's training-loop API surface (tensorflow/__init__.py:
86-232): gradient averaging matches large-batch single-process training,
initial-weight broadcast, tensor fusion bucket planning, and the IndexedSlices
sparse path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import fusion


class TestFusionPlanner:
    def _leaves(self, sizes, dtype=np.float32):
        return [jnp.zeros((s,), dtype) for s in sizes]

    def test_buckets_respect_threshold(self):
        # 4-byte elements; threshold 40 bytes = 10 elements.
        leaves = self._leaves([4, 4, 4, 4])
        buckets = fusion.plan_buckets(leaves, 40)
        assert [b.indices for b in buckets] == [(0, 1), (2, 3)]

    def test_zero_threshold_disables_fusion(self):
        leaves = self._leaves([2, 2, 2])
        buckets = fusion.plan_buckets(leaves, 0)
        assert [b.indices for b in buckets] == [(0,), (1,), (2,)]

    def test_dtype_breaks_bucket(self):
        leaves = [jnp.zeros((2,), np.float32), jnp.zeros((2,), np.float64),
                  jnp.zeros((2,), np.float32)]
        buckets = fusion.plan_buckets(leaves, 1 << 20)
        # Contiguous same-dtype runs only (mpi_ops.cc:1629-1634 rule).
        assert [b.indices for b in buckets] == [(0,), (1,), (2,)]

    def test_oversized_leaf_gets_own_bucket(self):
        leaves = self._leaves([1, 100, 1])
        buckets = fusion.plan_buckets(leaves, 40)
        assert [b.indices for b in buckets] == [(0,), (1,), (2,)]

    def test_fused_apply_roundtrip(self, world):
        leaves = [jnp.arange(5.0), jnp.arange(6.0).reshape(2, 3),
                  jnp.ones((4,))]
        # a plain-sum bucket arrives as the tuple of its leaves
        seen = []

        def double(x):
            seen.append([v.shape for v in x])
            return jax.tree.map(lambda v: v * 2, x)

        out = fusion.fused_apply(leaves, double, 1 << 20)
        assert seen == [[(5,), (2, 3), (4,)]]
        for a, b in zip(leaves, out):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a) * 2)

    def test_fused_apply_packs_a_bucket_that_needs_the_buffer(self, world):
        """``rs_ag`` cuts one flat buffer into shards: pack, one call,
        unpack (a single-leaf bucket is flattened alone)."""
        leaves = [jnp.arange(5.0), jnp.arange(6.0).reshape(2, 3),
                  jnp.ones((4,), jnp.int32)]
        seen = []

        def double(flat, algo=None):
            seen.append((flat.shape, algo))
            return flat * 2

        out = fusion.fused_apply(leaves, double, 1 << 20, algo="rs_ag")
        assert seen == [((11,), "rs_ag"), ((4,), "rs_ag")]
        for a, b in zip(leaves, out):
            assert b.shape == a.shape and b.dtype == a.dtype
            np.testing.assert_allclose(np.asarray(b), np.asarray(a) * 2)

    @pytest.mark.parametrize("kw,packed", [
        ({}, False), ({"algo": "rs_ag"}, True),
        ({"algo": "hierarchical"}, True), ({"channels": 2}, True),
        ({"wire_dtype": jnp.bfloat16}, True)])
    def test_bucket_packed_follows_the_wire(self, kw, packed):
        assert fusion.Bucket((0, 1), jnp.float32, 64, **kw).packed is packed


class TestReducedWhereTheyLie:
    """A plain-sum bucket reduced in its leaves' own shapes against the
    same bucket packed by hand (flatten, concatenate, one allreduce of the
    flat buffer, slice back), value for value on one seed."""

    @pytest.mark.parametrize("average", [True, False])
    @pytest.mark.parametrize("group", [0, 1, (2, 3), (1,)],
                             ids=["full", "subset", "family", "slots"])
    def test_matches_the_packed_bucket(self, group, average):
        from horovod_tpu.ops import exchange

        hvd.shutdown()
        hvd.init([[0, 1, 2], [0, 1], [2, 3]], devices=jax.devices()[:4])
        rng = np.random.RandomState(26)
        shapes = {"a": (3, 5), "b": (7,), "c": (4, 2, 2), "d": (6,),
                  "e": (2, 3), "f": (5,)}
        dtypes = {"a": jnp.float32, "b": jnp.float32, "c": jnp.bfloat16,
                  "d": jnp.bfloat16, "e": jnp.int32, "f": jnp.int32}
        tree = {k: hvd.rank_stack([
            jnp.asarray(rng.randn(*shapes[k]) * 100).astype(dtypes[k])
            for _ in range(4)]) for k in shapes}

        def packed(g):
            out = {}
            for keys in ("ab", "cd", "ef"):
                flat = hvd.allreduce(
                    jnp.concatenate([g[k].reshape(-1) for k in keys]),
                    group=group, average=average)
                o = 0
                for k in keys:
                    out[k] = flat[o:o + g[k].size].reshape(g[k].shape)
                    o += g[k].size
            return out

        @hvd.spmd
        def both(g):
            return (hvd.allreduce_gradients(g, group=group, average=average),
                    packed(g))

        lie, flat = both(tree)
        assert [b.indices for b in exchange.last_plan().buckets] == [
            (0, 1), (2, 3), (4, 5)]
        assert not any(b.packed for b in exchange.last_plan().buckets)
        hvd.shutdown()
        for k in shapes:
            assert lie[k].dtype == dtypes[k] and lie[k].shape == flat[k].shape
            np.testing.assert_array_equal(np.asarray(lie[k]),
                                          np.asarray(flat[k]))
            # and it did reduce: rank 0 is a member of every group tried
            assert not np.array_equal(np.asarray(lie[k])[0],
                                      np.asarray(tree[k])[0])

    def test_a_tuple_refuses_a_wire_that_needs_one_buffer(self, world):
        @hvd.spmd
        def step(a, b):
            return hvd.allreduce((a, b), algo="rs_ag")

        x = hvd.replicate(jnp.ones((4,)))
        with pytest.raises(hvd.HorovodError, match="plain sum only"):
            step(x, x)


class TestDistributedOptimizer:
    def test_gradient_averaging_matches_large_batch(self, world):
        """DP training with DistributedOptimizer over 8 ranks must equal
        single-process training on the concatenated batch — the defining
        correctness property of Horovod's data parallelism."""
        rng = np.random.RandomState(0)
        w0 = rng.randn(4, 3).astype(np.float32)
        xs = rng.randn(8, 16, 4).astype(np.float32)  # per-rank batches
        ys = rng.randn(8, 16, 3).astype(np.float32)

        def loss_fn(w, x, y):
            return jnp.mean((x @ w - y) ** 2)

        opt = hvd.DistributedOptimizer(optax.sgd(0.1))

        @hvd.spmd
        def step(w, opt_state, x, y):
            g = jax.grad(loss_fn)(w, x, y)
            updates, opt_state = opt.update(g, opt_state, w)
            return optax.apply_updates(w, updates), opt_state

        w_stacked = hvd.replicate(w0)
        opt_state = jax.tree.map(lambda t: np.broadcast_to(
            np.asarray(t)[None], (8,) + np.asarray(t).shape),
            optax.sgd(0.1).init(w0))
        w_new, _ = step(w_stacked, opt_state, xs, ys)

        # Single-process reference: mean over the full 128-sample batch.
        g_full = jax.grad(loss_fn)(w0, xs.reshape(-1, 4), ys.reshape(-1, 3))
        w_ref = w0 - 0.1 * np.asarray(g_full)
        for r in range(8):
            np.testing.assert_allclose(np.asarray(w_new)[r], w_ref,
                                       rtol=1e-5, atol=1e-6)

    def test_requires_spmd_context(self, world):
        with pytest.raises(hvd.HorovodError, match="hvd.spmd"):
            hvd.allreduce_gradients({"w": jnp.ones((2,))})

    def test_fusion_inside_optimizer(self, world):
        """Many small grads, tiny threshold → same result as unfused."""
        grads = {f"w{i}": jnp.full((3,), float(i)) for i in range(10)}

        @hvd.spmd
        def reduce_fused(g):
            return hvd.allreduce_gradients(g, fusion_threshold=24)

        @hvd.spmd
        def reduce_unfused(g):
            return hvd.allreduce_gradients(g, fusion_threshold=0)

        stacked = hvd.replicate(grads)
        a = reduce_fused(stacked)
        b = reduce_unfused(stacked)
        for k in grads:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]))
            np.testing.assert_allclose(np.asarray(a[k][0]),
                                       np.asarray(grads[k]))


class TestBroadcastVariables:
    def test_eager_stacked_broadcast(self, world):
        rng = np.random.RandomState(3)
        params = {"w": rng.randn(8, 4, 2).astype(np.float32),
                  "b": rng.randn(8, 2).astype(np.float32)}
        synced = hvd.broadcast_variables(params, root_rank=2)
        for k in params:
            for r in range(8):
                np.testing.assert_array_equal(np.asarray(synced[k])[r],
                                              params[k][2])

    def test_inside_spmd(self, world):
        @hvd.spmd
        def f(p):
            return hvd.broadcast_variables(p, root_rank=0)

        p = np.arange(8, dtype=np.float32).reshape(8, 1)
        np.testing.assert_allclose(np.asarray(f(p)), np.zeros((8, 1)))


class TestSparse:
    def test_indexed_slices_allgather_path(self, world):
        # Each rank updates rows [i, i+1] of a 16-row embedding.
        slices = [hvd.IndexedSlices(
            values=jnp.full((2, 3), float(i + 1)),
            indices=jnp.array([i, i + 1]),
            dense_shape=(16, 3)) for i in range(8)]
        outs = [hvd.allreduce_indexed_slices(s, average=False)
                for s in [slices[0]]]
        # Eager single-value submission: every rank sends the same slices,
        # gather = 8 copies.
        assert outs[0].values.shape == (16, 3)

    def test_sparse_in_spmd_matches_dense(self, world):
        """Sparse exchange then densify == dense allreduce of densified."""
        emb_rows, dim = 12, 4

        @hvd.spmd
        def sparse_step(vals, idx):
            s = hvd.IndexedSlices(values=vals, indices=idx,
                                  dense_shape=(emb_rows, dim))
            out = hvd.allreduce_indexed_slices(s, average=False)
            return out.to_dense()

        rng = np.random.RandomState(7)
        vals = rng.randn(8, 2, dim).astype(np.float32)
        idx = np.stack([np.array([i, (i + 3) % emb_rows]) for i in range(8)])
        dense_out = np.asarray(sparse_step(vals, idx))

        expected = np.zeros((emb_rows, dim), np.float32)
        for i in range(8):
            for j in range(2):
                expected[idx[i, j]] += vals[i, j]
        for r in range(8):
            np.testing.assert_allclose(dense_out[r], expected, rtol=1e-5)


class TestSubsetGroupGradients:
    def test_nonmembers_keep_their_gradients(self, grouped_world):
        """DistributedOptimizer on a subset group must not touch non-member
        devices' gradients (averaging-mask regression)."""

        @hvd.spmd
        def reduce_g(g):
            return hvd.allreduce_gradients(g, group=1)  # ranks (0,1,2)

        g = np.arange(8, dtype=np.float32).reshape(8, 1) + 1.0
        out = np.asarray(reduce_g(g))[:, 0]
        # Members 0-2 average (1+2+3)/3 = 2; non-members keep their own.
        np.testing.assert_allclose(out, [2, 2, 2, 4, 5, 6, 7, 8])

    def test_sparse_average_nonmember_unscaled(self, grouped_world):
        @hvd.spmd
        def f(vals, idx):
            s = hvd.IndexedSlices(values=vals, indices=idx, dense_shape=(8, 1))
            out = hvd.allreduce_indexed_slices(s, group=1, average=True)
            return out.values

        vals = np.ones((8, 1, 1), np.float32) * 6.0
        idx = np.zeros((8, 1), np.int64)
        out = np.asarray(f(vals, idx))
        # Members: gathered (3,1) values averaged -> 2.0 each.
        np.testing.assert_allclose(out[0][:, 0], [2.0, 2.0, 2.0])
        # Non-member rank 4: own value 6.0 at slot 0, unscaled.
        np.testing.assert_allclose(out[4][:, 0], [6.0, 0.0, 0.0])


class TestSpmdCompileCache:
    def test_step_fn_traces_once(self, world):
        traces = []

        def step(x):
            traces.append(1)
            return hvd.allreduce(x, average=False)

        f = hvd.spmd(step)
        x = np.ones((8, 2), np.float32)
        f(x); f(x); f(x)
        assert len(traces) <= 2  # one shard_map trace + possibly one jit pass


class TestShardedOptimizer:
    """ZeRO-1: reduce-scatter grads, 1/n state shard per rank, allgather
    updates. Exact-parity standard: sharded must reproduce the unsharded
    DistributedOptimizer step for elementwise inner optimizers."""

    def _params(self, seed=0):
        rng = np.random.RandomState(seed)
        return {
            "w1": rng.randn(5, 3).astype(np.float32),
            "b1": rng.randn(3).astype(np.float32),
            "w2": rng.randn(3, 2).astype(np.float32),
        }

    def _run_steps(self, inner, sharded, n_steps=4, seed=0):
        p0 = self._params(seed)
        rng = np.random.RandomState(seed + 1)
        xs = rng.randn(n_steps, 8, 4, 5).astype(np.float32)
        ys = rng.randn(n_steps, 8, 4, 2).astype(np.float32)

        def loss_fn(p, x, y):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - y) ** 2)

        opt = hvd.DistributedOptimizer(inner, sharded=sharded)

        @hvd.spmd
        def step(p, s, x, y):
            g = jax.grad(loss_fn)(p, x, y)
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s

        params = hvd.replicate(p0)
        state0 = opt.init(p0) if sharded else inner.init(p0)
        state = jax.tree.map(
            lambda t: np.broadcast_to(np.asarray(t)[None],
                                      (8,) + np.asarray(t).shape).copy(),
            state0)
        for i in range(n_steps):
            params, state = step(params, state, xs[i], ys[i])
        return params, state

    @pytest.mark.parametrize("inner", [
        optax.sgd(0.1, momentum=0.9),
        optax.adam(1e-2),
    ], ids=["sgd_momentum", "adam"])
    def test_parity_with_unsharded(self, world, inner):
        p_ref, _ = self._run_steps(inner, sharded=False)
        p_z, _ = self._run_steps(inner, sharded=True)
        for k in p_ref:
            np.testing.assert_allclose(np.asarray(p_z[k]),
                                       np.asarray(p_ref[k]),
                                       rtol=1e-5, atol=1e-6)

    def test_state_is_sharded_to_one_nth(self, world):
        """The memory claim: every optimizer-state leaf is 1/8 of the
        (padded) parameter count per device."""
        p0 = self._params()
        total = sum(int(np.prod(v.shape)) for v in p0.values())
        shard_len = -(-total // 8)
        opt = hvd.DistributedOptimizer(optax.adam(1e-2), sharded=True)
        state = opt.init(p0)
        mom_leaves = [l for l in jax.tree.leaves(state)
                      if np.asarray(l).ndim == 1]
        assert mom_leaves, "expected flat shard moment leaves"
        for leaf in mom_leaves:
            assert np.asarray(leaf).shape == (shard_len,)

    def test_trainer_sharded_smoke(self, world):
        """Trainer(sharded=True) trains and matches the unsharded Trainer."""
        from horovod_tpu.training import Trainer

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        rng = np.random.RandomState(3)
        w0 = {"w": rng.randn(4, 2).astype(np.float32)}
        xs = rng.randn(8, 16, 4).astype(np.float32)
        ys = rng.randn(8, 16, 2).astype(np.float32)

        results = {}
        for mode in (False, True):
            tr = Trainer(loss_fn, optax.adam(1e-2), sharded=mode)
            tr.init_state(w0)
            for _ in range(3):
                tr.train_step((xs, ys))
            results[mode] = np.asarray(tr.params["w"])
        np.testing.assert_allclose(results[True], results[False],
                                   rtol=1e-5, atol=1e-6)

    def test_sparse_raises(self, world):
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), sharded=True)
        grads = {"emb": hvd.IndexedSlices(values=jnp.ones((2, 3)),
                                          indices=jnp.asarray([0, 1]),
                                          dense_shape=(4, 3))}

        @hvd.spmd
        def step(g, s):
            return opt.update(g, s)

        state = jax.tree.map(
            lambda t: np.broadcast_to(np.asarray(t)[None],
                                      (8,) + np.asarray(t).shape),
            opt.init({"emb": jnp.zeros((4, 3))}))
        with pytest.raises(hvd.HorovodError, match="IndexedSlices"):
            step(hvd.replicate(grads), state)

    def test_eager_update_raises(self, world):
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), sharded=True)
        with pytest.raises(hvd.HorovodError, match="hvd.spmd"):
            opt.update({"w": jnp.ones((2,))}, opt.init({"w": jnp.ones((2,))}))

    def test_subset_group_parity_with_unsharded(self, world):
        """ZeRO-1 over a power-of-two subset group (the recursive-halving
        reducescatter path) must reproduce the unsharded subset-group
        DistributedOptimizer for the member ranks."""
        hvd.shutdown()
        hvd.init([[0, 1, 2, 3]])
        try:
            p0 = self._params(seed=5)
            rng = np.random.RandomState(6)
            grads = {k: np.broadcast_to(
                rng.randn(*v.shape).astype(np.float32)[None],
                (8,) + v.shape).copy() for k, v in p0.items()}
            results = {}
            for mode in (False, True):
                opt = hvd.DistributedOptimizer(
                    optax.sgd(0.1, momentum=0.9), sharded=mode, group=1)

                @hvd.spmd
                def step(p, s, g, opt=opt):
                    upd, s = opt.update(g, s, p)
                    return optax.apply_updates(p, upd), s

                inner_state = (opt.init(p0) if mode
                               else optax.sgd(0.1, momentum=0.9).init(p0))
                state = jax.tree.map(
                    lambda t: np.broadcast_to(
                        np.asarray(t)[None],
                        (8,) + np.asarray(t).shape).copy(), inner_state)
                params = hvd.replicate(p0)
                for _ in range(3):
                    params, state = step(params, state, grads)
                results[mode] = params
            for k in p0:
                a = np.asarray(results[True][k])[:4]
                b = np.asarray(results[False][k])[:4]
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        finally:
            hvd.shutdown()

    def test_fusion_threshold_with_sharded_raises(self, world):
        # ZeRO-1 moves one flat reduce-scatter per dtype; a fusion knob
        # would be silently dead — refuse it instead.
        with pytest.raises(hvd.HorovodError, match="fusion_threshold"):
            hvd.DistributedOptimizer(optax.sgd(0.1), sharded=True,
                                     fusion_threshold=64 << 20)

    def test_fp32_grads_for_bf16_params(self, world):
        """Mixed dtypes: buckets follow the PARAM layout init_fn built, so
        fp32 gradients for bf16 params update cleanly (not an opaque optax
        structure error)."""
        p0 = {"w": np.arange(6, dtype=np.float32).reshape(3, 2) / 8.0}
        p0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16), p0)
        opt = hvd.DistributedOptimizer(optax.sgd(0.5), sharded=True)

        @hvd.spmd
        def step(p, s, g):
            upd, s = opt.update(g, s, p)
            return optax.apply_updates(p, upd), s

        grads = hvd.replicate({"w": np.full((3, 2), 0.25, np.float32)})
        state = jax.tree.map(
            lambda t: np.broadcast_to(np.asarray(t)[None],
                                      (8,) + np.asarray(t).shape),
            opt.init(p0))
        p_new, _ = step(hvd.replicate(p0), state, grads)
        want = np.asarray(jax.tree.map(
            lambda t: t.astype(jnp.float32), p0)["w"]) - 0.5 * 0.25
        got = np.asarray(p_new["w"].astype(jnp.float32))
        for r in range(8):
            np.testing.assert_allclose(got[r], want, rtol=1e-2, atol=1e-2)

    def test_subset_group_nonmembers_hold_still(self, grouped_world):
        """Group 1 = ranks {0,1,2}: members step, non-members' params
        stay exactly put (zero updates)."""
        opt = hvd.DistributedOptimizer(optax.sgd(0.5), sharded=True,
                                       group=1)
        w0 = np.arange(6.0, dtype=np.float32).reshape(3, 2)

        @hvd.spmd
        def step(w, s, g):
            upd, s = opt.update(g, s, w)
            return optax.apply_updates(w, upd), s

        grads = hvd.replicate({"w": np.ones((3, 2), np.float32)})
        state = jax.tree.map(
            lambda t: np.broadcast_to(np.asarray(t)[None],
                                      (8,) + np.asarray(t).shape),
            opt.init({"w": w0}))
        w_new, _ = step(hvd.replicate({"w": w0}), state, grads)
        w_new = np.asarray(w_new["w"])
        for r in range(3):           # members: w - 0.5 * 1
            np.testing.assert_allclose(w_new[r], w0 - 0.5, rtol=1e-6)
        for r in range(3, 8):        # non-members: untouched
            np.testing.assert_allclose(w_new[r], w0, rtol=0, atol=0)


class TestFusedAdamW:
    """ops/optim.py — the bench LM's optimizer: AdamW with bf16 moment
    storage. Parity standard: fp32 moments reproduce optax.adamw to float
    tolerance over a multi-step trajectory; bf16 moments (the default)
    track it within the moment-rounding bound."""

    def _trajectory(self, opt, params, grads, steps=6):
        state = opt.init(params)
        for _ in range(steps):
            upd, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, upd)
        return params

    def _setup(self):
        from horovod_tpu.ops import optim

        rng = np.random.RandomState(0)
        params = {"a": jnp.asarray(rng.randn(6, 4), jnp.float32),
                  "b": {"c": jnp.asarray(rng.randn(5), jnp.float32)}}
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.randn(*p.shape), jnp.float32), params)
        return optim, params, grads

    def test_fp32_moments_match_optax_adamw(self):
        optim, params, grads = self._setup()
        ref = self._trajectory(
            optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.1),
            params, grads)
        got = self._trajectory(
            optim.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.1,
                        moment_dtype=jnp.float32), params, grads)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7), ref, got)

    def test_bf16_moments_track_fp32(self):
        optim, params, grads = self._setup()
        ref = self._trajectory(optax.adamw(1e-3, weight_decay=0.1),
                               params, grads)
        got = self._trajectory(optim.adamw(1e-3, weight_decay=0.1),
                               params, grads)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-4), ref, got)

    def test_moments_stored_bf16_and_update_decreases_loss(self):
        optim, params, _ = self._setup()
        opt = optim.adamw(1e-2, weight_decay=0.0)
        state = opt.init(params)
        assert all(l.dtype == jnp.bfloat16
                   for l in jax.tree.leaves((state.mu, state.nu)))

        def loss(p):
            return jnp.sum(p["a"] ** 2) + jnp.sum(p["b"]["c"] ** 2)

        p = params
        l0 = float(loss(p))
        for _ in range(20):
            g = jax.grad(loss)(p)
            upd, state = opt.update(g, state, p)
            p = optax.apply_updates(p, upd)
        assert float(loss(p)) < l0 * 0.8

    def test_composes_with_distributed_optimizer(self, world):
        from horovod_tpu.ops import optim

        opt = hvd.DistributedOptimizer(optim.adamw(1e-2, weight_decay=0.0))
        w0 = {"w": np.ones((4, 2), np.float32)}

        @hvd.spmd
        def step(w, s, g):
            upd, s = opt.update(g, s, w)
            return optax.apply_updates(w, upd), s

        grads = hvd.rank_stack([
            {"w": np.full((4, 2), float(r + 1), np.float32)}
            for r in range(hvd.size())])
        state = hvd.replicate(opt.init(w0))
        w_new, _ = step(hvd.replicate(w0), state, grads)
        rows = np.asarray(w_new["w"])
        # gradient averaging: every replica applies the same update
        np.testing.assert_allclose(
            rows, np.broadcast_to(rows[0:1], rows.shape), rtol=1e-6)
        assert np.all(rows < 1.0)  # positive grads: params stepped down
