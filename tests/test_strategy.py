"""Topology-aware allreduce decomposition tests (ops/topology.py,
ops/strategy.py, utils/costs.py and their wiring).

Covers: topology discovery (slice_index metadata, the
``HOROVOD_TOPOLOGY_SLICES`` simulation override), the α–β cost model and
its schema-versioned tuning cache, bit-exactness of ``rs_ag`` and
``hierarchical`` vs ``flat`` on the CPU-simulated pod — with and without
bf16/int8 compression, on divisible and non-divisible (explicitly padded)
bucket sizes — the refusal paths (subset groups, families, single-slice
hierarchical, eager), HLO-level structure of each lowering on the CPU
backend (reduce-scatter/all-gather per bucket, two-level replica_groups,
flat program-identity), the ``HOROVOD_ALLREDUCE_ALGO`` /
``HOROVOD_AUTOTUNE`` knobs, bucket ``algo`` tagging + ``describe()``, and
the ``prefetch_to_device`` depth satellite. The slow-marked class
re-proves the lowering structure on REAL v5e executables AOT-compiled via
``jax.experimental.topologies`` (the tests/test_overlap.py convention).
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import compression, fusion, strategy, topology
from horovod_tpu.utils import costs, env as _env


def _int_grid(n=8, m=37):
    """Integer-valued fp32 test data: every partial sum is exact in fp32
    (and in bf16 for the magnitudes used), so bit-exactness assertions
    test the DECOMPOSITION, not float associativity."""
    return (np.tile(np.arange(m, dtype=np.float32), (n, 1))
            + np.arange(n, dtype=np.float32)[:, None])


def _lowered_hlo(algo, nbytes=4096, compression_spec=None, grads=False,
                 slices=0, monkeypatch=None):
    """Pre-optimization HLO text of one allreduce (or a 3-bucket
    allreduce_gradients) step on the simulated mesh."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.core import context as _ctx
    from horovod_tpu.core.state import AXIS_NAME

    if slices and monkeypatch is not None:
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", str(slices))
    grp = hvd.get_group(0)

    def shard_fn(x):
        with _ctx.enter(AXIS_NAME, 0):
            if grads:
                g = {f"w{i}": x[0] for i in range(3)}
                r = hvd.allreduce_gradients(
                    g, fusion_threshold=0, algo=algo,
                    compression=compression_spec)
                # Consume every bucket's output or DCE drops it.
                out = sum(r.values())
            else:
                out = hvd.allreduce(x[0], average=False, algo=algo,
                                    compression=compression_spec,
                                    name="payload")
        return out[None]

    jitted = jax.jit(jax.shard_map(
        shard_fn, mesh=grp.mesh, in_specs=P(AXIS_NAME),
        out_specs=P(AXIS_NAME), check_vma=False))
    x = jax.ShapeDtypeStruct((grp.size, nbytes // 4), jnp.float32)
    return jitted.lower(x).as_text(dialect="hlo")


class TestEnvKnobs:
    def test_algo_default_unset_is_flat(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_ALLREDUCE_ALGO", raising=False)
        assert _env.allreduce_algo_default() == "flat"

    @pytest.mark.parametrize("v", ["flat", "rs_ag", "hierarchical", "auto"])
    def test_algo_valid_values(self, monkeypatch, v):
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGO", v)
        assert _env.allreduce_algo_default() == v

    def test_algo_typo_raises(self, monkeypatch):
        # The resilience-knob convention: a typo must not silently run
        # the default lowering the knob exists to change.
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGO", "rsag")
        with pytest.raises(ValueError, match="HOROVOD_ALLREDUCE_ALGO"):
            _env.allreduce_algo_default()

    def test_autotune_values(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_AUTOTUNE", raising=False)
        assert _env.autotune_enabled() is False
        monkeypatch.setenv("HOROVOD_AUTOTUNE", "0")
        assert _env.autotune_enabled() is False
        monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
        assert _env.autotune_enabled() is True
        monkeypatch.setenv("HOROVOD_AUTOTUNE", "yes")
        with pytest.raises(ValueError, match="HOROVOD_AUTOTUNE"):
            _env.autotune_enabled()

    def test_prefetch_depth_values(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_PREFETCH_DEPTH", raising=False)
        assert _env.prefetch_depth() == 1
        monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", "4")
        assert _env.prefetch_depth() == 4
        for bad in ("deep", "0", "-1"):
            monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", bad)
            with pytest.raises(ValueError, match="HOROVOD_PREFETCH_DEPTH"):
                _env.prefetch_depth()

    def test_topology_slices_typo_raises(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "two")
        with pytest.raises(ValueError, match="HOROVOD_TOPOLOGY_SLICES"):
            _env.topology_slices()
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "-2")
        with pytest.raises(ValueError, match="HOROVOD_TOPOLOGY_SLICES"):
            _env.topology_slices()


class TestTopologyDiscovery:
    def test_cpu_world_is_one_slice(self, world, monkeypatch):
        monkeypatch.delenv("HOROVOD_TOPOLOGY_SLICES", raising=False)
        topo = topology.discover(hvd.get_group(0))
        assert topo.group_size == 8
        assert not topo.multi_slice
        assert topo.num_slices == 1 and topo.local_size == 8

    def test_slices_override(self, world, monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        topo = topology.discover(hvd.get_group(0))
        assert topo.multi_slice
        assert topo.num_slices == 2 and topo.local_size == 4
        assert topo.slice_members() == [[0, 1, 2, 3], [4, 5, 6, 7]]

    def test_nondivisible_override_raises(self, world, monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "3")
        with pytest.raises(hvd.HorovodError, match="equal slices"):
            topology.discover(hvd.get_group(0))


def _tpu_ish_topo(local=4, slices=2):
    """A hand-built multi-slice topology with TPU-like constants, so cost
    ordering tests don't depend on the CPU seed values."""
    n = local * slices
    return topology.Topology(
        group_size=n,
        slice_of=tuple(i // local for i in range(n)),
        num_slices=slices, local_size=local, device_kind="TPU v5e",
        ici=topology.Link(alpha_us=1.0, gbps=90.0),
        dcn=topology.Link(alpha_us=25.0, gbps=12.5))


class TestCostModel:
    def test_hierarchical_infeasible_on_one_slice(self):
        topo = _tpu_ish_topo(local=8, slices=1)
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        assert model.predict_us("hierarchical", 1 << 20, topo) == float("inf")
        for nbytes in (1 << 10, 1 << 20, 1 << 26):
            assert model.choose(nbytes, topo) != "hierarchical"

    def test_hierarchical_wins_large_multi_slice(self):
        # The whole point of the decomposition: at pod scale only the
        # 1/local_size shard crosses DCN, so for bandwidth-bound buckets
        # hierarchical beats any single-level scheme by ~local_size.
        topo = _tpu_ish_topo()
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        assert model.choose(64 << 20, topo) == "hierarchical"
        t_h = model.predict_us("hierarchical", 64 << 20, topo)
        t_f = model.predict_us("flat", 64 << 20, topo)
        assert t_h < t_f / 2

    def test_flat_wins_small(self):
        # Tiny buckets are latency-bound: one α beats three.
        topo = _tpu_ish_topo()
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        assert model.choose(256, topo) == "flat"

    def test_rs_ag_wins_large_single_slice(self):
        # The overlap credit makes rs_ag reachable under auto: on one
        # slice (no hierarchical) a bandwidth-bound bucket prices below
        # flat because part of its all-gather hides behind neighboring
        # compute; latency-bound buckets still go flat.
        topo = _tpu_ish_topo(local=8, slices=1)
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        assert model.choose(64 << 20, topo) == "rs_ag"
        assert model.choose(256, topo) == "flat"

    def test_predict_monotone_in_bytes(self):
        topo = _tpu_ish_topo()
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        for algo in ("flat", "rs_ag", "hierarchical"):
            ts = [model.predict_us(algo, s, topo)
                  for s in (1 << 16, 1 << 20, 1 << 24)]
            assert ts == sorted(ts)

    def test_fusion_threshold_clamped(self):
        topo = _tpu_ish_topo()
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        t = model.fusion_threshold_bytes(topo)
        assert (1 << 20) <= t <= (256 << 20)

    def test_unknown_algo_raises(self):
        topo = _tpu_ish_topo()
        model = costs.CostModel(ici=topo.ici, dcn=topo.dcn)
        with pytest.raises(ValueError, match="unknown"):
            model.predict_us("tree", 1024, topo)


class TestTuningCache:
    def test_roundtrip_and_calibrated_model(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", path)
        costs.save_tuning_cache(
            {"ici": {"alpha_us": 2.5, "gbps": 42.0}},
            device_kind="TPU v5e", world=8, fusion_threshold=7 << 20)
        topo = _tpu_ish_topo()
        model = costs.model_for(topo)
        assert model.source == "calibrated"
        assert model.ici.gbps == 42.0 and model.ici.alpha_us == 2.5
        assert model.dcn == topo.dcn  # unmeasured level keeps seeds
        assert costs.tuned_fusion_threshold(topo) == 7 << 20

    def test_stale_schema_ignored_not_misread(self, tmp_path, monkeypatch):
        # The satellite contract: an old-layout cache must fall back to
        # the analytic model, never be field-guessed.
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", path)
        with open(path, "w") as f:
            json.dump({"schema": "horovod_tpu/allreduce-tuning/v0",
                       "device_kind": "TPU v5e",
                       "constants": {"ici": {"alpha_us": 99, "gbps": 1}}},
                      f)
        assert costs.load_tuning_cache() is None
        topo = _tpu_ish_topo()
        model = costs.model_for(topo)
        assert model.source == "analytic"
        assert model.ici == topo.ici

    def test_corrupt_and_missing_ignored(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", path)
        assert costs.load_tuning_cache() is None  # missing
        with open(path, "w") as f:
            f.write("{not json")
        assert costs.load_tuning_cache() is None  # corrupt

    def test_other_device_kind_ignored(self, tmp_path, monkeypatch):
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", path)
        costs.save_tuning_cache(
            {"ici": {"alpha_us": 9.0, "gbps": 9.0}},
            device_kind="TPU v4", world=8)
        model = costs.model_for(_tpu_ish_topo())  # a v5e topology
        assert model.source == "analytic"

    def test_auto_without_cache_uses_analytic_model(self, world,
                                                    monkeypatch):
        # Acceptance contract: auto with NO tuning cache must resolve
        # through the analytic seeds, not fail.
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", "/nonexistent/tune.json")
        monkeypatch.delenv("HOROVOD_TOPOLOGY_SLICES", raising=False)
        algo, topo = strategy.select(
            "auto", nbytes=1 << 20, group=hvd.get_group(0))
        assert algo in strategy.ALGORITHMS
        assert topo is not None


class TestDecompositionExactness:
    """rs_ag / hierarchical / auto are LOWERING decisions: bit-exact
    against flat on the simulated pod (integer-valued data, see
    _int_grid), compression on and off."""

    @pytest.mark.parametrize("m", [64, 37])  # divisible and padded
    def test_rs_ag_bit_exact(self, world, m):
        x = _int_grid(8, m)
        ref = hvd.spmd(lambda v: hvd.allreduce(v, average=False))(x)
        got = hvd.spmd(
            lambda v: hvd.allreduce(v, average=False, algo="rs_ag"))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("m", [64, 37])
    def test_hierarchical_bit_exact(self, world, monkeypatch, m):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        x = _int_grid(8, m)
        ref = hvd.spmd(lambda v: hvd.allreduce(v, average=False))(x)
        got = hvd.spmd(lambda v: hvd.allreduce(
            v, average=False, algo="hierarchical"))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_average_matches(self, world, monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        x = _int_grid(8, 40)
        ref = hvd.spmd(lambda v: hvd.allreduce(v))(x)
        for algo in ("rs_ag", "hierarchical", "auto"):
            got = hvd.spmd(lambda v, a=algo: hvd.allreduce(v, algo=a))(x)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("comp", ["bf16", "int8"])
    @pytest.mark.parametrize("algo", ["rs_ag", "hierarchical"])
    def test_compressed_bit_exact_vs_flat_compressed(self, world,
                                                     monkeypatch, comp,
                                                     algo):
        """Compression composes: compress once, both phases move the wire
        dtype — so a decomposed compressed allreduce is bit-identical to
        the flat compressed one (the int8 wire sum is integer arithmetic;
        the bf16 values here are exactly representable)."""
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        x = _int_grid(8, 37)
        ref = hvd.spmd(lambda v: hvd.allreduce(
            v, average=False, compression=comp))(x)
        got = hvd.spmd(lambda v: hvd.allreduce(
            v, average=False, compression=comp, algo=algo))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_gradient_path_algos_match(self, world, monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        g = {f"w{i}": _int_grid(8, 16 + i) for i in range(4)}
        ref = hvd.spmd(lambda gg: hvd.allreduce_gradients(gg))(g)
        for algo in ("rs_ag", "hierarchical", "auto"):
            got = hvd.spmd(lambda gg, a=algo: hvd.allreduce_gradients(
                gg, algo=a))(g)
            for k in g:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(ref[k]))

    def test_env_default_drives_gradient_path(self, world, monkeypatch):
        monkeypatch.setenv("HOROVOD_ALLREDUCE_ALGO", "rs_ag")
        g = {"w": _int_grid(8, 24)}
        got = hvd.spmd(lambda gg: hvd.allreduce_gradients(gg))(g)
        monkeypatch.delenv("HOROVOD_ALLREDUCE_ALGO")
        ref = hvd.spmd(lambda gg: hvd.allreduce_gradients(gg))(g)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(ref["w"]))

    def test_distributed_optimizer_algo_knob(self, world):
        import optax

        g = {"w": _int_grid(8, 16)}
        ref_opt = hvd.DistributedOptimizer(optax.sgd(0.5))
        got_opt = hvd.DistributedOptimizer(optax.sgd(0.5), algo="rs_ag")

        def step(opt):
            def f(gg):
                state = opt.init(jax.tree.map(lambda t: t, gg))
                upd, _ = opt.update(gg, state)
                return upd
            return hvd.spmd(f)(g)

        np.testing.assert_array_equal(np.asarray(step(got_opt)["w"]),
                                      np.asarray(step(ref_opt)["w"]))


class TestHLOStructure:
    """Lowering structure on the CPU backend's pre-optimization HLO —
    the cheap tier-1 twin of the slow AOT class below."""

    def test_flat_program_identical_to_default(self, world):
        # algo=None and algo="flat" must produce byte-identical HLO: the
        # strategy layer's OFF position is the exact pre-strategy
        # lowering.
        assert _lowered_hlo(None) == _lowered_hlo("flat")
        assert " reduce-scatter(" not in _lowered_hlo("flat")

    def test_rs_ag_ops_per_bucket(self, world):
        txt = _lowered_hlo("rs_ag", grads=True)
        # 3 gradient buckets (threshold 0): one reduce-scatter + one
        # all-gather EACH, and no gradient all-reduce left.
        assert txt.count(" reduce-scatter(") == 3
        assert txt.count(" all-gather(") == 3
        assert txt.count(" all-reduce(") == 0

    def test_rs_ag_compressed_keeps_bucket_count(self, world):
        txt = _lowered_hlo("rs_ag", grads=True, compression_spec="bf16")
        assert txt.count(" reduce-scatter(") == 3
        assert txt.count(" all-gather(") == 3
        assert "bf16" in txt  # wire dtype visible on the collectives

    def test_hierarchical_two_level_replica_groups(self, world,
                                                   monkeypatch):
        txt = _lowered_hlo("hierarchical", slices=2,
                           monkeypatch=monkeypatch)
        intra = "replica_groups={{0,1,2,3},{4,5,6,7}}"
        cross = "replica_groups={{0,4},{1,5},{2,6},{3,7}}"
        rs = [ln for ln in txt.splitlines() if " reduce-scatter(" in ln]
        ar = [ln for ln in txt.splitlines() if " all-reduce(" in ln]
        ag = [ln for ln in txt.splitlines() if " all-gather(" in ln]
        assert len(rs) == 1 and intra in rs[0]
        assert len(ar) == 1 and cross in ar[0]
        assert len(ag) == 1 and intra in ag[0]


def _world_of(n):
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:n])


def _ring_text(fn, shape, dtype=jnp.float32):
    """Lowered (pre-optimization) text of ``hvd.spmd(fn)`` on one
    rank-stacked leaf: shapes only, nothing runs."""
    return hvd.spmd(fn).lower(
        jax.ShapeDtypeStruct((hvd.size(),) + shape, dtype)).as_text()


class TestRingSum:
    """The plain sum of a large leaf over the whole axis: a ring
    reduce-scatter then all-gather of ``lax.ppermute`` (ops/strategy.py
    ``_ring_allreduce``), chosen by what the lowering sees of the leaf.
    The cases cut their slabs around 1 MiB, not the module's 16, so
    that lowering them stays cheap; the last case reads the constant."""

    @pytest.fixture(autouse=True)
    def _slabs_from_1_mib(self, monkeypatch):
        monkeypatch.setattr(strategy, "RING_MIN_SLAB_BYTES", 1 << 20)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("average", [False, True])
    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_equals_psum_and_every_rank_holds_the_same_bits(
            self, monkeypatch, n, average, dtype):
        _world_of(n)
        shape = (64 * n, 3, 32)  # a slab of 32 rows x 3 x 32
        x = np.asarray(jax.random.normal(
            jax.random.key(n), (n,) + shape, jnp.float32).astype(dtype))
        fn = lambda v: hvd.allreduce(v, average=average)
        monkeypatch.setattr(strategy, "RING_MIN_SLAB_BYTES", 1 << 10)
        assert "collective_permute" in _ring_text(fn, shape, x.dtype)
        got = np.asarray(hvd.spmd(fn)(x))
        monkeypatch.setattr(strategy, "RING_MIN_SLAB_BYTES", 1 << 40)
        assert "collective_permute" not in _ring_text(fn, shape, x.dtype)
        ref = np.asarray(hvd.spmd(fn)(x))
        hvd.shutdown()
        assert got.dtype == ref.dtype == x.dtype
        for r in range(1, n):  # replicas never drift: the same bits
            np.testing.assert_array_equal(got[r], got[0])
        # the two differ by the order of one n-term sum in the dtype
        room = (n * float(jnp.finfo(x.dtype).eps)
                * np.abs(x.astype(np.float64)).sum(0)
                / (n if average else 1))
        assert (np.abs(got[0].astype(np.float64)
                       - ref[0].astype(np.float64)) <= room).all()

    # 8 ranks: slabs of 1 MiB, but for "small"
    BIG, SMALL = (4096, 512), (256, 512)

    @pytest.mark.parametrize("case", [
        "small", "indivisible", "ragged_sublanes", "vector", "subgroup",
        "family", "compressed", "rs_ag", "channels", "one_rank"])
    def test_ineligible_inputs_lower_to_todays_text(self, monkeypatch, case):
        """Everything the ring is not for keeps ``lax.psum``'s text to the
        letter: compared with the lowering under a rule that picks no
        leaf."""
        hvd.shutdown()
        if case == "one_rank":
            hvd.init(devices=jax.devices()[:1])
        elif case == "family":
            hvd.init([[0, 1, 2, 3], [4, 5, 6, 7]])
        elif case == "subgroup":
            hvd.init([[0, 1, 2, 3]])
        else:
            hvd.init()
        shape = {"small": self.SMALL, "indivisible": (4100, 512),
                 "ragged_sublanes": (4096 + 16, 512),
                 "vector": (4096 * 512,)}.get(case, self.BIG)
        kwargs = {"subgroup": dict(group=1), "family": dict(group=(1, 2)),
                  "compressed": dict(compression="bf16"),
                  "rs_ag": dict(algo="rs_ag"),
                  "channels": dict(channels=2)}.get(case, {})
        fn = lambda v: hvd.allreduce(v, average=False, name="leaf", **kwargs)
        text = _ring_text(fn, shape)
        monkeypatch.setattr(strategy, "ring_eligible", lambda v, n: False)
        today = _ring_text(fn, shape)
        hvd.shutdown()
        assert text == today and "collective_permute" not in text

    def test_an_eligible_leaf_has_no_all_reduce(self, world):
        fn = lambda v: hvd.allreduce(v, average=False, name="leaf")
        text = _ring_text(fn, self.BIG)
        assert "all_reduce" not in text
        # two halves x (7 rounds of reduce-scatter + 7 of all-gather)
        assert text.count("collective_permute") == 2 * 2 * 7

    def test_a_bucket_keeps_one_psum_for_its_small_leaves(self, world):
        """A plain-sum bucket arrives as the tuple of its leaves: the
        large one goes round the ring, the others stay one ``psum``."""
        def fn(g):
            return hvd.allreduce_gradients(g, fusion_threshold=1 << 30)

        n = hvd.size()
        g = {"a": jax.ShapeDtypeStruct((n, 8), jnp.float32),
             "big": jax.ShapeDtypeStruct((n,) + self.BIG, jnp.float32),
             "c": jax.ShapeDtypeStruct((n, 3, 5), jnp.float32)}
        text = hvd.spmd(fn).lower(g).as_text()
        assert text.count("collective_permute") == 28
        assert text.count("all_reduce") == 2  # a psum binds one a leaf

    def test_the_rings_of_one_exchange_are_chained_last_leaf_first(
            self, world):
        """``allreduce_gradients`` traces the buckets whose leaves go
        round the ring after the others and from the last leaf to the
        first (the order a backward pass makes them in), and each ring
        waits, through an ``optimization_barrier`` on its leaf and the
        last slabs of the ring before it, until that one is done: one
        ring on the links at a time. The small leaf keeps its ``psum``,
        traced first."""
        def fn(g):
            return hvd.allreduce_gradients(g, fusion_threshold=0)

        n = hvd.size()
        g = {"a": jax.ShapeDtypeStruct((n, 4096, 512), jnp.float32),
             "b": jax.ShapeDtypeStruct((n, 8), jnp.float32),
             "c": jax.ShapeDtypeStruct((n, 8192, 256), jnp.float32),
             "d": jax.ShapeDtypeStruct((n, 2048, 1024), jnp.float32)}
        text = hvd.spmd(fn).lower(g).as_text()
        ops = [ln for ln in text.splitlines()
               if "all_reduce" in ln or "collective_permute" in ln
               or "optimization_barrier" in ln]
        kinds = ["psum" if "all_reduce" in ln
                 else "barrier" if "optimization_barrier" in ln
                 else "permute" for ln in ops]
        assert kinds[0] == "psum" and kinds.count("psum") == 1
        # a ring's own two barriers (its sums, its result) and, but for
        # the first ring, the one that ties it to the ring before
        assert kinds.count("barrier") == 3 * 3 - 1
        assert kinds.count("permute") == 3 * 28
        slabs = [ln for ln, kind in zip(ops, kinds) if kind == "permute"]
        # the first permute of each ring sends a slab of d, then c, then a
        assert ["1x1x128x1024" in slabs[0], "1x1x512x256" in slabs[28],
                "1x1x256x512" in slabs[56]] == [True] * 3
        ties = [ln for ln, kind in zip(ops, kinds) if kind == "barrier"]
        # c waits for d's last slabs, a for c's
        assert any("tensor<8192x256xf32>" in ln and "1x1x128x1024" in ln
                   for ln in ties)
        assert any("tensor<4096x512xf32>" in ln and "1x1x512x256" in ln
                   for ln in ties)

    def test_a_chained_exchange_sums_like_psum_on_every_rank(self):
        _world_of(4)
        rng = np.random.default_rng(0)
        g = {k: rng.standard_normal((4,) + shape).astype(np.float32)
             for k, shape in (("a", (2048, 512)), ("b", (8,)),
                              ("c", (8192, 128)))}
        fn = lambda t: hvd.allreduce_gradients(t, fusion_threshold=0)
        shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in g.items()}
        assert hvd.spmd(fn).lower(shapes).as_text().count(
            "collective_permute") == 2 * 12
        got = hvd.spmd(fn)(g)
        hvd.shutdown()
        for k, v in g.items():
            out = np.asarray(got[k])
            for r in range(1, 4):
                np.testing.assert_array_equal(out[r], out[0])
            room = 4 * 1.2e-7 * np.abs(v).sum(0)  # the order of one sum
            assert (np.abs(out[0] - v.mean(0, dtype=np.float64))
                    <= room).all()

    def test_trace_order_is_the_plans_but_for_the_ring_buckets(self, world):
        """``fusion.trace_order``: ring buckets after the others, the one
        with the last leaf first; no ring bucket (small leaves, a packed
        bucket, a group that is not the whole axis, no axis bound at all),
        the plan's order."""
        big, small = jnp.zeros((4096, 512)), jnp.zeros((8,))
        leaves = [big, small, big, small, big]
        plan = fusion.plan_buckets(leaves, 0)
        packed = fusion.plan_buckets(leaves, 0, algo="rs_ag")
        seen = {}

        def fn(x):
            seen["ring"] = fusion.trace_order(plan, leaves, 8)
            seen["subgroup"] = fusion.trace_order(plan, leaves, 4)
            seen["packed"] = fusion.trace_order(packed, leaves, 8)
            seen["unknown"] = fusion.trace_order(plan, leaves, None)
            return x

        hvd.spmd(fn).lower(jax.ShapeDtypeStruct((8, 1), jnp.float32))
        assert [b.indices for b in seen["ring"]] == [
            (1,), (3,), (4,), (2,), (0,)]
        assert seen["subgroup"] == seen["unknown"] == list(plan)
        assert seen["packed"] == list(packed)
        assert fusion.trace_order(plan, leaves, 8) == list(plan)  # no axis

    def test_error_feedback_follows_the_order_of_tracing(self, world,
                                                         monkeypatch):
        """The optimizer pairs each bucket with the local contribution
        its collective recorded, in the order ``fused_apply`` traced them:
        an integer leaf that goes round the ring (uncompressed, traced
        last) ahead of a compressed float leaf leaves the float leaf's
        residual the same as with no ring at all."""
        g = {"a": jnp.arange(4096 * 512, dtype=jnp.int32).reshape(4096, 512),
             "w": jnp.linspace(-1, 1, 64, dtype=jnp.float32)}
        e = {"a": jnp.zeros((4096, 512), jnp.int32),
             "w": jnp.zeros((64,), jnp.float32)}

        def step(g, e):
            return hvd.allreduce_gradients(g, compression="int8",
                                           average=False, error_residual=e)

        args = hvd.replicate(g), hvd.replicate(e)
        assert "collective_permute" in hvd.spmd(step).lower(*args).as_text()
        out, resid = hvd.spmd(step)(*args)
        monkeypatch.setattr(strategy, "RING_MIN_SLAB_BYTES", 1 << 40)
        assert "collective_permute" not in hvd.spmd(step).lower(
            *args).as_text()
        ref, ref_resid = hvd.spmd(step)(*args)
        assert np.abs(np.asarray(resid["w"])).max() > 0
        for got, want in ((out, ref), (resid, ref_resid)):
            for k in g:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))

    def test_the_slab_size_is_16_mib(self, world, monkeypatch):
        """The constant as it stands (PERF.md section 6, PR 30): on 8
        ranks a 128 MiB leaf goes round the ring, one a row of slabs
        shorter does not."""
        monkeypatch.undo()
        assert strategy.RING_MIN_SLAB_BYTES == 16 << 20
        fn = lambda v: hvd.allreduce(v, average=False, name="leaf")
        assert "all_reduce" not in _ring_text(fn, (8192, 4096))
        assert "collective_permute" not in _ring_text(fn, (8192 - 128, 4096))

    @pytest.mark.parametrize("coords,order", [
        ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)], [0, 2, 3, 1]),
        ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0),
          (0, 2, 0), (1, 2, 0), (0, 3, 0), (1, 3, 0)],
         [0, 2, 4, 6, 7, 5, 3, 1]),
        ([(0, 0, 0), (1, 0, 0), (2, 0, 0), (3, 0, 0),
          (0, 1, 0), (1, 1, 0), (2, 1, 0), (3, 1, 0)],
         [0, 1, 2, 3, 7, 6, 5, 4]),
        ([None] * 4, [0, 1, 2, 3])])
    def test_the_ring_follows_the_slices_cycle(self, coords, order):
        """Neighbours on the ring are one ICI hop apart on a 2 x k slice
        (rank order crosses a 2 x 2 slice's diagonal twice); devices that
        do not say where they lie keep rank order."""
        import types

        devices = [types.SimpleNamespace(**({} if c is None
                                            else {"coords": c}))
                   for c in coords]
        assert strategy._ring_order(devices) == order
        if coords[0] is not None:
            hops = [sum(abs(a - b) for a, b in zip(coords[p], coords[q]))
                    for p, q in zip(order, order[1:] + order[:1])]
            assert hops == [1] * len(order)


class TestRefusals:
    def test_subset_group_explicit_phased_raises(self, grouped_world):
        x = _int_grid(8, 8)
        for algo in ("rs_ag", "hierarchical"):
            with pytest.raises(hvd.HorovodError, match="full-axis"):
                hvd.spmd(lambda v, a=algo: hvd.allreduce(
                    v, group=1, algo=a))(x)

    def test_subset_group_auto_degrades_to_flat(self, grouped_world):
        x = _int_grid(8, 8)
        ref = hvd.spmd(lambda v: hvd.allreduce(v, group=1,
                                               average=False))(x)
        got = hvd.spmd(lambda v: hvd.allreduce(v, group=1, average=False,
                                               algo="auto"))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_family_explicit_phased_raises(self, world):
        x = _int_grid(8, 8)
        with pytest.raises(hvd.HorovodError, match="full-axis"):
            hvd.spmd(lambda v: hvd.allreduce(v, group=(0,),
                                             algo="rs_ag"))(x)

    def test_hierarchical_single_slice_raises(self, world, monkeypatch):
        monkeypatch.delenv("HOROVOD_TOPOLOGY_SLICES", raising=False)
        x = _int_grid(8, 8)
        with pytest.raises(hvd.HorovodError, match="multi-slice"):
            hvd.spmd(lambda v: hvd.allreduce(v, algo="hierarchical"))(x)

    def test_eager_algo_raises(self, world):
        with pytest.raises(hvd.HorovodError, match="hvd.spmd"):
            hvd.allreduce(jnp.ones((4,)), algo="rs_ag")

    def test_unknown_algo_raises(self, world):
        with pytest.raises(hvd.HorovodError, match="Unknown allreduce"):
            hvd.spmd(lambda v: hvd.allreduce(v, algo="tree"))(
                _int_grid(8, 8))

    def test_sharded_optimizer_refuses_algo(self, world):
        import optax

        with pytest.raises(hvd.HorovodError, match="sharded"):
            hvd.DistributedOptimizer(optax.sgd(0.1), sharded=True,
                                     algo="rs_ag")


class TestBucketTagging:
    def test_plan_annotates_algo(self):
        leaves = [jnp.zeros((4,), jnp.float32),
                  jnp.zeros((4,), jnp.float32),
                  jnp.zeros((2,), jnp.float32)]
        plain = fusion.plan_buckets(leaves, 32)
        assert [b.indices for b in plain] == [(0, 1), (2,)]
        assert all(b.algo == "flat" for b in plain)
        tagged = fusion.plan_buckets(leaves, 32, algo="rs_ag")
        assert all(b.algo == "rs_ag" for b in tagged)
        # Selector sees the wire-annotated bucket (16B and 4B on the
        # wire under bf16); boundaries unchanged.
        sel = fusion.plan_buckets(
            leaves, 32, compression=compression.Bf16Compressor(),
            algo=lambda b: "rs_ag" if b.bytes_on_wire > 8 else "flat")
        assert [b.indices for b in sel] == [b.indices for b in plain]
        assert [b.algo for b in sel] == ["rs_ag", "flat"]

    def test_describe_single_derivation(self):
        leaves = [jnp.zeros((8,), jnp.float32) for _ in range(2)]
        [b] = fusion.plan_buckets(
            leaves, 1 << 20, compression=compression.Bf16Compressor(),
            algo="hierarchical")
        d = b.describe()
        assert "2 tensors" in d and "16 float32" in d
        assert "64B" in d and "algo=hierarchical" in d
        assert "wire=bfloat16:32B" in d
        assert b.elems == 16

    def test_fused_apply_passes_bucket_algo(self):
        leaves = [jnp.ones((4,), jnp.float32) for _ in range(3)]
        seen = []

        def collective(flat, members=None, algo=None):
            seen.append((members, algo))
            return flat

        fusion.fused_apply(leaves, collective, 0,
                           labels=["a", "b", "c"], algo="rs_ag")
        assert seen == [(("a",), "rs_ag"), (("b",), "rs_ag"),
                        (("c",), "rs_ag")]


class TestAutotuneThreshold:
    def test_autotune_uses_cache_threshold(self, world, tmp_path,
                                           monkeypatch):
        """HOROVOD_AUTOTUNE=1 + a calibrated cache → the cache's
        threshold plans the buckets (observable as one fused collective
        where the 0-threshold default would emit three)."""
        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("HOROVOD_TUNING_CACHE", path)
        topo = topology.discover(hvd.get_group(0))
        costs.save_tuning_cache(
            {"ici": {"alpha_us": 1.0, "gbps": 50.0}},
            device_kind=topo.device_kind, world=8,
            fusion_threshold=1 << 20)
        monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
        monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
        assert costs.tuned_fusion_threshold(topo) == 1 << 20
        g = {f"w{i}": _int_grid(8, 16) for i in range(3)}
        ref = hvd.spmd(lambda gg: hvd.allreduce_gradients(
            gg, fusion_threshold=0))(g)
        got = hvd.spmd(lambda gg: hvd.allreduce_gradients(gg))(g)
        for k in g:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]))

    def test_explicit_env_threshold_wins_over_autotune(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "12345")
        # allreduce_gradients consults the env guard before retuning;
        # the observable contract is exercised via the env module here.
        assert _env.fusion_threshold_bytes() == 12345


class TestPrefetchDepth:
    def test_depth_preserves_order_and_count(self, world):
        from horovod_tpu.training import data as _data

        batches = [[np.full((8, 2), float(i), np.float32)]
                   for i in range(7)]
        out = list(_data.prefetch_to_device(iter(batches), depth=3))
        assert len(out) == 7
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b[0]),
                                          batches[i][0])

    def test_env_default_depth(self, world, monkeypatch):
        from horovod_tpu.training import data as _data

        monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", "2")
        batches = [[np.zeros((8, 1), np.float32)] for _ in range(3)]
        out = list(_data.prefetch_to_device(iter(batches)))
        assert len(out) == 3

    def test_bad_depth_arg_raises_at_call_site(self, world):
        from horovod_tpu.training import data as _data

        # Fail-fast: the raise must NOT wait for first iteration.
        with pytest.raises(ValueError, match="positive integer"):
            _data.prefetch_to_device(iter([]), depth=0)


# ---------------------------------------------------------------------------
# AOT proof on real v5e executables (the tests/test_overlap.py convention):
# slow-marked, skips cleanly where the TPU AOT compiler is unavailable.
# ---------------------------------------------------------------------------


def _topo_devices(name="v5e:2x4"):
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(name, platform="tpu").devices
    except Exception as e:
        pytest.skip(f"TPU AOT topology compiler unavailable: {e}")


def _aot_grad_program(devices, algo, n=8, compile_=True):
    """Lower (and optionally TPU-compile) a 3-bucket gradient step under
    ``algo`` for an AOT v5e slice; returns the HLO text."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.core import context as _ctx
    from horovod_tpu.core.state import AXIS_NAME

    hvd.shutdown()
    hvd.init(devices=devices)
    grp = hvd.get_group(0)

    def shard_fn(g):
        with _ctx.enter(AXIS_NAME, 0):
            gv = jax.tree.map(lambda t: t[0], g)
            out = hvd.allreduce_gradients(gv, fusion_threshold=0,
                                          algo=algo)
        return jax.tree.map(lambda t: t[None], out)

    jitted = jax.jit(jax.shard_map(
        shard_fn, mesh=grp.mesh, in_specs=P(AXIS_NAME),
        out_specs=P(AXIS_NAME), check_vma=False))
    shard = NamedSharding(grp.mesh, P(AXIS_NAME))
    g = {f"w{i}": jax.ShapeDtypeStruct((n, 256, 256), jnp.float32,
                                       sharding=shard) for i in range(3)}
    lowered = jitted.lower(g)
    txt = (lowered.compile().as_text() if compile_
           else lowered.as_text(dialect="hlo"))
    hvd.shutdown()
    return txt


@pytest.mark.slow
class TestStrategyAotV5e:
    def test_flat_program_identical_to_default(self):
        devices = _topo_devices()
        default = _aot_grad_program(devices, None, compile_=False)
        flat = _aot_grad_program(devices, "flat", compile_=False)
        assert default == flat
        assert " reduce-scatter(" not in flat

    def test_rs_ag_compiles_with_rs_and_ag_per_bucket(self):
        devices = _topo_devices()
        txt = _aot_grad_program(devices, "rs_ag", compile_=False)
        assert txt.count(" reduce-scatter(") == 3
        assert txt.count(" all-gather(") == 3
        assert txt.count(" all-reduce(") == 0
        # And it actually lowers on the real TPU backend.
        assert "is_scheduled=true" in _aot_grad_program(devices, "rs_ag")

    def test_hierarchical_two_level_replica_groups_compile(self,
                                                           monkeypatch):
        monkeypatch.setenv("HOROVOD_TOPOLOGY_SLICES", "2")
        devices = _topo_devices()
        txt = _aot_grad_program(devices, "hierarchical", compile_=False)
        assert "replica_groups={{0,1,2,3},{4,5,6,7}}" in txt
        assert "replica_groups={{0,4},{1,5},{2,6},{3,7}}" in txt
        assert "is_scheduled=true" in _aot_grad_program(devices,
                                                        "hierarchical")
