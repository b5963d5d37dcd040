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
