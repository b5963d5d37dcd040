"""Single source of truth for the CI test shards.

The reference gates merges on its suite under ``mpirun -np 1/2`` plus two
patched examples across a Travis matrix (``/root/reference/.travis.yml:39-108``).
This repo's analog: three balanced unit shards on the simulated 8-device
CPU mesh, plus a dedicated 2-process multihost job and the full
examples-as-integration-tests job. The GitHub workflow
(.github/workflows/ci.yml) and humans both resolve shards through this
script so the split can't drift between them.

Usage:
    python tools/ci_shard.py <shard>          # print the pytest args
    python tools/ci_shard.py <shard> --run    # exec pytest on the shard
Shards: unit-1 unit-2 unit-3 unit-4 multihost examples all
"""
import os
import subprocess
import sys

# Balanced by measured wall-clock (docs/ci.md records the timings), not by
# test count — test_sequence.py alone is ~9 min on the simulated mesh.
SHARDS = {
    "unit-1": [
        "tests/test_sequence.py",
        # chip_smoke.py's phase functions at toy width on the CPU mesh,
        # its gate and exit codes, the compile-cache helper.
        "tests/test_chip_smoke.py",
    ],
    "unit-2": [
        "tests/test_basics.py",
        "tests/test_collectives.py",
        "tests/test_optimizer.py",
        "tests/test_training.py",
        "tests/test_estimator.py",
        "tests/test_batchnorm.py",
        "tests/test_data.py",
        "tests/test_losses.py",
        "tests/test_transformer.py",
        "tests/test_models.py",
    ],
    "unit-3": [
        "tests/test_control_plane.py",  # moved from unit-2 (r5 rebalance)
        "tests/test_tensor_parallel.py",
        "tests/test_pipeline_parallel.py",
        "tests/test_expert_parallel.py",
        "tests/test_tools.py",
        "tests/test_overlap.py",  # skips where no TPU AOT compiler
        # ~9s of fast tests; its AOT scheduled-HLO check carries
        # @pytest.mark.slow so tier-1 (-m 'not slow') stays inside its cap.
        "tests/test_compression.py",
        # ~6s of fast injection-parser/CRC/backoff/liveness tests; the
        # multi-process fault drill inside is @pytest.mark.slow.
        "tests/test_resilience.py",
        # Allreduce decomposition layer: topology/cost-model/tuning-cache
        # units + CPU bit-exactness + CPU HLO structure; the AOT v5e
        # proofs inside are @pytest.mark.slow.
        "tests/test_strategy.py",
    ],
    # Serving layer in its own shard: unit-3 already runs near the
    # 2-core host's time cap, and the engine tests compile up to four
    # executables per Engine construction (~75s of fast tests incl.
    # the quantized-KV + prefix-sharing matrix and the speculative
    # draft-and-verify bit-identity/2+2-trace pins; the trained-LM
    # generation-quality gates and the kv-dtype speculation sweep are
    # @pytest.mark.slow — this shard applies no marker filter, so they
    # still run here).
    "unit-4": [
        "tests/test_serving.py",
        # hvd-lint static analysis: AST lints over the fixture corpus +
        # repo self-test, HLO schedule extraction/verification units,
        # golden-schedule snapshots, and the LM-step identity matrix
        # (lowering-only — no compiles beyond the tiny goldens).
        "tests/test_analysis.py",
        # Whole-step exchange scheduler: plan determinism + artifact
        # round-trip, bit-exact priority-vs-enum gradients across
        # algo x compression, exposed-comm accounting, and the
        # always-on recalibration loop's cache hygiene.
        "tests/test_exchange.py",
        # Block-wise int8/int4 compression: bounded-error matrix across
        # algo x simulated slices, phase-asymmetric lowering proofs,
        # error-feedback residual algebra + checkpoint round-trip, and
        # the new knob typo paths; the small-LM int4+EF convergence
        # gate is @pytest.mark.slow. (unit-3 already runs near the
        # 2-core host's cap.)
        "tests/test_block_compression.py",
        # Multi-channel collectives: channelized-lowering bit-exactness
        # across wire formats x algos, the per-channel cost model +
        # planner channel assignment, artifact channel checks, and the
        # channel-efficiency recalibration fit.
        "tests/test_channels.py",
        # Sparse embedding gradient exchange: dedup-and-merge
        # bit-exactness vs densify+allreduce, quantized value payloads,
        # the density auto-switch units, plan-artifact integration,
        # subset-group refusals, knob typo paths, and the sparse golden
        # schedules (~25s of fast tests, small lowerings only).
        "tests/test_sparse.py",
        # hvd-model protocol checker: exhaustive-interleaving sweeps of
        # the real extracted negotiation transition functions (clean +
        # exact exhaustiveness pins), HVD201-206 detection on broken
        # variants, the .world.json corpus, shrink-continue spec, and
        # the new knob typo paths (~6s, no compiles).
        "tests/test_model.py",
        # Elastic data parallelism: shrink/regrow knob validation, the
        # pure plan contracts, runtime reconfigure, consume-once fault
        # semantics, the KV join/admit handshake, exchange-plan elastic
        # provenance + lint checks, and the in-process
        # shrink-continue-regrow fit (~3s; the two-subprocess CRC drill
        # lives in tools/fault_drill.py --elastic).
        "tests/test_elastic.py",
        # hvd.tune(): calibration determinism, knob search argmin,
        # artifact round-trip/hash/stale-schema refusal, env-beats-tuned
        # precedence, bit-exact tuned-vs-default step, and the
        # perf_gate pass/fail/tolerance contract (~20s, tiny compiles).
        "tests/test_tune.py",
        # FSDP (ZeRO-2/3) over the data x fsdp mesh: the 3-step LM
        # bit-identity matrix off/zero2/zero3 x {none,bf16,int8_block}
        # on the 2-slice pod, per-chip state-byte caps, refusal paths,
        # plan fsdp-section round-trip, the sharded lint-gate rows, the
        # zero3 golden section, and the alpha-beta sharding pricing
        # (~70s; the LM compiles dominate).
        "tests/test_fsdp.py",
    ],
    "multihost": ["tests/test_multihost.py", "tests/test_scaleout.py"],
    "examples": ["tests/test_examples.py"],
}
SHARDS["all"] = sorted({f for fs in SHARDS.values() for f in fs})


def shard_files(name: str) -> list[str]:
    try:
        return SHARDS[name]
    except KeyError:
        raise SystemExit(
            f"unknown shard {name!r}; choose from {sorted(SHARDS)}")


def main() -> None:
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    files = shard_files(sys.argv[1])
    if "--run" in sys.argv[2:]:
        os.chdir(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "pytest", "-q", "-x", *files]))
    print(" ".join(files))


if __name__ == "__main__":
    main()
