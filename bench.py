"""Benchmark: ResNet-50 data-parallel training throughput (images/sec/chip).

The reference's headline benchmark is CNN throughput under
``tf_cnn_benchmarks --variable_update horovod`` with synthetic data
(docs/benchmarks.md:24-54). This harness is the TPU-native equivalent: a
full ResNet-50 v1.5 training step — forward, backward, fused gradient
allreduce via DistributedOptimizer, SGD+momentum update, BatchNorm stat
sync — on synthetic ImageNet data, bfloat16 compute, donated state buffers.

Batch size is 128/chip: measured throughput-optimal on TPU v5e (64 → 128 is
+15%, 256 is flat); tf_cnn_benchmarks takes batch as a flag the same way.

Methodology: ``STEPS_PER_CALL`` training steps run inside one compiled
program (``lax.scan``), the standard TPU device-loop pattern. On TPU the
per-step time is read from the DEVICE op timeline of a ``jax.profiler``
capture (first to last device op over the call, best of N captures), so
host dispatch is not charged to the kernels; a TPU capture without a
device timeline is an error, never a host-clocked number
(``core/xprof.timed_steps``). Off-TPU the wall clock is used, forced by
materializing the final loss.

Every leg after the ResNet headline runs inside a ``try``: a leg that
raises prints its traceback, the JSON line still prints with that leg's
fields null or absent, and the process then EXITS NON-ZERO naming the
legs that failed.

MFU: measured TFLOP/s over the chip's peak, using XLA's own cost analysis
for the step (24.49 GFLOP/image at batch 128, multiply-add = 2 FLOPs —
``tools/cost_model.py`` derivation; the analytic 3x-forward estimate under MAC=1
counting is half that, so always compare like for like).

``vs_baseline`` caveat: the ONLY absolute throughput the reference publishes
is 1656.82 images/sec on 16 Pascal GPUs (docs/benchmarks.md:50-54) — and
that run is **ResNet-101**, ~1.85x the XLA FLOPs/image of the default
ResNet-50, on 2017 hardware. ``--model resnet101`` runs the LIKE-FOR-LIKE
workload (measured: 1,864 img/s/chip, 84.4 TFLOP/s = 43% MFU on v5e —
one chip exceeds the reference's whole 16-GPU cluster); for the default
ResNet-50 the ratio is a historical anchor and MFU is the honest metric.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.core import state as _state
from horovod_tpu.core.xprof import timed_steps as _timed_steps
from horovod_tpu.models import resnet

# Reference per-accelerator anchor — ResNet-101 on 16 Pascal GPUs
# (docs/benchmarks.md:50-54); see the docstring caveat.
REFERENCE_R101_IMAGES_PER_SEC_PER_GPU = 1656.82 / 16
BATCH_PER_CHIP = 128
IMAGE_SIZE = 224
STEPS_PER_CALL = 10
WARMUP_CALLS = 2
MEASURE_CALLS = 3
# XLA cost analysis of one full train step at batch 128 (fwd+bwd+update),
# FLOPs with multiply-add = 2; derivation in repo `tools/cost_model.py`.
XLA_GFLOPS_PER_IMAGE = {"resnet50": 24.49, "resnet101": 45.3}


def _chip_peak_tflops() -> float | None:
    """bf16 peak of the chip under test from THE chip table
    (ops/topology.py); None off-TPU (no MFU there). A TPU that is not in
    the table raises — MFU fields never silently vanish."""
    from horovod_tpu.ops import topology

    dev = _state.target_device()
    if dev.platform != "tpu":
        return None
    return topology.chip_spec(dev.device_kind).peak_bf16_tflops


# Legs that raised in this run. main() prints the JSON line first and then
# exits non-zero naming them: a null field is an answer only with rc 0.
_FAILED_LEGS: list[str] = []


def _leg_failed(leg: str, e: Exception) -> None:
    import sys
    import traceback

    _FAILED_LEGS.append(leg)
    print(f"{leg} failed: {e}", file=sys.stderr)
    traceback.print_exc()


def build_resnet_bench(model_name: str = "resnet50",
                       batch_per_chip: int = BATCH_PER_CHIP,
                       steps_per_call: int = STEPS_PER_CALL,
                       compression: str = "none",
                       image_size: int = IMAGE_SIZE):
    """The exact benchmark step, reusable by sweep tools: initializes the
    runtime, builds + warms the compiled multi-step program over every
    chip, and returns ``(run_once, state)`` — ``run_once()`` executes
    ``steps_per_call`` chained steps and forces completion;
    ``state['loss']`` holds the latest per-rank losses.

    ``compression`` (``none``/``bf16``/``int8``): wire format for the
    fused gradient allreduce (ops/compression.py) — the BatchNorm stat
    sync stays uncompressed (a value collective, not a gradient)."""
    hvd.shutdown()
    hvd.init()
    n_chips = hvd.size()

    model_cls = (resnet.ResNet101 if model_name == "resnet101"
                 else resnet.ResNet50)
    model = model_cls(num_classes=1000, dtype=jnp.bfloat16)
    variables = resnet.init_variables(model, image_size=image_size)
    loss_fn = resnet.make_loss_fn(model)
    opt = optax.sgd(0.1, momentum=0.9)

    def train_step(variables, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            variables, batch)
        # The literal string (not None): "none" must stay the exact
        # uncompressed baseline even with HOROVOD_COMPRESSION exported,
        # or the reported byte accounting would lie about what ran.
        grads = hvd.allreduce_gradients(grads, compression=compression)
        updates, opt_state = opt.update(grads, opt_state, variables)
        variables = optax.apply_updates(variables, updates)
        variables = {
            "params": variables["params"],
            "batch_stats": jax.tree.map(lambda t: hvd.allreduce(t),
                                        aux["batch_stats"]),
        }
        return variables, opt_state, loss

    def multi_step(variables, opt_state, batch):
        def body(carry, _):
            variables, opt_state = carry
            variables, opt_state, loss = train_step(variables, opt_state,
                                                    batch)
            return (variables, opt_state), loss

        (variables, opt_state), losses = jax.lax.scan(
            body, (variables, opt_state), None, length=steps_per_call)
        return variables, opt_state, losses[-1]

    # Donating params/opt-state lets XLA update in place instead of
    # double-buffering the 100 MB of training state every step.
    step = hvd.spmd(multi_step, donate_argnums=(0, 1))
    vs = hvd.replicate(variables)
    opt_state = hvd.replicate(opt.init(variables))

    def make_batch(r):
        im, lb = resnet.synthetic_imagenet(batch_per_chip, image_size,
                                           seed=r)
        return (im.astype(jnp.bfloat16), lb)  # bf16 input: halve HBM reads

    batch = hvd.rank_stack([make_batch(r) for r in range(n_chips)])
    batch = hvd.device_put_ranked(batch)

    for _ in range(WARMUP_CALLS):
        vs, opt_state, loss = step(vs, opt_state, batch)
    float(np.asarray(loss)[0])  # force all warmup work to completion

    # Gradient-exchange byte accounting (logical vs wire) for the JSON.
    from horovod_tpu.ops import compression as _compression

    compressor = _compression.resolve(compression)
    grad_leaves = jax.tree.leaves(variables)
    grad_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in grad_leaves)
    grad_wire = sum(_compression.wire_bytes(int(np.prod(l.shape)), l.dtype,
                                            compressor,
                                            sum_width=hvd.size())
                    for l in grad_leaves)

    # step/batch exposed for tools that refeed the same compiled program
    # (tools/input_bench.py drives it from the real-JPEG pipeline).
    state = {"vs": vs, "os": opt_state, "loss": loss, "step": step,
             "batch": batch, "grad_bytes": grad_bytes,
             "grad_wire_bytes": grad_wire}

    def run_once():
        state["vs"], state["os"], state["loss"] = step(
            state["vs"], state["os"], batch)
        np.asarray(state["loss"])  # forces the chained sequence (all ranks)

    return run_once, state


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=["resnet50", "resnet101"],
                        default="resnet50",
                        help="resnet101 is the LIKE-FOR-LIKE comparison "
                             "against the reference's only published "
                             "absolute number (1656.82 img/s on 16 Pascal "
                             "GPUs, docs/benchmarks.md:50-54)")
    parser.add_argument("--compression",
                        choices=["none", "bf16", "int8", "int8_block",
                                 "int4"],
                        default="none",
                        help="wire format for the fused gradient allreduce "
                             "(ops/compression.py); the JSON then carries "
                             "grad_bytes/grad_wire_bytes")
    parser.add_argument("--gate", action="store_true",
                        help="CI-bounded run: tiny ResNet batch/steps so "
                             "the suite finishes on a CPU runner, same "
                             "JSON shape. BENCH_baseline.json is "
                             "generated in this mode and tools/"
                             "perf_gate.py compares like for like "
                             "(docs/ci.md has the recipe)")
    args = parser.parse_args()
    # Gate mode shrinks only the ResNet leg — every extra is already
    # CPU-sized. Batch AND image size drop (224px at any batch is
    # minutes/step on a CPU runner); absolute img/s here is NOT
    # comparable to the batch-128 headline, and the artifact says so
    # via "gate_mode".
    batch_per_chip = 2 if args.gate else BATCH_PER_CHIP
    steps_per_call = 2 if args.gate else STEPS_PER_CALL
    image_size = 64 if args.gate else IMAGE_SIZE

    from horovod_tpu.utils import env as _env

    _env.use_compile_cache()
    # Chip-health probe BEFORE the suite; repeated after, so a slow
    # episode starting or ending mid-run is bracketed.
    sanity_pre = _device_sanity_tflops()
    run_once, state = build_resnet_bench(args.model,
                                         batch_per_chip=batch_per_chip,
                                         steps_per_call=steps_per_call,
                                         compression=args.compression,
                                         image_size=image_size)
    sec_per_step = _timed_steps(run_once, steps_per_call, MEASURE_CALLS)
    losses = np.asarray(state["loss"])
    per_chip = batch_per_chip / sec_per_step
    assert np.all(np.isfinite(losses)), losses
    tflops = per_chip * XLA_GFLOPS_PER_IMAGE[args.model] / 1e3
    peak = _chip_peak_tflops()
    result = {
        "metric": f"{args.model}_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        # Historical anchor only: the reference figure is ResNet-101 on
        # 2017 Pascal GPUs (see module docstring).
        "vs_baseline": round(
            per_chip / REFERENCE_R101_IMAGES_PER_SEC_PER_GPU, 3),
        "tflops_per_chip": round(tflops, 1),
        "batch_per_chip": batch_per_chip,
    }
    if args.gate:
        result["gate_mode"] = True
        result["image_size"] = image_size
    if peak:
        result["mfu"] = round(tflops / peak, 3)
        result["peak_tflops"] = peak
    # Wire/logical byte ratio of the gradient exchange under the active
    # compression — 1.0 uncompressed, 0.5 bf16, 0.25 int8/int8_block,
    # 0.125 int4 — emitted on EVERY backend so BENCH artifacts always
    # carry the compression accounting.
    result["compression_wire_bytes_ratio"] = round(
        state["grad_wire_bytes"] / max(1, state["grad_bytes"]), 4)
    if args.compression != "none":
        result["compression"] = args.compression
        result["grad_bytes"] = state["grad_bytes"]
        result["grad_wire_bytes"] = state["grad_wire_bytes"]
    fa = _flash_attention_extra(peak)
    if fa:
        result.update(fa)
    lm = _lm_extra(peak)
    if lm:
        result.update(lm)
    ar = _allreduce_busbw_extra()
    if ar:
        result.update(ar)
    ex = _exchange_extra()
    if ex:
        result.update(ex)
    ab = _tuned_ab_extra()
    # On TPU _lm_extra already measured the full-size LM for the
    # headline field; the A/B's default arm only fills it elsewhere.
    lm_default = ab.pop("lm_t8k_tokens_per_sec_per_chip", None)
    if lm_default is not None:
        result.setdefault("lm_t8k_tokens_per_sec_per_chip", lm_default)
    result.update(ab)
    # Null-when-infeasible: the tuned A/B fields appear in EVERY
    # artifact (1-chip worlds have nothing to tune), so perf_gate can
    # distinguish "infeasible here" from "stopped running".
    for field in ("lm_t8k_tokens_per_sec_per_chip",
                  "lm_t8k_tokens_per_sec_per_chip_tuned",
                  "tuned_speedup_lm_t8k", "tuned_config_hash"):
        result.setdefault(field, None)
    result.update(_channels_extra())
    result.update(_sparse_extra())
    result.update(_elastic_extra())
    # Null-when-infeasible (the PR 5 convention): the multi-channel
    # fields appear in EVERY artifact so their absence is never
    # ambiguous (1-chip worlds have no wire to channelize).
    result.setdefault("allreduce_busbw_multichannel_gbps", None)
    # Null-when-infeasible: the FSDP fields appear in EVERY artifact
    # (1-chip worlds have no fsdp axis to shard over), so perf_gate can
    # distinguish "infeasible here" from "stopped running".
    result.update(_fsdp_extra())
    sv = _serving_extra()
    if sv:
        result.update(sv)
    # Null-when-infeasible: the speculative-decode fields appear in
    # EVERY artifact (speculation defaults off; the serving extra can
    # fail without taking the headline down), so perf_gate can
    # distinguish "off here" from "stopped running".
    for field in ("lm_decode_tokens_per_sec_b1_spec",
                  "serve_speculative_speedup",
                  "serve_speculative_accept_rate",
                  "serve_draft_overhead_ms",
                  "serve_recovery_ms",
                  "serve_deadline_miss_ratio",
                  "serve_journal_overhead_ms"):
        result.setdefault(field, None)
    sanity_post = _device_sanity_tflops()
    dev = _state.target_device()
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    if dev.platform != "tpu":
        result["timing"] = "host"
    sanities = [s for s in (sanity_pre, sanity_post) if s is not None]
    if sanities:
        # Chip-health reference: a plain big matmul's achieved TFLOP/s,
        # probed before AND after the suite (min reported). A chip that
        # another process is using shows up here as a fraction of its
        # peak, so a slow artifact is diagnosable instead of mysterious.
        result["device_sanity_tflops"] = min(sanities)
        if peak and min(sanities) < 0.5 * peak:
            result["device_degraded"] = True
    if _FAILED_LEGS:
        result["failed_legs"] = _FAILED_LEGS
    print(json.dumps(result), flush=True)
    if _FAILED_LEGS:
        raise SystemExit(
            f"bench: {len(_FAILED_LEGS)} leg(s) raised: "
            f"{', '.join(_FAILED_LEGS)}")


def _allreduce_busbw_extra() -> dict:
    """North-star #2 evidence: achieved ring-equivalent allreduce bus
    bandwidth (GB/s, nccl-tests convention) per decomposition
    (ops/strategy.py), probed at one 16 MB buffer via the
    tools/allreduce_bench harness — so every BENCH json carries the ICI
    busbw number whenever the world has inter-device traffic to measure.
    Skipped (no fields) on 1-chip worlds; a hierarchical row on a
    single-slice topology reports null rather than vanishing, so the
    artifact says WHY the number is absent."""
    if hvd.size() < 2:
        return {}
    extra: dict = {}
    try:
        from tools import allreduce_bench as _arb

        nbytes = 16 << 20
        extra["allreduce_busbw_bytes"] = nbytes
        for algo in ("flat", "rs_ag", "hierarchical"):
            try:
                row = _arb.bench_size(nbytes, hvd.size(), algo=algo,
                                      trials=2)
            except hvd.HorovodError:
                # e.g. hierarchical on a single-slice world.
                extra[f"allreduce_busbw_{algo}_gbps"] = None
                continue
            extra[f"allreduce_busbw_{algo}_gbps"] = row["value"]
        # int4 wire-format probe (ops/compression.py): effective busbw
        # on logical bytes at the packed 12.5% wire — the EQuARX-grade
        # compression evidence, on every backend (CPU XLA moves the s8
        # carrier too; only the absolute GB/s is host-bound there).
        try:
            row = _arb.bench_size(nbytes, hvd.size(),
                                  compression="int4", trials=2)
            extra["allreduce_busbw_int4_gbps"] = row["value"]
        except hvd.HorovodError:
            extra["allreduce_busbw_int4_gbps"] = None
        # Multi-channel probe (ops/strategy.py channelized lowerings):
        # the same 16 MB buffer split into 2 concurrent channel
        # instances — the busbw the channelized wire actually achieves,
        # next to the single-instance rows above.
        try:
            row = _arb.bench_size(nbytes, hvd.size(), channels=2,
                                  trials=2)
            extra["allreduce_busbw_multichannel_gbps"] = row["value"]
        except hvd.HorovodError:
            extra["allreduce_busbw_multichannel_gbps"] = None
    except Exception as e:  # algorithms measured before the failure are kept
        _leg_failed("allreduce_busbw", e)
    return extra


def _exchange_extra() -> dict:
    """Whole-step exchange-scheduler evidence (ops/exchange.py), on EVERY
    backend: exposed (non-overlapped) communication per LM training step
    under the enumeration-order baseline vs ``schedule=priority``, plus
    the committed plan's hash — the tentpole's win as a BENCH field, not
    a claim.

    Methodology: the same tiny-but-real LM step (transformer loss →
    grads → fused exchange → SGD update) is compiled three ways — no
    exchange, ``schedule=enum``, ``schedule=priority`` — and timed;
    ``t(mode) − t(no-comm)`` is the measured exposed communication (the
    compute is identical by construction, so the difference is exactly
    the wire time the schedule failed to hide). On TPU a device-timeline
    capture refines it to span-level truth
    (:func:`~horovod_tpu.ops.exchange.measured_exposed_comm_ms`); the
    wall-clock form works on any backend."""
    try:
        from jax import lax

        from horovod_tpu.models import transformer
        from horovod_tpu.ops import exchange as _exchange

        if not hvd.is_initialized():
            hvd.init()
        world = hvd.size()
        cfg = transformer.TransformerConfig(
            vocab_size=97, num_layers=2, num_heads=2, embed_dim=32,
            mlp_dim=64, max_seq_len=16, dtype=jnp.float32)
        params = transformer.init_params(cfg)
        loss_fn = transformer.make_loss_fn(cfg)
        opt = optax.sgd(0.1)
        opt_state = opt.init(params)
        K = 4

        def make_step(mode):
            def step(params, opt_state, tokens):
                def body(carry, _):
                    p, s = carry
                    loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
                    if mode is not None:
                        grads = hvd.allreduce_gradients(grads,
                                                        schedule=mode)
                    updates, s = opt.update(grads, s, p)
                    return (optax.apply_updates(p, updates), s), loss

                (p, s), losses = lax.scan(body, (params, opt_state),
                                          None, length=K)
                return p, s, losses[-1]

            return hvd.spmd(step)

        tokens = hvd.rank_stack([
            np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % 97 + r
            for r in range(world)])
        times, hashes = {}, {}
        for mode in (None, "enum", "priority"):
            step = make_step(mode)
            ps = hvd.replicate(params)
            ss = hvd.replicate(opt_state)
            state = {"p": ps, "s": ss}

            def run_once():
                state["p"], state["s"], loss = step(state["p"],
                                                    state["s"], tokens)
                float(np.asarray(loss)[0])

            run_once()  # compile + warm (registers the live plan)
            if mode is not None:
                plan = _exchange.last_plan()
                hashes[mode] = plan.plan_hash() if plan else None
            times[mode] = _timed_steps(run_once, K, 2)

        extra = {
            "exchange_schedule_hash": hashes.get("priority"),
            "exchange_step_ms_enum": round(times["enum"] * 1e3, 3),
            "exchange_step_ms_priority": round(times["priority"] * 1e3,
                                               3),
        }
        source = "wall-diff"
        exposed = {m: max(0.0, (times[m] - times[None]) * 1e3)
                   for m in ("enum", "priority")}
        if _state.target_platform() == "tpu":
            # Span-level truth where the profiler has a device plane.
            for mode in ("enum", "priority"):
                step = make_step(mode)
                ps, ss = hvd.replicate(params), hvd.replicate(opt_state)
                measured = _exchange.measured_exposed_comm_ms(
                    lambda: jax.block_until_ready(step(ps, ss, tokens)),
                    steps=K)
                if measured is not None:
                    exposed[mode] = measured
                    source = "device-spans"
        extra["exposed_comm_ms_enum"] = round(exposed["enum"], 3)
        extra["exposed_comm_ms_priority"] = round(exposed["priority"], 3)
        extra["exchange_exposed_source"] = source
        # NOT fed to the recalibrator: exposed time is the NON-overlapped
        # remainder of a multi-bucket exchange, not one collective's
        # t(S) — pairing it with whole-step bytes would fit garbage
        # constants. The loop's clean sources are per-collective bench
        # rows (tools/allreduce_bench.py) and device-timeline spans.
        return extra
    except Exception as e:
        _leg_failed("exchange", e)
        return {}


def _tuned_ab_extra() -> dict:
    """Tuned-vs-default A/B (horovod_tpu/tune; ROADMAP perf-gated CI):
    the same data-parallel LM training step timed twice — once under the
    repo's untuned knob defaults, once under a freshly committed
    ``hvd.tune()`` artifact — on EVERY backend with a wire to tune
    (1-chip worlds report null).

    The workload is the tiny-but-real LM step of ``_exchange_extra``
    (transformer loss → grads → fused exchange → SGD update, K scanned
    steps): small enough that the calibrate+search pass stays inside a
    bounded budget, real enough that every tuned knob (algo,
    compression, schedule, fusion threshold, channels) changes the
    compiled program. Fields:

    ``lm_t8k_tokens_per_sec_per_chip`` — the DEFAULT arm's tokens/sec
    (only where ``_lm_extra`` did not already measure the full-size LM;
    ``main`` merges with ``setdefault``); ``..._tuned`` — the tuned
    arm; ``tuned_speedup_lm_t8k`` — tuned/default ratio on the SAME
    workload and host, the number ``tools/perf_gate.py`` holds >= 1;
    ``tuned_config_hash`` — provenance of the artifact that ran.

    When the search commits the exact plan the defaults already produce
    (plan hashes equal) the speedup is REPORTED as exactly 1.0 — an
    honest tie, not a re-measurement of timer jitter."""
    if hvd.size() < 2:
        return {}
    try:
        import os
        import tempfile

        from jax import lax

        from horovod_tpu.models import transformer
        from horovod_tpu.ops import exchange as _exchange
        from horovod_tpu.tune import apply as _tune_apply

        if not hvd.is_initialized():
            hvd.init()
        world = hvd.size()
        cfg = transformer.TransformerConfig(
            vocab_size=97, num_layers=2, num_heads=2, embed_dim=32,
            mlp_dim=64, max_seq_len=16, dtype=jnp.float32)
        params = transformer.init_params(cfg)
        loss_fn = transformer.make_loss_fn(cfg)
        opt = optax.sgd(0.1)
        opt_state = opt.init(params)
        B, T, K = 2, 16, 4
        tokens = hvd.rank_stack([
            np.arange(B * T, dtype=np.int32).reshape(B, T) % 97 + r
            for r in range(world)])

        def measure():
            """Compile the step under the CURRENTLY active knob sources
            (env > tuned > default), time it, and return
            (sec_per_step, committed plan hash)."""
            def step(params, opt_state, tokens):
                def body(carry, _):
                    p, s = carry
                    loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
                    grads = hvd.allreduce_gradients(grads)
                    updates, s = opt.update(grads, s, p)
                    return (optax.apply_updates(p, updates), s), loss

                (p, s), losses = lax.scan(body, (params, opt_state),
                                          None, length=K)
                return p, s, losses[-1]

            step = hvd.spmd(step)
            state = {"p": hvd.replicate(params),
                     "s": hvd.replicate(opt_state)}

            def run_once():
                state["p"], state["s"], loss = step(state["p"],
                                                    state["s"], tokens)
                float(np.asarray(loss)[0])

            run_once()  # compile + warm (registers the live plan)
            plan = _exchange.last_plan()
            return (_timed_steps(run_once, K, 2),
                    plan.plan_hash() if plan else None)

        # Default arm: whatever was applied at init (HOROVOD_PROFILE=
        # auto / HOROVOD_TUNED_CONFIG) is lifted so this arm is the
        # honest untuned baseline the speedup is read against.
        _tune_apply.deactivate()
        t_default, hash_default = measure()

        tmp = tempfile.mkdtemp(prefix="hvd_bench_tune_")
        tuned = hvd.tune(path=os.path.join(tmp, "bench.tuned.json"),
                         budget_s=8.0)
        extra = {"tuned_config_hash": tuned.config_hash()}
        if tuned.knobs.get("HOROVOD_EXCHANGE_SCHEDULE") and \
                _tune_apply.active() is None:
            raise RuntimeError("tune() committed but did not activate")
        t_tuned, hash_tuned = measure()
        _tune_apply.deactivate()

        tok_default = B * T / t_default
        if hash_tuned == hash_default:
            # Same committed plan => same compiled exchange: report the
            # tie as exactly 1.0 instead of re-rolling timer jitter.
            tok_tuned, speedup = tok_default, 1.0
        else:
            tok_tuned = B * T / t_tuned
            speedup = tok_tuned / tok_default
        extra["lm_t8k_tokens_per_sec_per_chip"] = round(tok_default, 0)
        extra["lm_t8k_tokens_per_sec_per_chip_tuned"] = round(tok_tuned, 0)
        extra["tuned_speedup_lm_t8k"] = round(speedup, 3)
        return extra
    except Exception as e:
        _leg_failed("tuned_ab", e)
        return {}


def _channels_extra() -> dict:
    """Planner channel-choice evidence (ops/exchange.py
    ``_assign_channels``): plan a large-bucket gradient exchange with
    the planner cap raised to 4 and report the highest channel count the
    per-channel α–β model committed — ``exchange_channels_chosen``. A
    PLANNED quantity (shape-only leaves, no data moved), so it is
    deterministic and cheap on every backend; null when the world has no
    wire to channelize (1 chip). The matching measured number is
    ``allreduce_busbw_multichannel_gbps``."""
    try:
        from horovod_tpu.ops import exchange as _exchange
        from horovod_tpu.ops import topology as _topology

        if not hvd.is_initialized():
            hvd.init()
        if hvd.size() < 2:
            return {"exchange_channels_chosen": None}
        topo = _topology.discover(hvd.get_group(0))
        leaves = [jax.ShapeDtypeStruct((8 << 20,), jnp.float32)
                  for _ in range(4)]  # 4 x 32 MB fp32 buckets
        plan = _exchange.plan_exchange(
            leaves, 64 << 20, mode="priority", topo=topo,
            algo="flat", labels=[f"probe{i}" for i in range(4)],
            max_channels=4)
        return {"exchange_channels_chosen":
                max(b.channels for b in plan.buckets)}
    except Exception as e:
        _leg_failed("channels", e)
        return {"exchange_channels_chosen": None}


def _sparse_extra() -> dict:
    """Embedding-gradient exchange headline (ops/sparse.py; ROADMAP #4):
    a recommender-shaped sparse exchange — 256 hot-duplicated rows per
    rank of a 16384x64 fp32 table — timed through the padded-gather +
    dedup-and-merge lowering vs the densify+allreduce fallback, on EVERY
    backend (wall clock off-TPU, like the serving extras).

    Fields (always present; null only on probe failure):
    ``embedding_grad_exchange_gbps`` — gathered payload bytes received
    per rank per step over the sparse path's step time;
    ``embedding_grad_sparse_ms`` / ``embedding_grad_dense_ms`` — measured
    per-step times of the two lowerings; ``sparse_vs_dense_bytes_ratio``
    — deterministic wire accounting: per-rank gathered index+value bytes
    over the dense ring allreduce's bytes (< 1 means the sparse path
    moves fewer bytes at this density — the acceptance gate's
    low-density operating point); ``embedding_grad_density`` — group-
    gathered rows / table rows."""
    out = {"embedding_grad_exchange_gbps": None,
           "embedding_grad_sparse_ms": None,
           "embedding_grad_dense_ms": None,
           "sparse_vs_dense_bytes_ratio": None,
           "embedding_grad_density": None}
    try:
        # Workload, step builder, and byte accounting are shared with
        # the tools/allreduce_bench.py --sparse sweep — one definition,
        # so the two tools can never report diverging shapes/formulas.
        from tools import allreduce_bench as _arb

        if not hvd.is_initialized():
            hvd.init()
        world = hvd.size()
        R, D, C, K = 16384, 64, 256, 8
        vals, idx = _arb.sparse_workload(world, R, D, C, seed=0)

        times = {}
        for algo in ("gather", "dense"):
            step = _arb.make_sparse_step(algo, R, D, K,
                                         name_prefix="bench_sparse")
            acc = hvd.replicate(jnp.float32(0.0))

            def run_once(step=step, acc=acc):
                float(np.asarray(step(vals, idx, acc))[0])

            run_once()  # compile + warm
            times[algo] = _timed_steps(run_once, K, 2)

        acct = _arb.sparse_wire_accounting(world, R, D, C)
        out.update({
            "embedding_grad_exchange_gbps": round(
                acct["recv_bytes"] / times["gather"] / 1e9, 3),
            "embedding_grad_sparse_ms": round(times["gather"] * 1e3, 3),
            "embedding_grad_dense_ms": round(times["dense"] * 1e3, 3),
            "sparse_vs_dense_bytes_ratio": acct["bytes_ratio"],
            "embedding_grad_density": acct["density"],
        })
    except Exception as e:
        _leg_failed("sparse", e)
    return out


def _elastic_extra() -> dict:
    """Elastic transition timings (core/elastic.py; the fault drill's
    ``--elastic`` recovery path): ``elastic_shrink_recovery_ms`` is
    WorkerLost-to-resumed-step-loop, ``elastic_regrow_admit_ms`` is
    boundary-admission-to-resumed-step-loop, both for the most recent
    transition in THIS process. Emitted on EVERY backend, null whenever
    the run had no elastic transition (the common case — HOROVOD_ELASTIC
    defaults off), so their absence is never ambiguous."""
    from horovod_tpu.core import elastic as _elastic

    return _elastic.last_metrics()


def _fsdp_extra() -> dict:
    """FSDP (ZeRO-2/3, ops/mesh.py + parallel/optimizer.py) evidence on
    EVERY backend: the per-chip parameter footprint ratio of zero3 vs
    replicated (the capacity claim as a number, not prose), the
    gather-on-use exposed time, and the zero3 arm's tokens/sec.

    Methodology mirrors ``_exchange_extra``: the same tiny-but-real LM
    step is compiled replicated and zero3 (K scanned steps each);
    ``t(zero3) − t(off)`` is the wire time the sharded arm ADDS that
    XLA's latency-hiding scheduler failed to overlap — the gradient
    exchange is wire-neutral across modes (zero2/3 keep the replicated
    lowering's reduce-scatter prefix), so the difference prices exactly
    the per-layer parameter all-gathers (tune/search.price_sharding is
    the model of this number). All three fields are null when sharding
    is infeasible here (1-chip world)."""
    null = {"fsdp_param_bytes_per_chip_ratio": None,
            "fsdp_gather_exposed_ms": None,
            "lm_t8k_tokens_per_sec_per_chip_zero3": None}
    try:
        from jax import lax

        from horovod_tpu.models import transformer

        if not hvd.is_initialized():
            hvd.init()
        world = hvd.size()
        if world < 2:
            return null
        cfg = transformer.TransformerConfig(
            vocab_size=97, num_layers=2, num_heads=2, embed_dim=32,
            mlp_dim=64, max_seq_len=16, dtype=jnp.float32)
        params = transformer.init_params(cfg)
        loss_fn = transformer.make_loss_fn(cfg)
        opt = optax.sgd(0.1)
        B, T, K = 2, 16, 4
        tokens = hvd.rank_stack([
            np.arange(B * T, dtype=np.int32).reshape(B, T) % 97 + r
            for r in range(world)])

        dopt = hvd.DistributedOptimizer(opt, sharding="zero3")
        dopt.bind(params)
        shards0 = dopt.init_shards(params)
        opt_state0 = dopt.init(jax.tree.map(lambda t: t[0], shards0))

        # The capacity claim: bytes ONE chip holds of the parameters.
        full_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                         for t in jax.tree.leaves(params))
        shard_bytes = sum(int(np.prod(t.shape[1:])) * t.dtype.itemsize
                          for t in jax.tree.leaves(shards0))
        ratio = shard_bytes / max(1, full_bytes)

        def off_step(p, s, tokens):
            def body(carry, _):
                p, s = carry
                loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
                grads = hvd.allreduce_gradients(grads)
                updates, s = opt.update(grads, s, p)
                return (optax.apply_updates(p, updates), s), loss

            (p, s), losses = lax.scan(body, (p, s), None, length=K)
            return p, s, losses[-1]

        def z3_step(sh, s, tokens):
            def body(carry, _):
                sh, s = carry
                full = dopt.gather_params(sh)
                loss, grads = jax.value_and_grad(loss_fn)(full, tokens)
                sh, s = dopt.apply_gradients(grads, s, sh)
                return (sh, s), loss

            (sh, s), losses = lax.scan(body, (sh, s), None, length=K)
            return sh, s, losses[-1]

        times = {}
        for name, step, state0 in (
                ("off", hvd.spmd(off_step),
                 (hvd.replicate(params), hvd.replicate(opt.init(params)))),
                ("zero3", hvd.spmd(z3_step),
                 (shards0, hvd.replicate(opt_state0)))):
            state = {"a": state0[0], "b": state0[1]}

            def run_once(step=step, state=state):
                state["a"], state["b"], loss = step(state["a"],
                                                    state["b"], tokens)
                float(np.asarray(loss)[0])

            run_once()  # compile + warm
            times[name] = _timed_steps(run_once, K, 2)

        return {
            "fsdp_param_bytes_per_chip_ratio": round(ratio, 4),
            "fsdp_gather_exposed_ms": round(
                max(0.0, (times["zero3"] - times["off"]) * 1e3), 3),
            "lm_t8k_tokens_per_sec_per_chip_zero3": round(
                B * T / times["zero3"], 0),
        }
    except Exception as e:
        _leg_failed("fsdp", e)
        return null


def _serving_extra() -> dict:
    """Serving headline (docs/inference.md): steady-state continuous-
    batching decode throughput at B=1/8/64 concurrent requests, plus
    p50/p99 request latency under open-loop Poisson arrivals at a
    stated rate (tools/serve_bench.py). Unlike the training extras this
    runs on EVERY backend — the serving engine is the product surface
    the north star names, so the BENCH json must always carry real
    numbers for it (the model is the serve_bench tiny LM; the metric
    tracks engine overhead + decode math, not model scale)."""
    try:
        from horovod_tpu.models import transformer
        from horovod_tpu.serving import Engine
        from tools import serve_bench

        cfg = serve_bench.tiny_config(max_seq_len=64)
        params = transformer.init_params(cfg)
        extra: dict = {}
        for b in (1, 8, 64):
            extra[f"lm_decode_tokens_per_sec_b{b}"] = round(
                serve_bench.bench_decode_tokens_per_sec(
                    cfg, params, b, steps=16, prompt_len=8), 1)
        # Speculative decode headline (docs/inference.md): B=1
        # draft-and-verify vs plain B=1 decode on the SAME model — the
        # distilled pair (serve_bench.distilled_draft_pair) gives a
        # 1-layer draft that agrees with its 4-layer target exactly, so
        # the ratio measures the engine's speculation machinery (wide
        # verify + k draft forwards per k+1 emitted tokens), not draft
        # quality. serve_speculative_speedup is a same-process A/B
        # ratio like tuned_speedup_*, so its baseline band is tighter
        # than the absolute throughputs'.
        scfg, sparams, sdcfg, sdparams = serve_bench.distilled_draft_pair()
        sbase = serve_bench.bench_decode_tokens_per_sec(
            scfg, sparams, 1, steps=16, prompt_len=8)
        spec = serve_bench.bench_speculative_decode(
            scfg, sparams, speculate=8, draft_config=sdcfg,
            draft_params=sdparams, draft_kv_dtype="model")
        extra["lm_decode_tokens_per_sec_b1_spec"] = round(
            spec["tokens_per_sec"], 1)
        extra["serve_speculative_speedup"] = round(
            spec["tokens_per_sec"] / sbase, 3)
        extra["serve_speculative_accept_rate"] = (
            None if spec["accept_rate"] is None
            else round(spec["accept_rate"], 4))
        extra["serve_draft_overhead_ms"] = spec["draft_overhead_ms"]
        rate = 20.0
        engine = Engine(cfg, params, block_size=16, max_batch=8,
                        max_prompt_len=16)
        serve_bench.warm_engine(engine)
        load = serve_bench.run_load(
            engine, serve_bench.sample_workload(
                40, rate, vocab=cfg.vocab_size, seed=0))
        extra["serve_arrival_rate_per_sec"] = rate
        extra["serve_p50_ms"] = load["serve_p50_ms"]
        extra["serve_p99_ms"] = load["serve_p99_ms"]
        extra["serve_rejected"] = load["rejected"]
        # Paged-pool memory per cached token (scale planes included) for
        # the default pool and the quantized formats — pure layout math
        # (serving/kv_cache.py), so the ~4x/8x drop is visible in every
        # BENCH json even though the default engine stays fp32.
        from horovod_tpu.serving import kv_cache as _kvc

        dcfg = transformer.decode_config(cfg)
        extra["kv_cache_bytes_per_token"] = _kvc.kv_bytes_per_token(dcfg)
        extra["kv_cache_bytes_per_token_int8_block"] = \
            _kvc.kv_bytes_per_token(dcfg, "int8_block")
        extra["kv_cache_bytes_per_token_int4"] = \
            _kvc.kv_bytes_per_token(dcfg, "int4")
        # Prefix-cache effectiveness under a repeated-system-prompt
        # load: the shared span prefills once, every later admission
        # hits (tools/serve_bench.py --shared-prefix-len).
        peng = Engine(cfg, params, block_size=16, max_batch=8,
                      max_prompt_len=48, prefix_cache=True)
        serve_bench.warm_engine(peng)
        pload = serve_bench.run_load(
            peng, serve_bench.sample_workload(
                16, rate, vocab=cfg.vocab_size, seed=0,
                shared_prefix_len=16))
        extra["serve_prefix_hit_tokens_ratio"] = \
            pload["serve_prefix_hit_tokens_ratio"]
        # Resilience metrics (docs/inference.md "Fault tolerance in
        # serving"): journal append+fsync cost per engine step and the
        # deadline-miss ratio under the same open-loop load but with a
        # generous per-request deadline (healthy hardware serves every
        # request well inside it — a nonzero ratio IS the regression),
        # plus the crash-recovery drill's journal-replay cost. The
        # replay must be bit-identical; anything else is a product bug
        # worth failing the whole serving extra over.
        import os
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            jeng = Engine(cfg, params, block_size=16, max_batch=8,
                          max_prompt_len=16, deadline_ms=2000.0,
                          journal=os.path.join(td, "bench.journal.json"))
            serve_bench.warm_engine(jeng)
            jload = serve_bench.run_load(
                jeng, serve_bench.sample_workload(
                    24, rate, vocab=cfg.vocab_size, seed=0))
            extra["serve_journal_overhead_ms"] = round(
                jeng.journal.time_s * 1e3 / max(1, jeng.stats["steps"]),
                4)
            extra["serve_deadline_miss_ratio"] = round(
                jeng.stats["deadline_missed"] / jload["requests"], 4)
            rec = serve_bench.bench_recovery(
                cfg, params, os.path.join(td, "recovery.journal.json"))
            if not rec["bit_identical"]:
                raise RuntimeError(
                    "journal replay produced outputs that differ from "
                    "the uninterrupted run — recovery is not "
                    "bit-identical")
            extra["serve_recovery_ms"] = rec["serve_recovery_ms"]
        return extra
    except Exception as e:
        _leg_failed("serving", e)
        return {}


def _device_sanity_tflops() -> float | None:
    """Achieved TFLOP/s of a bare 4096-cubed bf16 matmul chain (device
    timeline, best of 2) — the chip-health reference the headline metrics
    are read against. None off-TPU (a wall-clocked probe would charge
    host dispatch to sub-ms matmul steps and fabricate a 'degraded'
    verdict) or when the probe raised (recorded as a failed leg)."""
    if _state.target_platform() != "tpu":
        return None
    try:
        from jax import lax

        from horovod_tpu.core import xprof

        n, steps = 4096, 20
        x = jnp.ones((n, n), jnp.bfloat16)
        w = jnp.ones((n, n), jnp.bfloat16) * 0.001

        @jax.jit
        def run(x):
            def body(c, _):
                return jnp.tanh(c @ w), ()
            c, _ = lax.scan(body, x, None, length=steps)
            return jnp.sum(c.astype(jnp.float32))

        float(run(x))
        t = xprof.timed_steps(lambda: float(run(x)), steps, 2)
        return round(2 * n ** 3 / t / 1e12, 1)
    except Exception as e:
        _leg_failed("device_sanity", e)
        return None


def _flash_attention_extra(peak: float | None) -> dict:
    """Secondary headline: flash-attention fwd+bwd at T=16k AND T=32k on
    one chip (the long-context hot op — docs/sequence-parallelism.md's
    table). Scanned steps, all three gradients consumed, device-timeline
    timing (`_timed_steps`). Skipped off-TPU (interpret mode)."""
    if _state.target_platform() != "tpu":
        return {}
    from jax import lax

    from horovod_tpu.ops import flash_attention as fa

    extra: dict = {}
    B, H, D = 1, 8, 128
    for T, steps, tag in ((16384, 20, "t16k"), (32768, 8, "t32k")):
        key = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                   for kk in jax.random.split(key, 3))
        loss = lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, True).astype(jnp.float32))
        grad = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def run(q, k, v, grad=grad, steps=steps):
            def body(c, _):
                dq, dk, dv = grad(c, k, v)
                s = (jnp.sum(dq.astype(jnp.float32))
                     + jnp.sum(dk.astype(jnp.float32))
                     + jnp.sum(dv.astype(jnp.float32)))
                return c + 0.0 * dq, s
            c, s = lax.scan(body, q, None, length=steps)
            return jnp.sum(s)

        float(run(q, k, v))  # compile + warm
        best = _timed_steps(lambda: float(run(q, k, v)), steps, 3)
        flops = 7 * 2 * B * H * T * T * D / 2
        extra[f"flash_attn_{tag}_fb_ms"] = round(best * 1e3, 2)
        extra[f"flash_attn_{tag}_tflops"] = round(flops / best / 1e12, 1)
        if peak:
            extra[f"flash_attn_{tag}_mfu"] = round(
                flops / best / 1e12 / peak, 3)
    return extra


def _lm_extra(peak: float | None) -> dict:
    """Third headline: long-context GPT-style LM training on one chip —
    the full new-framework stack in one number (flash-attention GQA
    kernel, rotary transformer, AdamW update). T=8k, ~160M params, bf16.
    FLOPs come from XLA's own cost analysis of the compiled step (the
    same convention as the ResNet number). Skipped off-TPU."""
    if _state.target_platform() != "tpu":
        return {}
    try:
        from jax import lax

        from horovod_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab_size=32_768, num_layers=8, num_heads=8, num_kv_heads=4,
            embed_dim=1024, mlp_dim=4096, max_seq_len=8192,
            dtype=jnp.bfloat16, attention="local")
        # B=2 measured throughput-optimal at T=8k (tools/lm_exp.py r5
        # sweep: B=1 108.1k tok/s, B=2 112.8k, B=4 107.0k) — same batch-
        # as-a-flag convention as the ResNet bench.
        B, T, K = 2, 8192, 5
        params = transformer.init_params(cfg)
        # The framework's fused AdamW (ops/optim.py): bf16 moment storage
        # cuts the update's HBM traffic from 28 to 20 bytes/param/step —
        # measured -0.9 ms/step vs optax.adamw at identical semantics
        # (fp32 params and update math; tools/lm_exp.py, r5).
        from horovod_tpu.ops import optim

        opt = optim.adamw(3e-4, weight_decay=0.1)
        opt_state = opt.init(params)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0,
                                    cfg.vocab_size, jnp.int32)

        # fused_head: the chunked-vocab cross-entropy (ops/losses.py) —
        # (N, V) logits never materialize in HBM in either direction. The
        # r4 device profile (tools/profile_lm.py) put ~10 ms/step of the
        # unfused path in fp32-logit materialization/convert traffic.
        loss_fn = transformer.make_loss_fn(cfg, fused_head=True)

        def multi_step(params, opt_state, tokens):
            def body(carry, _):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
                updates, opt_state = opt.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), loss

            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), None, length=K)
            return params, opt_state, losses[-1]

        step = jax.jit(multi_step, donate_argnums=(0, 1))
        compiled = step.lower(params, opt_state, tokens).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        # XLA's analysis counts the scan body ONCE (loop trip counts are
        # not multiplied) and reports zero for the flash-attention custom
        # call — verified against the analytic matmul count, which it
        # matches exactly. Add the attention FLOPs analytically (2 fwd +
        # 5 bwd matmuls, causal-halved — the tools/fa_bench.py convention).
        d_head = cfg.embed_dim // cfg.num_heads
        attn_flops = (cfg.num_layers * 7 * 2 * B * cfg.num_heads
                      * T * T * d_head / 2)
        # fused_head FLOP correction: when the chunked-vocab CE takes its
        # lax.scan path, XLA's cost analysis counts the body once; the
        # unrolled path (the bench config) is fully counted and needs no
        # correction. The helper lives next to the implementation
        # (ops/losses.py) so the accounting tracks the code path taken.
        from horovod_tpu.ops.losses import (default_chunk,
                                            scan_counted_once_flops)

        n_tok = B * (T - 1)
        head_flops = scan_counted_once_flops(
            n_tok, cfg.embed_dim, cfg.vocab_size,
            default_chunk(cfg.vocab_size))
        flops_per_step = (float(cost.get("flops", 0.0)) + attn_flops
                          + head_flops)

        params, opt_state, loss = compiled(params, opt_state, tokens)
        float(np.asarray(loss))
        lm_state = {"p": params, "o": opt_state}

        def run_once():
            lm_state["p"], lm_state["o"], loss = compiled(
                lm_state["p"], lm_state["o"], tokens)
            float(np.asarray(loss))

        best = _timed_steps(run_once, K, 3)
        extra = {
            "lm_t8k_tokens_per_sec_per_chip": round(B * T / best, 0),
            "lm_t8k_step_ms": round(best * 1e3, 2),
        }
        if flops_per_step and peak:
            extra["lm_t8k_mfu"] = round(
                flops_per_step / best / 1e12 / peak, 3)
        return extra
    except Exception as e:
        _leg_failed("lm_t8k", e)
        return {}


if __name__ == "__main__":
    main()
