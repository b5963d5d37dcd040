"""Open-loop load driver for the serving engine (serving/engine.py).

Two measurements, both reusable as a library by bench.py:

* :func:`bench_decode_tokens_per_sec` — steady-state decode throughput
  at a fixed concurrent batch (B=1/8/64 are the BENCH json columns):
  fill every slot, warm the executable, time N decode steps → B*N/dt.
* :func:`run_load` — the open-loop driver: Poisson arrivals at a stated
  rate with sampled prompt/output lengths, submitted on their schedule
  REGARDLESS of completions (open-loop — the load does not back off
  when the server lags, so queueing delay shows up in the latencies
  instead of silently throttling the offered load). Reports p50/p99
  request latency, completed-request and generated-token throughput,
  rejects, and preemptions.

Run:  python tools/serve_bench.py --smoke            # sub-minute CPU drill
      python tools/serve_bench.py --arrival-rate 50 --num-requests 200
      python tools/serve_bench.py --kv-dtype int8_block   # quantized pool
      python tools/serve_bench.py --shared-prefix-len 32  # repeated-prefix
                                                          # load, cache on

``--kv-dtype`` selects the paged pool's storage format (int8_block/int4
quantized pages — the `kv_cache_bytes_per_token` output field shows the
per-token HBM cost, scale planes included); ``--shared-prefix-len N``
prepends the same N tokens to every prompt and enables the prefix cache,
so `serve_prefix_hit_tokens_ratio` reports how much prefill the radix
index absorbed. ``--speculate K`` turns on draft-and-verify speculative
decoding (K draft tokens per step, self-speculation) and fills the
`lm_decode_tokens_per_sec_b1_spec` / `serve_speculative_accept_rate` /
`serve_draft_overhead_ms` fields (null when off). ``--smoke``
additionally prints one quantized+prefix row
(`serve_bench_quantized_prefix`) and one speculative row
(`serve_bench_speculative`). The arrival-rate flag refuses
unparsable/NaN/non-positive values (the resilience-knob convention: a
typo'd rate must not silently benchmark a different load).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _positive(raw, flag: str, unit: str) -> float:
    """Shared load-knob validator: unparsable, NaN, inf, and
    non-positive values raise ValueError — mirrors the
    HOROVOD_LIVENESS_TIMEOUT validation convention in utils/env.py.
    A typo'd load knob must refuse, not silently benchmark a
    different workload."""
    try:
        val = float(raw)
    except (TypeError, ValueError):
        val = float("nan")
    if val != val:
        raise ValueError(f"{flag} must be a number of {unit}, got {raw!r}")
    if math.isinf(val) or val <= 0:
        raise ValueError(
            f"{flag} must be a finite positive number of {unit}, "
            f"got {raw!r}")
    return val


def positive_rate(raw) -> float:
    """Parse an arrival rate (requests/second)."""
    return _positive(raw, "--arrival-rate", "requests/second")


def positive_duration(raw) -> float:
    """Parse a trace duration (seconds): the open-loop arrival trace is
    truncated to arrivals within this window."""
    return _positive(raw, "--duration", "seconds")


def positive_count(raw) -> int:
    """Parse a request cap: a positive INTEGER (12.5 requests is as
    much a typo as NaN requests)."""
    val = _positive(raw, "--max-requests", "requests")
    if val != int(val):
        raise ValueError(
            f"--max-requests must be a whole number of requests, "
            f"got {raw!r}")
    return int(val)


def tiny_config(max_seq_len: int = 64):
    """The CPU-serveable LM the drill and bench default to."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    return transformer.TransformerConfig(
        vocab_size=512, num_layers=2, num_heads=4, num_kv_heads=2,
        embed_dim=64, mlp_dim=128, max_seq_len=max_seq_len,
        dtype=jnp.float32)


def sample_workload(n: int, rate: float, prompt_range=(4, 12),
                    output_range=(4, 16), vocab: int = 512,
                    seed: int = 0, shared_prefix_len: int = 0):
    """Pre-drawn open-loop trace: Poisson arrivals (exponential gaps at
    ``rate``/s) with uniformly sampled prompt/output lengths.
    ``shared_prefix_len`` > 0 models repeated-system-prompt traffic:
    every request's prompt starts with the SAME ``shared_prefix_len``
    tokens (drawn once) followed by its private tail — the workload a
    prefix-shared cache turns into near-free prefill."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    plens = rng.integers(prompt_range[0], prompt_range[1] + 1, size=n)
    outs = rng.integers(output_range[0], output_range[1] + 1, size=n)
    shared = rng.integers(0, vocab, size=shared_prefix_len).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.integers(0, vocab, size=p).astype(np.int32)])
        for p in plens]
    return [{"arrival": float(arrivals[i]), "prompt": prompts[i],
             "max_new": int(outs[i]),
             "tenant": f"tenant{i % 2}"} for i in range(n)]


def run_load(engine, workload, max_wall_seconds: float = 300.0) -> dict:
    """Drive the engine open-loop through a :func:`sample_workload`
    trace; returns the latency/throughput metric dict."""
    from horovod_tpu.serving import AdmissionError

    t0 = time.monotonic()
    pending = sorted(workload, key=lambda w: w["arrival"])
    latencies, rejected, submitted = [], 0, {}
    idx = 0
    while len(latencies) + rejected < len(workload):
        now = time.monotonic() - t0
        if now > max_wall_seconds:
            raise RuntimeError(
                f"load run exceeded {max_wall_seconds}s wall cap with "
                f"{len(workload) - len(latencies) - rejected} requests "
                f"outstanding")
        while idx < len(pending) and pending[idx]["arrival"] <= now:
            w = pending[idx]
            try:
                req = engine.submit(w["prompt"], w["max_new"],
                                    tenant=w["tenant"])
                submitted[req.request_id] = w["arrival"]
            except AdmissionError:
                rejected += 1
            idx += 1
        if not engine.has_work():
            if idx < len(pending):  # open-loop idle: wait for the next
                time.sleep(max(0.0, pending[idx]["arrival"]
                               - (time.monotonic() - t0)))
            continue
        for done in engine.step():
            end = time.monotonic() - t0
            latencies.append((end - submitted[done.request_id]) * 1e3)
    wall = time.monotonic() - t0
    lat = np.asarray(latencies) if latencies else np.asarray([float("nan")])
    ingested = (engine.stats["prefill_tokens"]
                + engine.stats["prefix_hit_tokens"])
    return {
        "requests": len(workload),
        "completed": len(latencies),
        "rejected": rejected,
        "serve_p50_ms": round(float(np.percentile(lat, 50)), 2),
        "serve_p99_ms": round(float(np.percentile(lat, 99)), 2),
        "serve_mean_ms": round(float(lat.mean()), 2),
        "requests_per_sec": round(len(latencies) / wall, 2),
        "gen_tokens_per_sec": round(
            engine.stats["tokens_generated"] / wall, 1),
        "preemptions": engine.stats["preemptions"],
        # Prefix-cache effectiveness: prompt tokens whose pages came
        # from the radix index instead of being prefilled (0.0 with the
        # cache off or no repeated prefixes).
        "prefill_tokens": engine.stats["prefill_tokens"],
        "prefill_steps": engine.stats["prefill_steps"],
        "serve_prefix_hit_tokens_ratio": round(
            engine.stats["prefix_hit_tokens"] / ingested, 4) if ingested
            else 0.0,
        "kv_cache_bytes_per_token":
            engine.cache_stats()["kv_cache_bytes_per_token"],
        "kv_dtype": engine.kv_dtype,
        "wall_seconds": round(wall, 2),
    }


def bench_decode_tokens_per_sec(config, params, batch: int,
                                steps: int = 16, prompt_len: int = 8,
                                block_size: int = 16,
                                warmup: int = 2,
                                kv_dtype: str | None = None) -> float:
    """Steady-state decode throughput with every slot busy: prefill B
    identical-length prompts, warm the decode executable, then time
    ``steps`` engine steps (each advances all B slots one token)."""
    from horovod_tpu.serving import Engine

    # Token budget per request: 2 land in the first (admit+prefill+
    # decode) step, one per warmup step, one per timed step, plus one
    # spare so NO request finishes inside the timed window (a finishing
    # step decodes fewer tokens than it is credited for).
    max_new = warmup + steps + 3
    need = prompt_len + max_new
    if need > config.max_seq_len:
        raise ValueError(
            f"prompt_len+warmup+steps ({need}) exceeds max_seq_len "
            f"({config.max_seq_len}) — shrink the measurement")
    engine = Engine(config, params, block_size=block_size,
                    max_batch=batch, max_prompt_len=prompt_len,
                    kv_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    for _ in range(batch):
        engine.submit(
            rng.integers(0, config.vocab_size,
                         size=prompt_len).astype(np.int32),
            max_new_tokens=max_new)
    engine.step()  # admit + prefill (+ first decode)
    for _ in range(warmup):
        engine.step()
    tok0 = engine.stats["tokens_generated"]
    t0 = time.monotonic()
    for _ in range(steps):
        engine.step()
    dt = time.monotonic() - t0
    produced = engine.stats["tokens_generated"] - tok0
    if produced != batch * steps or engine.stats["preemptions"]:
        raise RuntimeError(
            f"decode measurement not clean: {produced} tokens in the "
            f"timed window (expected {batch * steps}), "
            f"{engine.stats['preemptions']} preemptions — the reported "
            f"throughput would be wrong")
    return produced / dt


def distilled_draft_pair(num_layers: int = 4, embed_dim: int = 64,
                         mlp_dim: int = 128, max_seq_len: int = 400,
                         vocab: int = 512, seed: int = 0):
    """A (target, draft) model pair whose draft agrees with the target
    EXACTLY: the target's upper blocks get their residual contributions
    (attention out-projection, MLP down-projection) zeroed, so its
    function collapses to its first block — and a 1-layer draft sharing
    the embed / block_0 / final-norm / lm_head weights computes the
    identical logits at a fraction of the cost. This is the
    perfectly-distilled-draft limit (accept rate 1.0): the measured
    speculative speedup isolates what the ENGINE's draft-and-verify
    machinery delivers when the draft is right, which is exactly the
    quantity ``tune.price_speculation`` prices real accept rates
    against. Returns ``(config, params, draft_config, draft_params)``."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=vocab, num_layers=num_layers, num_heads=4,
        num_kv_heads=2, embed_dim=embed_dim, mlp_dim=mlp_dim,
        max_seq_len=max_seq_len, dtype=jnp.float32)
    params = dict(transformer.init_params(cfg, seed))
    for l in range(1, num_layers):
        blk = dict(params[f"block_{l}"])
        attn = dict(blk["attn"])
        attn["out"] = {"kernel": jnp.zeros_like(attn["out"]["kernel"])}
        blk["attn"] = attn
        blk["Dense_1"] = {"kernel": jnp.zeros_like(blk["Dense_1"]["kernel"])}
        params[f"block_{l}"] = blk
    dcfg = cfg._replace(num_layers=1)
    dparams = {"Embed_0": params["Embed_0"], "block_0": params["block_0"],
               "RMSNorm_0": params["RMSNorm_0"],
               "lm_head": params["lm_head"]}
    return cfg, params, dcfg, dparams


def bench_speculative_decode(config, params, *, speculate: int = 4,
                             steps: int = 12, prompt_len: int = 8,
                             block_size: int = 16, warmup: int = 2,
                             kv_dtype: str | None = None,
                             draft_kv_dtype: str | None = None,
                             draft_config=None,
                             draft_params=None) -> dict:
    """Steady-state B=1 draft-and-verify throughput (the low-batch
    regime speculation exists for): one request. With no draft model
    the target self-speculates (accept rate ~1.0 by construction; the
    speedup is then pure dispatch/gather amortization); pass a
    :func:`distilled_draft_pair` draft for the cheap-agreeing-draft
    measurement bench.py headlines. Returns tokens/sec, the measured
    accept rate, and the draft's share of step time in ms. The window
    must stay clean — no finish, no preemption — or the throughput
    credit would be wrong; raises otherwise."""
    from horovod_tpu.serving import Engine

    # Every step may emit up to speculate+1 tokens; the budget keeps the
    # request alive past the timed window so no step is short-changed.
    need = prompt_len + 1 + (warmup + steps + 1) * (speculate + 1)
    if need > config.max_seq_len:
        raise ValueError(
            f"speculative window needs {need} positions but max_seq_len "
            f"is {config.max_seq_len} — shrink steps/k or grow the model")
    engine = Engine(config, params, block_size=block_size, max_batch=1,
                    max_prompt_len=prompt_len, kv_dtype=kv_dtype,
                    speculate=speculate, draft_kv_dtype=draft_kv_dtype,
                    draft_config=draft_config, draft_params=draft_params)
    rng = np.random.default_rng(0)
    engine.submit(rng.integers(0, config.vocab_size,
                               size=prompt_len).astype(np.int32),
                  max_new_tokens=config.max_seq_len - prompt_len)
    engine.step()  # admit + prefill (+ first burst)
    for _ in range(warmup):
        engine.step()
    tok0 = engine.stats["tokens_generated"]
    draft0 = engine.stats["draft_time_s"]
    calls0 = engine.stats["draft_calls"]
    t0 = time.monotonic()
    for _ in range(steps):
        engine.step()
    dt = time.monotonic() - t0
    produced = engine.stats["tokens_generated"] - tok0
    if engine.stats["finished"] or engine.stats["preemptions"]:
        raise RuntimeError(
            "speculative decode measurement not clean: a request "
            "finished or was preempted inside the timed window")
    draft_ms = ((engine.stats["draft_time_s"] - draft0) * 1e3
                / max(1, engine.stats["draft_calls"] - calls0))
    return {
        "tokens_per_sec": produced / dt,
        "accept_rate": engine.spec_accept_rate,
        "draft_overhead_ms": round(draft_ms, 3),
        "speculate_k": speculate,
        "draft_kv_dtype": engine.draft_kv_dtype,
    }


def bench_recovery(config, params, journal_path: str, *,
                   num_requests: int = 4, interrupt_steps: int = 3,
                   prompt_len: int = 6, max_new: int = 10,
                   block_size: int = 16, kv_dtype: str | None = None,
                   seed: int = 0) -> dict:
    """Crash-recovery drill as a measurement: run a journaled batch,
    abandon the engine mid-decode (the journal's per-step flush is the
    crash artifact), then time a fresh engine's ``recover()`` replay
    and finish the batch. Outputs — committed prefixes plus recomputed
    continuations — must be bit-identical to an uninterrupted run of
    the same batch; ``bit_identical`` reports that comparison and
    ``serve_recovery_ms`` the journal-replay cost bench.py publishes."""
    from horovod_tpu.serving import Engine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, config.vocab_size,
                            size=prompt_len).astype(np.int32)
               for _ in range(num_requests)]

    def _engine(journal=None):
        return Engine(config, params, block_size=block_size,
                      max_batch=num_requests,
                      max_prompt_len=prompt_len + max_new,
                      kv_dtype=kv_dtype, journal=journal)

    def _drain(eng, outputs):
        while eng.has_work():
            for done in eng.step():
                outputs[done.request_id] = list(done.output)

    reference: dict[int, list[int]] = {}
    ref = _engine()
    for p in prompts:
        ref.submit(p, max_new)
    _drain(ref, reference)

    outputs: dict[int, list[int]] = {}
    interrupted = _engine(journal=journal_path)
    for p in prompts:
        interrupted.submit(p, max_new)
    for _ in range(interrupt_steps):
        for done in interrupted.step():
            outputs[done.request_id] = list(done.output)
    # Simulated crash: the engine is abandoned here — no close, no
    # final flush beyond the per-step one, exactly what a dead process
    # leaves behind.
    del interrupted

    restarted = _engine(journal=journal_path)
    t0 = time.monotonic()
    recovered = restarted.recover()
    recovery_ms = (time.monotonic() - t0) * 1e3
    _drain(restarted, outputs)

    return {
        "requests": num_requests,
        "recovered": len(recovered),
        "interrupt_steps": interrupt_steps,
        "serve_recovery_ms": round(recovery_ms, 3),
        "bit_identical": outputs == reference,
        "kv_dtype": restarted.kv_dtype,
    }


def warm_engine(engine) -> None:
    """Serve one throwaway request so both executables compile BEFORE
    the measured window — first-request latency under load should
    measure queueing+decode, not XLA compilation."""
    engine.generate_batch([np.zeros((2,), np.int32)], 2)
    for k in ("tokens_generated", "preemptions", "prefill_tokens",
              "prefix_hit_tokens", "prefill_steps"):
        engine.stats[k] = 0


def main() -> None:
    # kv_cache is numpy-only at import time (jax loads lazily inside it),
    # and KV_DTYPES is the single source of truth for pool formats.
    from horovod_tpu.serving.kv_cache import KV_DTYPES

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="sub-minute CPU drill: tiny model, light "
                             "load — the CI-runnable proof the serving "
                             "path works end to end")
    parser.add_argument("--arrival-rate", type=positive_rate, default=20.0,
                        help="open-loop Poisson arrival rate, requests/s "
                             "(unparsable/NaN/non-positive values raise)")
    parser.add_argument("--num-requests", type=int, default=60)
    parser.add_argument("--max-requests", type=positive_count,
                        default=None,
                        help="hard cap on submitted requests (validated "
                             "like --arrival-rate: unparsable/NaN/"
                             "non-positive/fractional values raise)")
    parser.add_argument("--duration", type=positive_duration, default=None,
                        help="truncate the open-loop trace to arrivals "
                             "within this many seconds (validated like "
                             "--arrival-rate)")
    parser.add_argument("--fault", default=None,
                        help="fault spec forwarded to HOROVOD_FAULT_INJECT "
                             "(core/resilience.py grammar, e.g. "
                             "'stuck_decode@step=3,ms=9000') — parsed "
                             "eagerly so a typo'd spec refuses instead of "
                             "benchmarking with no fault armed")
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--kv-dtype", default="model",
                        choices=["model", *KV_DTYPES],
                        help="paged-KV pool storage format (int8_block "
                             "~4x / int4 ~8x less HBM per cached token; "
                             "docs/inference.md 'Quantized KV cache')")
    parser.add_argument("--shared-prefix-len", type=int, default=0,
                        help="repeated-prefix workload: every prompt "
                             "starts with the same N tokens (enables the "
                             "prefix cache so the shared span is "
                             "prefilled once and then hit)")
    parser.add_argument("--speculate", type=int, default=0,
                        help="draft length k for speculative decoding "
                             "(0 = off): measures B=1 draft-and-verify "
                             "throughput next to the plain B=1 rate")
    parser.add_argument("--decode-batches", type=int, nargs="*",
                        default=[1, 8],
                        help="batch sizes for the steady-state decode "
                             "throughput sweep")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    from horovod_tpu.utils import env as _env

    _env.use_compile_cache()
    if args.shared_prefix_len < 0:
        raise SystemExit("--shared-prefix-len must be >= 0")
    if 0 < args.shared_prefix_len < args.block_size:
        # Prefixes only share as FULL blocks; a sub-block prefix would
        # silently benchmark with the cache OFF (ratio 0.0) — refuse
        # loudly, same convention as the arrival-rate guard.
        raise SystemExit(
            f"--shared-prefix-len {args.shared_prefix_len} is shorter "
            f"than one block (--block-size {args.block_size}): a prefix "
            f"shares as full blocks only, so this run would measure the "
            f"prefix cache disabled. Use 0 (off) or >= block_size.")
    if args.smoke:
        args.num_requests = min(args.num_requests, 30)
        args.decode_batches = [1, 8]
    if args.max_requests is not None:
        args.num_requests = min(args.num_requests, args.max_requests)
    if args.fault is not None:
        from horovod_tpu.core import resilience as _core_res

        _core_res.parse_fault_spec(args.fault)  # typo'd spec refuses here
        os.environ["HOROVOD_FAULT_INJECT"] = args.fault
        _core_res.reset_injector()

    from horovod_tpu.models import transformer
    from horovod_tpu.serving import Engine

    # The model's sequence capacity grows with the shared prefix so the
    # workload's prompts (prefix + up to 12 private tokens) plus outputs
    # (up to 16) always fit — a --shared-prefix-len run must measure the
    # cache, not silently reject its own requests.
    cfg = tiny_config(max_seq_len=max(64, args.shared_prefix_len + 32))
    params = transformer.init_params(cfg)
    kvd = None if args.kv_dtype == "model" else args.kv_dtype

    result = {"metric": "serve_bench", "arrival_rate_per_sec":
              args.arrival_rate, "smoke": bool(args.smoke)}
    for b in args.decode_batches:
        tps = bench_decode_tokens_per_sec(cfg, params, b,
                                          block_size=args.block_size,
                                          kv_dtype=kvd)
        result[f"lm_decode_tokens_per_sec_b{b}"] = round(tps, 1)

    # Speculative fields ride the main row on every backend — null when
    # off, so downstream json consumers see a stable schema.
    result["lm_decode_tokens_per_sec_b1_spec"] = None
    result["serve_speculative_accept_rate"] = None
    result["serve_draft_overhead_ms"] = None
    if args.speculate < 0:
        raise SystemExit("--speculate must be >= 0 (0 disables)")
    if args.speculate:
        scfg = tiny_config(
            max_seq_len=max(cfg.max_seq_len,
                            8 + 1 + 16 * (args.speculate + 1)))
        # Self-speculation with the draft pool in the model's own dtype:
        # accept rate ~1.0, so the headline measures the real win
        # (dispatch amortization), not quantization disagreement.
        spec = bench_speculative_decode(
            scfg, params, speculate=args.speculate,
            block_size=args.block_size, kv_dtype=kvd,
            draft_kv_dtype="model")
        result["lm_decode_tokens_per_sec_b1_spec"] = round(
            spec["tokens_per_sec"], 1)
        result["serve_speculative_accept_rate"] = (
            None if spec["accept_rate"] is None
            else round(spec["accept_rate"], 4))
        result["serve_draft_overhead_ms"] = spec["draft_overhead_ms"]

    # Shared prefixes only share as FULL blocks: a prefix shorter than
    # one block can never hit. max_prompt_len covers prefix + the
    # longest sampled private tail.
    prefix_on = args.shared_prefix_len >= args.block_size
    pmax = 16 + args.shared_prefix_len
    engine = Engine(cfg, params, block_size=args.block_size,
                    max_batch=args.max_batch, max_prompt_len=pmax,
                    kv_dtype=kvd, prefix_cache=prefix_on)
    warm_engine(engine)
    workload = sample_workload(args.num_requests, args.arrival_rate,
                               vocab=cfg.vocab_size, seed=args.seed,
                               shared_prefix_len=args.shared_prefix_len)
    if args.duration is not None:
        workload = [w for w in workload if w["arrival"] <= args.duration]
        if not workload:
            raise SystemExit(
                f"--duration {args.duration}s truncates the trace to zero "
                f"arrivals at --arrival-rate {args.arrival_rate}/s — "
                f"nothing to measure")
    result.update(run_load(engine, workload))
    print(json.dumps(result))

    if args.smoke:
        # The quantized + prefix-shared row: int8_block pages under a
        # repeated-prefix load (one block's worth of shared prefix) —
        # CI's proof the two capacity levers compose end to end
        # (tests/test_examples.py runs --smoke). Same fit guarantee as
        # above: prompts are block_size + up to 12 tokens.
        qcfg = tiny_config(max_seq_len=max(64, args.block_size + 44))
        qeng = Engine(qcfg, params, block_size=args.block_size,
                      max_batch=args.max_batch,
                      max_prompt_len=args.block_size + 16,
                      kv_dtype="int8_block", prefix_cache=True)
        warm_engine(qeng)
        qload = run_load(qeng, sample_workload(
            min(args.num_requests, 16), args.arrival_rate,
            vocab=qcfg.vocab_size, seed=args.seed,
            shared_prefix_len=args.block_size))
        qrow = {"metric": "serve_bench_quantized_prefix",
                "kv_dtype": "int8_block",
                "shared_prefix_len": args.block_size}
        qrow.update(qload)
        print(json.dumps(qrow))

        # The speculative row: B=1 draft-and-verify vs plain B=1 decode
        # on the same model — CI's proof the 2+2-executable speculative
        # path works end to end and actually emits more than one token
        # per step. The distilled pair's 1-layer draft agrees with the
        # 4-layer target exactly (accept rate 1.0), so the ratio
        # measures the engine's speculation machinery, not draft
        # quality.
        k = args.speculate or 8
        scfg, sparams, dcfg, dparams = distilled_draft_pair(
            max_seq_len=max(400, 8 + 1 + 16 * (k + 1) + args.block_size))
        base = bench_decode_tokens_per_sec(scfg, sparams, 1,
                                           block_size=args.block_size)
        spec = bench_speculative_decode(scfg, sparams, speculate=k,
                                        block_size=args.block_size,
                                        draft_config=dcfg,
                                        draft_params=dparams,
                                        draft_kv_dtype="model")
        srow = {"metric": "serve_bench_speculative",
                "speculate_k": k,
                "draft_kv_dtype": spec["draft_kv_dtype"],
                "lm_decode_tokens_per_sec_b1": round(base, 1),
                "lm_decode_tokens_per_sec_b1_spec": round(
                    spec["tokens_per_sec"], 1),
                "serve_speculative_speedup": round(
                    spec["tokens_per_sec"] / base, 3),
                "serve_speculative_accept_rate": (
                    None if spec["accept_rate"] is None
                    else round(spec["accept_rate"], 4)),
                "serve_draft_overhead_ms": spec["draft_overhead_ms"]}
        print(json.dumps(srow))

        # The recovery row: journaled batch interrupted mid-decode,
        # fresh engine replays the journal and finishes it — CI's proof
        # the crash-safe journal + recover() path delivers bit-identical
        # outputs (docs/inference.md 'Fault tolerance in serving').
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            rrow = {"metric": "serve_bench_recovery"}
            rrow.update(bench_recovery(
                cfg, params,
                os.path.join(td, "serve_bench.journal.json"),
                block_size=args.block_size, kv_dtype=kvd,
                seed=args.seed))
        if not rrow["bit_identical"]:
            raise SystemExit(
                "serve_bench_recovery: journal replay produced outputs "
                "that differ from the uninterrupted run — recovery is "
                "not bit-identical")
        print(json.dumps(rrow))


if __name__ == "__main__":
    main()
