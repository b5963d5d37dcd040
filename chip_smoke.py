"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, every local chip (1 or 4; it adapts and says which). It takes
the README's own path — ``hvd.init`` → ``hvd.DistributedOptimizer`` →
``hvd.spmd`` → ``broadcast_global_variables`` — on the width-1024 LM
``bench.py`` defines (vocab 32,768, 8 layers, 8 heads / 4 KV heads, MLP
4096, bf16, T=8192, fused-CE loss), then serves the trained weights through
``serving.Engine``, then holds each Pallas kernel to its plain-JAX reference
and reads a profiler capture of the train step. Depth is the model's own;
the weights are random, made from a seed.

Phases (each timed, compile and run apart; a phase that raises is a failed
phase and the exit code is non-zero — never a ``null``):

  gate         platform must be ``tpu`` and ``device_kind`` in the chip
               table (ops/topology.py) BEFORE anything compiles. JAX falls
               back to the CPU by itself when libtpu fails to start and
               ``JAX_PLATFORMS`` is unset, so this is the first defence.
  collectives  eager allreduce / allgather / broadcast / gather on group 0
               and (n >= 3) on one of two overlapping subset groups,
               against numpy.
  train        loss finite, falling, equal on every rank; replicas
               bit-equal; the compiled step holds 2 x num_layers Pallas
               custom calls and, for n > 1, all-reduces; per-device
               ``memory_stats()`` after set-up and after the steps.
  profiler     ``xprof.timed_steps`` on the train step: device ms from the
               ``/device:TPU:*`` ``XLA Ops`` line beside host-clock ms.
  serve        ``serving.Engine`` on ONE chip (the engine is a one-chip
               program): greedy outputs equal ``transformer.generate``, one
               trace per executable, pool invariants; again with
               ``int8_block`` pages and with ``speculate=4``.
  kernels      flash attention fwd+bwd (causal GQA, segment ids, window),
               ``flash_attention_lse``, both BN kernels — compiled, not
               interpreted, against plain JAX.

The phases are importable functions taking a :class:`SmokeConfig`, so
``tests/test_chip_smoke.py`` drives the same code at toy width on the CPU
mesh; ``main()`` owns the gate and the full-width config.

Last stdout line on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Without an accelerator, or in a directory that holds nothing else of the
repo, it exits non-zero and prints no result. It starts no child process.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback
from typing import Any, Callable

import numpy as np


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one smoke run. ``model`` is a ``TransformerConfig``; the
    serving engine runs the same weights with ``serve_max_seq_len`` of
    context (parameters do not depend on it — positions are rotary)."""

    model: Any
    batch_per_chip: int
    seq_len: int
    train_steps: int
    learning_rate: float
    pattern_period: int
    profile_steps: int
    serve_max_seq_len: int
    serve_prompt_lens: tuple[int, ...]
    serve_max_new: int
    serve_max_batch: int
    serve_block_size: int
    speculate: int
    # kernels phase: (B, T, H, Hkv, D) for attention, (N, H, W, C) for BN
    attn_shape: tuple[int, int, int, int, int]
    attn_window: int
    bn_shape: tuple[int, int, int, int]


def full_config() -> SmokeConfig:
    """The width-1024 LM of ``bench.py``'s LM leg, at its full width and
    sequence length."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    model = transformer.TransformerConfig(
        vocab_size=32_768, num_layers=8, num_heads=8, num_kv_heads=4,
        embed_dim=1024, mlp_dim=4096, max_seq_len=8192,
        dtype=jnp.bfloat16, attention="local")
    return SmokeConfig(
        model=model, batch_per_chip=2, seq_len=8192, train_steps=24,
        learning_rate=1e-3, pattern_period=16, profile_steps=3,
        serve_max_seq_len=512, serve_prompt_lens=(16, 48, 96, 160, 256),
        serve_max_new=32, serve_max_batch=4, serve_block_size=16,
        speculate=4, attn_shape=(1, 2048, 4, 2, 128), attn_window=512,
        bn_shape=(8, 28, 28, 256))


# ---------------------------------------------------------------------------
# gate
# ---------------------------------------------------------------------------


def gate() -> dict:
    """Refuse anything that is not a known TPU, before any compile.
    Returns the device triple the result line carries."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"no accelerator: jax.devices()[0] is platform={d0.platform!r} "
            f"device_kind={d0.device_kind!r} x{len(devices)} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            f"chip_smoke.py runs on a TPU only")
    from horovod_tpu.ops import topology

    spec = topology.chip_spec(d0.device_kind)  # unknown kind raises
    _say("gate", f"platform={d0.platform} device_kind={d0.device_kind!r} "
                 f"count={len(devices)} peak_bf16_tflops="
                 f"{spec.peak_bf16_tflops}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def describe_installation() -> None:
    """Versions, compile-cache directory, cost-model source — what a
    reader needs to tell two runs apart."""
    import importlib.metadata as md

    import jax
    import jaxlib

    import horovod_tpu as hvd
    from horovod_tpu.ops import topology
    from horovod_tpu.utils import costs, env

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    _say("gate", f"python={sys.version.split()[0]} jax={jax.__version__} "
                 f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    _say("gate", f"compile cache: {env.use_compile_cache()} "
                 f"(JAX_COMPILATION_CACHE_DIR="
                 f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    # The train phase traces its step twice: to run it, then to read its
    # HLO. A Pallas kernel's Mosaic body is serialized WITH its MLIR
    # locations, which by default hold the whole Python stack of the
    # trace, so the second trace would be another cache key and compile
    # from scratch (40 s for the LM step; my chip run, PR 21). Keep the
    # innermost frame only.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    hvd.init()
    model = costs.model_for(topology.discover(hvd.get_group(0)))
    _say("gate", f"cost model source: {model.source} "
                 f"(tuning cache {env.tuning_cache_path()})")
    hvd.shutdown()


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------


def phase_collectives(cfg: SmokeConfig, ctx: dict) -> None:
    import jax

    import horovod_tpu as hvd

    n = len(jax.devices())
    hvd.shutdown()
    if n >= 3:
        # Two overlapping subset groups — the fork's feature.
        hvd.init([list(range(0, n - 1)), list(range(1, n))])
        groups = [0, 1]
    else:
        hvd.init()
        groups = [0]
    try:
        _say("collectives", "groups: " + str(
            [list(hvd.get_group(g).ranks) for g in range(hvd.num_groups())]))
        rng = np.random.RandomState(0)
        for g in groups:
            size = hvd.size(g)
            xs = [rng.randn(3, 5).astype(np.float32) for _ in range(size)]
            got = hvd.allreduce(list(xs), group=g, average=False)
            for o in got:
                np.testing.assert_allclose(np.asarray(o), np.sum(xs, axis=0),
                                           rtol=1e-5, atol=1e-5)
            # Allgatherv: first dimensions differ per rank.
            rows = [rng.randn(r % 3 + 1, 4).astype(np.float32)
                    for r in range(size)]
            cat = np.concatenate(rows, axis=0)
            np.testing.assert_array_equal(
                np.asarray(hvd.allgather(list(rows), group=g)), cat)
            root = size - 1
            for o in hvd.broadcast(list(xs), root_rank=root, group=g):
                np.testing.assert_array_equal(np.asarray(o), xs[root])
            gathered = hvd.gather(list(rows), root_rank=root, group=g)
            for r, o in enumerate(gathered):
                np.testing.assert_array_equal(
                    np.asarray(o), cat if r == root else rows[r])
            _say("collectives", f"group {g} (size {size}): allreduce "
                                f"allgather broadcast gather == numpy")
    finally:
        hvd.shutdown()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _memory_lines(phase: str, when: str) -> list[int] | None:
    """Print per-device memory; returns bytes_in_use per device, or None
    where the backend reports none (CPU)."""
    import jax

    in_use = []
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats:
            _say(phase, f"memory {when}: {d} reports no memory_stats")
            return None
        in_use.append(int(stats["bytes_in_use"]))
        _say(phase, f"memory {when}: {d} in_use="
                    f"{stats['bytes_in_use'] / 2**20:.0f} MiB peak="
                    f"{stats.get('peak_bytes_in_use', 0) / 2**20:.0f} MiB")
    return in_use


def _first_row(tree):
    """Rank 0's row of a rank-stacked pytree, left on rank 0's device."""
    def row(t):
        for s in t.addressable_shards:
            if (s.index[0].start or 0) == 0:
                return s.data[0]
        raise SmokeFailure("rank 0's row is not addressable")

    import jax

    return jax.tree.map(row, tree)


def smoke_tokens(cfg: SmokeConfig, world: int) -> np.ndarray:
    """(world, B, T) int32: a repeating pattern of ``pattern_period``
    distinct tokens (a seeded draw from the vocabulary), rolled by one
    position per rank so every rank trains on different data."""
    rng = np.random.RandomState(0)
    base = rng.permutation(cfg.model.vocab_size)[:cfg.pattern_period]
    reps = -(-(cfg.seq_len + world) // cfg.pattern_period)
    line = np.tile(base.astype(np.int32), reps)
    return np.stack([
        np.broadcast_to(line[r:r + cfg.seq_len],
                        (cfg.batch_per_chip, cfg.seq_len))
        for r in range(world)])


def phase_train(cfg: SmokeConfig, ctx: dict) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.core import state as _state
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import optim
    from horovod_tpu.utils import env as _env

    hvd.shutdown()
    hvd.init()
    n = hvd.size()
    mcfg = cfg.model
    on_tpu = _state.target_platform() == "tpu"

    t0 = time.perf_counter()
    params = jax.jit(lambda: transformer.init_params(mcfg))()
    n_params = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(params))
    loss_fn = transformer.make_loss_fn(mcfg, fused_head=True)
    opt = hvd.DistributedOptimizer(
        optim.adamw(cfg.learning_rate, weight_decay=0.1))

    def train_step(p, s, toks):
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = hvd.spmd(train_step, donate_argnums=(0, 1))
    ps = hvd.broadcast_global_variables(hvd.replicate(params), root_rank=0)
    ss = hvd.replicate(jax.jit(opt.init)(params))
    batch = hvd.rank_stack(list(smoke_tokens(cfg, n)))
    del params
    jax.block_until_ready((ps, ss, batch))
    setup_s = time.perf_counter() - t0
    _say("train", f"world={n} params={n_params / 1e6:.1f}M "
                  f"B={cfg.batch_per_chip}/chip T={cfg.seq_len} "
                  f"set-up {setup_s:.1f}s")
    in_use = _memory_lines("train", "after set-up")
    if in_use is not None and n > 1:
        # rank_stack/replicate place each row on its own chip: chip 0 must
        # not hold the other replicas too.
        _check(max(in_use) <= 1.5 * min(in_use),
               f"set-up memory is uneven across chips: {in_use}")

    # What the first call is traced with — kept for the HLO inspection
    # below: on a one-device mesh the step's outputs come back with an
    # equivalent but differently spelled sharding, which would make the
    # same program a second compile-cache key.
    specs = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=t.sharding),
        (ps, ss, batch))
    t0 = time.perf_counter()
    ps, ss, loss = step(ps, ss, batch)
    losses = [np.asarray(loss)]
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(cfg.train_steps - 1):
        ps, ss, loss = step(ps, ss, batch)
        losses.append(np.asarray(loss))
    steady_s = (time.perf_counter() - t0) / max(1, cfg.train_steps - 1)
    ctx["compile_s"] += max(0.0, first_s - steady_s)
    _say("train", f"first step (compile + run) {first_s:.1f}s; then "
                  f"{steady_s * 1e3:.1f} ms/step by the host clock")
    curve = [float(l[0]) for l in losses]
    _say("train", "loss: " + " ".join(f"{v:.3f}" for v in curve))
    losses = np.stack(losses)
    _check(bool(np.all(np.isfinite(losses))), f"loss not finite: {curve}")
    _check(bool(np.all(losses == losses[:, :1])),
           f"loss differs across ranks: {losses[-1]}")
    _check(curve[-1] < curve[0],
           f"loss did not fall: {curve[0]} -> {curve[-1]}")
    _memory_lines("train", "after steps")

    @hvd.spmd
    def drift(p):
        root = hvd.broadcast_variables(p, root_rank=0)
        diff = sum(jnp.sum(a != b) for a, b in zip(jax.tree.leaves(p),
                                                   jax.tree.leaves(root)))
        return hvd.allreduce(diff, average=False)

    differing = int(np.asarray(drift(ps))[0])
    _check(differing == 0,
           f"replicas differ in {differing} parameter elements")
    _say("train", f"replicas bit-equal across {n} rank(s)")

    t0 = time.perf_counter()
    lowered = step.lower(*specs)
    lower_s = time.perf_counter() - t0
    # The same program, built as the wrapper builds it: a cache hit.
    hlo = lowered.compile(
        compiler_options=_env.xla_compiler_options()).as_text()
    inspect_s = time.perf_counter() - t0
    pallas = hlo.count('custom_call_target="tpu_custom_call"')
    allreduces = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    # local_attention takes the flash kernel above T=2048 on a TPU: one
    # forward and one fused backward call per layer.
    want = 2 * mcfg.num_layers if on_tpu and cfg.seq_len > 2048 else 0
    _say("train", f"compiled step: {pallas} Pallas custom calls (want "
                  f"{want}), {allreduces} all-reduces; inspection "
                  f"{inspect_s:.1f}s of which trace + lower {lower_s:.1f}s")
    _check(pallas == want,
           f"{pallas} Pallas custom calls in the train step, want {want}")
    _check(n == 1 or allreduces > 0,
           "no all-reduce in a multi-chip train step")
    ctx["train"] = {"step": step, "ps": ps, "ss": ss, "batch": batch}


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------


def phase_profiler(cfg: SmokeConfig, ctx: dict) -> None:
    from horovod_tpu.core import state as _state
    from horovod_tpu.core import xprof

    tr = ctx.get("train")
    _check(tr is not None, "needs the train phase's compiled step")

    def run_once():
        for _ in range(cfg.profile_steps):
            tr["ps"], tr["ss"], loss = tr["step"](tr["ps"], tr["ss"],
                                                  tr["batch"])
        np.asarray(loss)  # forces the chained steps

    info: dict = {}
    per_step = xprof.timed_steps(run_once, cfg.profile_steps, trials=2,
                                 info=info)
    want = "device" if _state.target_platform() == "tpu" else "host"
    _check(info["timing"] == want,
           f"timed_steps used the {info['timing']} clock, want {want}")
    _check(per_step > 0 and np.isfinite(per_step), f"bad time {per_step}")
    _say("profiler", f"timing={info['timing']}: {per_step * 1e3:.2f} "
                     f"ms/step from the capture, {info['host_s'] * 1e3:.2f} "
                     f"ms/step by the host clock (information, not a "
                     f"result; the reader stops at the first device plane)")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def phase_serve(cfg: SmokeConfig, ctx: dict) -> None:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.models import transformer

    tr = ctx.pop("train", None)
    if tr is not None:
        params = _first_row(tr["ps"])
        origin = "trained"
    else:
        params = jax.jit(lambda: transformer.init_params(cfg.model))()
        origin = "fresh (the train phase left none)"
    del tr
    scfg = cfg.model._replace(max_seq_len=cfg.serve_max_seq_len)
    line = smoke_tokens(cfg, 1)[0, 0]
    prompts = [np.asarray(line[i:i + p], np.int32)
               for i, p in enumerate(cfg.serve_prompt_lens)]
    _say("serve", f"{origin} weights on one chip ({jax.devices()[0]}); "
                  f"context {scfg.max_seq_len}, prompts "
                  f"{list(cfg.serve_prompt_lens)}, {cfg.serve_max_new} new "
                  f"each, {cfg.serve_max_batch} slots")

    t0 = time.perf_counter()
    want = [np.asarray(transformer.generate(
        scfg, params, jnp.asarray(p[None]),
        max_new_tokens=cfg.serve_max_new))[0] for p in prompts]
    _say("serve", f"transformer.generate reference: "
                  f"{time.perf_counter() - t0:.1f}s")

    def run(label, exact, **kw):
        t0 = time.perf_counter()
        eng = serving.Engine(
            scfg, params, block_size=cfg.serve_block_size,
            max_batch=cfg.serve_max_batch,
            max_prompt_len=max(cfg.serve_prompt_lens), **kw)
        reqs = [eng.submit(p, cfg.serve_max_new, tenant=f"t{i % 2}")
                for i, p in enumerate(prompts)]
        done = eng.step()  # admit + prefill + first decode: compiles here
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        done += eng.run_until_idle()
        rest_s = time.perf_counter() - t0
        ctx["compile_s"] += first_s
        _check(len(done) == len(reqs)
               and all(len(r.output) == cfg.serve_max_new for r in reqs),
               f"{label}: not every request produced "
               f"{cfg.serve_max_new} tokens: "
               f"{[len(r.output) for r in reqs]}")
        got = [r.full_sequence() for r in reqs]
        for g in got:
            _check(bool(np.all((g >= 0) & (g < scfg.vocab_size))),
                   f"{label}: token out of range")
        same = [bool(np.array_equal(g, w)) for g, w in zip(got, want)]
        spec = eng.speculate_k > 0
        traces = {"prefill": eng._prefill_traces,
                  "decode": eng.decode_trace_count,
                  "verify": eng.verify_trace_count,
                  "draft": eng.draft_trace_count,
                  "draft_prefill": eng.draft_prefill_trace_count}
        want_traces = {"prefill": 1, "decode": 0 if spec else 1,
                       "verify": int(spec), "draft": int(spec),
                       "draft_prefill": int(spec)}
        eng.pool.check_invariants()
        _say("serve", f"{label}: {sum(same)}/{len(same)} requests equal "
                      f"generate; traces {traces}; first step "
                      f"{first_s:.1f}s, rest {rest_s:.1f}s for "
                      f"{eng.stats['tokens_generated']} tokens in "
                      f"{eng.stats['steps']} steps"
                      + (f"; accept rate {eng.spec_accept_rate:.3f}"
                         if spec else ""))
        _check(traces == want_traces,
               f"{label}: traces {traces}, want {want_traces}")
        if exact:
            _check(all(same), f"{label}: outputs differ from "
                              f"transformer.generate: {same}")
        return eng

    run(f"{jnp.dtype(scfg.dtype).name} pages", exact=True)
    # Quantized pages change the numbers the attend reads, so equality
    # with generate is reported, not required.
    run("int8_block pages", exact=False, kv_dtype="int8_block")
    eng = run(f"speculate={cfg.speculate} (self-draft)", exact=True,
              speculate=cfg.speculate, draft_kv_dtype="model")
    _check(eng.spec_accept_rate == 1.0,
           f"self-speculation accept rate {eng.spec_accept_rate}, want 1.0")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _aot(label: str, fn: Callable, args, ctx: dict, on_tpu: bool,
         want_pallas: int):
    """Compile ``fn`` ahead of time, require ``want_pallas`` Mosaic calls
    in it on a TPU (compiled, not interpreted), run it, time both."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    ctx["compile_s"] += compile_s
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    _say("kernels", f"{label}: compile {compile_s:.1f}s, run "
                f"{(time.perf_counter() - t0) * 1e3:.1f} ms, "
                f"{calls} Pallas custom calls")
    if on_tpu:
        _check(calls == want_pallas,
               f"{label}: {calls} Pallas custom calls, want {want_pallas}")
    return out


def _close(label: str, got, want, tol: float) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _check(got.shape == want.shape, f"{label}: shape {got.shape} vs "
                                    f"{want.shape}")
    _check(bool(np.all(np.isfinite(got))), f"{label}: not finite")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want))) + 1e-6
    _check(err <= tol * scale,
           f"{label}: max |diff| {err:.4g} > {tol} x {scale:.4g}")


def phase_kernels(cfg: SmokeConfig, ctx: dict) -> None:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.core import state as _state
    from horovod_tpu.ops import batchnorm as bn
    from horovod_tpu.ops import flash_attention as fa

    on_tpu = _state.target_platform() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    b, t, h, hkv, d = cfg.attn_shape
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, t, h, d), dtype)
    k = jax.random.normal(kk, (b, t, hkv, d), dtype)
    v = jax.random.normal(kv, (b, t, hkv, d), dtype)
    g = jax.random.normal(kg, (b, t, h, d), dtype)
    # Three packed documents of unequal length.
    seg = jnp.asarray(np.repeat(
        np.arange(3), [t // 2, t // 4, t - t // 2 - t // 4])[None], jnp.int32)
    seg = jnp.broadcast_to(seg, (b, t))

    def fwd_bwd(attn):
        def f(q, k, v, g):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(g)
        return f

    cases = {
        "causal GQA": {},
        "segment ids": dict(q_segment_ids=seg, kv_segment_ids=seg),
        f"window={cfg.attn_window}": dict(window=cfg.attn_window),
    }
    for label, kw in cases.items():
        got = _aot(f"flash_attention fwd+bwd, {label}",
                   fwd_bwd(lambda q, k, v: fa.flash_attention(
                       q, k, v, True, **kw)),
                   (q, k, v, g), ctx, on_tpu, want_pallas=2)
        want = jax.jit(fwd_bwd(lambda q, k, v: fa.blockwise_attention(
            q, k, v, causal=True, **kw)))(q, k, v, g)
        for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
            _close(f"flash_attention {label} {name}", a, w, 3e-2)

    out, lse = _aot("flash_attention_lse",
                    lambda q, k, v: fa.flash_attention_lse(q, k, v, True),
                    (q, k, v), ctx, on_tpu, want_pallas=1)
    _close("flash_attention_lse out", out,
           fa.blockwise_attention(q, k, v, causal=True), 3e-2)
    kx = jnp.repeat(k, h // hkv, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kx) / d ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    _close("flash_attention_lse lse", lse,
           jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1), 2e-2)

    x = jax.random.normal(kq, cfg.bn_shape, dtype)
    dy = jax.random.normal(kk, cfg.bn_shape, dtype)
    xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    s1, s2 = _aot("channel_sums", bn.channel_sums, (x,), ctx,
                  on_tpu, want_pallas=1)
    _close("channel_sums sum(x)", s1, jnp.sum(xf, axes), 1e-2)
    _close("channel_sums sum(x^2)", s2, jnp.sum(xf * xf, axes), 1e-2)
    mean = jnp.mean(xf, axes)
    rstd = jax.lax.rsqrt(jnp.var(xf, axes) + 1e-5)
    sdy, sdx = _aot("channel_grad_sums", bn.channel_grad_sums,
                    (dy, x, mean, rstd), ctx, on_tpu, want_pallas=1)
    _close("channel_grad_sums sum(dy)", sdy, jnp.sum(dyf, axes), 1e-2)
    _close("channel_grad_sums sum(dy*xhat)", sdx,
           jnp.sum(dyf * (xf - mean) * rstd, axes), 1e-2)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

PHASES: tuple[tuple[str, Callable[[SmokeConfig, dict], None]], ...] = (
    ("collectives", phase_collectives),
    ("train", phase_train),
    ("profiler", phase_profiler),
    ("serve", phase_serve),
    ("kernels", phase_kernels),
)


def run_phases(cfg: SmokeConfig, phases=None) -> list[str]:
    """Run every phase in order; a phase that raises is recorded (with its
    traceback) and the rest still run, so one call reports everything that
    is broken. Returns the names of the phases that failed."""
    failed = []
    ctx: dict = {"compile_s": 0.0}
    for name, phase in (PHASES if phases is None else phases):
        t0 = time.perf_counter()
        try:
            phase(cfg, ctx)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            failed.append(name)
            _say(name, f"FAILED after {time.perf_counter() - t0:.1f}s")
        else:
            _say(name, f"ok in {time.perf_counter() - t0:.1f}s")
    _say("total", f"compile time inside the phases: "
                  f"{ctx['compile_s']:.1f}s")
    return failed


def main() -> int:
    t0 = time.perf_counter()
    try:
        device = gate()
    except Exception as e:
        print(f"chip_smoke: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    describe_installation()
    failed = run_phases(full_config())
    _say("total", f"{time.perf_counter() - t0:.1f}s wall")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
