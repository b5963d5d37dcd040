"""Allreduce bus-bandwidth benchmark — the framework's second north-star
metric (BASELINE.md: "allreduce bus bandwidth (GB/s) matching
NCCL-ring-equivalent on ICI").

The reference's transport never published an absolute number for this; the
NCCL convention is the comparison point: for an allreduce of S bytes over n
ranks, the "bus bandwidth" a ring algorithm needs is

    busbw = (2 * (n - 1) / n) * S / t

which makes numbers comparable across world sizes (nccl-tests convention).
Our allreduce lowers to XLA's psum over ICI, so this measures the whole
data plane: fusion-size sweep included, since Horovod's fusion threshold
exists exactly to keep collectives in the bandwidth-bound regime
(reference docs/tensor-fusion.md).

**Algorithm sweep** (``--algo flat rs_ag hierarchical auto``): re-times
each buffer size under each allreduce decomposition (ops/strategy.py) and
reports, per (size, algo):

* ``value`` — achieved ring-equivalent bus bandwidth (GB/s, logical
  bytes — the apples-to-apples number across algorithms);
* ``predicted_busbw_gbps`` / ``cost_model`` — the α–β cost model's
  prediction for the same (size, algo, topology) and whether the
  constants were analytic seeds or calibrated (utils/costs.py);
* ``collective_ops`` — per-opcode counts (``all-reduce`` /
  ``reduce-scatter`` / ``all-gather``) in the program's pre-optimization
  HLO: ``rs_ag`` must show one reduce-scatter + one all-gather per
  bucket at unchanged total collective count, ``hierarchical`` the
  two-level structure;
* ``chosen_algo`` — under ``auto``, what the cost model picked.

``hierarchical`` needs a multi-slice topology; on single-slice (or
simulated CPU) worlds set ``HOROVOD_TOPOLOGY_SLICES=N`` to exercise the
lowering, else the row reports itself skipped.

**Calibration** (``--calibrate``): times the flat algorithm across a size
sweep, fits the α–β line ``t(S) = α + ring·S/β`` by least squares, and
persists the constants (plus the resulting 90%-busbw fusion threshold and
the raw measurements) to the schema-versioned tuning cache
(``HOROVOD_TUNING_CACHE``, default ``~/.horovod_tpu/allreduce_tuning.json``
— utils/costs.py). ``HOROVOD_ALLREDUCE_ALGO=auto`` then selects from the
measured constants; a cache with an unknown schema version is ignored,
never misread.

**Compression sweep** (``--compression bf16 int8 int8_block int4``):
re-times each buffer size with the gradient-compression wire formats
(ops/compression.py) and reports wire bytes / effective + wire busbw /
collective counts / measured max abs error vs the fp32 exchange per
(size, compression) — see docs/benchmarks.md for the column legend.
``int4`` rows show the packed-nibble 12.5% wire; block formats carry
their per-block scale exchange in the collective counts.

**Channel sweep** (``--channels 1 2 4``): re-times each buffer size with
the bucket split into N concurrent channel instances (ops/strategy.py
channelized lowerings — bit-exact at any count) and reports busbw, the
per-channel α–β cost-model prediction, and per-opcode HLO collective
counts per channel count (a channels=2 flat row shows exactly 2
all-reduces). Channelized flat rows feed the recalibration loop's
per-level channel-efficiency fit.

**Exchange-schedule A/B** (``--schedule enum priority``): times a fused
multi-leaf gradient exchange per whole-step schedule (ops/exchange.py)
against a no-comm baseline of identical compute, so each row carries a
MEASURED ``exposed_comm_ms`` (non-overlapped communication per step) plus
the committed plan's ``exchange_schedule_hash``. ``--smoke`` runs a
sub-minute version of the size sweep + schedule A/B for CI. Flat
uncompressed rows also feed the always-on α–β recalibration loop
(``HOROVOD_RECALIBRATION``, ops/exchange.py) — the bench doubles as a
live-machine calibration source.

Methodology as in bench.py / fa_bench.py: steps chained inside one
compiled scan, scalar-only host transfer, per-step inputs perturbed so XLA
cannot CSE the collectives away.

Run on any world: a real pod slice (one process per host), or the
simulated mesh (HOROVOD_CPU_DEVICES=8 — numbers then reflect host memory
bandwidth, useful only to validate the harness; CPU XLA also widens the
bf16 wire back to fp32 inside its backend, so wire_bytes is the TPU
truth, not a CPU measurement). A 1-chip world has no inter-device
traffic; the tool says so and exits.

Prints ONE JSON line per (buffer size, compression/algo):
{"metric": "allreduce_busbw", "bytes": S, "value": GB/s, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvd
from horovod_tpu.ops import compression as _compression
from horovod_tpu.ops import exchange as _exchange
from horovod_tpu.ops import strategy as _strategy
from horovod_tpu.ops import topology as _topology
from horovod_tpu.utils import costs as _costs
from horovod_tpu.utils import env as _envmod

STEPS = 10
CALIBRATE_SIZES_MB = [0.0625, 0.25, 1, 4, 16, 64]
SMOKE_SIZES_MB = [0.0625, 0.25]
SPARSE_DENSITIES = [0.01, 0.05, 0.25]
_COLLECTIVE_OPCODES = (" all-reduce(", " reduce-scatter(", " all-gather(",
                       " all-to-all(")


def _comp_arg(name: str):
    """None for the uncompressed baseline path, else the spec string."""
    return None if name == "none" else name


def count_collective_ops(nbytes: int, compression: str,
                         algo: str = "flat",
                         channels: int = 1) -> dict | None:
    """Per-opcode collective counts in the pre-optimization HLO of ONE
    allreduce step under (``compression``, ``algo``, ``channels``) — the
    collective-count evidence that neither knob fragments the fusion
    structure (bf16: unchanged; int8: +1 scalar pmax per bucket for the
    scale; rs_ag: the all-reduce becomes one reduce-scatter + one
    all-gather; hierarchical: RS + AR + AG; channels=C: C instances of
    the decomposition's shape, the channelized lowering's signature)."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.core import context as _ctx
    from horovod_tpu.core.state import AXIS_NAME

    grp = hvd.get_group(0)
    comp = _comp_arg(compression)

    def shard_fn(x):
        with _ctx.enter(AXIS_NAME, 0):
            out = hvd.allreduce(x[0], average=False, compression=comp,
                                algo=algo, channels=channels,
                                name="bench_payload")
        return out[None]

    jitted = jax.jit(jax.shard_map(
        shard_fn, mesh=grp.mesh, in_specs=P(AXIS_NAME),
        out_specs=P(AXIS_NAME), check_vma=False))
    x = jax.ShapeDtypeStruct((grp.size, nbytes // 4), jnp.float32)
    try:
        txt = jitted.lower(x).as_text(dialect="hlo")
    except Exception:
        return None
    return {op.strip(" ("): txt.count(op) for op in _COLLECTIVE_OPCODES}


def measure_compression_error(nbytes: int, compression: str,
                              algo: str = "flat") -> float:
    """Measured max abs error of one compressed allreduce-average vs the
    exact fp32 exchange of the same data — the lossy-path evidence column
    (bounded-error tests pin the same quantity in CI; the bench reports
    it per size so regressions show in artifacts, not just asserts)."""
    n = nbytes // 4
    x = (jnp.arange(n, dtype=jnp.float32) / n) * 2.0 - 1.0

    exact = hvd.spmd(lambda v: hvd.allreduce(v, average=True))
    comp = hvd.spmd(lambda v: hvd.allreduce(v, average=True,
                                            compression=compression,
                                            algo=algo))
    xs = hvd.replicate(x)
    a = np.asarray(exact(xs))[0]
    b = np.asarray(comp(xs))[0]
    return float(np.max(np.abs(a - b)))


def bench_size(nbytes: int, world: int, compression: str = "none",
               algo: str = "flat", trials: int = 3,
               channels: int = 1) -> dict:
    n = nbytes // 4                       # fp32 elements
    x = jnp.arange(n, dtype=jnp.float32) / n
    comp = _comp_arg(compression)

    def step_fn(x, seed):
        def body(carry, i):
            y = hvd.allreduce(carry * (1.0 + 1e-6 * i), average=False,
                              compression=comp, algo=algo,
                              channels=channels)
            # Keep magnitudes stable so the loop can run forever.
            return y / world, ()
        out, _ = jax.lax.scan(body, x * seed, jnp.arange(STEPS))
        return jnp.sum(out)

    step = hvd.spmd(step_fn)
    xs = hvd.replicate(x)
    seed = hvd.replicate(jnp.float32(1.0))
    out = step(xs, seed)
    float(np.asarray(out)[0])             # compile + settle
    best = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        out = step(xs, seed)
        float(np.asarray(out)[0])
        best = min(best, (time.perf_counter() - t0) / STEPS)
    busbw = 2 * (world - 1) / world * nbytes / best
    # Always-on recalibration (ops/exchange.py): every measured row is a
    # free α–β sample — the bench IS a source of the live-machine fit.
    # Channelized flat rows feed the per-level channel-efficiency fit
    # instead (their wall time is a concurrent-instances observation,
    # not one collective's t(S)).
    if compression == "none" and algo == "flat" \
            and _envmod.recalibration_enabled():
        topo = _topology.discover(hvd.get_group(0))
        level = "dcn" if topo.multi_slice else "ici"
        if channels > 1:
            _exchange.recalibrator().observe_channels(
                level, channels, nbytes, best, world)
        else:
            _exchange.recalibrator().observe(level, nbytes, best, world)
        _exchange.recalibrator().maybe_persist(topo)
    result = {
        "metric": "allreduce_busbw",
        "bytes": nbytes,
        "value": round(busbw / 1e9, 2),
        "unit": "GB/s",
        "algbw_gbps": round(nbytes / best / 1e9, 2),
        "time_us": round(best * 1e6, 1),
        "world": world,
        "backend": jax.default_backend(),
    }
    if channels != 1:
        result["channels"] = channels
    if algo != "flat":
        result["algo"] = algo
        if algo == "auto":
            topo = _topology.discover(hvd.get_group(0))
            model = _costs.model_for(topo)
            result["chosen_algo"] = model.choose(nbytes, topo)
    if compression != "none":
        compressor = _compression.resolve(compression)
        wire = _compression.wire_bytes(n, np.float32, compressor,
                                       sum_width=world)
        result.update({
            "compression": compression,
            "wire_bytes": wire,
            "wire_fraction": round(wire / nbytes, 4),
            # value (above) is the EFFECTIVE busbw on logical bytes;
            # this is the rate on the bytes the wire physically carries.
            "wire_busbw_gbps": round(
                2 * (world - 1) / world * wire / best / 1e9, 2),
            "max_abs_err_vs_fp32": round(
                measure_compression_error(nbytes, compression, algo), 6),
        })
    ops = count_collective_ops(nbytes, compression, algo,
                               channels=channels)
    if ops is not None:
        if algo == "flat" and channels == 1:
            # Back-compat with earlier rounds' field name: every flat row
            # (incl. the compression sweep, whose docs/benchmarks.md table
            # documents this column) keeps the plain all-reduce count.
            result["allreduce_ops"] = ops["all-reduce"]
        result["collective_ops"] = ops
    return result


def sparse_workload(world: int, rows: int, dim: int, rows_per_rank: int,
                    seed: int = 17):
    """The shared sparse-exchange workload: Zipf-hot per-rank indices
    (duplicate hot rows across ranks are the common case the
    dedup-and-merge exists for) + fp32 value blocks. One builder for
    this sweep AND bench.py's ``embedding_grad_*`` fields, so the two
    tools can never measure different workload shapes."""
    rng = np.random.RandomState(seed)
    idx = np.stack([(rng.zipf(1.3, rows_per_rank) - 1) % rows
                    for _ in range(world)]).astype(np.int32)
    vals = rng.randn(world, rows_per_rank, dim).astype(np.float32)
    return vals, idx


def make_sparse_step(algo: str, rows: int, dim: int, steps: int,
                     name_prefix: str = "sparse_ab"):
    """The shared spmd A/B step: ``steps`` chained sparse exchanges with
    perturbed inputs (no CSE) whose merged values feed a scalar
    accumulator (nothing dead-code-eliminated)."""
    def step_fn(v, i, acc):
        def body(carry, k):
            vv, a = carry
            s = hvd.IndexedSlices(vv * (1.0 + 1e-6 * k), i, (rows, dim))
            o = hvd.allreduce_indexed_slices(
                s, average=True, algo=algo,
                name=f"{name_prefix}_{algo}")
            return (vv, a + jnp.sum(o.values)), ()

        (vv, a), _ = jax.lax.scan(body, (v, acc), jnp.arange(steps))
        return a

    return hvd.spmd(step_fn)


def sparse_wire_accounting(world: int, rows: int, dim: int,
                           rows_per_rank: int) -> dict:
    """Deterministic byte accounting of the sparse-vs-dense A/B (the
    acceptance gate's ratio): ``recv_bytes`` is the gather payload
    received per rank per step (value + index blocks from each peer),
    ``ring_bytes`` the dense flat allreduce's ring-equivalent bytes
    (the full logical table on 1-rank worlds, where there is no ring)."""
    row_bytes = dim * 4 + 4                       # fp32 row + int32 index
    recv = max(1, world - 1) * rows_per_rank * row_bytes
    dense_bytes = rows * dim * 4
    ring = (2 * (world - 1) / world * dense_bytes if world > 1
            else dense_bytes)
    return {
        "row_bytes": row_bytes,
        "recv_bytes": recv,
        "dense_bytes": dense_bytes,
        "ring_bytes": ring,
        "bytes_ratio": round(recv / ring, 4),
        "density": round(world * rows_per_rank / rows, 4),
    }


def bench_sparse(density: float, world: int, rows: int = 1 << 14,
                 dim: int = 64, trials: int = 3,
                 steps: int = STEPS) -> dict:
    """One sparse-exchange A/B row for the ``--sparse`` density sweep
    (ops/sparse.py): a ``rows x dim`` fp32 embedding table whose
    per-rank gradient touches ``density·rows/world`` Zipf-hot rows,
    timed through the padded-gather + dedup-and-merge lowering AND the
    densify+allreduce fallback, with the α–β cost model's predictions
    (``predicted_sparse_us``/``predicted_dense_us``), its
    ``predicted_algo`` auto-choice, and the recalibratable
    ``crossover_density`` alongside — measured vs model in one row."""
    C = max(1, int(density * rows) // max(1, world))
    vals, idx = sparse_workload(world, rows, dim, C)

    times = {}
    for algo in ("gather", "dense"):
        step = make_sparse_step(algo, rows, dim, steps,
                                name_prefix="sparse_sweep")
        acc = hvd.replicate(jnp.float32(0.0))
        out = step(vals, idx, acc)
        float(np.asarray(out)[0])  # compile + settle
        best = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            out = step(vals, idx, acc)
            float(np.asarray(out)[0])
            best = min(best, (time.perf_counter() - t0) / steps)
        times[algo] = best
    acct = sparse_wire_accounting(world, rows, dim, C)
    topo = _topology.discover(hvd.get_group(0))
    model = _costs.model_for(topo)
    pred_sparse = model.predict_sparse_gather_us(C * acct["row_bytes"],
                                                 topo)
    pred_dense = model.predict_us("flat", acct["dense_bytes"], topo)
    return {
        "metric": "sparse_exchange",
        "density": acct["density"],
        "rows_per_rank": C,
        "dense_rows": rows,
        "dim": dim,
        "value": round(acct["recv_bytes"] / times["gather"] / 1e9, 3),
        "unit": "GB/s",
        "sparse_time_us": round(times["gather"] * 1e6, 1),
        "dense_time_us": round(times["dense"] * 1e6, 1),
        "bytes_ratio": acct["bytes_ratio"],
        "predicted_sparse_us": round(pred_sparse, 1),
        "predicted_dense_us": round(pred_dense, 1),
        "predicted_algo": model.choose_sparse(
            rows_per_rank=C, row_bytes=acct["row_bytes"],
            dense_nbytes=acct["dense_bytes"], dense_rows=rows, topo=topo,
            density_threshold=_envmod.sparse_density_threshold()),
        "crossover_density": round(
            model.sparse_crossover_density(acct["row_bytes"], rows,
                                           dim * 4, topo), 4),
        "cost_model": model.source,
        "world": world,
        "backend": jax.default_backend(),
    }


def sweep_sparse(densities, world, trials: int = 3,
                 steps: int = STEPS, rows: int = 1 << 14,
                 dim: int = 64) -> None:
    for d in densities:
        if not 0 < d <= 1:
            raise SystemExit(
                f"--sparse densities must be in (0, 1], got {d}")
        print(json.dumps(bench_sparse(d, world, rows=rows, dim=dim,
                                      trials=trials, steps=steps)))


def bench_exchange(mode: str | None, world: int, nleaves: int = 12,
                   base_elems: int = 4096, threshold: int = 1 << 16,
                   trials: int = 3, steps: int = STEPS) -> dict:
    """Time one fused multi-leaf gradient exchange per step under a
    whole-step schedule (ops/exchange.py) — the A/B harness behind
    ``--schedule enum priority``. ``mode=None`` runs the NO-COMM
    baseline (identical compute, exchange skipped), so
    ``exposed_comm_ms = t(mode) − t(None)`` is a *measured*
    non-overlapped-communication number on any backend."""
    sizes = [base_elems * (1 + (i % 3)) for i in range(nleaves)]
    grads = {f"w{i:02d}": jnp.arange(n, dtype=jnp.float32) / n
             for i, n in enumerate(sizes)}

    def step_fn(grads, seed):
        def body(carry, i):
            g = {k: v * (1.0 + 1e-6 * i) for k, v in carry.items()}
            if mode is not None:
                g = hvd.allreduce_gradients(
                    g, fusion_threshold=threshold, schedule=mode)
            return g, ()
        out, _ = jax.lax.scan(body, jax.tree.map(lambda v: v * seed,
                                                 grads), jnp.arange(steps))
        return sum(jnp.sum(v) for v in out.values())

    step = hvd.spmd(step_fn)
    gs = hvd.replicate(grads)
    seed = hvd.replicate(jnp.float32(1.0))
    out = step(gs, seed)
    float(np.asarray(out)[0])  # compile + settle
    best = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        out = step(gs, seed)
        float(np.asarray(out)[0])
        best = min(best, (time.perf_counter() - t0) / steps)
    result = {
        "metric": "exchange_step",
        "schedule": mode or "none",
        "time_us": round(best * 1e6, 1),
        "leaves": nleaves,
        "grad_bytes": sum(sizes) * 4,
        "world": world,
        "backend": jax.default_backend(),
    }
    if mode is not None:
        plan = _exchange.last_plan()
        if plan is not None:
            result["exchange_schedule_hash"] = plan.plan_hash()
            result["buckets"] = len(plan.buckets)
    return result


def sweep_exchange(modes, world, trials: int = 3, steps: int = STEPS,
                   nleaves: int = 12) -> None:
    """The ``--schedule`` A/B: no-comm baseline first, then each mode
    with its measured exposed communication per step."""
    base = bench_exchange(None, world, trials=trials, steps=steps,
                          nleaves=nleaves)
    print(json.dumps(base))
    for mode in modes:
        row = bench_exchange(mode, world, trials=trials, steps=steps,
                             nleaves=nleaves)
        row["exposed_comm_ms"] = round(
            max(0.0, (row["time_us"] - base["time_us"]) / 1e3), 3)
        print(json.dumps(row))


def _predicted(result: dict, topo, model) -> dict:
    """Attach the cost model's view to a measured row."""
    algo = result.get("chosen_algo", result.get("algo", "flat"))
    t_us = model.predict_us(algo, result["bytes"], topo,
                            channels=result.get("channels", 1))
    if t_us and t_us != float("inf"):
        n = topo.group_size
        pred = 2 * (n - 1) / n * result["bytes"] / (t_us * 1e-6)
        result["predicted_busbw_gbps"] = round(pred / 1e9, 2)
        result["cost_model"] = model.source
    return result


def calibrate(sizes_mb, trials: int = 3) -> None:
    """Fit α–β from a flat-algorithm size sweep; persist the tuning cache.

    Least squares on ``t(S) = α + ring·S/β``: the intercept is the
    per-collective latency, the slope the inverse bus bandwidth. The
    measured level is the flat ring's bottleneck link — ICI on a
    single-slice world, DCN when the ring crosses slices — so the cache
    only overwrites the constants this world can actually see."""
    world = hvd.size()
    topo = _topology.discover(hvd.get_group(0))
    rows, ts, ss = [], [], []
    for mb in sizes_mb:
        nbytes = int(mb * 2 ** 20)
        row = bench_size(nbytes, world, trials=trials)
        rows.append(row)
        print(json.dumps(row))
        ss.append(nbytes)
        ts.append(row["time_us"] * 1e-6)
    ring = 2 * (world - 1) / world
    slope, intercept = np.polyfit(np.asarray(ss, np.float64),
                                  np.asarray(ts, np.float64), 1)
    # A tiny-sweep fit can go degenerate (negative intercept on a noisy
    # host); clamp to physical values rather than poisoning the cache.
    alpha_us = max(float(intercept) * 1e6, 0.1)
    gbps = max(ring / max(float(slope), 1e-15) / 1e9, 0.01)
    level = "dcn" if topo.multi_slice else "ici"
    constants = {level: {"alpha_us": round(alpha_us, 2),
                         "gbps": round(gbps, 3)}}
    model = _costs.model_from_constants(constants, topo)
    path = _costs.save_tuning_cache(
        constants, device_kind=topo.device_kind, world=world,
        fusion_threshold=model.fusion_threshold_bytes(topo),
        measured=[{"bytes": r["bytes"], "time_us": r["time_us"],
                   "busbw_gbps": r["value"]} for r in rows])
    print(json.dumps({
        "metric": "allreduce_calibration",
        "path": path,
        "schema": _costs.SCHEMA,
        "level": level,
        "alpha_us": round(alpha_us, 2),
        "busbw_gbps": round(gbps, 3),
        "fusion_threshold": model.fusion_threshold_bytes(topo),
        "world": world,
        "backend": jax.default_backend(),
    }))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes-mb", type=float, nargs="*",
                        default=[1, 4, 16, 64])
    parser.add_argument("--compression", nargs="*", default=[],
                        choices=["none", "bf16", "int8", "int8_block",
                                 "int4"],
                        help="extra wire formats to sweep after the fp32 "
                             "baseline of each size (ops/compression.py; "
                             "int8_block/int4 are the block-scale "
                             "formats, int4 nibble-packed at 12.5% wire)")
    parser.add_argument("--algo", nargs="*", default=[],
                        choices=["flat", "rs_ag", "hierarchical", "auto"],
                        help="extra allreduce decompositions to sweep "
                             "after the flat baseline of each size "
                             "(ops/strategy.py); hierarchical needs a "
                             "multi-slice topology or "
                             "HOROVOD_TOPOLOGY_SLICES=N")
    parser.add_argument("--channels", nargs="*", type=int, default=[],
                        help="channel counts to A/B after each size's "
                             "single-channel baseline (e.g. --channels "
                             "1 2 4): each bucket splits into that many "
                             "concurrent channel instances "
                             "(ops/strategy.py channelized lowerings; "
                             "bit-exact at any count). Rows report "
                             "busbw + the per-channel cost-model "
                             "prediction + per-opcode HLO collective "
                             "counts per channel count")
    parser.add_argument("--calibrate", action="store_true",
                        help="fit the α–β cost model from a flat size "
                             "sweep and write the schema-versioned tuning "
                             "cache (HOROVOD_TUNING_CACHE)")
    parser.add_argument("--schedule", nargs="*", default=[],
                        choices=["enum", "priority"],
                        help="whole-step exchange schedules to A/B on a "
                             "fused multi-leaf gradient exchange "
                             "(ops/exchange.py); each row reports the "
                             "measured exposed (non-overlapped) "
                             "communication per step vs a no-comm "
                             "baseline")
    parser.add_argument("--sparse", nargs="*", type=float, default=None,
                        metavar="DENSITY",
                        help="sparse-exchange density sweep "
                             "(ops/sparse.py): for each density, A/B the "
                             "padded-gather + dedup-and-merge lowering "
                             "against densify+allreduce on a 16k x 64 "
                             "fp32 table, with cost-model predictions "
                             "and the recalibratable crossover density "
                             "per row. No values = "
                             f"{SPARSE_DENSITIES}")
    parser.add_argument("--smoke", action="store_true",
                        help="sub-minute CI path: tiny flat size sweep "
                             "(+ one channelized row) + one sparse A/B "
                             "row + enum/priority schedule A/B at "
                             "reduced steps/trials (the workflow gate)")
    args = parser.parse_args()

    _envmod.use_compile_cache()
    hvd.init()
    world = hvd.size()
    if world < 2:
        print(json.dumps({"metric": "allreduce_busbw", "value": None,
                          "note": "world size 1: allreduce is a no-op; "
                                  "run on a multi-device mesh"}))
        return
    if args.smoke:
        topo = _topology.discover(hvd.get_group(0))
        model = _costs.model_for(topo)
        for mb in SMOKE_SIZES_MB:
            print(json.dumps(_predicted(
                bench_size(int(mb * 2 ** 20), world, trials=1),
                topo, model)))
        # One channelized row (the CI examples job's multi-channel
        # signal): the largest smoke size at 2 channels.
        print(json.dumps(_predicted(
            bench_size(int(SMOKE_SIZES_MB[-1] * 2 ** 20), world,
                       trials=1, channels=2), topo, model)))
        # One sparse A/B row (the CI examples job's sparse-exchange
        # signal): a low-density point where the gather must win on
        # bytes (the acceptance operating point).
        print(json.dumps(bench_sparse(0.05, world, rows=4096, dim=16,
                                      trials=1, steps=5)))
        sweep_exchange(["enum", "priority"], world, trials=1, steps=5,
                       nleaves=8)
        _flush_recalibration()
        return
    if args.calibrate:
        calibrate(CALIBRATE_SIZES_MB)
        return
    if args.schedule:
        # A schedule-only invocation is its own mode (the --calibrate /
        # --smoke convention): don't fall through into minutes of the
        # default size sweep nobody asked for.
        sweep_exchange(args.schedule, world)
        _flush_recalibration()
        return
    if args.sparse is not None:
        # Sparse-only invocation: its own mode, same convention.
        sweep_sparse(args.sparse or SPARSE_DENSITIES, world)
        _flush_recalibration()
        return
    comp_sweep = [c for c in args.compression if c != "none"]
    algo_sweep = [a for a in args.algo if a != "flat"]
    chan_sweep = [c for c in args.channels if c != 1]
    for c in chan_sweep:
        if c < 1:
            raise SystemExit(f"--channels values must be >= 1, got {c}")
    topo = _topology.discover(hvd.get_group(0))
    model = _costs.model_for(topo)
    for mb in args.sizes_mb:
        nbytes = int(mb * 2 ** 20)
        base = _predicted(bench_size(nbytes, world), topo, model)
        print(json.dumps(base))
        for comp in comp_sweep:
            row = bench_size(nbytes, world, compression=comp)
            row["speedup_vs_none"] = round(
                base["time_us"] / row["time_us"], 3)
            print(json.dumps(row))
        for algo in algo_sweep:
            try:
                row = bench_size(nbytes, world, algo=algo)
            except hvd.HorovodError as e:
                print(json.dumps({
                    "metric": "allreduce_busbw", "bytes": nbytes,
                    "algo": algo, "value": None,
                    "note": f"skipped: {e}"}))
                continue
            row["speedup_vs_flat"] = round(
                base["time_us"] / row["time_us"], 3)
            print(json.dumps(_predicted(row, topo, model)))
        for ch in chan_sweep:
            row = bench_size(nbytes, world, channels=ch)
            row["speedup_vs_1ch"] = round(
                base["time_us"] / row["time_us"], 3)
            print(json.dumps(_predicted(row, topo, model)))
    _flush_recalibration()


def _flush_recalibration() -> None:
    """End-of-run recalibration flush: short sweeps (fewer rows than the
    Recalibrator's periodic persist threshold) still land their α–β
    samples in the tuning cache. No-op when the fit is degenerate or
    HOROVOD_RECALIBRATION=0."""
    if not _envmod.recalibration_enabled():
        return
    topo = _topology.discover(hvd.get_group(0))
    if _exchange.recalibrator().maybe_persist(topo, force=True):
        print(json.dumps({
            "metric": "allreduce_recalibration",
            "path": _envmod.tuning_cache_path(),
            "schema": _costs.SCHEMA,
            "constants": _exchange.recalibrator().constants(),
        }))


if __name__ == "__main__":
    main()
