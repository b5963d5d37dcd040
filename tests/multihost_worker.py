"""Multi-host worker: one process per 'host', 4 CPU devices each.

Run by tests/test_multihost.py as ``python multihost_worker.py <pid> <nprocs>
<port>``. Exercises the cross-process control plane the reference builds out
of MPI point-to-point messaging (mpi_ops.cc:1464-1733): eager collective
matrix, mismatch errors, schedule validation, stall warnings, checkpoint
resume. Prints ``ALL SUBTESTS PASSED`` on success.
"""

import os
import sys
import time

PID = int(sys.argv[1])
NPROCS = int(sys.argv[2])
PORT = int(sys.argv[3])
TMPDIR = sys.argv[4]
DEVS = int(os.environ.get("HOROVOD_TEST_DEVS_PER_PROC", "4"))

os.environ.setdefault("HOROVOD_STALL_CHECK_TIME", "2")

import jax  # noqa: E402

# jax_num_cpu_devices beats any device-count XLA_FLAGS the environment
# carries (CI exports an 8-device one for the in-process suite).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.config.update("jax_num_cpu_devices", DEVS)

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.utils.distributed import init_distributed  # noqa: E402


def log(msg):
    print(f"[p{PID}] {msg}", flush=True)


def expect_error(fn, substr):
    try:
        fn()
    except hvd.HorovodError as e:
        assert substr in str(e), f"error {e!r} lacks {substr!r}"
        return str(e)
    raise AssertionError(f"expected HorovodError containing {substr!r}")


def main():
    init_distributed(coordinator_address=f"localhost:{PORT}",
                     num_processes=NPROCS, process_id=PID)
    assert jax.process_count() == NPROCS

    # --- rank/size surface (reference mpi_ops_test.py:71-83) --------------
    world = hvd.global_size()
    nloc = hvd.local_size()
    assert world == 4 * NPROCS, world
    assert nloc == 4, nloc
    assert hvd.rank() == PID * 4, hvd.rank()
    assert hvd.local_rank() == 0
    lranks = hvd.get_group(0).local_member_ranks()
    assert list(lranks) == list(range(PID * 4, PID * 4 + 4))
    log("rank/size OK")

    # --- eager allreduce: sum of all global ranks -------------------------
    vals = [np.full((3,), float(r), np.float32) for r in lranks]
    outs = hvd.allreduce(vals, average=False)
    want = sum(range(world))
    assert len(outs) == nloc
    for o in outs:
        np.testing.assert_allclose(np.asarray(o), want)
    log("eager allreduce OK")

    # --- eager broadcast from a root on the OTHER process -----------------
    root = 5  # lives on p1
    vals = [np.full((2, 2), float(r), np.float32) for r in lranks]
    outs = hvd.broadcast(vals, root_rank=root)
    for o in outs:
        np.testing.assert_allclose(np.asarray(o), float(root))
    log("eager broadcast OK")

    # --- eager allgather with variable first dims -------------------------
    vals = [np.full((r + 1, 2), float(r), np.float32) for r in lranks]
    gathered = hvd.allgather(vals)
    assert gathered.shape == (sum(r + 1 for r in range(world)), 2)
    row = 0
    for r in range(world):
        np.testing.assert_allclose(np.asarray(gathered[row:row + r + 1]),
                                   float(r))
        row += r + 1
    log("eager allgather OK")

    # --- eager gather: root row gets concat, others keep input ------------
    vals = [np.full((2,), float(r), np.float32) for r in lranks]
    outs = hvd.gather(vals, root_rank=0)
    for j, r in enumerate(lranks):
        if r == 0:
            assert outs[j].shape == (2 * world,)
        else:
            np.testing.assert_allclose(np.asarray(outs[j]), float(r))
    log("eager gather OK")

    # --- eager reducescatter (sum + scatter across processes) -------------
    vals = [np.arange(world * 2, dtype=np.float32) + r for r in lranks]
    outs = hvd.reducescatter(vals, name="rs_eager")
    total = np.arange(world * 2, dtype=np.float32) * world + sum(range(world))
    for j, r in enumerate(lranks):
        np.testing.assert_allclose(np.asarray(outs[j]),
                                   total[2 * r:2 * r + 2])
    log("eager reducescatter OK")

    # --- eager alltoall (device collective across processes) --------------
    vals = [np.arange(world, dtype=np.float32) + 100 * r for r in lranks]
    outs = hvd.alltoall(vals, name="a2a_eager")
    for j, r in enumerate(lranks):
        want = np.asarray([100 * src + r for src in range(world)], np.float32)
        np.testing.assert_allclose(np.asarray(outs[j]), want)
    log("eager alltoall OK")

    # --- steady-state verdict cache (VERDICT r4 #5) -----------------------
    # A named eager collective re-issued with identical metadata must
    # replay its validated verdict without touching the KV store; with
    # HOROVOD_EAGER_CACHE=0 every call renegotiates. Both modes must give
    # identical results; the measured per-call overhead drop is printed
    # for docs/benchmarks.md.
    from horovod_tpu.core import multihost as _mh

    iters = 30
    vals = [np.full((4,), float(r), np.float32) for r in lranks]
    want_sum = float(sum(range(world))) * 1.0

    jax.block_until_ready(
        hvd.allreduce(vals, name="steady", average=False))  # validate+cache
    neg = _mh.negotiator()
    assert any(fp[0] == "steady" for fp in neg._verdicts), "verdict not cached"
    t0 = time.perf_counter()
    for _ in range(iters):
        # Force each call: un-synced floods of cross-process dispatches
        # wedge the gloo CPU backend (both loops pay the same execution
        # cost, so the cached < uncached comparison is undisturbed).
        outs = jax.block_until_ready(
            hvd.allreduce(vals, name="steady", average=False))
    cached_s = (time.perf_counter() - t0) / iters
    np.testing.assert_allclose(np.asarray(outs[0]), want_sum)

    os.environ["HOROVOD_EAGER_CACHE"] = "0"
    try:
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = jax.block_until_ready(
                hvd.allreduce(vals, name="steady", average=False))
        uncached_s = (time.perf_counter() - t0) / iters
    finally:
        os.environ.pop("HOROVOD_EAGER_CACHE", None)
    np.testing.assert_allclose(np.asarray(outs[0]), want_sum)
    assert cached_s < uncached_s, (cached_s, uncached_s)
    log(f"eager verdict cache OK ({uncached_s * 1e3:.2f} ms/call "
        f"renegotiated -> {cached_s * 1e3:.2f} ms/call cached, "
        f"{uncached_s / cached_s:.1f}x)")

    # --- cross-process mismatch errors (mpi_ops_test.py:284-356) ----------
    dt = np.float32 if PID == 0 else np.int32
    msg = expect_error(
        lambda: hvd.allreduce([np.zeros((2,), dt)] * nloc, name="mm_dtype"),
        "Mismatched data types")
    log(f"dtype mismatch error OK: {msg[:60]}...")

    shape = (2,) if PID == 0 else (3,)
    expect_error(
        lambda: hvd.allreduce([np.zeros(shape, np.float32)] * nloc,
                              name="mm_shape", average=False),
        "Mismatched allreduce tensor shapes")
    log("shape mismatch error OK")

    rootpick = 0 if PID == 0 else 1
    expect_error(
        lambda: hvd.broadcast([np.zeros((2,), np.float32)] * nloc,
                              root_rank=rootpick, name="mm_root"),
        "Mismatched broadcast root ranks")
    log("root mismatch error OK")

    # --- stall warning: p1 delays its submission (mpi_ops.cc:1369-1412) ---
    if PID == 1:
        time.sleep(4.5)
    outs = hvd.allreduce([np.ones((1,), np.float32)] * nloc, name="slowpoke",
                         average=False)
    np.testing.assert_allclose(np.asarray(outs[0]), world)
    log("stall path completed OK")

    # --- compiled DP training step over both processes --------------------
    import optax

    wdim = 4

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = hvd.DistributedOptimizer(optax.sgd(0.05))

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, hvd.allreduce(loss, name="step_loss")

    sstep = hvd.spmd(step)
    rng = np.random.RandomState(0)  # same on both processes
    params0 = {"w": rng.randn(wdim, 2).astype(np.float32)}
    import optax as _ox

    params = hvd.replicate(params0)
    opt_state = hvd.replicate(_ox.sgd(0.05).init(params0))
    data = rng.randn(world, 8, wdim).astype(np.float32)
    target = rng.randn(world, 8, 2).astype(np.float32)
    batch_x = hvd.rank_stack([data[r] for r in lranks])
    batch_y = hvd.rank_stack([target[r] for r in lranks])
    losses = []
    for i in range(10):
        params, opt_state, loss = sstep(params, opt_state, (batch_x, batch_y))
        row = hvd.local_values(loss)[0]
        losses.append(float(np.asarray(row)))
    assert losses[-1] < losses[0], losses
    rows = hvd.local_values(params)
    for r in rows[1:]:
        np.testing.assert_allclose(r["w"], rows[0]["w"], rtol=1e-6)
    log(f"spmd train step OK ({losses[0]:.4f} -> {losses[-1]:.4f})")

    # --- ZeRO-1 sharded optimizer across processes ------------------------
    # reduce-scatter + allgather both cross the process boundary; parity
    # standard: identical params to the unsharded run above after the same
    # schedule (elementwise inner optimizer => exact).
    zopt = hvd.DistributedOptimizer(optax.sgd(0.05), sharded=True)

    def zstep(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = zopt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, hvd.allreduce(loss, name="zstep_loss")

    zs = hvd.spmd(zstep)
    zparams = hvd.replicate(params0)
    zstate = hvd.replicate(zopt.init(params0))
    for i in range(10):
        zparams, zstate, zloss = zs(zparams, zstate, (batch_x, batch_y))
        np.asarray(hvd.local_values(zloss)[0])  # force (gloo flood wedge)
    zrows = hvd.local_values(zparams)
    np.testing.assert_allclose(zrows[0]["w"], rows[0]["w"], rtol=1e-5,
                               atol=1e-6)
    log("ZeRO-1 cross-process parity OK")

    # --- sequence parallelism across processes ----------------------------
    # Ring attention over the full 8-device world: the K/V ring's ppermute
    # hops cross the process boundary (the DCN analog), which the
    # reference's single-transport MPI design never distinguishes — nor do
    # we. Output must equal full attention over the concatenated sequence.
    b, h, d = 1, 2, 8
    t_local = 2
    t_total = t_local * world
    rng_sp = np.random.RandomState(7)  # identical on both processes
    q = rng_sp.randn(b, t_total, h, d).astype(np.float32) * 0.5
    k = rng_sp.randn(b, t_total, h, d).astype(np.float32) * 0.5
    v = rng_sp.randn(b, t_total, h, d).astype(np.float32) * 0.5

    @hvd.spmd
    def ringf(qs, ks, vs):
        return hvd.ring_attention(qs, ks, vs, causal=True, impl="blockwise")

    shard = lambda x, r: x[:, r * t_local:(r + 1) * t_local]
    qs = hvd.rank_stack([shard(q, r) for r in lranks])
    ks = hvd.rank_stack([shard(k, r) for r in lranks])
    vs = hvd.rank_stack([shard(v, r) for r in lranks])
    out_rows = hvd.local_values(ringf(qs, ks, vs))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t_total, t_total), bool))[None, None],
                 s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v)
    for j, r in enumerate(lranks):
        np.testing.assert_allclose(np.asarray(out_rows[j]),
                                   shard(want, r), atol=3e-2, rtol=3e-2)
    log("cross-process ring attention OK")

    # --- schedule-divergence detection ------------------------------------
    nm = "diverge_a" if PID == 0 else "diverge_b"

    @hvd.spmd
    def bad(x):
        return hvd.allreduce(x, name=nm)

    expect_error(lambda: bad(jnp.ones((world, 2))),
                 "Mismatched collective schedules")
    log("schedule divergence error OK")

    # --- checkpoint / resume ----------------------------------------------
    from horovod_tpu.training import checkpoint as ckpt

    ckdir = os.path.join(TMPDIR, "ckpt")
    state = {"params": params, "epoch": 0}
    if hvd.rank() == 0:
        ckpt.save(ckdir, state, epoch=3)
    # Agreement intersects every rank's verified scan (rank-local-
    # filesystem safe), so rank 0's save must be visible before the peers
    # scan: an eager allreduce is the barrier. (A real resume never races —
    # the checkpoints exist before the restarted job scans.)
    hvd.allreduce([np.zeros((1,), np.float32)] * nloc, average=False,
                  name="ckpt_save_barrier")
    epoch = ckpt.agree_on_resume_epoch(ckdir)
    assert epoch == 3, epoch
    restored = ckpt.load(ckdir, state, epoch=epoch)
    rrows = hvd.local_values(restored["params"])
    np.testing.assert_allclose(rrows[0]["w"], rows[0]["w"], rtol=1e-6)
    log("checkpoint resume OK")

    # --- SHARDED checkpoint: per-rank rows survive across processes -------
    # The replicated-convention save keeps one row (lossy for TP/EP
    # shards); save_sharded writes every process's rows to its own file.
    shdir = os.path.join(TMPDIR, "ckpt_sharded")
    myrows = hvd.rank_stack([np.full((2,), float(r), np.float32)
                             for r in lranks])
    ckpt.save_sharded(shdir, {"w": myrows}, epoch=1)
    restored_sh = ckpt.load_sharded(
        shdir, {"w": hvd.rank_stack([np.zeros((2,), np.float32)
                                     for _ in lranks]), "epoch": 0})
    for j, r in enumerate(lranks):
        np.testing.assert_allclose(
            np.asarray(hvd.local_values(restored_sh["w"])[j]), float(r))
    assert restored_sh["epoch"] == 1
    log("sharded checkpoint roundtrip OK")

    # --- group hosted entirely by ONE process -----------------------------
    # Process 1 has no members of group 1; it must still participate in the
    # negotiation (empty submission) so the collective completes instead of
    # deadlocking.
    hvd.shutdown()
    # shutdown closed (flushed) the coordinator's timeline; preserve it
    # before re-init truncates the file, so the harness can inspect it.
    tlpath = os.environ.get("HOROVOD_TIMELINE")
    if tlpath and PID == 0 and os.path.exists(tlpath):
        import shutil

        shutil.copy(tlpath, tlpath + ".phase1")
    hvd.init([[0, 1, 2, 3], [4, 5, 6, 7]])
    sub = hvd.get_group(1)
    my_sub = sub.local_member_ranks()
    assert list(my_sub) == (list(range(4)) if PID == 0 else [])
    vals = [np.full((2,), float(r), np.float32) for r in my_sub]
    outs = hvd.allreduce(vals, group=1, average=False, name="sub_only")
    if PID == 0:
        for o in outs:
            np.testing.assert_allclose(np.asarray(o), 6.0)  # 0+1+2+3
    else:
        assert outs == []
    log("no-member group negotiation OK")

    # --- group-family allreduce across processes --------------------------
    # Families (tensor parallelism's DP-family sync) must partition
    # correctly when the family's groups straddle the process boundary:
    # groups {0..3} (all on p0) and {4..7} (all on p1) reduce in ONE
    # collective.
    @hvd.spmd
    def fam(x):
        return hvd.allreduce(x, group=(1, 2), average=False, name="fam")

    xg = hvd.rank_stack([np.full((2,), float(r), np.float32)
                         for r in hvd.get_group(0).local_member_ranks()])
    fam_rows = hvd.local_values(fam(xg))
    want = 6.0 if PID == 0 else 22.0  # 0+1+2+3 / 4+5+6+7
    for row in fam_rows:
        np.testing.assert_allclose(np.asarray(row), want)
    log("cross-process family allreduce OK")

    # --- auto-name desync: crisp divergence error, not a stall ------------
    # Process 1 issues an extra UNNAMED collective where process 0 issues
    # its named one: the index-keyed negotiation must raise a schedule-
    # divergence HorovodError naming BOTH tensors on both processes
    # (VERDICT r2 #6; the reference could only surface this as a stall
    # warning, mpi_ops.cc:1369-1412). Runs last: the divergence leaves
    # process 1's auto-name counter ahead, which is the point.
    lranks0 = hvd.get_group(0).local_member_ranks()
    if PID == 1:
        msg = expect_error(
            lambda: hvd.allreduce([np.ones((2,), np.float32)] * len(lranks0),
                                  average=False),
            "Mismatched collective sequence")
    else:
        msg = expect_error(
            lambda: hvd.allreduce([np.ones((2,), np.float32)] * len(lranks0),
                                  name="sync_after_desync", average=False),
            "Mismatched collective sequence")
    assert "sync_after_desync" in msg and "HorovodAllreduce_" in msg, msg
    # Recovery: a matching named collective completes normally.
    outs = hvd.allreduce([np.ones((1,), np.float32)] * len(lranks0),
                         name="desync_recover", average=False)
    np.testing.assert_allclose(np.asarray(outs[0]), 8.0)
    log("auto-name desync crisp error OK")

    # --- cached-negotiation divergence timeout (VERDICT r4 #5 trade) ------
    # Process 1 issues a collective process 0 never does. With the verdict
    # cache the peers never rendezvous to compare names, so the worker must
    # die on the bounded HOROVOD_NEGOTIATION_TIMEOUT with an error that
    # names the tensor and points at HOROVOD_EAGER_CACHE=0 — not hang for
    # the 600 s default. Runs LAST: afterwards the processes' negotiation
    # indices are misaligned by design and no further collectives happen.
    done_flag = os.path.join(TMPDIR, "p1_timeout_done")
    if PID == 1:
        os.environ["HOROVOD_NEGOTIATION_TIMEOUT"] = "2"
        try:
            msg = expect_error(
                lambda: hvd.allreduce(
                    [np.ones((2,), np.float32)] * len(lranks0),
                    name="only_p1", average=False),
                "HOROVOD_EAGER_CACHE=0")
            assert "only_p1" in msg, msg
        finally:
            os.environ.pop("HOROVOD_NEGOTIATION_TIMEOUT", None)
            with open(done_flag, "w") as f:
                f.write("done")
    else:
        # p0 hosts the coordination service: it must outlive p1's bounded
        # wait, however loaded the host is — poll p1's sentinel file
        # rather than guessing with a sleep.
        deadline = time.monotonic() + 120
        while not os.path.exists(done_flag):
            if time.monotonic() > deadline:
                raise AssertionError(
                    "p1 never finished its divergence-timeout subtest")
            time.sleep(0.2)
    log("cached-negotiation divergence timeout OK")

    print(f"[p{PID}] ALL SUBTESTS PASSED", flush=True)


def main_nproc():
    """Generic N-process suite (run when NPROCS != 2): the 2-process file
    plus VERDICT r3 #6 — at >2 processes the negotiator must NAME the one
    diverging process, and training must hold exact replica agreement
    across every process boundary."""
    init_distributed(coordinator_address=f"localhost:{PORT}",
                     num_processes=NPROCS, process_id=PID)
    assert jax.process_count() == NPROCS
    world = hvd.global_size()
    assert world == DEVS * NPROCS, world
    assert hvd.rank() == PID * DEVS
    lranks = hvd.get_group(0).local_member_ranks()
    assert list(lranks) == list(range(PID * DEVS, PID * DEVS + DEVS))
    log("rank/size OK")

    # eager allreduce across all processes
    vals = [np.full((3,), float(r), np.float32) for r in lranks]
    outs = hvd.allreduce(vals, average=False)
    for o in outs:
        np.testing.assert_allclose(np.asarray(o), sum(range(world)))
    log("eager allreduce OK")

    # compiled DP training step: replicas agree bit-for-bit across hosts
    import optax

    rng = np.random.RandomState(0)
    w0 = {"w": rng.randn(4, 2).astype(np.float32)}
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    @hvd.spmd
    def step(p, s, b):
        g = jax.grad(loss_fn)(p, b)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    params = hvd.replicate(w0)
    state = hvd.replicate(opt.init(w0))
    batches = hvd.rank_stack([
        (np.random.RandomState(100 + r).randn(8, 4).astype(np.float32),
         np.random.RandomState(200 + r).randn(8, 2).astype(np.float32))
        for r in lranks])
    for _ in range(3):
        params, state = step(params, state, batches)
    rows = [np.asarray(r["w"]) for r in hvd.local_values(params)]
    for row in rows[1:]:
        np.testing.assert_array_equal(row, rows[0])
    log("train-step replica agreement OK")

    # seeded schedule desync: ONLY process 2 builds a different program;
    # the error must name it (process 0 vs process 2) on every process.
    nm = "seeded_desync" if PID != 2 else "rogue_name"

    @hvd.spmd
    def bad(x):
        return hvd.allreduce(x, name=nm)

    msg = expect_error(lambda: bad(jnp.ones((world, 2))),
                       "Mismatched collective schedules")
    assert "process 0 and process 2 diverge" in msg, msg
    assert "seeded_desync" in msg and "rogue_name" in msg, msg
    log("seeded desync names process 2 OK")

    # recovery: a clean collective completes after the failed validation
    outs = hvd.allreduce([np.ones((2,), np.float32)] * len(lranks),
                         average=False, name="post_desync")
    np.testing.assert_allclose(np.asarray(outs[0]), float(world))
    log("post-desync recovery OK")

    print(f"[p{PID}] ALL SUBTESTS PASSED", flush=True)


if __name__ == "__main__":
    main() if NPROCS == 2 else main_nproc()
