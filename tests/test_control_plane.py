"""The control plane: request validation, the fusion planner's partition
and the timeline writer, held to the reference's words.

Validation messages are compared whole with literal strings (the
reference's format, mpi_ops.cc ConstructMPIResponse); the planner with the
partition written out below; the timeline by reading the Chrome-tracing
file back.
"""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import negotiate as neg
from horovod_tpu.core import state as _state
from horovod_tpu.core import timeline
from horovod_tpu.core.state import HorovodError
from horovod_tpu.ops import fusion


def _req(rank, name="t", op=neg.CollectiveOp.ALLREDUCE, dtype="float32",
         shape=(2, 3), root=-1):
    return neg.Request(rank=rank, name=name, op=op, dtype=dtype, shape=shape,
                       root_rank=root)


MISMATCH_CASES = [
    # (requests, the whole message) — each exercises one
    # ConstructMPIResponse check
    ([_req(0), _req(1, dtype="int32")] + [_req(r) for r in range(2, 8)],
     "Mismatched data types: One or more ranks sent tensors of type "
     "float32, but one or more other ranks sent tensors of type int32 for "
     "tensor t."),
    ([_req(0), _req(1, op=neg.CollectiveOp.ALLGATHER)]
     + [_req(r) for r in range(2, 8)],
     "Mismatched collective operations: One or more ranks did an "
     "allreduce, but one or more other ranks did an allgather on tensor t."),
    ([_req(0), _req(1, shape=(3, 3))] + [_req(r) for r in range(2, 8)],
     "Mismatched allreduce tensor shapes: One or more ranks sent tensors of "
     "shape [2, 3], but one or more other ranks sent tensors of shape "
     "[3, 3] on tensor t."),
    ([_req(r, op=neg.CollectiveOp.ALLGATHER) for r in range(7)]
     + [_req(7, op=neg.CollectiveOp.ALLGATHER, shape=(2,))],
     "Mismatched allgather tensor shapes: One or more ranks sent tensors of "
     "rank 2, but one or more other ranks sent tensors of rank 1 on tensor "
     "t."),
    ([_req(r, op=neg.CollectiveOp.ALLGATHER) for r in range(7)]
     + [_req(7, op=neg.CollectiveOp.ALLGATHER, shape=(4, 9))],
     "Mismatched allgather tensor shapes: trailing dimensions of tensor t "
     "differ between ranks ([2, 3] vs [4, 9]); only the first dimension may "
     "vary."),
    ([_req(r, op=neg.CollectiveOp.GATHER, root=0) for r in range(7)]
     + [_req(7, op=neg.CollectiveOp.GATHER, root=3)],
     "Mismatched gather root ranks: One rank specified root rank 0, but "
     "another rank specified root rank 3 for tensor t."),
    ([_req(r, op=neg.CollectiveOp.BROADCAST, root=55) for r in range(8)],
     "Invalid root rank 55 for tensor t in a group of size 8."),
    ([_req(r, op=neg.CollectiveOp.ALLGATHER, shape=()) for r in range(8)],
     "Rank zero tried to allgather a rank-zero tensor t, which is not "
     "allowed."),
    ([_req(0), _req(0)] + [_req(r) for r in range(2, 8)],
     "Tensor t was submitted twice by rank 0."),
]


class TestValidation:
    @pytest.mark.parametrize("case", range(len(MISMATCH_CASES)))
    def test_mismatch_raises_the_references_message(self, world, case):
        requests, message = MISMATCH_CASES[case]
        with pytest.raises(HorovodError) as err:
            neg.validate(requests, 8)
        assert str(err.value) == message

    def test_success_responses_match(self, world):
        reqs = [_req(r, op=neg.CollectiveOp.ALLGATHER, shape=(r + 1, 4))
                for r in range(8)]
        resp = neg.validate(reqs, 8)
        assert resp.tensor_sizes == tuple(range(1, 9))

    def test_gather_root_recorded(self, world):
        reqs = [_req(r, op=neg.CollectiveOp.GATHER, shape=(2, 2), root=5)
                for r in range(8)]
        assert neg.validate(reqs, 8).root_rank == 5

    @pytest.mark.parametrize("submitted", [7, 9])
    def test_request_count_must_be_the_groups(self, world, submitted):
        with pytest.raises(HorovodError) as err:
            neg.validate([_req(r) for r in range(submitted)], 8)
        assert str(err.value) == (
            f"Tensor t has {submitted} request(s) but the group has 8 "
            f"rank(s); every rank must submit the collective.")

    def test_reconfigured_world_validates_at_its_new_size(self, world):
        """After an elastic shrink the group's size is the new world's:
        what the old world submitted is refused, and an errored
        negotiation leaves nothing behind for the next one."""
        _state.reconfigure(range(4))
        assert hvd.size() == 4
        with pytest.raises(HorovodError, match=r"has 8 request\(s\) but "
                                               r"the group has 4 rank"):
            neg.validate([_req(r) for r in range(8)], hvd.size())
        resp = neg.validate([_req(r) for r in range(4)], hvd.size())
        assert resp.name == "t"
        out = hvd.allreduce([np.ones((2,), np.float32)] * 4, average=False)
        np.testing.assert_allclose(np.asarray(out[0]), 4.0)


def _written_out_partition(leaves, threshold):
    """Contiguous same-dtype runs of at most ``threshold`` bytes, an
    oversized leaf alone; 0 = every leaf alone. As (indices, bytes)."""
    runs = []
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize
        last = runs[-1] if runs else None
        if (threshold > 0 and last is not None
                and leaves[last[0][-1]].dtype == leaf.dtype
                and last[1] + nbytes <= threshold):
            runs[-1] = (last[0] + (i,), last[1] + nbytes)
        else:
            runs.append(((i,), nbytes))
    return runs


class TestFusionPlanner:
    @pytest.mark.parametrize("threshold", [0, 24, 40, 1 << 20])
    def test_plan_is_the_written_out_partition(self, world, threshold):
        rng = np.random.RandomState(0)
        leaves = []
        for _ in range(20):
            n = int(rng.randint(1, 30))
            dt = [np.float32, np.float64, np.int32][int(rng.randint(3))]
            leaves.append(jnp.zeros((n,), dt))
        plan = fusion.plan_buckets(leaves, threshold)
        assert [(b.indices, b.total_bytes) for b in plan] \
            == _written_out_partition(leaves, threshold)
        assert [b.priority for b in plan] == list(range(len(plan)))


def _events(path):
    # Chrome tracing tolerates the trailing comma / missing ']' (the
    # reference also leaves the array open while streaming).
    return json.loads(open(path).read().rstrip().rstrip(",") + "]")


class TestTimeline:
    def test_chrome_trace_written(self, tmp_path, world):
        path = str(tmp_path / "timeline.json")
        tl = timeline.session()
        tl.start(path)
        try:
            neg.validate([_req(r, name="gradA") for r in range(2)], 2)
            tl.start_activity("gradA", "XLA_ALLREDUCE")
            tl.end_activity("gradA", "XLA_ALLREDUCE")
        finally:
            tl.stop()
        events = _events(path)
        names = [e["name"] for e in events]
        assert "process_name" in names            # tensor metadata row
        assert "NEGOTIATE_allreduce" in names     # negotiation phases
        assert "XLA_ALLREDUCE" in names           # execution activity
        phases = {e["ph"] for e in events}
        assert {"B", "E", "M"} <= phases
        # Per-rank ready ticks: one instant 'X' event named by each rank as
        # its request lands (NegotiateRankReady, timeline.cc:117-125).
        ticks = [e for e in events if e["ph"] == "X"]
        assert sorted(t["name"] for t in ticks) == ["0", "1"]
        assert all(t["dur"] == 0 for t in ticks)

    @pytest.mark.parametrize("device_mode", [False, True])
    def test_one_writer_whatever_the_device_mode(self, tmp_path,
                                                 monkeypatch, device_mode):
        """``HOROVOD_TIMELINE_DEVICE`` is latched at start and decides
        where per-step rows come from — never who writes the file: host
        events and explicit-timestamp events both land in either mode."""
        if device_mode:
            monkeypatch.setenv("HOROVOD_TIMELINE_DEVICE", "1")
        else:
            monkeypatch.delenv("HOROVOD_TIMELINE_DEVICE", raising=False)
        path = str(tmp_path / "tl.json")
        tl = timeline.Timeline()
        tl.start(path)
        try:
            assert tl.active and tl.device_mode is device_mode
            assert type(tl._writer) is timeline._ChromeTraceWriter
            monkeypatch.setenv("HOROVOD_TIMELINE_DEVICE",
                               "0" if device_mode else "1")
            assert tl.device_mode is device_mode  # a late flip is not read
            tl.event("w", "QUEUE", "B")
            tl.event("w", "QUEUE", "E")
            tl.event_at("w", "XLA_ALLREDUCE", 5.0e6, 12.5)
        finally:
            tl.stop()
        assert not tl.active
        row = [e for e in _events(path) if e["ph"] != "M"]
        assert [(e["name"], e["ph"]) for e in row] == [
            ("QUEUE", "B"), ("QUEUE", "E"), ("XLA_ALLREDUCE", "X")]
        assert row[2]["dur"] == 12.5


class TestTimelineEndToEnd:
    def test_env_var_enables_timeline(self, tmp_path):
        """HOROVOD_TIMELINE=<file> at init time traces eager collectives
        (mpi_ops.cc:1486-1489 behavior)."""
        import json

        path = str(tmp_path / "tl.json")
        os.environ["HOROVOD_TIMELINE"] = path
        try:
            hvd.shutdown()
            hvd.init()
            hvd.allreduce([np.ones((2,), np.float32)] * 8,
                          name="grads/dense0")
            hvd.shutdown()  # flushes + closes
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)
        events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
        names = [e["name"] for e in events]
        assert "NEGOTIATE_allreduce" in names
        assert "XLA_ALLREDUCE" in names
        # the tensor appears as its own chrome 'process'
        procs = [e for e in events if e["name"] == "process_name"]
        assert any(p["args"]["name"] == "grads/dense0" for p in procs)
        # every rank's ready tick is on the tensor's row
        pid = next(p["pid"] for p in procs
                   if p["args"]["name"] == "grads/dense0")
        ticks = [e for e in events if e["ph"] == "X" and e["pid"] == pid]
        assert sorted(t["name"] for t in ticks) == [str(r) for r in range(8)]

    def test_grouped_collective_rank_ready_events(self, tmp_path):
        """A grouped collective's timeline row shows one NegotiateRankReady
        tick per GROUP-LOCAL rank, so a late rank in a subset group is
        visible in the trace (VERDICT r1 #8; timeline.cc:117-125)."""
        import json

        path = str(tmp_path / "tl_group.json")
        os.environ["HOROVOD_TIMELINE"] = path
        try:
            hvd.shutdown()
            hvd.init([[0, 1, 2], [2, 3, 4]])
            hvd.allreduce([np.ones((2,), np.float32)] * 3,
                          name="grads/grouped", group=1)
            hvd.shutdown()
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)
        events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
        procs = [e for e in events if e["name"] == "process_name"]
        pid = next(p["pid"] for p in procs
                   if p["args"]["name"] == "grads/grouped")
        row = [e for e in events if e["pid"] == pid and e["ph"] != "M"]
        # NEGOTIATE span brackets the per-rank ticks
        assert row[0]["name"] == "NEGOTIATE_allreduce" and row[0]["ph"] == "B"
        ticks = [e for e in row if e["ph"] == "X"]
        assert sorted(t["name"] for t in ticks) == ["0", "1", "2"]

    def test_compiled_hot_path_emits_per_step_events(self, tmp_path,
                                                     monkeypatch):
        """A Trainer.fit run under HOROVOD_TIMELINE shows one B/E
        ``hvd/spmd/dispatch`` pair per training step on the ``_hvd`` row
        (the per-step row; per-collective truth is device mode's) plus
        trace-time NEGOTIATE rows and the parts of each build — and the
        timeline waits for no step: turning it on must not remove the
        pipelining it is there to show."""
        import json

        import jax
        import jax.numpy as jnp
        import optax

        from horovod_tpu.training import Trainer

        path = str(tmp_path / "tl_hot.json")
        os.environ["HOROVOD_TIMELINE"] = path
        waited = []
        try:
            hvd.shutdown()
            hvd.init()

            def loss_fn(p, batch):
                x, y = batch
                return jnp.mean((x @ p["w"] - y) ** 2)

            rng = np.random.RandomState(0)
            tr = Trainer(loss_fn, optax.sgd(0.1))
            tr.init_state({"w": rng.randn(4, 2).astype(np.float32)})
            batch = (rng.randn(8, 8, 4).astype(np.float32),
                     rng.randn(8, 8, 2).astype(np.float32))
            n_steps = 3
            for _ in range(n_steps):
                tr.train_step(batch)

            # The wrapper itself, driven as a user's loop drives it: no
            # step is waited for while the timeline is on.
            step = hvd.spmd(lambda x: hvd.allreduce(x) * 2.0)
            x = hvd.replicate(jnp.ones((4,)))
            real = jax.block_until_ready
            monkeypatch.setattr(
                jax, "block_until_ready",
                lambda out: (waited.append(1), real(out))[1])
            for _ in range(n_steps):
                x = step(x)
            monkeypatch.undo()
            hvd.shutdown()
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)
        assert waited == []
        events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
        procs = {e["pid"]: e["args"]["name"] for e in events
                 if e["name"] == "process_name"}
        # The fused gradient allreduce row exists (negotiated at trace
        # time) and carries NO per-step span: each would say "the step".
        ar_pids = [pid for pid, nm in procs.items()
                   if nm.startswith("HorovodAllreduce")]
        assert ar_pids, f"no allreduce rows in {sorted(procs.values())}"
        assert not [e for e in events if e["name"] == "XLA_ALLREDUCE"]
        assert any(e["name"] == "NEGOTIATE_ALLREDUCE" for e in events)
        # One B/E dispatch pair a call of a compiled program, properly
        # nested inside its program's build on the first call.
        hvd_pid = next(pid for pid, nm in procs.items() if nm == "_hvd")
        row = [e for e in events if e["pid"] == hvd_pid]
        # Each build's parts, as JAX timed them: complete events there.
        for part in ("trace", "lower", "compile"):
            assert len([e for e in row if e["ph"] == "X" and e["name"]
                        == f"hvd/spmd/build/{part}"]) >= 2
        for ph in "BE":
            assert len([e for e in row if e["ph"] == ph
                        and e["name"] == "hvd/spmd/dispatch"]) >= 2 * n_steps
        # hvd/init opened the file inside itself: it has no pair here.
        assert [e["name"] for e in row if e["ph"] == "B"][0] == \
            "hvd/replicate"
        depth = 0
        for e in row:
            depth += {"B": 1, "E": -1}.get(e["ph"], 0)
            assert depth >= 0
        assert depth == 0


class TestXprofSpanMapping:
    """core/xprof.py: pure mapping of xplane events onto the negotiated
    schedule — the device-fidelity timeline mode's core logic."""

    SCHED = [["HorovodAllreduce_0", "ALLREDUCE", "float32", [8], 0, -1],
             ["HorovodAllgather_0", "ALLGATHER", "float32", [8], 0, -1]]

    def test_collectives_order_matched_and_async_merged(self):
        from horovod_tpu.core import xprof

        events = [
            ("%concatenate.1 = f32[64] concatenate(...)", 10.0, 2.0),
            ("%all-reduce-start.3 = f32[64] all-reduce-start(...)", 13.0,
             1.0),
            ("%all-reduce-done.3 = f32[64] all-reduce-done(...)", 20.0,
             2.0),
            ("%slice.7 = f32[8] slice(...)", 23.0, 1.0),
            ("%all-gather.5 = f32[64] all-gather(...)", 25.0, 4.0),
            ("%fusion.2 = f32[8] fusion(...)", 30.0, 1.0),
        ]
        spans = xprof.map_device_spans(self.SCHED, events)
        by_act = {s[1]: s for s in spans}
        # async pair merged: start 13 → done end 22
        ar = by_act["XLA_ALLREDUCE"]
        assert ar[0] == "HorovodAllreduce_0"
        assert ar[2] == 13.0 and ar[3] == 9.0
        ag = by_act["XLA_ALLGATHER"]
        assert ag[0] == "HorovodAllgather_0"
        assert ag[2] == 25.0 and ag[3] == 4.0
        # the concatenate before the allreduce is the pack; the slice
        # between the collectives is the unpack
        assert by_act["MEMCPY_IN_FUSION_BUFFER"][2] == 10.0
        assert by_act["MEMCPY_OUT_FUSION_BUFFER"][2] == 23.0
        step = by_act["DEVICE_STEP"]
        assert step[0] == "_device" and step[2] == 10.0 and step[3] == 21.0

    def test_no_events_yields_no_spans(self):
        from horovod_tpu.core import xprof

        assert xprof.map_device_spans(self.SCHED, []) == []

    def test_bucket_members_repeat_on_member_rows(self):
        """A schedule row carrying fusion-bucket member labels (7th
        element) maps the bucket's device span onto each member tensor's
        row as well — the reference timeline shows every fused tensor
        individually."""
        from horovod_tpu.core import xprof

        sched = [["HorovodAllreduce_0", "ALLREDUCE", "float32", [64], 0,
                  -1, ["params/w", "params/b"]]]
        events = [("%all-reduce.1 = f32[64] all-reduce(...)", 5.0, 3.0)]
        spans = xprof.map_device_spans(sched, events)
        rows = {s[0]: s for s in spans if s[0] != "_device"}
        assert rows["HorovodAllreduce_0"][1] == "XLA_ALLREDUCE"
        for m in ("params/w", "params/b"):
            assert rows[m][1] == "XLA_ALLREDUCE [HorovodAllreduce_0]"
            assert rows[m][2] == 5.0 and rows[m][3] == 3.0

    def test_pack_unpack_window_bounds_both_edges(self):
        """An op overlapping a collective (or outside any inter-collective
        gap it could belong to) is NOT a fusion-buffer copy: the window is
        bounded on both edges (ADVICE r4 — one-edged matching labelled
        ubiquitous slices as unpacks)."""
        from horovod_tpu.core import xprof

        events = [
            # concatenate AFTER the last collective: not a pack.
            ("%all-reduce.1 = f32[64] all-reduce(...)", 10.0, 4.0),
            ("%slice.1 = f32[8] slice(...)", 15.0, 1.0),   # valid unpack
            ("%all-gather.1 = f32[64] all-gather(...)", 17.0, 4.0),
            ("%concatenate.9 = f32[64] concatenate(...)", 22.0, 2.0),
            # slice OVERLAPPING a collective: not an unpack.
            ("%slice.2 = f32[8] slice(...)", 18.0, 1.0),
        ]
        spans = xprof.map_device_spans(self.SCHED, events)
        packs = [s for s in spans if s[1] == "MEMCPY_IN_FUSION_BUFFER"]
        unpacks = [s for s in spans if s[1] == "MEMCPY_OUT_FUSION_BUFFER"]
        assert packs == []
        assert len(unpacks) == 1 and unpacks[0][2] == 15.0

    def test_bitcast_is_not_an_unpack(self):
        from horovod_tpu.core import xprof

        events = [
            ("%all-reduce.1 = f32[64] all-reduce(...)", 10.0, 4.0),
            ("%bitcast.1 = f32[8] bitcast(...)", 15.0, 1.0),
            ("%all-gather.1 = f32[64] all-gather(...)", 17.0, 4.0),
        ]
        spans = xprof.map_device_spans(self.SCHED, events)
        assert not [s for s in spans
                    if s[1] == "MEMCPY_OUT_FUSION_BUFFER"]

    def test_device_mode_end_to_end_on_cpu(self, tmp_path):
        """HOROVOD_TIMELINE_DEVICE=1 on the CPU world: the sampled capture
        has no device plane, so the timeline records the NO_DEVICE_PLANE
        marker (plus the host-side SCHEDULE span from fusion planning) and
        steady-state steps emit nothing — no per-step blocking."""
        import json

        import jax.numpy as jnp
        import optax

        from horovod_tpu.training import Trainer

        path = str(tmp_path / "tl_dev.json")
        os.environ["HOROVOD_TIMELINE"] = path
        os.environ["HOROVOD_TIMELINE_DEVICE"] = "1"
        try:
            hvd.shutdown()
            hvd.init()

            def loss_fn(p, batch):
                x, y = batch
                return jnp.mean((x @ p["w"] - y) ** 2)

            rng = np.random.RandomState(0)
            tr = Trainer(loss_fn, optax.sgd(0.1))
            tr.init_state({"w": rng.randn(4, 2).astype(np.float32)})
            batch = (rng.randn(8, 8, 4).astype(np.float32),
                     rng.randn(8, 8, 2).astype(np.float32))
            for _ in range(3):
                tr.train_step(batch)
            hvd.shutdown()
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)
            os.environ.pop("HOROVOD_TIMELINE_DEVICE", None)
        events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
        procs = {e["pid"]: e["args"]["name"] for e in events
                 if e["name"] == "process_name"}
        fb_pids = [p for p, nm in procs.items() if nm == "_fusion_buffer"]
        assert fb_pids, f"no _fusion_buffer row in {sorted(procs.values())}"
        assert any(e["name"] == "SCHEDULE" for e in events
                   if e["pid"] == fb_pids[0])
        dev_pids = [p for p, nm in procs.items() if nm == "_device"]
        assert dev_pids and any(
            e["name"] == "NO_DEVICE_PLANE" for e in events
            if e["pid"] == dev_pids[0])
        # exactly one sample: the marker appears once, not once per step
        assert len([e for e in events if e["name"] == "NO_DEVICE_PLANE"]) \
            == 1
        # Trace-time negotiation rows + the build's parts are present.
        assert any(e["name"] == "NEGOTIATE_ALLREDUCE" for e in events)
        hvd_pid = next(p for p, nm in procs.items() if nm == "_hvd")
        assert {"hvd/spmd/build/trace", "hvd/spmd/build/lower",
                "hvd/spmd/build/compile"} <= {
                    e["name"] for e in events if e["pid"] == hvd_pid}

    def test_device_mode_interval_resamples(self, tmp_path):
        """HOROVOD_TIMELINE_DEVICE_INTERVAL=2: executions 0, 2 and 4 of
        the compiled program are sampled (first always, then every N-th) —
        steady-state drift becomes visible, unlike the sample-once default
        (one marker in test_device_mode_end_to_end_on_cpu)."""
        import json

        import jax.numpy as jnp
        import optax

        from horovod_tpu.training import Trainer

        path = str(tmp_path / "tl_dev_int.json")
        os.environ["HOROVOD_TIMELINE"] = path
        os.environ["HOROVOD_TIMELINE_DEVICE"] = "1"
        os.environ["HOROVOD_TIMELINE_DEVICE_INTERVAL"] = "2"
        try:
            hvd.shutdown()
            hvd.init()

            def loss_fn(p, batch):
                x, y = batch
                return jnp.mean((x @ p["w"] - y) ** 2)

            rng = np.random.RandomState(0)
            tr = Trainer(loss_fn, optax.sgd(0.1))
            tr.init_state({"w": rng.randn(4, 2).astype(np.float32)})
            batch = (rng.randn(8, 8, 4).astype(np.float32),
                     rng.randn(8, 8, 2).astype(np.float32))
            for _ in range(5):
                tr.train_step(batch)
            hvd.shutdown()
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)
            os.environ.pop("HOROVOD_TIMELINE_DEVICE", None)
            os.environ.pop("HOROVOD_TIMELINE_DEVICE_INTERVAL", None)
        events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
        # On the CPU world each sample records NO_DEVICE_PLANE: one per
        # sampled execution → steps 0, 2, 4.
        assert len([e for e in events
                    if e["name"] == "NO_DEVICE_PLANE"]) == 3

    def test_timeline_spmd_shape_change_retraces(self, tmp_path):
        """With the timeline on, spmd compiles ahead-of-time — the cache
        must key on the argument signature so a shape change (last short
        batch) retraces instead of feeding the wrong executable."""
        path = str(tmp_path / "tl_shapes.json")
        os.environ["HOROVOD_TIMELINE"] = path
        try:
            hvd.shutdown()
            hvd.init()

            @hvd.spmd
            def double(x):
                return hvd.allreduce(x, name="shapes", average=False)

            a = double(np.ones((8, 4), np.float32))
            b = double(np.ones((8, 6), np.float32))   # new shape: retrace
            np.testing.assert_allclose(np.asarray(a), 8.0)
            np.testing.assert_allclose(np.asarray(b), 8.0)
            hvd.shutdown()
        finally:
            os.environ.pop("HOROVOD_TIMELINE", None)


# ---------------------------------------------------------------------------
# Nothing is compiled or loaded: the package is Python down to JAX
# ---------------------------------------------------------------------------


def test_runs_where_nothing_can_be_compiled_or_loaded(monkeypatch):
    import ctypes
    import subprocess

    def refuse(*args, **kwargs):
        raise AssertionError("the control plane started a process or "
                             "loaded a library")

    monkeypatch.setattr(subprocess, "run", refuse)
    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(ctypes, "CDLL", refuse)
    assert importlib.util.find_spec(".native",
                                    package="horovod_tpu.core") is None
    hvd.shutdown()
    hvd.init()
    try:
        out = hvd.allreduce([np.full((2,), r, np.float32) for r in range(8)],
                            average=False, name="no_compiler")
        np.testing.assert_allclose(np.asarray(out[0]), 28.0)
    finally:
        hvd.shutdown()
