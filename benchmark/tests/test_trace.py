"""``trace.py``'s reductions on a small hand-made capture (two devices, an
async all-reduce partly hidden behind a fusion) and, where it is there, on
the recorded capture beside this file. Run by hand (not part of tier-1)."""

import glob
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trace", os.path.join(os.path.dirname(HERE), "trace.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


trace = _load()

# (name, start_us, dur_us)
DEV0 = [("%fusion.1 = bf16[8]", 0.0, 100.0),
        ("%slice-start.9", 1.0, 1.0), ("%slice-done.9", 90.0, 1.0),
        ("%all-reduce-start.3 = f32[4]", 100.0, 5.0),
        ("%fusion.2", 110.0, 40.0),
        ("%all-reduce-done.3", 180.0, 20.0),
        ("%tpu_custom_call.7", 200.0, 50.0)]
DEV1 = [("%fusion.1 = bf16[8]", 0.0, 90.0),
        ("%all-reduce-start.3 = f32[4]", 100.0, 5.0),
        ("%all-reduce-done.3", 105.0, 95.0),
        ("%tpu_custom_call.7", 200.0, 30.0)]


def test_names():
    assert trace.hlo_base("%all-reduce-start.3 = f32[4]") == \
        "all-reduce-start"
    assert trace.hlo_base("fusion.12") == "fusion"
    # the opcode, not the instruction's name: a psum is an all-reduce and a
    # Pallas call under shard_map a custom call (as the chip names them)
    assert trace.hlo_base(
        "%psum.168 = f32[150994944]{0:T(1024)} all-reduce(f32[150994944]"
        "{0:T(1024)} %bitcast.447), channel_id=3") == "all-reduce"
    kernel = ("%shard_map.1704 = (f32[2,1,24,8192,128]{4,3,2,1,0:T(8,128)}, "
              "bf16[1,2,8192,128]{3,2,1,0:T(8,128)(2,1)}) custom-call("
              "bf16[1,24,8192,128]{3,2,1,0} %x), "
              'custom_call_target="tpu_custom_call"')
    assert trace.hlo_base(kernel) == "custom-call"
    assert trace.is_pallas_call(kernel, "custom-call")
    assert trace.is_pallas_call("%tpu_custom_call.15", "tpu_custom_call")
    assert not trace.is_pallas_call("%custom-call.2 = f32[8] custom-call("
                                    "f32[8] %x), custom_call_target=\"Sharding\"",
                                    "custom-call")
    assert trace.is_collective("all-reduce")
    assert trace.is_collective("all-reduce-scatter")
    assert not trace.is_collective("fusion")


def test_merge_async_pairs():
    merged = trace.merge_async(DEV0)
    assert [(b, s, e) for _, b, s, e in merged] == [
        ("fusion", 0.0, 100.0), ("slice-start", 1.0, 2.0),
        ("slice-done", 90.0, 91.0), ("all-reduce", 100.0, 200.0),
        ("fusion", 110.0, 150.0), ("tpu_custom_call", 200.0, 250.0)]


def test_reductions_over_every_device():
    tr = trace.Trace({"/device:TPU:0": DEV0, "/device:TPU:1": DEV1},
                     [("bench/wait_step", 0.0, 300.0)])
    assert tr.window_us() == (0.0, 250.0)
    # as recorded: device 0 is idle 105..110 and 150..180 between the
    # all-reduce's start and its done, device 1 90..100
    assert tr.busy_us() == {"/device:TPU:0": 215.0, "/device:TPU:1": 220.0}
    # device 0 hides 40 of its 100 us of all-reduce behind fusion.2
    assert tr.exposed_collective_us() == {"/device:TPU:0": 60.0,
                                          "/device:TPU:1": 100.0}
    kernel = tr.op_seconds(trace.is_pallas_call)
    assert kernel["/device:TPU:0"] == pytest.approx(50e-6)
    assert kernel["/device:TPU:1"] == pytest.approx(30e-6)
    top = tr.top_ops(2)
    assert top[0] == ["fusion.1", pytest.approx(95e-6)]
    assert top[1] == ["all-reduce-done.3", pytest.approx(57.5e-6)]
    assert tr.idle_gaps(3) == [["wait_step", pytest.approx(30e-6)],
                               ["wait_step", pytest.approx(5e-6)]]


def test_recorded_capture():
    """``recorded/tiny_dp4.xplane.pb``: three steps of a tiny data-parallel
    program on four v5e chips (``record_trace.py``; my chip run, PR 24).
    Every device plane is read, each step's all-reduce is found under its
    ``psum`` name, and the harness's spans come from the host plane."""
    assert glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb"))
    tr = trace.Trace.load(os.path.join(HERE, "recorded"))
    assert sorted(tr.devices) == [f"/device:TPU:{i}" for i in range(4)]
    lo, hi = tr.window_us()
    for name, events in tr.devices.items():
        assert [b for _, b, _, _ in events] == ["fusion", "all-reduce",
                                                "fusion"] * 3
        assert 0 < tr.busy_us()[name] <= hi - lo
        # nothing else runs beside a synchronous all-reduce: all exposed
        reduce_us = sum(e - s for _, b, s, e in events if b == "all-reduce")
        assert tr.exposed_collective_us()[name] == pytest.approx(reduce_us)
    assert {n for n, _, _ in tr.host} == {"bench/dispatch",
                                          "bench/wait_step"}
    assert tr.top_ops(1)[0][0] in ("psum_invariant.7", "fusion.3",
                                   "fusion.2")
