"""``scopes.py``'s reductions: the phase of an ``op_name``, of a fusion
(``mixed``), self times, and — on the pair recorded on the chip beside
this file (``record_scoped.py``) — that every event of every device falls
in exactly one phase and the readers find what they name. Run by hand
(not part of tier-1): ``python3 -m pytest benchmark/tests -q``."""

import importlib.util
import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PAIR = os.path.join(HERE, "recorded_scoped")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(os.path.dirname(HERE), name + ".py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


scopes = _load("scopes")
trace = _load("trace")

FWD = "jit(train_step)/shard_map/jvp(hvd.model)/Transformer/block_0/mlp/dot"
BWD = "jit(train_step)/shard_map/transpose(jvp(hvd.model))/head/dot_general"


def test_phase_looks_through_the_components():
    assert scopes.phase(FWD) == "forward"
    assert scopes.phase(BWD) == "backward"
    # a custom VJP's backward rule nests under the transpose
    assert scopes.phase("jit(f)/shard_map/transpose(jvp(hvd.model))/"
                        "Transformer/block_1/attn/jvp(x)/mul") == "backward"
    assert scopes.phase("jit(f)/shard_map/hvd.exchange/"
                        "MEMCPY_IN_FUSION_BUFFER/concatenate") == "exchange"
    assert scopes.phase("jit(f)/shard_map/hvd.update/mul") == "update"
    assert scopes.phase("hvd.model/Transformer/Embed_0/take") == "forward"
    # the user's apply_updates, the loss's own all-reduce, a parameter
    for name in ("jit(f)/shard_map/add", "jit(f)/shard_map/psum",
                 "sargs[0]['w']", ""):
        assert scopes.phase(name) == "other"


UPD = "jit(f)/shard_map/hvd.update/mul"
TANH = "jit(f)/shard_map/jvp(hvd.model)/Transformer/block_0/mlp/tanh"
BMUL = "jit(f)/shard_map/transpose(jvp(hvd.model))/Transformer/block_0/mul"


@pytest.mark.parametrize("entry, want", [
    (None, "other"),
    ([FWD, []], "forward"),
    ([BWD, [BWD, "sargs[0]['w']", ""]], "backward"),
    # a weight gradient's matmul fused with the AdamW pass it feeds and
    # the user's add: the matmul's, however many the others are
    (["jit(f)/shard_map/add", [BWD, UPD, UPD + "2", UPD + "3",
                               "jit(f)/shard_map/add"]], "backward"),
    # a backward matmul that recomputes the forward's elementwise work
    (["x", [TANH, TANH + "2", BWD]], "backward"),
    # two phases' matmuls in one fusion: nobody's
    (["x", [BWD, FWD + "_general", UPD]], "mixed"),
    # no matmul: the phase most members carry, and a tie is nobody's
    (["x", [TANH, BMUL, BMUL + "2"]], "backward"),
    (["x", [TANH, BMUL]], "mixed"),
    (["jit(f)/shard_map/add", ["jit(f)/shard_map/add"]], "other"),
])
def test_a_fusion_goes_to_its_matmul_then_to_most_members(entry, want):
    assert scopes.phase_of(entry) == want


def test_self_time_leaves_out_what_is_nested():
    events = [("%while.2", "while", 150.0, 250.0),
              ("%fusion.1", "fusion", 0.0, 100.0),
              ("%fusion.3", "fusion", 160.0, 180.0),
              ("%fusion.4", "fusion", 180.0, 240.0),
              ("%fusion.5", "fusion", 260.0, 270.0)]
    assert scopes.self_us(events) == [20.0, 100.0, 20.0, 60.0, 10.0]


def _run_of(events_by_device, steps, record):
    tr = trace.Trace({d: [(n, s, e - s) for n, s, e in ev]
                      for d, ev in events_by_device.items()})
    run = types.SimpleNamespace(trace=tr, window={"steps": steps}, chips=4)
    scopes.record = lambda: record
    return run


def test_readers_on_a_hand_made_capture():
    record = {"spans": [], "programs": {
        "warmup/1": {"dispatches": 1, "counters": {}, "scopes": None},
        "train_step/3": {"dispatches": 5, "counters": {}, "scopes": {
            "fusion.1": [FWD, [FWD]], "hvd_flash_fwd.2": ["", []],
            "fusion.3": ["x", [BMUL, UPD]],
            "psum.4": ["jit(f)/shard_map/hvd.exchange/psum", []]}}}}
    kernel = ('%hvd_flash_fwd.2 = (f32[8]) custom-call(f32[8] %x), '
              'custom_call_target="tpu_custom_call"')
    dev = [("%fusion.1 = f32[8] fusion(...)", 0.0, 100.0),
           (kernel, 100.0, 130.0),
           ("%fusion.3 = f32[8] fusion(...)", 130.0, 170.0),
           ("%psum.4 = f32[8] all-reduce(...)", 170.0, 190.0),
           ("%copy.9 = f32[8] copy(...)", 190.0, 200.0)]
    run = _run_of({"/device:TPU:0": dev}, 2, record)
    by = {p: scopes.seconds_by(run, record, lambda ev, p=p: ev.phase == p)[
        "/device:TPU:0"] * 1e6 for p in scopes.PHASES}
    assert by == pytest.approx({"forward": 100.0, "backward": 0.0,
                                "exchange": 20.0, "update": 0.0,
                                "other": 40.0, "mixed": 40.0})
    assert scopes.phase_ms_per_step(run, "forward") == pytest.approx(0.05)
    assert scopes.kernel_ms_per_step(run, "hvd_flash_fwd") == \
        pytest.approx(0.015)
    assert scopes.kernel_ms_per_step(run, "hvd_flash_bwd") is None
    # one all-reduce under hvd.exchange in the capture's two steps
    assert scopes.exchange_collectives_per_step(run) == 0.5
    # a program without a record, or without a map, reads nothing
    for rec in (None, {"programs": {}}, {"programs": {"a/1": {
            "dispatches": 1, "counters": {}, "scopes": None}}}):
        scopes.record = lambda rec=rec: rec
        assert scopes.phase_ms_per_step(run, "forward") is None
        assert scopes.exchange_collectives_per_step(run) is None
        assert scopes.window_dispatches(run, rec) == []
        assert scopes.span_seconds(rec, "hvd/init") is None


@pytest.fixture(scope="module")
def pair():
    with open(os.path.join(PAIR, "tiny_scoped.record.json")) as f:
        record = json.load(f)
    tr = trace.Trace.load(PAIR)
    run = types.SimpleNamespace(trace=tr, window={"steps": 3},
                                chips=len(tr.raw))
    scopes.record = lambda: record
    return run, record


def test_recorded_pair_every_event_in_exactly_one_phase(pair):
    """``recorded_scoped/``: three steps of a tiny scoped ``hvd.spmd`` LM
    step on four v5e chips and the program's record of it
    (``record_scoped.py``; my chip run, PR 25)."""
    run, record = pair
    assert len(run.trace.raw) == 4
    program = scopes.step_program(record)
    assert program["dispatches"] == 4 and program["scopes"]
    everything = scopes.seconds_by(run, record, lambda ev: True)
    by = {p: scopes.seconds_by(run, record, lambda ev, p=p: ev.phase == p)
          for p in scopes.PHASES}
    for device, events in run.trace.raw.items():
        assert sum(by[p][device] for p in scopes.PHASES) == \
            pytest.approx(everything[device])
        # self times add up to the device's busy time: nothing twice
        assert everything[device] * 1e6 == pytest.approx(
            run.trace.busy_us()[device], rel=1e-3)
        for p in ("forward", "backward", "exchange", "update"):
            assert by[p][device] > 0, (device, p)
        # every instruction of the capture is one the map holds
        missing = {scopes.instr_key(n) for n, _, _, _ in events} - set(
            program["scopes"])
        assert not missing, sorted(missing)[:5]


def test_recorded_pair_kernels_and_collectives_by_name(pair):
    run, record = pair
    fwd = scopes.kernel_ms_per_step(run, "hvd_flash_fwd")
    bwd = scopes.kernel_ms_per_step(run, "hvd_flash_bwd")
    pallas = max(run.trace.op_seconds(run.trace.is_pallas_call).values())
    assert fwd > 0 and bwd > 0
    assert (fwd + bwd) * 3 / 1e3 == pytest.approx(pallas, rel=1e-3)
    # What the device runs: one all-reduce a step, under hvd.exchange —
    # the plan's one bucket, with the loss's own scalar all-reduce riding
    # along (XLA's combiner merged it in: the result is a pair).
    assert scopes.exchange_collectives_per_step(run) == 1
    scoped = scopes.step_program(record)["scopes"]
    for events in run.trace.raw.values():
        reduces = [n for n, b, _, _ in events if b == "all-reduce"]
        assert len(reduces) == 3
        for name in reduces:
            assert "/hvd.exchange/psum" in scoped[scopes.instr_key(name)][0]
            assert name.partition(" = ")[2].startswith("(f32[1443072]")
    assert scopes.step_counter("exchange.wire_bytes") == 4 * 1443072


def test_recorded_pair_host_spans(pair):
    run, record = pair
    spans = scopes.window_dispatches(run, record)
    assert len(spans) == 3 and all(s[0] == "hvd/spmd/dispatch"
                                   for s in spans)
    assert scopes.span_seconds(record, "hvd/init") > 0
    assert scopes.span_seconds(record, "hvd/spmd/build") > 0
    builds_in_window = [s for s in record["spans"]
                        if s[0] == "hvd/spmd/build" and s[1] > spans[0][1]]
    assert builds_in_window == []
