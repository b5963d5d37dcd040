"""Fault-tolerance layer tests (core/resilience.py + crash-safe checkpoints).

Fast tier-1 coverage: fault-spec parsing, KV error classification against
the REAL jax distributed-client error strings, bounded retry/backoff,
heartbeat/liveness, atomic+manifested checkpoints with torn-write fallback,
set-intersection resume agreement, and Trainer restore/resume. The
multi-process crash drill (tools/fault_drill.py) is ``slow``-marked.
"""

import atexit
import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import multihost
from horovod_tpu.core import resilience as res
from horovod_tpu.core import state as _state
from horovod_tpu.core import timeline
from horovod_tpu.training import callbacks, checkpoint as ckpt, loop
from horovod_tpu.utils import env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_resilience():
    """Injector/liveness/retry state is process-global and env-derived;
    reset around every test so specs can't leak."""
    res._reset_for_tests()
    yield
    res._reset_for_tests()


class FakeKV:
    """Dict-backed stand-in for the jax coordination-service client, raising
    the real client's error strings."""

    def __init__(self):
        self.d = {}
        self.fail_next = 0  # raise UNAVAILABLE for this many get calls
        self.gets = 0

    def key_value_set(self, key, value, allow_overwrite=False):
        if not allow_overwrite and key in self.d:
            raise RuntimeError(f"ALREADY_EXISTS: key {key}")
        self.d[key] = value

    def blocking_key_value_get(self, key, timeout_ms):
        self.gets += 1
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError(
                "UNAVAILABLE: failed to connect to all addresses; last "
                "error: UNKNOWN: ipv4:127.0.0.1:9999: Failed to connect to "
                "remote host: Connection refused")
        if key in self.d:
            return self.d[key]
        raise RuntimeError(
            f"DEADLINE_EXCEEDED: GetKeyValue() timed out with key: {key} "
            f"and duration: {timeout_ms}ms")

    def key_value_delete(self, key):
        self.d.pop(key, None)


# ---------------------------------------------------------------------------
# Fault-spec parsing + injector
# ---------------------------------------------------------------------------

def test_parse_fault_spec():
    faults = res.parse_fault_spec(
        "kv_timeout@seq=3;crash@rank=1,step=5;torn_write@epoch=2")
    assert [f.kind for f in faults] == ["kv_timeout", "crash", "torn_write"]
    assert faults[0].attrs == {"seq": 3}
    assert faults[1].attrs == {"rank": 1, "step": 5}
    assert faults[2].attrs == {"epoch": 2}
    assert faults[1].describe() == "crash@rank=1,step=5"
    assert res.parse_fault_spec(None) == ()
    assert res.parse_fault_spec("  ;; ") == ()


@pytest.mark.parametrize("bad,match", [
    ("explode@step=1", "unknown fault kind"),
    ("kv_timeout@bogus=1", "bad attribute"),
    ("crash@step=soon", "must be an integer"),
    ("crash@rank=0", "requires attribute"),   # step missing
    ("kv_timeout", "requires attribute"),     # seq missing
])
def test_parse_fault_spec_rejects(bad, match):
    with pytest.raises(ValueError, match=match):
        res.parse_fault_spec(bad)


def test_injector_kv_fault_window():
    inj = res.FaultInjector(res.parse_fault_spec("kv_timeout@seq=2,times=3"))
    due = [s for s in range(8) if inj.kv_fault_due(s)]
    assert due == [2, 3, 4]
    assert [inj.next_kv_seq() for _ in range(3)] == [0, 1, 2]


def test_injector_crash_and_torn_write():
    inj = res.FaultInjector(
        res.parse_fault_spec("crash@rank=1,step=5;torn_write@epoch=2"))
    assert inj.crash_due(5, ranks=(0, 1, 2)) is not None
    assert inj.crash_due(5, ranks=(0, 3)) is None      # rank 1 not hosted
    assert inj.crash_due(4, ranks=(1,)) is None        # wrong step
    # rank omitted matches any process
    inj2 = res.FaultInjector(res.parse_fault_spec("crash@step=7"))
    assert inj2.crash_due(7, ranks=(3,)) is not None
    # span covers multi-step compiled calls (steps_per_call > 1): a fault
    # step inside the call's window fires even when not call-aligned
    assert inj2.crash_due(4, ranks=(3,), span=4) is not None  # 4 <= 7 < 8
    assert inj2.crash_due(8, ranks=(3,), span=4) is None      # window passed
    # torn_write is consume-once: a retried save of the epoch succeeds
    assert inj.torn_write_due(2) is True
    assert inj.torn_write_due(2) is False
    assert inj.torn_write_due(None) is False


def test_maybe_crash_noop_without_spec():
    res.maybe_crash(0, ranks=(0,))  # must not exit


# ---------------------------------------------------------------------------
# KV error classification — the real jax distributed-client strings
# ---------------------------------------------------------------------------

# Captured from jax 0.4.37's DistributedRuntimeClient (poll timeout) and the
# tsl coordination service's gRPC error formats.
POLL_TIMEOUT = ("DEADLINE_EXCEEDED: GetKeyValue() timed out with key: "
                "hvd/neg/g1/s0/p1 and duration: 200ms")
NOT_FOUND = "NOT_FOUND: /hvd/resp/g1/s3"
CONN_REFUSED = ("UNAVAILABLE: failed to connect to all addresses; last "
                "error: UNKNOWN: ipv4:127.0.0.1:9999: Failed to connect to "
                "remote host: Connection refused")
CONN_TIMEOUT = "UNAVAILABLE: connection attempt timed out before receiving "\
               "SETTINGS frame"
SHUTDOWN_STATE = ("FAILED_PRECONDITION: Agent must be in CONNECTED state. "
                  "It is currently in state: SHUTDOWN")
SERVICE_STOPPED = ("INTERNAL: Coordination service has stopped. "
                   "GetKeyValue() from task /job:jax_worker/task:1 failed.")
CANCELLED = "CANCELLED: Cancelled by shutdown"


def test_classify_pending_vs_transient_vs_fatal():
    assert res.classify_kv_error(Exception(POLL_TIMEOUT)) == "pending"
    assert res.classify_kv_error(Exception(NOT_FOUND)) == "pending"
    assert res.classify_kv_error(Exception(CONN_REFUSED)) == "transient"
    # a connection-level timeout is a service fault, NOT a pending poll —
    # the naive TIMEOUT-substring check misclassified exactly this
    assert res.classify_kv_error(Exception(CONN_TIMEOUT)) == "transient"
    assert res.classify_kv_error(Exception(SHUTDOWN_STATE)) == "fatal"
    assert res.classify_kv_error(Exception(SERVICE_STOPPED)) == "fatal"
    assert res.classify_kv_error(Exception(CANCELLED)) == "fatal"
    # unknown errors are fatal: never retried forever
    assert res.classify_kv_error(Exception("something novel")) == "fatal"


def test_is_kv_timeout_never_true_for_dead_service():
    """The retry layer must never treat a dead/refusing service as a pending
    poll and sweep it forever (ISSUE 4 satellite: multihost.py:85)."""
    for s in (POLL_TIMEOUT, NOT_FOUND):
        assert multihost._is_kv_timeout(Exception(s)) is True
    for s in (CONN_REFUSED, CONN_TIMEOUT, SHUTDOWN_STATE, SERVICE_STOPPED,
              CANCELLED):
        assert multihost._is_kv_timeout(Exception(s)) is False


# ---------------------------------------------------------------------------
# Retry with backoff
# ---------------------------------------------------------------------------

def test_kv_retry_then_success(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")
    kv = FakeKV()
    kv.key_value_set("k", "v")
    kv.fail_next = 2
    assert res.kv_get(kv, "k", 100) == "v"
    assert res.retry_count() == 2


def test_kv_retry_exhaustion_names_key(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")
    monkeypatch.setenv("HOROVOD_KV_RETRIES", "2")
    kv = FakeKV()
    kv.fail_next = 99
    with pytest.raises(hvd.HorovodError) as ei:
        res.kv_get(kv, "hvd/neg/g1/s4/p0", 100)
    msg = str(ei.value)
    assert "hvd/neg/g1/s4/p0" in msg and "HOROVOD_KV_RETRIES" in msg
    assert kv.gets == 3  # 1 attempt + 2 retries, bounded


def test_kv_fatal_not_retried(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")

    class DeadKV:
        calls = 0

        def blocking_key_value_get(self, key, t):
            self.calls += 1
            raise RuntimeError(SERVICE_STOPPED)

    kv = DeadKV()
    with pytest.raises(RuntimeError, match="has stopped"):
        res.kv_get(kv, "k", 100)
    assert kv.calls == 1


def test_kv_pending_passes_through(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")
    kv = FakeKV()
    with pytest.raises(RuntimeError, match="DEADLINE_EXCEEDED"):
        res.kv_get(kv, "unset", 10)
    assert kv.gets == 1  # pending is the caller's poll loop, never retried


def test_kv_set_retry_after_landed_set_is_success(monkeypatch):
    """A retried set whose earlier attempt landed before the transient fault
    hits ALREADY_EXISTS on the retry — that IS success, not an error."""
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")

    class FlakySetKV(FakeKV):
        def __init__(self):
            super().__init__()
            self.flake_next = 1  # raise AFTER the value lands, once

        def key_value_set(self, key, value, allow_overwrite=False):
            super().key_value_set(key, value, allow_overwrite)
            if self.flake_next:
                self.flake_next -= 1
                raise RuntimeError("UNAVAILABLE: socket closed")

    kv = FlakySetKV()
    assert res.kv_set(kv, "k", "v1") is None
    assert kv.d["k"] == "v1"
    assert res.retry_count() == 1


def test_kv_set_first_attempt_duplicate_surfaces(monkeypatch):
    """ALREADY_EXISTS on the FIRST attempt is a genuine duplicate-key
    collision (e.g. a seq/generation replay), not a landed retry — it must
    surface, as it did pre-resilience."""
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")
    kv = FakeKV()
    res.kv_set(kv, "k", "v1")
    with pytest.raises(RuntimeError, match="ALREADY_EXISTS"):
        res.kv_set(kv, "k", "v2")
    assert kv.d["k"] == "v1"


def test_backoff_decorrelated_jitter_bounds(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "10")
    monkeypatch.setenv("HOROVOD_KV_RETRIES", "6")
    sleeps = []
    monkeypatch.setattr(res.time, "sleep", lambda s: sleeps.append(s * 1000))
    kv = FakeKV()
    kv.key_value_set("k", "v")
    kv.fail_next = 6
    assert res.kv_get(kv, "k", 100) == "v"
    assert len(sleeps) == 6
    cap = 10 * res._BACKOFF_CAP_FACTOR
    prev = 10.0
    for ms in sleeps:
        assert 10.0 <= ms <= min(cap, max(10.0, prev * 3)) + 1e-9
        prev = ms


def test_injected_kv_fault_retried(monkeypatch):
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "1")
    monkeypatch.setenv("HOROVOD_FAULT_INJECT", "kv_timeout@seq=0,times=1")
    res.reset_injector()
    kv = FakeKV()
    kv.key_value_set("k", "v")
    assert res.kv_get(kv, "k", 100) == "v"
    assert res.retry_count() == 1


# ---------------------------------------------------------------------------
# Heartbeat / liveness
# ---------------------------------------------------------------------------

def test_heartbeat_publishes_and_stops():
    kv = FakeKV()
    hb = res.Heartbeat(kv, pid=0, interval=0.02)
    hb.start()
    try:
        time.sleep(0.1)
        key = res._hb_key(_state.generation(), 0)
        t_pub = json.loads(kv.d[key])["t"]
        assert abs(time.time() - t_pub) < 5.0
    finally:
        hb.stop()


def test_liveness_names_dead_process(monkeypatch):
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "1")
    kv = FakeKV()
    kv.key_value_set(res._hb_key(_state.generation(), 1),
                     json.dumps({"t": time.time() - 30.0}))
    lv = res.Liveness()
    with pytest.raises(hvd.HorovodError) as ei:
        lv.check(kv, [1], context="negotiating tensor grad_0 (index 7)")
    msg = str(ei.value)
    assert "process 1" in msg and "last heartbeat" in msg
    assert "negotiating tensor grad_0" in msg


def test_liveness_fresh_peer_and_disabled(monkeypatch):
    kv = FakeKV()
    kv.key_value_set(res._hb_key(_state.generation(), 1),
                     json.dumps({"t": time.time()}))
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "5")
    lv = res.Liveness()
    lv.check(kv, [1])              # fresh heartbeat: alive
    lv.check(kv, [2])              # never-seen peer: startup grace
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "0")
    kv.key_value_set(res._hb_key(_state.generation(), 3),
                     json.dumps({"t": time.time() - 1e6}))
    res.Liveness().check(kv, [3])  # disabled: no-op even for stale peers


def test_liveness_grace_restored_by_generation_bump(monkeypatch):
    """A pre-bump heartbeat sighting must not age a slow-but-healthy peer
    into a dead verdict after Trainer.restore bumps the generation: the
    last-seen cache is generation-keyed, so the never-heartbeat startup
    grace applies afresh."""
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "1")
    kv = FakeKV()
    gen = _state.generation()
    kv.key_value_set(res._hb_key(gen, 1), json.dumps({"t": time.time() - 30}))
    lv = res.Liveness()
    with pytest.raises(hvd.HorovodError):
        lv.check(kv, [1])  # stale in THIS generation: dead
    monkeypatch.setattr(_state, "generation", lambda: gen + 1)
    lv.check(kv, [1])  # new generation, no new-gen key yet: startup grace


def test_wait_kv_timeout_and_liveness(monkeypatch):
    kv = FakeKV()
    with pytest.raises(res.KVTimeout) as ei:
        res.wait_kv(kv, "never/set", 60, poll_ms=20)
    assert ei.value.key == "never/set"
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "1")
    kv.key_value_set(res._hb_key(_state.generation(), 0),
                     json.dumps({"t": time.time() - 30.0}))
    with pytest.raises(hvd.HorovodError, match="process 0"):
        res.wait_kv(kv, "never/set", 60_000, pids=(0,), poll_ms=20,
                    context="waiting for the coordinator's verdict")


# ---------------------------------------------------------------------------
# Env knobs
# ---------------------------------------------------------------------------

def test_env_knob_parsing(monkeypatch):
    for var in ("HOROVOD_KV_RETRIES", "HOROVOD_KV_BACKOFF_MS",
                "HOROVOD_LIVENESS_INTERVAL", "HOROVOD_LIVENESS_TIMEOUT"):
        monkeypatch.delenv(var, raising=False)
    assert env.kv_retries() == 3
    assert env.kv_backoff_ms() == 50.0
    assert env.liveness_interval_seconds() == 10.0
    assert env.liveness_timeout_seconds() == 0.0
    monkeypatch.setenv("HOROVOD_KV_RETRIES", "7")
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "2.5")
    monkeypatch.setenv("HOROVOD_LIVENESS_INTERVAL", "1")
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "30")
    assert env.kv_retries() == 7
    assert env.kv_backoff_ms() == 2.5
    assert env.liveness_interval_seconds() == 1.0
    assert env.liveness_timeout_seconds() == 30.0
    monkeypatch.setenv("HOROVOD_KV_RETRIES", "1O")  # letter-O typo
    with pytest.raises(ValueError, match="KV_RETRIES"):
        env.kv_retries()  # a typo'd budget must not silently run defaults
    monkeypatch.setenv("HOROVOD_KV_BACKOFF_MS", "junk")
    with pytest.raises(ValueError, match="KV_BACKOFF"):
        env.kv_backoff_ms()
    monkeypatch.setenv("HOROVOD_LIVENESS_INTERVAL", "O")  # letter-O typo
    with pytest.raises(ValueError, match="LIVENESS_INTERVAL"):
        env.liveness_interval_seconds()
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "junk")
    with pytest.raises(ValueError, match="LIVENESS_TIMEOUT"):
        env.liveness_timeout_seconds()  # hang-bounding knob: typo must raise
    monkeypatch.setenv("HOROVOD_LIVENESS_TIMEOUT", "inf")
    assert env.liveness_timeout_seconds() == 0.0


# ---------------------------------------------------------------------------
# Timeline atexit flush (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_timeline_atexit_registered_and_idempotent(tmp_path, monkeypatch):
    registered = []
    monkeypatch.setattr(timeline.atexit, "register",
                        lambda fn: registered.append(fn))
    monkeypatch.setattr(timeline.atexit, "unregister",
                        lambda fn: registered.remove(fn))
    path = str(tmp_path / "tl.json")
    tl = timeline._ChromeTraceWriter(path)
    assert registered == [tl.close]
    tl.event("t0", "QUEUE", "B")
    tl.close()
    assert registered == []  # unregistered after explicit close
    tl.close()               # idempotent: atexit firing after stop() is fine
    tl.event("t0", "QUEUE", "E")  # late event after close: dropped, no raise
    events = json.loads(open(path).read().rstrip().rstrip(",") + "]")
    assert any(e.get("name") == "QUEUE" for e in events)


def test_timeline_atexit_flushes_buffered_events(tmp_path):
    """The last <=1s of buffered events must survive an uncaught exception:
    the atexit hook closes (flushes) the writer at interpreter teardown."""
    path = tmp_path / "crash_tl.json"
    script = (
        "from horovod_tpu.core import timeline\n"
        f"tl = timeline._ChromeTraceWriter({str(path)!r})\n"
        "tl.event('grad_0', 'NEGOTIATE_ALLREDUCE', 'B')\n"
        "raise RuntimeError('uncaught crash')\n"
    )
    r = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "uncaught crash" in r.stderr
    events = json.loads(path.read_text().rstrip().rstrip(",") + "]")
    assert any(e.get("name") == "NEGOTIATE_ALLREDUCE" for e in events)


# ---------------------------------------------------------------------------
# Crash-safe checkpoints
# ---------------------------------------------------------------------------

def _save_epochs(d, n, torn=None, monkeypatch=None):
    saved = {}
    for e in range(n):
        if torn is not None and e == torn:
            monkeypatch.setenv("HOROVOD_FAULT_INJECT",
                               f"torn_write@epoch={torn}")
            res.reset_injector()
        w = np.arange(16, dtype=np.float32) * (e + 1)
        ckpt.save(str(d), {"params": {"w": w}}, epoch=e)
        saved[e] = w
        if torn is not None and e == torn:
            monkeypatch.delenv("HOROVOD_FAULT_INJECT")
            res.reset_injector()
    return saved


def test_checkpoint_atomic_write_and_manifest(tmp_path):
    _save_epochs(tmp_path, 1)
    names = os.listdir(tmp_path)
    assert "checkpoint-00000.msgpack" in names
    assert "checkpoint-00000.manifest.json" in names
    assert not any(".tmp" in n for n in names)
    man = json.load(open(tmp_path / "checkpoint-00000.manifest.json"))
    ent = man["files"]["checkpoint-00000.msgpack"]
    data = open(tmp_path / "checkpoint-00000.msgpack", "rb").read()
    assert ent["size"] == len(data)
    assert ent["crc32"] == res.zlib_crc(data) if hasattr(res, "zlib_crc") \
        else True
    ok, why = ckpt.verify_epoch(str(tmp_path), 0)
    assert ok, why


def test_torn_write_skipped_and_fallback(tmp_path, monkeypatch):
    saved = _save_epochs(tmp_path, 3, torn=2, monkeypatch=monkeypatch)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert ckpt.latest_epoch(str(tmp_path)) == 1
    assert any("torn write" in str(w.message) for w in caught)
    assert ckpt.latest_epoch(str(tmp_path), verify=False) == 2
    restored = ckpt.load(str(tmp_path),
                         {"params": {"w": np.zeros(16, np.float32)},
                          "epoch": -1})
    assert restored["epoch"] == 1
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  saved[1])  # bit-identical fallback
    with pytest.raises(hvd.HorovodError, match="integrity"):
        ckpt.load(str(tmp_path),
                  {"params": {"w": np.zeros(16, np.float32)}, "epoch": -1},
                  epoch=2)


def test_corrupt_payload_detected_by_crc(tmp_path):
    _save_epochs(tmp_path, 2)
    p = tmp_path / "checkpoint-00001.msgpack"
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF  # same size, flipped bit
    p.write_bytes(bytes(raw))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert ckpt.latest_epoch(str(tmp_path)) == 0
    assert any("CRC32" in str(w.message) for w in caught)


def test_legacy_checkpoint_without_manifest_accepted(tmp_path):
    from flax import serialization

    # a pre-manifest checkpoint: raw msgpack, no sidecar
    data = serialization.to_bytes({"params": {"w": np.ones(4, np.float32)},
                                   "epoch": 5})
    (tmp_path / "checkpoint-00005.msgpack").write_bytes(data)
    assert ckpt.latest_epoch(str(tmp_path)) == 5
    restored = ckpt.load(str(tmp_path),
                         {"params": {"w": np.zeros(4, np.float32)},
                          "epoch": -1})
    assert restored["epoch"] == 5


def test_sharded_checkpoint_manifest_roundtrip(tmp_path, world):
    rows = hvd.rank_stack([np.full((2,), float(r), np.float32)
                           for r in range(hvd.size())])
    ckpt.save_sharded(str(tmp_path), {"w": rows}, epoch=1)
    assert any("manifest" in n for n in os.listdir(tmp_path))
    assert ckpt.latest_sharded_epoch(str(tmp_path)) == 1
    ok, why = ckpt.verify_sharded_epoch(str(tmp_path), 1)
    assert ok, why
    # corrupt this process's shard: the scan must skip the epoch
    shard = tmp_path / "checkpoint-00001.shard000.msgpack"
    raw = bytearray(shard.read_bytes())
    raw[0] ^= 0xFF
    shard.write_bytes(bytes(raw))
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        assert ckpt.latest_sharded_epoch(str(tmp_path)) == -1
    with pytest.raises(hvd.HorovodError, match="integrity"):
        ckpt.load_sharded(str(tmp_path), {"w": rows, "epoch": 0}, epoch=1)


# ---------------------------------------------------------------------------
# Resume agreement + Trainer restore
# ---------------------------------------------------------------------------

def test_agree_on_resume_epoch_skips_torn(tmp_path, world, monkeypatch):
    _save_epochs(tmp_path, 4, torn=3, monkeypatch=monkeypatch)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        assert ckpt.agree_on_resume_epoch(str(tmp_path)) == 2
    assert ckpt.agree_on_resume_epoch(str(tmp_path / "empty")) == -1


def test_agree_on_resume_epoch_crc_checks_agreed(tmp_path, world):
    _save_epochs(tmp_path, 3)
    p = tmp_path / "checkpoint-00002.msgpack"
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF  # same size: survives the size-only scan
    p.write_bytes(bytes(raw))
    with pytest.raises(hvd.HorovodError, match="CRC"):
        ckpt.agree_on_resume_epoch(str(tmp_path))


def test_load_sharded_epoch_none_agrees_and_skips_torn(tmp_path, world,
                                                       monkeypatch):
    rows = hvd.rank_stack([np.full((2,), float(r), np.float32)
                           for r in range(hvd.size())])
    ckpt.save_sharded(str(tmp_path), {"w": rows}, epoch=1)
    monkeypatch.setenv("HOROVOD_FAULT_INJECT", "torn_write@epoch=2")
    res.reset_injector()
    ckpt.save_sharded(str(tmp_path), {"w": rows}, epoch=2)
    monkeypatch.delenv("HOROVOD_FAULT_INJECT")
    res.reset_injector()
    template = {"w": hvd.rank_stack([np.zeros((2,), np.float32)
                                     for _ in range(hvd.size())]),
                "epoch": 0}
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        restored = ckpt.load_sharded(str(tmp_path), template)
    assert restored["epoch"] == 1  # torn epoch 2 excluded from agreement
    with pytest.raises(FileNotFoundError):
        ckpt.load_sharded(str(tmp_path / "empty"), template)


def _make_trainer(world):
    import jax.numpy as jnp

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.RandomState(0)
    w0 = {"w": rng.randn(4, 2).astype(np.float32)}
    n = hvd.size()
    xs = rng.randn(n, 8, 4).astype(np.float32)
    ys = rng.randn(n, 8, 2).astype(np.float32)
    batch = (hvd.rank_stack([xs[r] for r in range(n)]),
             hvd.rank_stack([ys[r] for r in range(n)]))
    tr = loop.Trainer(loss_fn, loop.sgd(0.05))
    tr.init_state(w0)
    return tr, batch, w0


def test_trainer_restore_bumps_generation(tmp_path, world):
    tr, batch, w0 = _make_trainer(world)
    cb = callbacks.ModelCheckpointCallback(str(tmp_path), every_epochs=1)
    tr.fit([batch], epochs=2, steps_per_epoch=2, callbacks=[cb],
           verbose=False)
    w_after = np.asarray(tr.params["w"])

    tr2, batch2, _ = _make_trainer(world)
    gen_before = _state.generation()
    assert tr2.restore(str(tmp_path)) == 2
    assert _state.generation() == gen_before + 1
    np.testing.assert_array_equal(np.asarray(tr2.params["w"]), w_after)
    hist = tr2.fit([batch2], epochs=3, steps_per_epoch=2, verbose=False)
    assert tr2.epoch == 3 and len(hist["loss"]) == 1  # one resumed epoch


def test_trainer_fit_resume_param(tmp_path, world):
    tr, batch, _ = _make_trainer(world)
    cb = callbacks.ModelCheckpointCallback(str(tmp_path), every_epochs=1)
    tr.fit([batch], epochs=2, steps_per_epoch=2, callbacks=[cb],
           verbose=False)
    tr2, batch2, _ = _make_trainer(world)
    tr2.fit([batch2], epochs=3, steps_per_epoch=2, callbacks=[cb],
            verbose=False, resume=str(tmp_path))
    assert tr2.epoch == 3
    # fresh directory: resume= starts clean at epoch 0
    tr3, batch3, _ = _make_trainer(world)
    tr3.fit([batch3], epochs=1, steps_per_epoch=2, verbose=False,
            resume=str(tmp_path / "nothing_here"))
    assert tr3.epoch == 1


def test_fit_resume_conflicts_with_initial_epoch(tmp_path, world):
    tr, batch, _ = _make_trainer(world)
    with pytest.raises(hvd.HorovodError, match="initial_epoch"):
        tr.fit([batch], epochs=1, steps_per_epoch=1, verbose=False,
               resume=str(tmp_path), initial_epoch=0)


def test_trainer_restore_requires_state(world, tmp_path):
    import jax.numpy as jnp

    tr = loop.Trainer(lambda p, b: jnp.float32(0.0), loop.sgd(0.1))
    with pytest.raises(hvd.HorovodError, match="init_state"):
        tr.restore(str(tmp_path))


# ---------------------------------------------------------------------------
# The end-to-end drill (multi-process: slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fault_drill_end_to_end(tmp_path):
    """tools/fault_drill.py --scenario all: every injected fault path —
    retried kv_timeout surfaced with its key, dead rank named from a
    negotiate-style wait, torn write skipped with bit-identical fallback,
    and a killed+restarted worker resuming bit-identically (acceptance
    criteria of ISSUE 4)."""
    env_ = dict(os.environ)
    for var in ("HOROVOD_FAULT_INJECT", "HOROVOD_TIMELINE"):
        env_.pop(var, None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fault_drill.py"),
         "--scenario", "all", "--workdir", str(tmp_path)],
        env=env_, cwd=REPO, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    assert "FAULT DRILL PASSED: kv_timeout, liveness, torn_write, crash" \
        in r.stdout
