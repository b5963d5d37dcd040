"""Multi-host (multi-controller) control plane.

The reference's entire background-thread + coordinator machinery exists to
coordinate N independent processes: every rank MPI_Sends its ``MPIRequest``s
to rank 0, which cross-validates and broadcasts a response
(/root/reference/horovod/tensorflow/mpi_ops.cc:1464-1733). On TPU pods the
same N-independent-processes problem appears in multi-controller JAX (one
process per host): each process traces and compiles the SAME program, and
nothing in stock JAX tells you *which process diverged* when they don't — you
get a hang or a cryptic XLA error.

This module is the TPU-native coordinator. The JAX **coordination service**
(the KV store + barriers every multi-controller job already runs,
``jax.distributed.initialize`` — the analog of ``MPI_Init``) replaces
MPI_Send/Probe/Recv as the control-plane transport:

* :class:`Negotiator` — name-keyed cross-process request validation. Each
  process submits a descriptor (name, op, dtype, shape, root, group) for the
  ranks it hosts; process 0 collects one entry per process, merges them into
  per-rank requests, runs the same validation as the single-controller path
  (``negotiate.validate``, byte-matching the reference's
  ``ConstructMPIResponse`` messages, mpi_ops.cc:374-592), and publishes the
  verdict. Every process raises the same :class:`HorovodError` on mismatch —
  the multi-process analog of the reference's error-path tests
  (mpi_ops_test.py:284-356).
* **Stall detection that can actually fire** (mpi_ops.cc:1369-1412): while
  waiting for slow processes, the coordinator periodically reports tensors
  that have requests from only a subset of processes, naming ready and
  missing ranks in the reference's format. Single-controller eager mode
  submits all ranks atomically, so this path is where stall detection is
  real.
* **Schedule validation for compiled programs**: before executing a freshly
  traced ``hvd.spmd`` program, every process negotiates its full ordered
  collective schedule (names + metadata). SPMD correctness requires identical
  programs; auto-generated names drifting out of sync across processes — the
  exact failure Horovod's name-keyed negotiation exists to catch
  (mpi_ops.cc:341-366) — is reported with the first divergence instead of a
  silent hang.

Control-plane traffic is host-side gRPC to the coordination service; tensor
bytes still move only through XLA collectives over ICI/DCN.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Sequence

import jax

from horovod_tpu.analysis import protocol as _proto
from horovod_tpu.core import negotiate as _neg
from horovod_tpu.core import resilience as _res
from horovod_tpu.core.state import HorovodError
from horovod_tpu.utils import env as _env

# KV keys are generation-scoped and built by the pure protocol module
# (analysis/protocol.py neg_key/verdict_key/sched_key) — the SAME key
# builders the hvd-model checker explores, so the checker's HVD205
# generation-isolation sweep covers the live namespace by construction.
# A monotonically increasing per-process negotiation index keeps keys
# unique across repeated negotiations of the same tensor name (each
# training step re-negotiates in eager mode, exactly like the reference
# re-keys its MessageTable every tick — mpi_ops.cc:589).
_GET_POLL_MS = 200

# Which (name, op, group_size) submissions may replay a cached verdict —
# and which must pay the full rendezvous — is the pure lockstep decision
# _proto.replay_fingerprint (CACHEABLE_OPS excludes the allgather family,
# whose verdicts carry per-rank sizes; AUTO_NAME-generated names are
# fresh every call, so caching them would only grow the dict without
# bound — steady-state replay requires EXPLICIT name= arguments, the
# stable-name contract the reference gets for free from graph-node names,
# mpi_ops.py:191-209).


def _is_kv_timeout(e: Exception) -> bool:
    """True when a blocking_key_value_get raised because the key isn't set
    yet (poll timeout) rather than because the service died or refused.
    Delegates to the resilience layer's three-way classification
    (pending / transient / fatal) so a connection-refused or
    service-shut-down error is never mistaken for a pending poll and
    swept forever (tests/test_resilience.py pins the real jax client
    error strings)."""
    return _res.is_kv_timeout(e)


def _kv_delete(client, key: str) -> None:
    try:
        client.key_value_delete(key)
    except Exception:
        pass  # best-effort cleanup; absent API or missing key is fine


def active() -> bool:
    """True when this job runs multi-controller (one process per host)."""
    return jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def _kv_client():
    """The coordination-service KV client.

    jax exposes the distributed client only under ``jax._src``; there is no
    public KV API as of jax 0.9. Gated here so a rename breaks one function
    with a clear message instead of every call site.
    """
    try:
        from jax._src import distributed

        client = distributed.global_state.client
    except Exception as e:  # pragma: no cover - jax internals moved
        raise HorovodError(
            "Multi-host coordination needs the JAX distributed client "
            "(jax.distributed.initialize must run first; jax internals may "
            f"have moved): {e}") from None
    if client is None:
        raise HorovodError(
            "Multi-host coordination requires jax.distributed.initialize() "
            "before hvd.init() (the analog of launching under mpirun).")
    return client


class Negotiator:
    """Cross-process name-keyed request negotiation (coordinator = process 0).

    One instance per ``hvd.init`` generation. Every process must issue its
    eager collectives in one consistent global order (the rendezvous is
    keyed by each process's negotiation index); concurrent submission from
    multiple Python threads is not supported — thread scheduling would
    order the indices differently per process. The reference's name-keyed
    MessageTable tolerated reordering because its background thread
    decoupled submission from negotiation (mpi_ops.cc:1464-1733); here
    negotiation is synchronous, which is also what makes desync errors
    crisp.
    """

    def __init__(self, generation: int) -> None:
        self.generation = generation
        self._counts: dict[str, int] = {}
        self._seq = 0
        self._lock = threading.Lock()
        self.stall_seconds = _env.stall_warning_seconds()
        # Validated-verdict cache: fingerprint of this process's submission
        # -> the agreed Response. A steady-state eager loop re-issues the
        # same collectives with the same metadata every step; without the
        # cache each call pays >=2 blocking KV round-trips through the
        # coordination service ON THE CALLER'S CRITICAL PATH (the
        # reference re-validates per tick too, but behind its background
        # thread — mpi_ops.cc:1464-1733). Replay is metadata-sound for
        # size-invariant ops only (protocol.CACHEABLE_OPS); the detection
        # trade and the HOROVOD_EAGER_CACHE kill switch are documented on
        # negotiate().
        self._verdicts: dict[tuple, _neg.Response] = {}

    # -- key plumbing -------------------------------------------------------

    def _epoch(self, name: str) -> int:
        with self._lock:
            n = self._counts.get(name, 0)
            self._counts[name] = n + 1
            return n

    def _next_seq(self) -> int:
        with self._lock:
            i = self._seq
            self._seq += 1
            return i

    def _key(self, seq: int, pid: int) -> str:
        return _proto.neg_key(self.generation, seq, pid)

    def _verdict_key(self, seq: int) -> str:
        return _proto.verdict_key(self.generation, seq)

    # -- the protocol -------------------------------------------------------

    def negotiate(self, name: str, requests: Sequence[_neg.Request],
                  group_size: int,
                  op: "_neg.CollectiveOp | None" = None) -> _neg.Response:
        """Submit this process's per-rank requests; return the validated
        response every process agrees on, or raise the coordinator's error.

        The rendezvous is keyed by a per-process NEGOTIATION INDEX, not by
        the tensor name: each process's i-th eager collective meets the
        others' i-th at index i, and the coordinator cross-checks that they
        all carry the same name. A drifted auto-name (one process issued an
        extra unnamed collective) therefore raises a crisp schedule-
        divergence error naming both tensors instead of stalling two
        name-keyed rendezvous forever (the failure mode the reference's
        name-keyed MessageTable can only surface as a stall warning,
        mpi_ops.cc:1369-1412). Index-keying loses nothing: eager
        negotiation blocks, so every process issues its collectives in
        program order anyway. A process with NO members of the group
        submits an empty request list at the same index, so the
        coordinator still hears from every process.

        **Steady-state amortization**: a resubmission whose (name, op,
        group_size) fingerprint already validated replays
        the cached verdict WITHOUT touching the coordination service —
        zero KV round-trips (measured on the 2-process CPU world: ~7 ms
        of negotiation overhead per eager call drops to zero, 18.8 →
        11.9 ms/call end-to-end; tests/multihost_worker.py prints the
        numbers). The FIRST occurrence
        of every distinct collective still cross-validates fully. The
        trade: a process that structurally diverges mid-run among
        already-validated names (e.g. reorders two cached collectives) is
        no longer caught at negotiation time — exactly the reference's
        exposure, whose name-keyed MessageTable also matches any
        re-submission of a known-good name (mpi_ops.cc:341-366). And a
        process that issues a NEW collective while its peers replay
        cached ones blocks at a seq index the peers never reach: the
        coordinator surfaces that as periodic stall warnings naming the
        missing ranks, a non-coordinator as a timeout error naming the
        tensor and pointing here (no longer the pre-cache crisp
        divergence error — the peers never rendezvous to compare names).
        ``HOROVOD_EAGER_CACHE=0`` disables replay for full per-call
        validation.
        """
        # Cacheability — and the HIT decision itself — MUST be decided
        # identically on every process, including one that drives no ranks
        # of the group and submits an empty request list, or their
        # negotiation sequence counters drift apart. The fingerprint is
        # therefore (name, op, group_size) ONLY — metadata-independent,
        # exactly the reference's name-keyed MessageTable replay semantics
        # (mpi_ops.cc:341-366): a member process whose request metadata is
        # in the fingerprint would cache-miss on a legitimate dtype/shape
        # change while a memberless process (empty request tuple,
        # fingerprint never changes) cache-hits — seq counters drift and
        # the job hangs. The trade inherited with name-keyed replay: a
        # named collective resubmitted with DIFFERENT metadata replays the
        # old verdict unvalidated (allgather-family ops, whose verdict
        # carries sizes, are excluded via protocol.CACHEABLE_OPS anyway); use
        # distinct names for shape-varying collectives, or
        # HOROVOD_EAGER_CACHE=0 for full per-call validation.
        fp = _proto.replay_fingerprint(
            name, None if op is None else op.value, group_size,
            tuple(r.op.value for r in requests),
            _env.eager_cache_enabled())
        if fp is not None:
            hit = self._verdicts.get(fp)
            if hit is not None:
                return hit
        seq = self._next_seq()
        client = _kv_client()
        pid = jax.process_index()
        payload = json.dumps({
            "name": name,
            "requests": [
                {"rank": r.rank, "name": r.name, "op": r.op.value,
                 "dtype": r.dtype, "shape": list(r.shape),
                 "root_rank": r.root_rank, "group": r.group}
                for r in requests
            ],
        })
        _res.kv_set(client, self._key(seq, pid), payload)

        if pid == 0:
            verdict = self._coordinate(client, name, seq, group_size)
            _res.kv_set(client, self._verdict_key(seq), verdict)
        else:
            try:
                # Chunked wait: between poll chunks the liveness registry is
                # consulted, so a DEAD coordinator raises a fatal error
                # naming it instead of burning the whole negotiation timeout.
                verdict = _res.wait_kv(
                    client, self._verdict_key(seq),
                    _env.negotiation_timeout_ms(), pids=(0,),
                    context=(f"waiting for the coordinator's verdict on "
                             f"tensor {name} (negotiation index {seq})"))
            except _res.KVTimeout as e:
                raise HorovodError(
                    f"Timed out waiting for the coordinator's verdict on "
                    f"tensor {name} (negotiation index {seq}). With the "
                    f"eager verdict cache enabled this usually means this "
                    f"process issued a collective its peers did not (they "
                    f"replayed cached verdicts and never reached index "
                    f"{seq}) — a schedule divergence. Re-run with "
                    f"HOROVOD_EAGER_CACHE=0 to get per-call validation "
                    f"naming the diverging tensors.") from e
        data = json.loads(verdict)
        if data.get("error"):
            raise HorovodError(data["error"])
        resp = _neg.Response(
            name=data["name"], op=_neg.CollectiveOp(data["op"]),
            dtype=data["dtype"], tensor_sizes=tuple(data["tensor_sizes"]),
            root_rank=data["root_rank"])
        if fp is not None:
            self._verdicts[fp] = resp
        return resp

    def _coordinate(self, client, name: str, seq: int,
                    group_size: int) -> str:
        """Process 0: gather every process's submission at this negotiation
        index (stall-sweeping while short), cross-check the names, merge,
        validate, serialize the verdict."""
        from horovod_tpu.core import timeline as _tl

        nprocs = jax.process_count()
        t0 = time.monotonic()
        last_warn = t0
        tl = _tl.session()
        negotiating = False  # NEGOTIATE_<op> opened once the op is known
        per_proc: dict[int, dict] = {}
        while len(per_proc) < nprocs:
            for p in range(nprocs):
                if p in per_proc:
                    continue
                try:
                    raw = _res.kv_get(client, self._key(seq, p),
                                      _GET_POLL_MS)
                except Exception as e:
                    if _is_kv_timeout(e):
                        continue  # just not submitted yet — keep sweeping
                    raise HorovodError(
                        f"Coordination service failed while negotiating "
                        f"tensor {name}: {e}") from e
                per_proc[p] = json.loads(raw)
                # Coordinator-side trace of negotiation progress: a
                # NEGOTIATE_<op> span opened at the first arrival with one
                # instant tick per rank AS EACH PROCESS LANDS, so the trace
                # shows which rank was late (NegotiateStart/RankReady,
                # timeline.cc:105-125). The reference's timeline is
                # coordinator-only for the same reason (mpi_ops.cc:351-363).
                if tl.active and per_proc[p]["requests"]:
                    if not negotiating:
                        op = _neg.CollectiveOp(
                            per_proc[p]["requests"][0]["op"])
                        tl.event(name, f"NEGOTIATE_{op.name.lower()}", "B")
                        negotiating = True
                    for r in per_proc[p]["requests"]:
                        tl.rank_ready(name, r["rank"])
            # A missing process may be slow (stall warning below) or DEAD:
            # the liveness registry turns the latter into a fatal error
            # naming the dead rank(s) instead of an indefinite sweep
            # (opt-in via HOROVOD_LIVENESS_TIMEOUT; rate-limited inside).
            if len(per_proc) < nprocs:
                _res.liveness().maybe_check(
                    client, [p for p in range(nprocs) if p not in per_proc],
                    context=f"negotiating tensor {name} (index {seq})")
            now = time.monotonic()
            if (len(per_proc) < nprocs
                    and self.stall_seconds > 0
                    and now - last_warn > self.stall_seconds):
                last_warn = now
                ready = sorted(r["rank"] for sub in per_proc.values()
                               for r in sub["requests"])
                missing = sorted(set(range(group_size)) - set(ready))
                # Reference format: CheckForStalledTensors, mpi_ops.cc:1380-1410.
                print(
                    "WARNING: One or more tensors were submitted to be "
                    "reduced, gathered or broadcasted by subset of ranks and "
                    "are waiting for remainder of ranks for more than "
                    f"{int(self.stall_seconds)} seconds. This may indicate "
                    "that different ranks are trying to submit different "
                    "tensors or that only subset of ranks is submitting "
                    "tensors, which will cause deadlock.\n"
                    f"Stalled ops: {name} "
                    f"[ready ranks: {ready}] [missing ranks: {missing}]",
                    flush=True)
        # Request keys are read only by the coordinator — free them now. The
        # previous index's verdict can also go: every process submitted at
        # THIS index, which it can only do after reading the last verdict.
        # (The reference clears its MessageTable entry per response the same
        # way, mpi_ops.cc:589 — without this the KV store grows per step
        # forever.)
        for p in range(nprocs):
            _kv_delete(client, self._key(seq, p))
        if seq > 0:
            _kv_delete(client, self._verdict_key(seq - 1))
        if negotiating:
            tl.event(name, "NEGOTIATE", "E")
        # The verdict — the crisp every-process's-i-th-collective-must-BE-
        # the-same-collective desync check, then merge + validate — is the
        # pure transition function the hvd-model checker explores
        # (analysis/protocol.py coordinate; validation itself byte-matches
        # the reference's ConstructMPIResponse messages). The arrival-time
        # NEGOTIATE/rank-ready events were emitted above, so nothing here
        # touches the timeline.
        return json.dumps(_proto.coordinate(per_proc, name, seq, group_size))

    # -- compiled-program schedule validation -------------------------------

    def validate_schedule(self, tag: str, schedule: list) -> None:
        """Cross-validate the ordered collective schedule of a freshly traced
        SPMD program: every process must have traced the identical sequence
        (names, ops, dtypes, shapes, groups, roots). ``tag`` identifies the
        program (wrapper id + signature).

        The multi-controller analog of per-tensor negotiation, hoisted to
        trace time: in compiled SPMD, order is fixed at trace, so one check
        per compilation covers every step that program will ever run.
        """
        client = _kv_client()
        pid = jax.process_index()
        epoch = self._epoch(f"sched/{tag}")
        key = _proto.sched_key(self.generation, tag, epoch)
        payload = json.dumps(schedule)
        _res.kv_set(client, f"{key}/p{pid}", payload)
        if pid == 0:
            # The coordinator waits indefinitely by default, sweeping stall
            # warnings (the CheckForStalledTensors contract — slow peers may
            # just be tracing/compiling a big program); only
            # non-coordinators bound their wait with
            # HOROVOD_NEGOTIATION_TIMEOUT. HOROVOD_SCHEDULE_TIMEOUT
            # (seconds; opt-in) hard-caps the sweep so a CRASHED peer —
            # which would otherwise hang the whole job forever — produces
            # a fatal, diagnosable error naming the missing process.
            cap_ms = _env.schedule_timeout_ms()
            error = None
            for p in range(1, jax.process_count()):
                t0 = last_warn = time.monotonic()
                while True:
                    try:
                        raw = _res.kv_get(client, f"{key}/p{p}",
                                          _GET_POLL_MS)
                        break
                    except Exception as e:
                        if not _is_kv_timeout(e):
                            raise HorovodError(
                                f"Coordination service failed while "
                                f"validating the schedule of program "
                                f"{tag}: {e}") from e
                        # Dead peer → fatal error naming it, without
                        # waiting for the (opt-in, possibly unbounded)
                        # schedule-timeout cap below.
                        _res.liveness().maybe_check(
                            client, (p,),
                            context=(f"waiting for process {p}'s "
                                     f"collective schedule for program "
                                     f"{tag}"))
                        now = time.monotonic()
                        if cap_ms and (now - t0) * 1000 > cap_ms:
                            raise HorovodError(
                                f"Coordinator gave up waiting for process "
                                f"{p}'s collective schedule for program "
                                f"{tag} after {int(now - t0)} seconds "
                                f"(HOROVOD_SCHEDULE_TIMEOUT). The process "
                                f"has likely crashed or structurally "
                                f"diverged; restart the job once the "
                                f"failed host is back.") from e
                        if (self.stall_seconds > 0
                                and now - last_warn > self.stall_seconds):
                            last_warn = now
                            print(
                                f"WARNING: process {p} has not submitted "
                                f"its collective schedule for program "
                                f"{tag} after {int(now - t0)} seconds; "
                                f"it may still be tracing/compiling, or "
                                f"it may have diverged.", flush=True)
                _kv_delete(client, f"{key}/p{p}")
                other = json.loads(raw)
                mismatch = _first_divergence(schedule, other)
                if mismatch and not error:
                    error = (
                        f"Mismatched collective schedules across processes "
                        f"for program {tag}: process 0 and process {p} "
                        f"diverge at position {mismatch[0]}: "
                        f"{mismatch[1]} vs {mismatch[2]}. All processes "
                        f"must build the same program; check for "
                        f"process-dependent control flow or unnamed "
                        f"collectives issued in different orders.")
            _res.kv_set(client, f"{key}/verdict",
                        json.dumps({"error": error}))
        else:
            try:
                raw = _res.wait_kv(
                    client, f"{key}/verdict",
                    _env.negotiation_timeout_ms(), pids=(0,),
                    context=(f"waiting for the coordinator's schedule "
                             f"verdict for program {tag}"))
            except _res.KVTimeout as e:
                raise HorovodError(
                    f"Timed out waiting for the coordinator's schedule "
                    f"verdict for program {tag} "
                    f"(HOROVOD_NEGOTIATION_TIMEOUT). The coordinator may "
                    f"still be waiting on a slower process's trace, or "
                    f"this process's schedule diverged.") from e
            error = json.loads(raw).get("error")
        if error:
            raise HorovodError(error)


def _first_divergence(a: list, b: list):
    # Pure comparison shared with the model checker (analysis/protocol.py).
    return _proto.first_divergence(a, b)


# -- module-level negotiator bound to the current init generation -----------

_negotiator: Negotiator | None = None
_negotiator_lock = threading.Lock()


def negotiator() -> Negotiator:
    from horovod_tpu.core import state as _state

    gen = _state.generation()
    global _negotiator
    with _negotiator_lock:
        if _negotiator is None or _negotiator.generation != gen:
            _negotiator = Negotiator(gen)
        return _negotiator
