"""Name-keyed negotiation semantics: request matching and validation.

The reference's coordinator collects one ``MPIRequest`` per rank per tensor
name and cross-validates them before issuing a collective
(``IncrementTensorCount`` mpi_ops.cc:341-366, ``ConstructMPIResponse``
mpi_ops.cc:374-592). On TPU with a single controller the requests for all
ranks are visible in one place, so "negotiation" reduces to the validation and
bookkeeping — but the *contract* is preserved exactly: the tensor NAME is the
cross-rank correlation key, and any mismatch in dtype / op / shape / root
raises :class:`HorovodError` with a message in the reference's format, which
is what the reference's error-path tests assert (mpi_ops_test.py:284-356).

The semantic checks themselves live in the side-effect-free protocol module
(:mod:`horovod_tpu.analysis.protocol` — ``validate_requests``), which the
``hvd-model`` checker exhaustively explores; this module is the live wrapper
that converts to/from the runtime's types and raises :class:`HorovodError`.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Sequence

from horovod_tpu.analysis import protocol as _proto
from horovod_tpu.core.state import HorovodError


class CollectiveOp(enum.Enum):
    # Values match the reference's MPIRequest_RequestType wire enum
    # (tensorflow/wire/mpi_message.fbs; GATHER added by the fork at
    # mpi_message_generated.h:71). Sourced from the pure protocol module
    # so the model checker and the runtime share one encoding.
    ALLREDUCE = _proto.OP_ALLREDUCE
    ALLGATHER = _proto.OP_ALLGATHER
    BROADCAST = _proto.OP_BROADCAST
    GATHER = _proto.OP_GATHER
    ALLTOALL = _proto.OP_ALLTOALL  # extension beyond the fork (0.19 API)
    REDUCESCATTER = _proto.OP_REDUCESCATTER  # extension (upstream 0.27 API)


@dataclasses.dataclass(frozen=True)
class Request:
    """One rank's intent to run a collective on a named tensor — the analog of
    ``MPIRequest`` (mpi_message.h:43-97)."""

    rank: int  # group-local rank submitting the request
    name: str
    op: CollectiveOp
    dtype: str
    shape: tuple[int, ...]
    root_rank: int = -1  # broadcast/gather only
    group: int = 0  # which group's communicator (mpi_message.h carries the
    #               group implicitly via which state's queue it sits in)


@dataclasses.dataclass(frozen=True)
class Response:
    """Validated execution plan for one named tensor — the analog of
    ``MPIResponse`` (mpi_message.h:103-140). ``tensor_sizes`` carries the
    per-rank first dimensions for allgather/gather, exactly the role of the
    response's ``tensor_sizes`` field (mpi_message.h:124-129)."""

    name: str
    op: CollectiveOp
    dtype: str
    tensor_sizes: tuple[int, ...] = ()
    root_rank: int = -1


def _to_proto(r: Request) -> _proto.Req:
    return _proto.Req(rank=r.rank, name=r.name, op=r.op.value, dtype=r.dtype,
                      shape=tuple(r.shape), root_rank=r.root_rank,
                      group=r.group)


def validate(requests: Sequence[Request], group_size: int) -> Response:
    """Cross-validate all ranks' requests for one tensor name: the semantic
    checks of ``ConstructMPIResponse`` (mpi_ops.cc:374-592) — dtype match
    (:387-398), op match (:400-416), exact shape match for
    allreduce/broadcast (:423-451), rank-count + trailing-dim match with
    per-rank first-dim collection for allgather/gather (:453-517), root-rank
    agreement for broadcast/gather (:519-539). Raises :class:`HorovodError`
    on any mismatch.

    The checks themselves are the pure transition function
    ``analysis.protocol.validate_requests`` — the exact code the
    ``hvd-model`` checker explores; this function converts types, raises,
    and writes the negotiation phases to the timeline (timeline.cc
    NEGOTIATE events via IncrementTensorCount). The error messages stay
    byte-identical to the reference's (mpi_ops_test.py:284-356 asserts
    them).
    """
    from horovod_tpu.core import timeline as _tl

    tl = _tl.session()
    traced = tl.active and bool(requests)
    if traced:
        tag = f"NEGOTIATE_{requests[0].op.name.lower()}"
        tl.event(requests[0].name, tag, "B")
        # Per-rank ready ticks (NegotiateRankReady, timeline.cc:117-125) —
        # in eager single-controller mode all ranks land atomically, so the
        # ticks are adjacent; in multi-host mode the coordinator emits them
        # as each process's submission arrives (multihost.Negotiator).
        for r in requests:
            tl.rank_ready(r.name, r.rank)
    try:
        verdict = _proto.validate_requests(
            tuple(_to_proto(r) for r in requests), group_size)
        if verdict.error is not None:
            raise HorovodError(verdict.error)
    finally:
        if traced:
            tl.event(requests[0].name, tag, "E")
    return Response(name=verdict.name, op=CollectiveOp(verdict.op),
                    dtype=verdict.dtype, tensor_sizes=verdict.tensor_sizes,
                    root_rank=verdict.root_rank)
