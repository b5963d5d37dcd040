"""SPMD trace context: how collectives know they are inside a mesh program.

The reference distinguishes graph construction (TF ops are built once,
mpi_ops.py:191-270) from execution (the background thread runs MPI,
mpi_ops.cc:1464-1733). The TPU-native analog: ``hvd.spmd`` wraps a step
function in ``jax.shard_map`` over a group's mesh, and while that function is
being traced, a ``TraceContext`` is active so that ``hvd.allreduce`` et al.
lower to ``lax.psum``/``lax.all_gather`` on the mesh axis instead of launching
an eager dispatch, and ``hvd.rank()`` returns the traced per-device axis index.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
from jax import lax

from horovod_tpu.core import state as _state


@dataclasses.dataclass
class TraceContext:
    """Active while tracing a shard_map'ed step function.

    ``axis_name`` is the mesh axis carrying the ranks; ``group_index`` is the
    group whose mesh the program runs on (its ranks define the world the traced
    program sees).
    """

    axis_name: str
    group_index: int
    # Trace-time tensor-name registry: name -> (op, dtype, shape, group,
    # root). The reference's define-by-name contract makes the tensor name
    # the cross-rank correlation key (mpi_ops.py:191-209); two different
    # collectives under one name in one program is the coordinator-error
    # case (ConstructMPIResponse, mpi_ops.cc:374-592). SPMD makes cross-rank
    # mismatch impossible, so the remaining detectable misuse is same-name /
    # different-metadata within one traced program.
    names: dict = dataclasses.field(default_factory=dict)
    # name -> tuple of member-tensor labels, for collectives that carry a
    # fusion bucket (fused_apply reduces several gradients in one
    # allreduce); lets the device timeline map the bucket span back onto
    # its member rows. Not part of the metadata compare: a re-trace with
    # the same collective keeps the first registration's members.
    members: dict = dataclasses.field(default_factory=dict)

    def register(self, name: str, op: str, dtype, shape, group: int,
                 root_rank: int | None = None,
                 members: tuple[str, ...] | None = None) -> None:
        from horovod_tpu.core.state import HorovodError

        meta = (op, str(dtype), tuple(shape), group, root_rank)
        prev = self.names.get(name)
        if prev is None:
            self.names[name] = meta
            if members:
                self.members[name] = tuple(members)
            return
        if prev == meta:
            return  # same collective re-traced (e.g. inside lax.scan) — fine
        if prev[0] != op:
            raise HorovodError(
                f"Mismatched collective operations: tensor {name} was "
                f"submitted as both {prev[0]} and {op} in one program.")
        if prev[1] != meta[1]:
            raise HorovodError(
                f"Mismatched data types: tensor {name} was submitted with "
                f"type {prev[1]} and type {meta[1]} in one program.")
        if prev[2] != meta[2]:
            raise HorovodError(
                f"Mismatched {op.lower()} tensor shapes: tensor {name} was "
                f"submitted with shape {list(prev[2])} and shape "
                f"{list(meta[2])} in one program.")
        raise HorovodError(
            f"Tensor {name} was submitted twice with conflicting group/root "
            f"({prev[3:]} vs {meta[3:]}); use distinct names.")

    def member_positions(self, group: int) -> list[int]:
        """Mesh-axis positions of ``group``'s members, in group-rank order.

        The single source of the target-group → program-mesh mapping used by
        both grouped collectives (axis_index_groups) and the sequence-
        parallel rings. Raises if a member is outside the program's mesh.
        """
        from horovod_tpu.core.state import HorovodError

        target = _state.get_group(group)
        if group == self.group_index:
            return list(range(target.size))
        prog = _state.get_group(self.group_index)
        positions = []
        for r in target.ranks:
            if r not in prog.ranks:
                raise HorovodError(
                    f"Group {group} rank {r} is not part of the mesh the "
                    f"SPMD program runs on (group {self.group_index}).")
            positions.append(prog.ranks.index(r))
        return positions

    def _axis_index(self):
        return lax.axis_index(self.axis_name)

    def rank(self, group: int = 0):
        """Traced group-local rank of the executing device.

        When the program runs on group G's mesh, the axis index IS the G-local
        rank. For a different group g, map axis index -> global rank -> g-local
        rank via a gather from a constant table (compiles to a tiny
        dynamic-slice; -1 for non-members, matching the reference's 'not a
        member' convention).
        """
        import jax.numpy as jnp

        idx = self._axis_index()
        prog_group = _state.get_group(self.group_index)
        if group == self.group_index:
            return idx
        target = _state.get_group(group)
        table = jnp.array(
            [target.group_rank_of(r) for r in prog_group.ranks], dtype=jnp.int32)
        return table[idx]

    def global_rank(self):
        import jax.numpy as jnp

        idx = self._axis_index()
        prog_group = _state.get_group(self.group_index)
        table = jnp.array(prog_group.ranks, dtype=jnp.int32)
        return table[idx]

    def local_rank(self):
        """Traced rank within the executing device's host (uniform hosts)."""
        nlocal = max(1, len(jax.local_devices()))
        return self.global_rank() % nlocal


_tls = threading.local()


def current() -> TraceContext | None:
    return getattr(_tls, "ctx", None)


class _Scope:
    def __init__(self, ctx: TraceContext) -> None:
        self.ctx = ctx
        self.prev: Any = None

    def __enter__(self) -> TraceContext:
        self.prev = getattr(_tls, "ctx", None)
        _tls.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc) -> None:
        _tls.ctx = self.prev


def enter(axis_name: str, group_index: int) -> _Scope:
    return _Scope(TraceContext(axis_name=axis_name, group_index=group_index))
