"""Multi-host initialization — the launcher story.

The reference's cluster boundary is ``mpirun`` + ``MPI_Init_thread``
(mpi_ops.cc:281-314, docs/running.md): N processes discover each other
through MPI. The TPU-native equivalent is the JAX distributed service: one
process per host, coordinated through ``jax.distributed.initialize``, after
which ``jax.devices()`` spans the whole pod slice and every hvd group/
collective works across hosts unchanged (collectives ride ICI within a
slice, DCN across slices — XLA's concern, not ours).

On Cloud TPU pods the coordinator address, process count and process id are
discovered from the TPU metadata environment automatically, so
``init_distributed()`` with no arguments is the whole launcher.
"""

from __future__ import annotations

import jax


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     group_ranks=None) -> None:
    """``jax.distributed.initialize`` + ``hvd.init`` in one call.

    The analog of the reference's ``mpirun ... ; hvd.init()`` pair. Safe to
    call when the distributed service is already up (re-initialization is
    skipped, matching InitializeHorovodOnce semantics).
    """
    if not jax.distributed.is_initialized():
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        jax.distributed.initialize(**kwargs)

    import horovod_tpu as hvd

    hvd.init(group_ranks)


def shutdown_distributed() -> None:
    """Tear down hvd state and the distributed service (job end)."""
    import horovod_tpu as hvd

    hvd.shutdown()
    try:
        jax.distributed.shutdown()
    except (RuntimeError, AttributeError):
        pass  # service was never up (single host)
