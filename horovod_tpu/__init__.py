"""horovod_tpu — a TPU-native framework with the capabilities of
rbpittman/horovod (Horovod v0.11.3 + custom MPI groups + rooted Gather).

Public API parity map (reference → here):

* ``hvd.init([[0,1,2],[2,3,4]])`` (mpi_ops.py:81-110) → :func:`init`, with the
  upstream-style no-argument default global group the fork left unfinished
  (SURVEY §2.9).
* ``rank/size/local_rank/local_size/global_rank/global_size``
  (mpi_ops.cc:1905-2001) → same names; ranks are TPU devices.
* ``allreduce/allgather/gather/broadcast`` with ``group=`` kwarg
  (mpi_ops.py:191-270) → same names, lowered to XLA collectives over ICI.
* ``DistributedOptimizer`` / ``broadcast_global_variables``
  (tensorflow/__init__.py:86-232) → :mod:`horovod_tpu.parallel.optimizer`.
* Keras callbacks (keras/callbacks.py) → :mod:`horovod_tpu.training`.
* Timeline / stall detection / env config (mpi_ops.cc:1486-1495, timeline.cc)
  → :mod:`horovod_tpu.core.timeline`, ``HOROVOD_TIMELINE`` etc.
"""

import time as _time

_t0 = _time.perf_counter_ns()  # the record's ``hvd/import`` row starts here

from horovod_tpu.utils.env import apply_platform_overrides as _apply_env

_apply_env()  # HOROVOD_CPU_DEVICES=N: simulated pod, before any backend exists
del _apply_env

from horovod_tpu.core.state import (
    AXIS_NAME,
    HorovodError,
    NotInitializedError,
    get_group,
    global_rank,
    global_size,
    init,
    is_initialized,
    local_rank,
    local_size,
    num_groups,
    rank,
    shutdown,
    size,
)
from horovod_tpu.ops.collectives import (
    allgather,
    allreduce,
    alltoall,
    reducescatter,
    broadcast,
    gather,
)
from horovod_tpu.ops.compression import (Bf16Compressor, Compressor,
                                          Int8Compressor)
from horovod_tpu.ops.flash_attention import (blockwise_attention,
                                              flash_attention,
                                              flash_attention_lse)
from horovod_tpu.ops.sparse import IndexedSlices, allreduce_indexed_slices
from horovod_tpu.parallel.optimizer import (
    DistributedOptimizer,
    ErrorFeedbackState,
    allreduce_gradients,
    broadcast_global_variables,
    broadcast_variables,
    sharded_optimizer,
)
from horovod_tpu.parallel.sequence import (
    local_attention,
    ring_attention,
    ulysses_attention,
    zigzag_positions,
    zigzag_shard,
    zigzag_unshard,
)
from horovod_tpu.parallel.expert import moe_capacity, moe_mlp
from horovod_tpu.parallel.pipeline import (gpipe, pipeline_1f1b,
                                            stage_split)
from horovod_tpu.parallel.tensor import (
    column_parallel,
    row_parallel,
    shard_columns,
    shard_rows,
    tp_attention,
    tp_mlp,
    tp_mlp_sp,
)
from horovod_tpu.parallel.spmd import (
    device_put_ranked,
    local_values,
    rank_stack,
    replicate,
    spmd,
)

__version__ = "0.1.0"

# Profile-guided auto-configuration (horovod_tpu/tune): note this
# rebinds the ``tune`` attribute from the subpackage module to the
# function — internal code must import ``from horovod_tpu.tune import
# ...`` (module form), which resolves via sys.modules and is unaffected.
from horovod_tpu.tune import TunedConfig, tune, tune_report  # noqa: E402

# Subpackage namespaces (imported after the base API so their modules can use
# `import horovod_tpu as hvd` at call time).
from horovod_tpu import training  # noqa: E402
# ``hvd.callbacks.*`` — the reference's Keras callback namespace
# (keras/callbacks.py; used as hvd.callbacks.BroadcastGlobalVariablesCallback
# in examples/keras_mnist.py:71-75).
from horovod_tpu.training import callbacks  # noqa: E402

from horovod_tpu.core import timeline as _timeline  # noqa: E402

_timeline.session().imported(_t0, _time.perf_counter_ns())  # and ends here

__all__ = [
    "AXIS_NAME",
    "Bf16Compressor",
    "Compressor",
    "DistributedOptimizer",
    "ErrorFeedbackState",
    "HorovodError",
    "Int8Compressor",
    "IndexedSlices",
    "NotInitializedError",
    "allgather",
    "alltoall",
    "reducescatter",
    "allreduce_gradients",
    "allreduce_indexed_slices",
    "broadcast_global_variables",
    "broadcast_variables",
    "sharded_optimizer",
    "allreduce",
    "broadcast",
    "blockwise_attention",
    "flash_attention",
    "flash_attention_lse",
    "device_put_ranked",
    "gather",
    "local_attention",
    "ring_attention",
    "column_parallel",
    "row_parallel",
    "shard_columns",
    "shard_rows",
    "stage_split",
    "gpipe",
    "pipeline_1f1b",
    "moe_capacity",
    "moe_mlp",
    "tp_attention",
    "tp_mlp",
    "tp_mlp_sp",
    "ulysses_attention",
    "zigzag_positions",
    "zigzag_shard",
    "zigzag_unshard",
    "get_group",
    "global_rank",
    "global_size",
    "init",
    "is_initialized",
    "local_rank",
    "local_size",
    "num_groups",
    "rank",
    "local_values",
    "rank_stack",
    "replicate",
    "shutdown",
    "size",
    "spmd",
    "TunedConfig",
    "tune",
    "tune_report",
    "__version__",
]
