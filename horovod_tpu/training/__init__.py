"""Training layer: Keras-equivalent callbacks, fit loop, checkpointing.

Parity with the reference's ``horovod/keras`` package (optimizer wrapper is
:func:`horovod_tpu.DistributedOptimizer`; the value-level collectives are the
eager forms of :mod:`horovod_tpu.ops.collectives`)."""

from horovod_tpu.training import checkpoint
from horovod_tpu.training import data
from horovod_tpu.training.callbacks import (
    BroadcastGlobalVariablesCallback,
    Callback,
    LearningRateScheduleCallback,
    LearningRateWarmupCallback,
    MetricAverageCallback,
    ModelCheckpointCallback,
)
from horovod_tpu.training.estimator import Estimator, EstimatorSpec, ModeKeys
from horovod_tpu.training.loop import Trainer, adadelta, adam, sgd

# The reference exposes the broadcast-on-start behavior twice: as a Keras
# callback (keras/callbacks.py:8) and as a tf.train.SessionRunHook
# (tensorflow/__init__.py:97). Here both styles are the same object — the
# Trainer consumes it as a callback, the Estimator applies it implicitly.
BroadcastGlobalVariablesHook = BroadcastGlobalVariablesCallback

__all__ = [
    "BroadcastGlobalVariablesCallback",
    "BroadcastGlobalVariablesHook",
    "Callback",
    "Estimator",
    "EstimatorSpec",
    "LearningRateScheduleCallback",
    "LearningRateWarmupCallback",
    "MetricAverageCallback",
    "ModeKeys",
    "ModelCheckpointCallback",
    "Trainer",
    "adadelta",
    "adam",
    "checkpoint",
    "sgd",
]
