"""Training callbacks — parity with the reference's Keras callbacks.

Reference: ``horovod/keras/callbacks.py`` —
``BroadcastGlobalVariablesCallback`` (on_train_begin weight sync, :8-34),
``MetricAverageCallback`` (epoch-end allreduce of metrics, :37-87),
``LearningRateScheduleCallback`` with momentum correction (:90-199), and
``LearningRateWarmupCallback`` implementing the Goyal et al. linear warmup
``lr/size → lr`` (:202-259). The TPU-native host is
:class:`horovod_tpu.training.Trainer`; the callback event vocabulary is
Keras's, so porting a reference training script is mechanical.
"""

from __future__ import annotations

import math
from typing import Callable

import numpy as np

import horovod_tpu as hvd


class Callback:
    """Keras-style callback: the Trainer calls these hooks around the loop."""

    trainer = None  # set by Trainer.fit

    def set_trainer(self, trainer) -> None:
        self.trainer = trainer

    def on_train_begin(self, logs: dict | None = None) -> None: ...

    def on_train_end(self, logs: dict | None = None) -> None: ...

    def on_epoch_begin(self, epoch: int, logs: dict | None = None) -> None: ...

    def on_epoch_end(self, epoch: int, logs: dict | None = None) -> None: ...

    def on_batch_begin(self, batch: int, logs: dict | None = None) -> None: ...

    def on_batch_end(self, batch: int, logs: dict | None = None) -> None: ...


class BroadcastGlobalVariablesCallback(Callback):
    """Broadcast initial parameters and optimizer state from ``root_rank`` at
    the start of training (keras/callbacks.py:8-34). This is the consistency
    mechanism for random init and checkpoint restore (tensorflow/__init__.py:
    97-104): rank 0 restores, everyone else receives."""

    def __init__(self, root_rank: int = 0, group: int = 0) -> None:
        self.root_rank = root_rank
        self.group = group

    def on_train_begin(self, logs: dict | None = None) -> None:
        self.trainer.sync_state(self.root_rank, self.group)


class MetricAverageCallback(Callback):
    """Average epoch metrics over ranks before they are reported
    (keras/callbacks.py:37-87). On the single-controller Trainer the
    per-rank metrics are already visible host-side; the averaging contract
    (every rank logs the same value) is preserved.

    Pass ``keys`` to name the per-rank metrics explicitly (each a
    length-``size`` leading-dim array in ``logs``; keys absent from a
    given epoch's logs are ignored) — the reference averages only its
    cached metric variables (keras/callbacks.py:61-77), never arbitrary
    log values, and the explicit form is that contract. The DEFAULT
    (``keys=None``) remains the legacy shape-sniffing heuristic for
    backward compatibility: any log whose leading dim equals the group
    size gets averaged. Beware the heuristic's hazard — it silently
    averages a legitimate length-``size`` vector metric (e.g. a
    10-class histogram on an 10-device world); pass ``keys`` whenever
    your logs might carry such vectors.
    """

    def __init__(self, group: int = 0, *,
                 keys: list[str] | None = None) -> None:
        # ``group`` keeps its historical first-positional slot; ``keys``
        # is keyword-only so no existing positional caller can silently
        # re-bind.
        self.keys = None if keys is None else set(keys)
        self.group = group

    def on_epoch_end(self, epoch: int, logs: dict | None = None) -> None:
        if not logs:
            return
        for key, value in list(logs.items()):
            if self.keys is not None and key not in self.keys:
                continue
            arr = np.asarray(value)
            if arr.ndim >= 1 and arr.shape[0] == hvd.size(self.group):
                mean = np.mean(arr, axis=0)
                logs[key] = float(mean) if mean.ndim == 0 else mean
            elif self.keys is not None and arr.ndim >= 1:
                # A registered non-scalar whose leading dim is NOT the
                # group size is a real shape bug — fail loudly. Scalars
                # pass through: the Trainer already reduces its own
                # metrics (loop.py), so registering them is harmless.
                raise hvd.HorovodError(
                    f"MetricAverageCallback: registered metric {key!r} does "
                    f"not carry a per-rank leading dim of size "
                    f"{hvd.size(self.group)} (got shape {arr.shape}).")


class LearningRateScheduleCallback(Callback):
    """Multiply the base LR by ``multiplier(epoch)`` within an epoch window
    (keras/callbacks.py:90-199).

    ``staircase=True`` applies the multiplier per epoch; ``staircase=False``
    interpolates per batch using ``epoch + batch/steps_per_epoch``, matching
    the reference's fractional-epoch behavior (:147-157). With momentum
    correction (:128-144), when the LR changes the optimizer's momentum
    buffer is rescaled by ``new_lr / old_lr`` so the effective update
    magnitude stays smooth (Goyal et al. 2017 gradual-warmup appendix).
    """

    def __init__(self, multiplier: Callable[[float], float] | float,
                 start_epoch: int = 0, end_epoch: int | None = None,
                 staircase: bool = True, momentum_correction: bool = True,
                 steps_per_epoch: int | None = None) -> None:
        self.start_epoch = start_epoch
        self.end_epoch = end_epoch
        self.staircase = staircase
        self.momentum_correction = momentum_correction
        self.steps_per_epoch = steps_per_epoch
        self.initial_lr: float | None = None
        self.current_epoch: int | None = None
        if callable(multiplier):
            self.multiplier = multiplier
        else:
            self.multiplier = lambda epoch: multiplier

    def _in_window(self, epoch: int) -> bool:
        if epoch < self.start_epoch:
            return False
        return self.end_epoch is None or epoch < self.end_epoch

    def _adjust(self, epoch: float) -> None:
        old_lr = self.trainer.get_lr()
        new_lr = self.initial_lr * self.multiplier(epoch)
        self.trainer.set_lr(new_lr)
        if self.momentum_correction and old_lr > 0:
            self.trainer.scale_momentum(new_lr / old_lr)

    def on_train_begin(self, logs: dict | None = None) -> None:
        if self.initial_lr is None:
            self.initial_lr = self.trainer.get_lr()

    def on_epoch_begin(self, epoch: int, logs: dict | None = None) -> None:
        self.current_epoch = epoch
        if self.staircase and self._in_window(epoch):
            self._adjust(epoch)

    def on_batch_begin(self, batch: int, logs: dict | None = None) -> None:
        if self.staircase or not self._in_window(self.current_epoch or 0):
            return
        if not self.steps_per_epoch:
            raise hvd.HorovodError(
                "LearningRateScheduleCallback with staircase=False requires "
                "steps_per_epoch (keras/callbacks.py:121 contract).")
        epoch = (self.current_epoch or 0) + float(batch) / self.steps_per_epoch
        self._adjust(epoch)


class LearningRateWarmupCallback(LearningRateScheduleCallback):
    """Linear LR warmup from ``lr / size`` to ``lr`` over ``warmup_epochs``
    (keras/callbacks.py:202-259): with large-batch data parallelism the LR is
    scaled by world size, and the warmup ramps into it —
    ``lr = initial_lr * (epoch * (size - 1) / warmup_epochs + 1) / size``
    (formula at :213-226)."""

    def __init__(self, warmup_epochs: int = 5, momentum_correction: bool = True,
                 steps_per_epoch: int | None = None, verbose: bool = False,
                 group: int = 0) -> None:
        self.group = group
        self.verbose = verbose

        def multiplier(epoch: float) -> float:
            size = hvd.size(self.group)
            return (epoch * (size - 1) / warmup_epochs + 1) / size

        super().__init__(multiplier=multiplier, start_epoch=0,
                         end_epoch=warmup_epochs, staircase=False,
                         momentum_correction=momentum_correction,
                         steps_per_epoch=steps_per_epoch)

    def on_epoch_end(self, epoch: int, logs: dict | None = None) -> None:
        if self.end_epoch is not None and epoch == self.end_epoch - 1 \
                and self.verbose:
            print(f"Epoch {epoch + 1}: finished gradual learning rate warmup "
                  f"to {self.trainer.get_lr():.6g}.")


class ModelCheckpointCallback(Callback):
    """Rank-0-writes checkpointing, the reference's convention
    (examples/keras_mnist_advanced.py:103-104, SURVEY §5.4): only the
    controller whose first device is the root writes; restore happens via
    ``BroadcastGlobalVariablesCallback``."""

    def __init__(self, directory: str, every_epochs: int = 1,
                 root_rank: int = 0, group: int = 0) -> None:
        self.directory = directory
        self.every_epochs = every_epochs
        self.root_rank = root_rank
        self.group = group

    def on_epoch_end(self, epoch: int, logs: dict | None = None) -> None:
        if hvd.rank(self.group) != self.root_rank:
            return
        if (epoch + 1) % self.every_epochs == 0:
            from horovod_tpu.training import checkpoint as _ckpt

            _ckpt.save(self.directory, self.trainer.train_state(), epoch)
