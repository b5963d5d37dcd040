"""Trainer — the Keras-style fit loop hosting the callbacks.

The reference has no training loop of its own; it decorates Keras/TF loops
(optimizer wrapper + callbacks + session hooks). A JAX stack has no Keras, so
this module provides the minimal host: a data-parallel fit loop over
``hvd.spmd`` step functions with Keras-compatible callback events, LR control
(via ``optax.inject_hyperparams``), momentum correction hooks, and the
rank-0-writes checkpoint convention. Reference parity anchors:
``DistributedOptimizer`` wiring (tensorflow/__init__.py:132-192), callback
vocabulary (keras/callbacks.py), examples' train loops
(examples/keras_mnist.py, examples/tensorflow_mnist.py:116-119).
"""

from __future__ import annotations

from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.core import elastic as _elastic
from horovod_tpu.core import resilience as _res
from horovod_tpu.core.state import HorovodError
from horovod_tpu.ops import mesh as _mesh
from horovod_tpu.tune import apply as _tune_apply
from horovod_tpu.utils import env as _env


def sgd(learning_rate: float, momentum: float = 0.0,
        nesterov: bool = False) -> optax.GradientTransformation:
    """SGD with runtime-adjustable LR (what LR-schedule callbacks need)."""
    return optax.inject_hyperparams(optax.sgd)(
        learning_rate=learning_rate, momentum=momentum, nesterov=nesterov)


def adam(learning_rate: float, **kwargs) -> optax.GradientTransformation:
    """Adam with runtime-adjustable LR."""
    return optax.inject_hyperparams(optax.adam)(
        learning_rate=learning_rate, **kwargs)


def adadelta(learning_rate: float = 1.0, **kwargs) -> optax.GradientTransformation:
    """Adadelta (keras_mnist uses it, examples/keras_mnist.py:61)."""
    return optax.inject_hyperparams(optax.adadelta)(
        learning_rate=learning_rate, **kwargs)


class LRControlMixin:
    """Runtime LR / momentum control over an ``optax.inject_hyperparams``
    optimizer state in ``self.opt_state`` — what the LR-schedule callbacks
    drive (keras/callbacks.py:90-199). Shared by :class:`Trainer` and
    :class:`horovod_tpu.training.Estimator`."""

    def _hyperparams(self) -> dict:
        hp = getattr(self.opt_state, "hyperparams", None)
        if hp is None or "learning_rate" not in hp:
            raise HorovodError(
                "LR schedule callbacks need an optimizer built with "
                "horovod_tpu.training.sgd/adam/... (optax.inject_hyperparams).")
        return hp

    def get_lr(self) -> float:
        hp = self._hyperparams()
        return float(np.asarray(hp["learning_rate"]).reshape(-1)[0])

    def set_lr(self, value: float) -> None:
        hp = self._hyperparams()
        old = hp["learning_rate"]
        hp["learning_rate"] = jnp.full_like(jnp.asarray(old), value)

    def scale_momentum(self, factor: float) -> None:
        """Momentum correction (keras/callbacks.py:128-144): rescale momentum
        buffers when the LR changes so update magnitudes stay smooth."""
        if abs(factor - 1.0) < 1e-12:
            return

        def scale(state):
            if isinstance(state, optax.TraceState):
                return optax.TraceState(
                    trace=jax.tree.map(lambda t: t * factor, state.trace))
            return state

        self.opt_state = jax.tree.map(
            scale, self.opt_state,
            is_leaf=lambda s: isinstance(s, optax.TraceState))


class Trainer(LRControlMixin):
    """Data-parallel trainer over a group's mesh.

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux_metrics)`` with
    ``has_aux=True``) is traced per-rank; gradients are averaged across the
    group by :func:`hvd.DistributedOptimizer` with tensor fusion. All state
    (params / opt state) lives in the rank-stacked layout — leading axis =
    group size, one replica per device.
    """

    def __init__(self, loss_fn: Callable, optimizer: optax.GradientTransformation,
                 group: int = 0, has_aux: bool = False,
                 fusion_threshold: int | None = None,
                 steps_per_call: int = 1, sharded: bool = False,
                 schedule: str | None = None,
                 sharding: str | None = None) -> None:
        # ``schedule``: whole-step gradient-exchange schedule
        # ("enum"/"priority", ops/exchange.py); None defers to
        # HOROVOD_EXCHANGE_SCHEDULE like the DistributedOptimizer knob.
        # ``sharding``: the FSDP modes ("zero2"/"zero3", ops/mesh.py);
        # None defers to HOROVOD_SHARDING (tuned configs may set it,
        # explicit env beats tuned). zero3 changes the step shape: the
        # trainer holds parameter SHARDS and gathers full parameters
        # per layer inside the step (gather-on-use).
        self.loss_fn = loss_fn
        self.base_optimizer = optimizer
        if sharding is None:
            tuned = _tune_apply.override("HOROVOD_SHARDING")
            self.sharding = (_mesh.resolve_sharding(tuned)
                             if tuned is not None
                             else _env.sharding_mode())
        else:
            self.sharding = _mesh.resolve_sharding(sharding)
        if self.sharding != "off" and _env.elastic_enabled():
            # Mirrors the hvd.init refusal: _elastic_shrink/_maybe_regrow
            # re-replicate state, which would desync fsdp shards.
            raise HorovodError(
                f"HOROVOD_ELASTIC=1 is incompatible with Trainer("
                f"sharding={self.sharding!r}): the elastic shrink/regrow "
                f"path re-replicates training state and would desync "
                f"sharded (ZeRO-2/3) layouts. Use the replicated path "
                f"(sharding='off') with elastic training.")
        self.optimizer = hvd.DistributedOptimizer(
            optimizer, group=group, fusion_threshold=fusion_threshold,
            sharded=sharded, schedule=schedule, sharding=self.sharding)
        self.group = group
        self.has_aux = has_aux
        self.params = None
        self.opt_state = None
        self.last_aux = None
        self.epoch = 0
        if steps_per_call < 1:
            raise HorovodError("steps_per_call must be >= 1.")
        # steps_per_call > 1 runs K optimizer steps inside ONE compiled
        # program (lax.scan device loop, the bench.py pattern): host dispatch
        # amortizes across K steps. fit() then feeds K batches per call and
        # fires batch callbacks once per call.
        self.steps_per_call = steps_per_call
        self._step = self._build_step()

    # -- state ---------------------------------------------------------------

    def init_state(self, params) -> None:
        """Replicate fresh parameters and optimizer state across the group.

        In sharded (ZeRO-1/ZeRO-2) mode the wrapper's init produces
        shard-shaped state (1/n of the parameter space per device) whose
        zero init is rank-agnostic, so the replicate-the-eager-init
        layout still holds. ZeRO-3 instead binds the parameter layout
        and stacks PER-RANK parameter shards (rank ``d*F+f`` holds shard
        ``f``); the inner optimizer state is shard-shaped zeros, again
        rank-agnostic.
        """
        if self.sharding == "zero3":
            opt = self.optimizer
            opt.bind(params)
            self.params = opt.init_shards(params)
            shard_view = jax.tree.map(lambda t: t[0], self.params)
            self.opt_state = hvd.replicate(opt.init(shard_view),
                                           self.group)
            return
        self.params = hvd.replicate(params, self.group)
        self.opt_state = hvd.replicate(self.optimizer.init(params),
                                       self.group)

    def load_state(self, params_stacked, opt_state_stacked,
                   epoch: int = 0) -> None:
        self.params = params_stacked
        self.opt_state = opt_state_stacked
        self.epoch = epoch

    def train_state(self) -> dict:
        return {"params": self.params, "opt_state": self.opt_state,
                "epoch": self.epoch}

    def restore(self, directory: str) -> int:
        """Crash-safe resume (what ``fit(resume=...)`` calls): agree with
        every rank on the newest epoch ALL can load
        (:func:`checkpoint.agree_on_resume_epoch` — torn/corrupt epochs are
        already skipped by the manifest scan), restore it, bump the
        coordination generation so the restarted run's negotiation and
        heartbeat keys can never collide with stale pre-crash KV state, and
        re-broadcast rank 0's state so every replica resumes bit-identical.

        Returns the epoch training will resume at (``self.epoch``;
        unchanged when the directory holds no loadable checkpoint).
        Requires ``init_state``/``load_state`` first — the fresh state is
        the restore template, and stays in place on a fresh start.
        """
        from horovod_tpu.core import state as _state
        from horovod_tpu.training import checkpoint as _ckpt

        if self.sharding != "off":
            raise HorovodError(
                f"Trainer.restore/fit(resume=...) supports only the "
                f"replicated path; sharding={self.sharding!r} state is "
                f"rank-divergent (each rank holds its own fsdp shard) "
                f"and must round-trip via "
                f"checkpoint.save_sharded/load_sharded.")
        if self.params is None:
            raise HorovodError(
                "Trainer.init_state/load_state must run before "
                "restore/fit(resume=...) — the fresh state is the restore "
                "template.")
        if not hvd.get_group(self.group).local_member_ranks():
            # The agreement hands a memberless process only its LOCAL scan
            # (gathered results live on member ranks), so it could branch
            # away from the members' restore sequence (generation bump +
            # re-broadcast) and wedge their next collective. Refuse loudly
            # instead of desyncing.
            raise HorovodError(
                f"Trainer.restore/fit(resume=...) called on a process "
                f"hosting no members of group {self.group}: restore's "
                f"generation bump and state re-broadcast are group "
                f"collectives this process cannot follow consistently. "
                f"Run restore only where the trainer's group has members.")
        epoch = _ckpt.agree_on_resume_epoch(directory, group=self.group)
        if epoch < 0:
            return self.epoch
        # agree_on_resume_epoch CRC-verified the agreed epoch on THIS rank
        # before returning it — verify=False skips load's second
        # full-payload CRC read, leaving the deserialize read alone on the
        # recovery critical path.
        restored = _ckpt.load(directory, self.train_state(), epoch=epoch,
                              group=self.group, verify=False)
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.epoch = epoch + 1
        _state.bump_generation()
        self._step = self._build_step()  # recompile under the new generation
        self.sync_state(group=self.group)
        return self.epoch

    def sync_state(self, root_rank: int = 0, group: int | None = None) -> None:
        """Broadcast params + optimizer state from ``root_rank`` — what
        BroadcastGlobalVariablesCallback runs at train begin."""
        if self.sharding != "off":
            raise HorovodError(
                f"Trainer.sync_state does not apply to sharding="
                f"{self.sharding!r}: optimizer state (and for zero3, "
                f"parameters) is intentionally rank-divergent — rank "
                f"d*F+f holds fsdp shard f — so broadcasting one rank's "
                f"rows would overwrite every other shard. Sharded state "
                f"persists via checkpoint.save_sharded/load_sharded.")
        g = self.group if group is None else group
        self.params = hvd.broadcast_variables(self.params, root_rank, g)
        self.opt_state = hvd.broadcast_variables(self.opt_state, root_rank, g)

    # -- the step ------------------------------------------------------------

    def _build_step(self):
        def grad(params, batch):
            if self.has_aux:
                (loss, aux), grads = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(params, batch)
            else:
                loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
                aux = {}
            return loss, aux, grads

        if self.sharding == "zero3":
            # ZeRO-3 step shape: ``params`` here are per-rank SHARDS.
            # gather_params issues the per-layer all-gathers in
            # first-needed order ahead of the forward (gather-on-use);
            # apply_gradients reduce-scatters gradients and updates
            # shard-to-shard — the full parameters never leave the trace.
            def step(param_shards, opt_state, batch):
                params = self.optimizer.gather_params(param_shards)
                loss, aux, grads = grad(params, batch)
                param_shards, opt_state = self.optimizer.apply_gradients(
                    grads, opt_state, param_shards)
                return param_shards, opt_state, loss, aux
        elif self.sharding == "zero2":
            # fsdp_apply=True: the optimizer applies the update
            # SHARD-side and gathers the new parameters — the
            # bit-identity path (parallel/optimizer.py
            # sharded_zero2_optimizer docstring).
            def step(params, opt_state, batch):
                loss, aux, grads = grad(params, batch)
                params, opt_state = self.optimizer.update(
                    grads, opt_state, params, fsdp_apply=True)
                return params, opt_state, loss, aux
        else:
            def step(params, opt_state, batch):
                loss, aux, grads = grad(params, batch)
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, loss, aux

        if self.steps_per_call == 1:
            return hvd.spmd(step, group=self.group)

        def multi_step(params, opt_state, batches):
            # `batches` leaves carry a leading device-loop axis of length K.
            def body(carry, batch):
                params, opt_state = carry
                params, opt_state, loss, aux = step(params, opt_state, batch)
                return (params, opt_state), (loss, aux)

            (params, opt_state), (losses, auxes) = jax.lax.scan(
                body, (params, opt_state), batches)
            last_aux = jax.tree.map(lambda t: t[-1], auxes)
            # Mean over the K scanned steps: epoch metrics must not become a
            # 1-in-K sample of the loss curve when steps_per_call changes.
            return params, opt_state, jnp.mean(losses), last_aux

        return hvd.spmd(multi_step, group=self.group)

    def train_step(self, batch):
        """One fused DP step on a rank-stacked batch; returns (loss, aux)
        with per-rank leading axes."""
        if self.params is None:
            raise HorovodError("Trainer.init_state/load_state must run first.")
        self.params, self.opt_state, loss, aux = self._step(
            self.params, self.opt_state, batch)
        self.last_aux = aux  # rank-stacked; callbacks may consume (e.g. BN stats)
        return loss, aux

    # -- the loop ------------------------------------------------------------

    def fit(self, data: Iterable, epochs: int, steps_per_epoch: int,
            callbacks: list | None = None, verbose: bool = True,
            initial_epoch: int | None = None,
            resume: str | None = None) -> dict:
        """Keras-shaped fit: ``data`` yields rank-stacked batches.

        ``resume=<checkpoint dir>`` restores the newest complete checkpoint
        every rank can load before training (see :meth:`restore`) — the
        crash-restart entry point: a preempted/killed job relaunches with
        the same ``fit`` call plus ``resume=`` and continues from the last
        complete epoch. A directory with no loadable checkpoint starts
        fresh.

        Returns a history dict {metric: [per-epoch values]}.
        """
        if resume is not None:
            if initial_epoch is not None:
                # initial_epoch would silently override the restored resume
                # point: the LR schedule would replay from scratch and the
                # checkpoint callback would overwrite the history restore
                # exists to protect.
                raise HorovodError(
                    "fit(resume=...) and initial_epoch are mutually "
                    "exclusive: resume restores the agreed epoch and "
                    "continues from it. Drop initial_epoch, or load "
                    "explicitly and pass initial_epoch without resume.")
            self.restore(resume)
        callbacks = list(callbacks or [])
        for cb in callbacks:
            cb.set_trainer(self)
        history: dict[str, list] = {"loss": []}
        start = self.epoch if initial_epoch is None else initial_epoch

        for cb in callbacks:
            cb.on_train_begin()
        data_iter = iter(data)

        def next_batch():
            # Keras-fit contract: a finite re-iterable (e.g. a list holding
            # one epoch of batches) is cycled across epochs; a generator that
            # simply runs dry is a user error worth a clear message.
            nonlocal data_iter
            try:
                return next(data_iter)
            except StopIteration:
                data_iter = iter(data)
                try:
                    return next(data_iter)
                except StopIteration:
                    raise HorovodError(
                        "Training data iterator is exhausted and not "
                        "re-iterable; pass an infinite generator or a "
                        "re-iterable collection of batches.") from None

        spc = self.steps_per_call
        if spc > 1 and steps_per_epoch % spc != 0:
            raise HorovodError(
                f"steps_per_epoch ({steps_per_epoch}) must be divisible by "
                f"steps_per_call ({spc}).")

        # Group-local ranks this process hosts: the crash-injection rank
        # space (HOROVOD_FAULT_INJECT=crash@rank=R,step=S — resilience.py).
        local_ranks = hvd.get_group(self.group).local_member_ranks()

        # Elastic runtime (HOROVOD_ELASTIC=1): survivors of a WorkerLost
        # shrink the world and continue in-process; dropped ranks rejoin at
        # step boundaries (core/elastic.py). The data layout keeps the
        # ORIGINAL full-world rank axis; _elastic_rows slices batches down
        # to the current membership.
        self._elastic = (
            _elastic.ElasticController(self.group)
            if _env.elastic_enabled() else None)
        self._full_ranks = hvd.get_group(self.group).ranks
        self._elastic_rows = self._membership_rows()
        self._elastic_snapshot_due = None

        for epoch in range(start, epochs):
            self.epoch = epoch
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            losses = []
            n_calls = steps_per_epoch // spc
            call_idx = 0
            while call_idx < n_calls:
                # Callbacks see the TRUE step index: staircase=False LR
                # schedules compute fractional epochs as step/steps_per_epoch
                # (callbacks.py), which must not rescale with steps_per_call.
                batch_idx = call_idx * spc
                global_step = epoch * steps_per_epoch + batch_idx
                if self._elastic is not None:
                    self._maybe_regrow(global_step, spc)
                try:
                    _res.maybe_crash(global_step, local_ranks, span=spc)
                    for cb in callbacks:
                        cb.on_batch_begin(batch_idx)
                    if spc > 1:
                        batch = jax.tree.map(
                            lambda *leaves: jnp.stack(leaves, axis=1),
                            *[next_batch() for _ in range(spc)])
                    else:
                        batch = next_batch()
                    loss, aux = self.train_step(self._adapt_batch(batch))
                except _res.WorkerLost as err:
                    if self._elastic is None:
                        raise
                    self._elastic_shrink(err)
                    local_ranks = hvd.get_group(
                        self.group).local_member_ranks()
                    continue  # retry this call boundary at the new world size
                if self._elastic_snapshot_due is not None:
                    # The re-planned exchange schedule only exists once a
                    # step has traced at the new world size — stamp it now.
                    self._elastic.snapshot_live_plan(
                        self._elastic_snapshot_due,
                        dropped=self._elastic.dropped)
                    self._elastic_snapshot_due = None
                # The loss stays on device: converting it here would block the
                # host every step and throw away XLA's dispatch-ahead
                # pipelining. Callbacks get a 0-d device scalar (floatable on
                # demand, Keras contract); the host syncs once per epoch.
                loss_scalar = jnp.mean(loss)
                batch_logs = {"loss": loss_scalar}
                losses.append(loss_scalar)
                for cb in callbacks:
                    cb.on_batch_end(batch_idx, batch_logs)
                call_idx += 1
            logs = {"loss": float(np.mean(np.asarray(losses)))}
            for cb in callbacks:
                cb.on_epoch_end(epoch, logs)
            history["loss"].append(logs["loss"])
            for k, v in logs.items():
                if k != "loss":
                    history.setdefault(k, []).append(v)
            if verbose and hvd.rank(self.group) == 0:
                print(f"Epoch {epoch + 1}/{epochs} - loss: {logs['loss']:.4f}"
                      f" - lr: {self._lr_repr()}")
            self.epoch = epoch + 1
        for cb in callbacks:
            cb.on_train_end()
        return history

    # -- elastic transitions (core/elastic.py) -------------------------------

    def _membership_rows(self):
        """Row indices of the current group members within the ORIGINAL
        rank-stacked data layout captured at fit start, or None when the
        membership is the full original world (identity — no slicing)."""
        current = tuple(hvd.get_group(self.group).ranks)
        full = tuple(getattr(self, "_full_ranks", current))
        if current == full:
            return None
        try:
            return tuple(full.index(r) for r in current)
        except ValueError:
            raise HorovodError(
                f"Elastic membership {list(current)} includes ranks outside "
                f"the original world {list(full)}; the rank-stacked data "
                f"layout has no rows for them.") from None

    def _adapt_batch(self, batch):
        """Slice a full-world rank-stacked batch down to the rows of the
        current (post-shrink) membership and place them on its mesh (the
        full-world stack lives on the full world's devices). Identity at
        full world."""
        rows = getattr(self, "_elastic_rows", None)
        if rows is None:
            return batch
        idx = np.asarray(rows)
        return hvd.device_put_ranked(
            jax.tree.map(lambda t: t[idx], batch), self.group)

    def _elastic_shrink(self, err: _res.WorkerLost) -> None:
        """Execute the pre-verified shrink contract in-process: snapshot
        the elected coordinator's state row while the old mesh is still
        addressable, reconfigure group 0 to the survivors (generation
        bump + cache roll), replicate + re-broadcast from the elected
        root, and re-trace the step so fusion plan and exchange schedule
        re-resolve at the new world size."""
        import time as _time

        ctl = self._elastic
        t0 = _time.perf_counter()
        dead = ctl.resolve_dead(err)
        try:
            plan = ctl.plan_shrink(dead)
        except HorovodError as refusal:
            raise refusal from err
        ctl.snapshot_live_plan("pre_shrink")
        old_ranks = tuple(hvd.get_group(self.group).ranks)
        root_row = old_ranks.index(plan.coordinator)
        # Pull state rows to host BEFORE reconfigure tears the old group
        # down — the survivors' source of truth is the elected root's row.
        params_rows = hvd.local_values(self.params, self.group)
        opt_rows = hvd.local_values(self.opt_state, self.group)
        ctl.commit_shrink(plan)
        self.params = hvd.replicate(params_rows[root_row], self.group)
        self.opt_state = hvd.replicate(opt_rows[root_row], self.group)
        self._step = self._build_step()  # fusion/exchange re-plan on trace
        # The elected coordinator is min(survivors) = group-local rank 0 of
        # the rebuilt group; the broadcast re-negotiates under the bumped
        # generation, proving the shrunk mesh works before training resumes.
        self.sync_state(root_rank=0, group=self.group)
        self._elastic_rows = self._membership_rows()
        self._elastic_snapshot_due = "post_shrink"
        ctl.finish_shrink(t0)
        print(f"horovod_tpu elastic: shrunk to world "
              f"{list(plan.survivors)} (generation "
              f"{plan.generation}); training continues.", flush=True)

    def _maybe_regrow(self, step: int, span: int) -> None:
        """Admit announced joiners at this step boundary, if any: mirror
        path of the shrink — reconfigure over the union, re-broadcast
        state from a surviving member (the rejoining rank has no state),
        re-trace the step."""
        import time as _time

        ctl = self._elastic
        plan = ctl.poll_regrow(step, span)
        if plan is None:
            return
        t0 = _time.perf_counter()
        survivors = tuple(hvd.get_group(self.group).ranks)
        # State source must be a CURRENT member: plan.coordinator is
        # min(members) and may be the rejoining rank itself (e.g. rank 0
        # died and came back), which holds no state yet.
        src = survivors[0]
        params_rows = hvd.local_values(self.params, self.group)
        opt_rows = hvd.local_values(self.opt_state, self.group)
        ctl.commit_regrow(plan)
        new_ranks = tuple(hvd.get_group(self.group).ranks)
        self.params = hvd.replicate(params_rows[0], self.group)
        self.opt_state = hvd.replicate(opt_rows[0], self.group)
        self._step = self._build_step()
        self.sync_state(root_rank=new_ranks.index(src), group=self.group)
        self._elastic_rows = self._membership_rows()
        self._elastic_snapshot_due = "post_regrow"
        ctl.finish_regrow(t0)
        print(f"horovod_tpu elastic: regrew to world {list(plan.members)} "
              f"(admitted {list(plan.joined)}, generation "
              f"{plan.generation}); training continues.", flush=True)

    def _lr_repr(self) -> str:
        try:
            return f"{self.get_lr():.6g}"
        except HorovodError:
            return "n/a"
