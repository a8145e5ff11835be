"""Epoch driver for pipeline-parallel training.

The counterpart of the reference's ``model_parallel.py`` main loop + the
per-role loops in ``utils.py:34-210`` — but one driver instead of three
role-specialized ones, because the single-controller runtime sees all stages.
Metrics/logging/timing match the reference's rank-0 behavior
(``model_parallel.py:110-125``): loss and accuracy are computed where the
data lives (stage 0), per-batch compute and data-load times are averaged per
epoch. Adds checkpoint/resume, which the reference's pipeline path lacks
entirely (SURVEY.md §5 "Checkpoint/resume").
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.config import TrainConfig
from distributed_model_parallel_tpu.data.loader import (
    BatchLoader,
    maybe_prefetch,
    resolve_input_size,
)
from distributed_model_parallel_tpu.data.registry import load_dataset
from distributed_model_parallel_tpu.models import get_model
from distributed_model_parallel_tpu.parallel.pipeline import PipelineRunner
from distributed_model_parallel_tpu.train.checkpoint import Checkpointer
from distributed_model_parallel_tpu.train.logging_util import RunLogger
from distributed_model_parallel_tpu.utils import tracing
from distributed_model_parallel_tpu.utils.tracing import span
from distributed_model_parallel_tpu.train.metrics import AverageMeter, StepTimer
from distributed_model_parallel_tpu.train.optim import make_optimizer
from distributed_model_parallel_tpu.train.trainer import EpochResult, eval_now


class PipelineTrainer:
    def __init__(self, config: TrainConfig, devices=None):
        self.plan_decision = None
        if config.strategy == "auto":
            # Autotune the single-controller pipeline (autotune/,
            # docs/AUTOTUNE.md): the stage count is fixed by the device
            # list, so the planner picks the microbatch count (GPipe
            # bubble vs boundary-latency alpha cost) and turns the
            # cost-balanced stage cut on; the decision lands as a typed
            # `plan` telemetry record below.
            from distributed_model_parallel_tpu.autotune.planner import (
                plan_for_stage_pipeline,
            )

            n_stages = (config.mesh.stage if config.mesh.stage > 1
                        else len(devices if devices is not None
                                 else jax.devices()))
            config, self.plan_decision = plan_for_stage_pipeline(config,
                                                                 n_stages)
        self.config = config
        if devices is None:
            devices = jax.devices()[:max(config.mesh.stage, 1)]
        if len(devices) < config.mesh.stage:
            # Fail loudly rather than silently training a shallower pipeline
            # than the config (and logs) claim.
            raise ValueError(
                f"pipeline depth {config.mesh.stage} needs that many devices, "
                f"but only {len(devices)} are available; on CPU pass the "
                f"stage count via the CLI flag (scripts/_cpu_devices.py needs "
                f"it in argv before jax initializes) or set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{config.mesh.stage}")
        self.devices = devices

        train_ds, eval_ds = load_dataset(config.data)
        self.train_ds, self.eval_ds = train_ds, eval_ds
        self.train_loader = BatchLoader(train_ds, config.data.batch_size,
                                        shuffle=config.data.shuffle,
                                        seed=config.data.seed,
                                        use_native=config.data.use_native,
                                        num_workers=config.data.num_workers)
        self.eval_loader = BatchLoader(
            eval_ds, min(config.data.eval_batch_size, len(eval_ds)),
            shuffle=False, use_native=config.data.use_native,
            num_workers=config.data.num_workers)

        # On-device resize when the configured input size differs from the
        # dataset's native resolution (same rule as the DP Trainer).
        resize_to, in_hw = resolve_input_size(train_ds.images.shape,
                                              config.data.image_size)
        in_shape = (in_hw, in_hw, train_ds.images.shape[3])

        model = get_model(config.model)
        if config.optimizer.ema_decay is not None:
            raise ValueError(
                "ema_decay is implemented by the data-parallel Trainer "
                "(gspmd/fsdp), not the pipeline trainer — no silent ignores")
        tx = make_optimizer(config.optimizer, len(self.train_loader),
                            config.epochs)
        boundaries = config.stage_boundaries
        if boundaries is None and config.auto_partition:
            # Cost-balanced split: minimax over XLA per-unit FLOPs, replacing
            # both the reference's hard-coded ranges (model_parallel.py:99-157)
            # and the equal-unit-count default.
            from distributed_model_parallel_tpu.parallel.auto_partition import (
                auto_boundaries,
                microbatch_rows,
            )

            n_chunks = len(devices) * max(1, config.virtual_stages)
            micro = microbatch_rows(config.data.batch_size,
                                    config.num_microbatches)
            boundaries = auto_boundaries(
                model, (micro,) + in_shape, n_chunks)
        self.runner = PipelineRunner(
            model, devices, tx=tx, rng=jax.random.key(config.seed),
            sample_shape=(2,) + train_ds.images.shape[1:],
            resize_to=resize_to,
            mean=train_ds.mean, std=train_ds.std,
            boundaries=boundaries,
            num_microbatches=config.num_microbatches,
            augment=config.data.augment,
            schedule=config.pipeline_schedule,
            virtual_stages=config.virtual_stages,
            bn_momentum=config.model.bn_momentum)

        from distributed_model_parallel_tpu.train.preemption import (
            PreemptionGuard,
        )

        self.preemption = PreemptionGuard()
        self.logger = RunLogger(
            config.log_dir, config.log_name,
            meta=dict(workload="cnn-pipeline", model=config.model.name,
                      batch_size=config.data.batch_size,
                      n_stages=len(self.devices),
                      num_microbatches=config.num_microbatches,
                      pipeline_schedule=config.pipeline_schedule))
        # Span sink for this thread (utils/tracing.py) — resume/checkpoint
        # spans below land on this run's stream.
        tracing.install(self.logger.telemetry)
        # Live status exporter (utils/statusz.py) — see Trainer: start or
        # join the process's exporter, publish this run under /statusz.
        from distributed_model_parallel_tpu.utils import statusz

        statusz.maybe_serve(config.statusz_port)
        statusz.register_trainer(self, "pipeline")
        from distributed_model_parallel_tpu.train.resilience import (
            RecoverySupervisor,
        )
        from distributed_model_parallel_tpu.utils.faults import FaultInjector

        self.faults = FaultInjector(config.recovery.faults)
        from distributed_model_parallel_tpu.utils.faults import (
            validate_corruption_plan,
        )

        validate_corruption_plan(
            self.faults.plan, 1,
            context="the single-controller pipeline (one copy per stage)")
        self.ckpt = Checkpointer(config.checkpoint_dir,
                                 keep=config.recovery.keep_checkpoints,
                                 injector=self.faults,
                                 meta_fn=self._ckpt_meta)
        # Slice identity for the device-health sentinel feeds
        # (utils/health.py; no-ops outside orchestrated runs).
        self._device_ids = tuple(sorted(d.id for d in self.devices))
        self.resilience = RecoverySupervisor(
            config.recovery, logger=self.logger, ckpt=self.ckpt,
            preemption=self.preemption, slot="pipeline-good",
            injector=self.faults,
            check_finite_every=config.check_finite_every,
            consistency_every=config.consistency_every,
            device_ids=self._device_ids)
        from distributed_model_parallel_tpu.train.guards import GuardRunner

        self.guards = GuardRunner(
            check_finite_every=config.check_finite_every,
            stall_budget_s=config.stall_budget_s, logger=self.logger,
            watchdog_interval_s=config.recovery.watchdog_interval_s,
            on_stall=self.resilience.on_stall, injector=self.faults,
            device_ids=self._device_ids)
        from distributed_model_parallel_tpu.train.consistency import (
            ConsistencySentinel,
        )

        # Meshless single-controller engine: one copy of every stage, so
        # the sentinel honestly degrades to its on-device finiteness
        # fingerprint (cross-replica detection requires redundancy —
        # train/consistency.py topology notes).
        self.sentinel = ConsistencySentinel(
            config.consistency_every, None, logger=self.logger,
            guards=self.guards,
            barrier_timeout_s=config.recovery.barrier_timeout_s)
        from distributed_model_parallel_tpu.train.elastic import (
            EmergencyCheckpointer,
        )

        self.emergency = EmergencyCheckpointer(
            self.ckpt, "pipeline-emergency", config.emergency_every,
            logger=self.logger)
        self.best_acc = 0.0
        self.start_epoch = 0
        # Cooperative-scheduling hook (orchestrator/): called with this
        # trainer at every train-step boundary, before the preemption poll
        # — see Trainer.step_hook.
        self.step_hook = None
        # Stateless per-step augmentation rng (base key x global step) +
        # host-side step counter — the exact-continuation pair
        # (train/elastic.py).
        self._rng_base = jax.random.key(config.seed + 1)
        self._global_step = 0
        # Trainer-authoritative loader position (epoch, consumed batches);
        # see Trainer._resume_tree for why the loader's own state is not
        # trusted (prefetch-worker auto-advance race).
        self._loader_pos = (0, 0)
        if config.resume and any(self.ckpt.exists(n)
                                 for n in ("pipeline", "pipeline-preempt",
                                           "pipeline-emergency",
                                           "pipeline-good")):
            self._resume()
        if self.plan_decision is not None:
            # After _resume so a re-plan is stamped with the exact global
            # step the run continues from.
            from distributed_model_parallel_tpu.autotune.planner import (
                emit_plan_record,
            )

            emit_plan_record(self.logger.telemetry, self.plan_decision,
                             global_step=self._global_step)
            self.logger.log_line(self.plan_decision.describe())

    def _ckpt_meta(self):
        """Manifest stamp: saving topology + exact position
        (train/checkpoint.py, train/elastic.py)."""
        return {"workload": "cnn-pipeline",
                "mesh": {**self.config.mesh.axis_sizes(),
                         "dcn_data": self.config.mesh.dcn_data},
                "n_devices": len(self.devices),
                "global_step": self._global_step}

    def _resume_tree(self):
        # Trainer-side position, loader re-synced — see
        # Trainer._resume_tree for the prefetch-worker race this avoids.
        from distributed_model_parallel_tpu.train import elastic

        ep, cur = self._loader_pos
        tree = elastic.build_resume_tree(ep, cur, len(self.train_loader),
                                         self._global_step,
                                         self.resilience.budgets())
        self.train_loader.position(int(tree["loader_epoch"]),
                                   int(tree["batch_cursor"]))
        return tree

    def _ckpt_tree(self):
        # opt_state is stored per chunk (optax wraps each chunk's
        # unit-tuple in its own state structure, so a flat merge like
        # params' is not possible); exact continuation needs it — momentum
        # buffers lost on resume silently change the trajectory.
        return {"params": self.runner.merged_params(),
                "model_state": self.runner.merged_model_state(),
                "opt_state": tuple(jax.device_get(st.opt_state)
                                   for st in self.runner.stages),
                "best_acc": jnp.asarray(self.best_acc, jnp.float32),
                "epoch": jnp.asarray(self.start_epoch, jnp.int32),
                "resume": self._resume_tree()}

    def _apply_resume_tree(self, restored: dict, *, budgets: bool) -> None:
        """Adopt the exact-continuation position; ``budgets=False`` on
        in-run recovery restores (see Trainer._restore_good)."""
        from distributed_model_parallel_tpu.train import elastic

        ri = restored.get("resume")
        if ri is None:
            return
        ep, cur, gs, retries, lr_scale = elastic.unpack_resume_tree(ri)
        self.train_loader.load_state_dict({"epoch": ep, "batch_cursor": cur})
        self._loader_pos = (self.train_loader.epoch,
                            self.train_loader.cursor)
        self._global_step = gs
        if budgets:
            self.resilience.restore_budgets(retries, lr_scale)
            if lr_scale != 1.0:
                self._apply_lr_shrink(lr_scale)

    def _push_restored(self, restored) -> None:
        """Scatter a restored checkpoint tree back onto the per-stage
        devices (chunk c lives on device c % S — matches PipelineRunner's
        round-robin virtual-stage placement)."""
        params, state = restored["params"], restored["model_state"]
        opt = restored.get("opt_state")   # absent in legacy checkpoints
        for s, (lo, hi) in enumerate(self.runner.slices):
            dev = self.runner.devices[s % self.runner.num_stages]
            self.runner.stages[s].params = jax.device_put(
                tuple(params[lo:hi]), dev)
            self.runner.stages[s].model_state = jax.device_put(
                tuple(state[lo:hi]), dev)
            if opt is not None:
                self.runner.stages[s].opt_state = jax.device_put(
                    opt[s], dev)
        self.best_acc = float(restored["best_acc"])

    def _resume(self):
        from distributed_model_parallel_tpu.train import elastic

        # Newest-valid slot wins (best-acc / preemption / emergency), with
        # torn-version and torn-slot fallback; pre-elastic checkpoints
        # (no "resume" subtree) restore through the legacy template.
        tmpl = self._ckpt_tree()
        legacy = {k: v for k, v in tmpl.items()
                  if k not in ("resume", "opt_state")}
        name, restored = elastic.elastic_restore(
            self.ckpt, (tmpl, legacy),
            # The supervisor's good slot is the last resort: it makes a
            # torn preemption/emergency save survivable (dmp_soak.py).
            ("pipeline", "pipeline-preempt", "pipeline-emergency",
             "pipeline-good"),
            on_fallback=self.resilience.note_fallback)
        self._push_restored(restored)
        self.start_epoch = int(restored["epoch"])
        self._apply_resume_tree(restored, budgets=True)
        self.start_epoch = max(self.start_epoch, self.train_loader.epoch)
        self.logger.telemetry.resume(
            slot=name, epoch=self.start_epoch,
            loader_epoch=self.train_loader.epoch,
            batch_cursor=self.train_loader.cursor,
            global_step=self._global_step,
            mesh=self._ckpt_meta()["mesh"])
        self.logger.log_line(
            f"resume: slot {name!r} -> epoch {self.start_epoch} "
            f"batch {self.train_loader.cursor} "
            f"(global step {self._global_step})")

    def _restore_good(self):
        """Recovery restore from the supervisor's "last good" slot
        (train/resilience.py), with torn-version fallback. Position rides
        along; budgets stay live (see Trainer._restore_good)."""
        restored = self.ckpt.restore(
            self._ckpt_tree(), self.resilience.slot, allow_fallback=True,
            on_fallback=self.resilience.note_fallback)
        self._push_restored(restored)
        self._apply_resume_tree(restored, budgets=False)

    def _apply_lr_shrink(self, factor: float) -> None:
        """Recovery-time LR shrink (mirrors Trainer._apply_lr_shrink):
        scale the configured LR, rebuild the optimizer and have the runner
        re-jit its per-stage programs (PipelineRunner.rebuild_optimizer).
        Stage opt_state structure is unchanged — the schedule is a
        closure — so the restored state carries over."""
        import dataclasses

        opt = dataclasses.replace(
            self.config.optimizer,
            learning_rate=self.config.optimizer.learning_rate * factor)
        self.config = self.config.replace(optimizer=opt)
        self.runner.rebuild_optimizer(
            make_optimizer(opt, len(self.train_loader), self.config.epochs))

    def _poll_step_faults(self, pending: list) -> None:
        """Serve planned step-site faults (utils/faults.py): poison the
        just-queued step metrics or the per-stage params, or request a
        simulated preemption."""
        from distributed_model_parallel_tpu.utils.faults import poison

        for spec in self.faults.poll("step"):
            if spec.kind == "preempt":
                self.preemption.request()
            elif spec.kind == "nan_loss" and pending:
                mm, b = pending[-1]
                pending[-1] = (poison(mm), b)
            elif spec.kind == "nan_params":
                for stage in self.runner.stages:
                    stage.params = poison(stage.params)

    def _sentinel_tree(self) -> dict:
        """The per-stage state the sentinel's finiteness fingerprint
        covers (one data replica — no cross-replica redundancy here)."""
        return {"params": tuple(s.params for s in self.runner.stages),
                "model_state": tuple(s.model_state
                                     for s in self.runner.stages),
                "opt_state": tuple(s.opt_state
                                   for s in self.runner.stages)}

    def _run_epoch(self, epoch: int, train: bool) -> EpochResult:
        meters = {k: AverageMeter(k) for k in ("loss", "acc1", "acc5")}
        timer = StepTimer()
        base = 0
        if train:
            # Start of `epoch`, or the mid-epoch cursor a resumed run
            # loaded; position() after each dispatched step keeps the
            # persistent cursor in lockstep with the stage state
            # (train/elastic.py).
            self.train_loader.set_epoch(epoch)
            base = self.train_loader.cursor
            self._loader_pos = (epoch, base)
        loader = self.train_loader if train else self.eval_loader
        loader = maybe_prefetch(loader, self.config.data.prefetch)
        # Metrics stay on device between sync points (train path): a
        # per-step host fetch serializes upload/compute across steps.
        # Step time is reported as the wall-clock
        # residual after loader-fetch time — per-phase meters would
        # misattribute the async dispatch cost of non-drain steps.
        pending: list = []

        def update(m, b):
            meters["loss"].update(m["loss"], int(b))
            meters["acc1"].update(m["correct@1"] / b * 100, int(b))
            meters["acc5"].update(m["correct@5"] / b * 100, int(b))

        def drain():
            # The blocking fetch is the sync point — guard it (stall watch
            # + metric finiteness; train/guards.py:GuardRunner).
            with span("drain", n=len(pending)), self.guards.watch():
                finalized = [(self.runner.finalize_metrics(mm, b), b)
                             for mm, b in pending]
            if self.guards.enabled and finalized:
                self.guards.after_sync(
                    [m for m, _ in finalized], len(finalized),
                    params=tuple(s.params for s in self.runner.stages))
            if train and self.sentinel.enabled and finalized:
                # Finiteness fingerprint of the per-stage state (one cheap
                # on-device reduction per stage; raises NonFiniteError into
                # fit()'s recovery path — train/consistency.py). The
                # meshless sentinel (one replica) can only pass or raise —
                # if this path ever gains replicated state, a repaired
                # tree MUST be spliced back like Trainer._run_sentinel
                # does, not dropped while telemetry claims "repaired".
                fixed = self.sentinel.after_sync(len(finalized),
                                                 self._sentinel_tree)
                if fixed is not None:
                    raise RuntimeError(
                        "meshless sentinel returned a repair — splice it "
                        "back into the stages before training on")
            for m, b in finalized:
                update(m, b)
            pending.clear()

        max_inflight = max(1, self.config.max_inflight_steps)
        t_epoch = time.perf_counter()
        n_steps = 0
        # Per-window residual tracking for the telemetry step records: the
        # report's percentiles need per-window samples, not the epoch
        # running mean (which hides stragglers).
        win_wall, win_data, win_steps = t_epoch, 0.0, 0
        timer.mark()
        for i, (images, labels) in enumerate(loader):
            if train and self.step_hook is not None:
                self.step_hook(self)
            if train and self.preemption.requested():
                break
            timer.data_ready()          # pure loader-fetch time
            n_steps += 1
            if train:
                gi = base + i
                sub = jax.random.fold_in(self._rng_base, self._global_step)
                pending.append(
                    (self.runner.train_step_device(sub, images, labels),
                     float(labels.shape[0])))
                self._global_step += 1
                self._loader_pos = (epoch, gi + 1)
                if self.faults.enabled:
                    self._poll_step_faults(pending)
                log_now = gi % self.config.log_every_n_steps == 0
                if log_now or len(pending) >= max_inflight:
                    drain()
                self.emergency.after_step(1, self._ckpt_tree)
                if log_now:
                    now = time.perf_counter()
                    d_data = timer.data.sum - win_data
                    d_steps = max(1, n_steps - win_steps)
                    run_step = max(0.0, now - win_wall - d_data) / d_steps
                    win_wall, win_data, win_steps = (now, timer.data.sum,
                                                     n_steps)
                    # Per-window health signal (utils/health.py; no-op
                    # outside orchestrated runs, first compile window
                    # skipped).
                    from distributed_model_parallel_tpu.utils import health

                    health.observe_step_warmed(self, self._device_ids,
                                               run_step, d_steps)
                    self.logger.log_step(
                        epoch, gi, loss=meters["loss"].avg,
                        acc1=meters["acc1"].avg,
                        step_time_s=run_step,
                        data_time_s=timer.data.last,
                        samples_per_s=self.config.data.batch_size
                        / max(run_step, 1e-9))
            else:
                m = self.runner.eval_step(images, labels)
                update(m, m["batch"])
            timer.mark()                # dispatch time -> residual, not data
        drain()
        if train and self.sentinel.enabled:
            # Cover any tail steps the cadence missed before the epoch is
            # declared clean — an epoch shorter than the cadence would
            # otherwise never be checked (train/consistency.py flush).
            # Same pass-or-raise contract as the drain-site check above.
            fixed = self.sentinel.flush(self._sentinel_tree)
            if fixed is not None:
                raise RuntimeError(
                    "meshless sentinel returned a repair — splice it "
                    "back into the stages before training on")
        wall = time.perf_counter() - t_epoch
        step_avg = max(0.0, wall - timer.data.sum) / max(1, n_steps)
        return EpochResult(meters["loss"].avg, meters["acc1"].avg,
                           meters["acc5"].avg, step_avg, timer.data.avg)

    def fit(self, epochs: int | None = None) -> list[dict]:
        """Epoch loop with eval, best-acc checkpointing, preemption-safe
        stop, and (when ``recovery.max_retries > 0``) automatic restore-
        and-retry on non-finite detections (train/resilience.py)."""
        from distributed_model_parallel_tpu.train.guards import (
            NonFiniteError,
            ReplicaDivergenceError,
        )

        epochs = epochs if epochs is not None else self.config.epochs
        history = []
        with self.preemption.installed():
            self.resilience.begin(self._ckpt_tree)
            epoch = self.start_epoch
            while epoch < epochs:
                try:
                    with span("train_epoch", epoch=epoch):
                        tr = self._run_epoch(epoch, train=True)
                except NonFiniteError as e:
                    if self.resilience.recover_nonfinite(
                            e, epoch=epoch, restore=self._restore_good,
                            shrink_lr=self._apply_lr_shrink):
                        continue        # state restored — redo the epoch
                    raise
                except ReplicaDivergenceError as e:
                    if self.resilience.recover_divergence(
                            e, epoch=epoch, restore=self._restore_good):
                        continue        # state restored — redo the epoch
                    raise
                if self.preemption.requested():
                    # Partial epoch: resume at this epoch (the pipeline
                    # path had NO checkpointing at all in the reference,
                    # SURVEY.md §5).
                    from distributed_model_parallel_tpu.train.preemption import (
                        checkpoint_on_preempt,
                    )

                    self.start_epoch = epoch
                    checkpoint_on_preempt(self.preemption, self.ckpt,
                                          self._ckpt_tree(),
                                          "pipeline-preempt", self.logger,
                                          epoch,
                                          global_step=self._global_step)
                    break
                if eval_now(epoch, epochs, self.config.eval_every):
                    with span("evaluate", epoch=epoch):
                        ev = self._run_epoch(epoch, train=False)
                else:
                    ev = None
                record = dict(epoch=epoch, loss_train=tr.loss,
                              acc1_train=tr.acc1,
                              loss_val=ev.loss if ev else None,
                              acc1_val=ev.acc1 if ev else None,
                              time_per_batch=tr.step_time,
                              time_load_per_batch=tr.data_time)
                self.logger.log_epoch(**record)
                self.logger.telemetry.memory()
                history.append(record)
                if ev is not None and ev.acc1 > self.best_acc:
                    self.best_acc = ev.acc1
                    self.start_epoch = epoch + 1
                    self.ckpt.save(self._ckpt_tree(), "pipeline")
                # Finite-checked epoch state = the recovery restore point.
                self.resilience.note_good(self._ckpt_tree)
                epoch += 1
        self.logger.finish(epochs_run=len(history))
        return history
