"""End-to-end trainer for the Transformer LM flagship.

Drives ``parallel/spmd_pipeline.make_spmd_train_step`` — the single-jit
dp x pp x tp x sp program — with the same harness conveniences the CNN
trainers have (epoch loop, logging, checkpoint/resume, timing meters).
The dataset is a deterministic synthetic token stream (zero-egress
environment); real corpora drop in by replacing ``make_token_stream``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.config import (
    MeshConfig,
    OptimizerConfig,
    RecoveryConfig,
)
from distributed_model_parallel_tpu.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
    make_spmd_train_step,
    shard_params,
)
from distributed_model_parallel_tpu.train.checkpoint import Checkpointer
from distributed_model_parallel_tpu.train.logging_util import RunLogger
from distributed_model_parallel_tpu.utils import tracing
from distributed_model_parallel_tpu.utils.tracing import span
from distributed_model_parallel_tpu.train.metrics import AverageMeter, StepTimer
from distributed_model_parallel_tpu.train.optim import make_optimizer


def make_token_stream(vocab_size: int, n_tokens: int, seed: int = 0
                      ) -> np.ndarray:
    """Deterministic order-1 Markov token stream — learnable structure so
    loss visibly drops below the unigram entropy."""
    rng = np.random.default_rng(seed)
    # sparse transition matrix: each token prefers ~4 successors
    prefs = rng.integers(0, vocab_size, size=(vocab_size, 4))
    out = np.empty(n_tokens, np.int32)
    tok = 0
    for i in range(n_tokens):
        out[i] = tok
        if rng.random() < 0.8:
            tok = int(prefs[tok, rng.integers(0, 4)])
        else:
            tok = int(rng.integers(0, vocab_size))
    return out


@dataclasses.dataclass(frozen=True)
class LMTrainConfig:
    model: tfm.TransformerConfig = tfm.TransformerConfig()
    # "spmd" = run the configured mesh as-is (the single-jit
    # dp x pp x tp x sp x ep program); "auto" = let the parallelism
    # autotuner (autotune/, docs/AUTOTUNE.md) pick the axis degrees for
    # the LIVE device count — enumerate feasible factorizations, filter
    # by HBM feasibility, rank with the alpha-beta comm/compute cost
    # model, rewrite `mesh` (+ `num_microbatches`, and the model's
    # sp_axis when a sequence axis is planned) from the winner, and emit
    # a typed `plan` telemetry record. Elastic restarts re-plan on the
    # refitted mesh instead of blindly shrinking dp.
    strategy: str = "spmd"
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=lambda: OptimizerConfig(learning_rate=0.1,
                                                weight_decay=0.0))
    batch_size: int = 8
    seq_len: int = 128
    num_microbatches: int = 1
    # SPMD pipeline schedule: "gpipe" (whole-program AD; all M microbatches'
    # residuals live at peak) or "1f1b" (hand-interleaved backward; peak
    # activation memory bounded by the stage count, not M —
    # parallel/spmd_pipeline.make_1f1b_loss_and_grad).
    pipeline_schedule: str = "gpipe"
    # Megatron interleaved virtual stages (1f1b only): device s owns V
    # model chunks; the trainer interleaves the block rows at init so the
    # whole run (optimizer state included) lives in storage order.
    virtual_stages: int = 1
    steps_per_epoch: int = 50
    epochs: int = 1
    n_tokens: int = 200_000
    seed: int = 0
    # Held-out evaluation (the reference evals every epoch,
    # data_parallel.py:160-172): the stream's trailing ``eval_fraction``
    # never appears in training batches; ``eval_batches`` fixed batches
    # from it are scored each ``eval_every`` epochs (0 disables eval).
    # ``eval_batches=None`` means auto: 8 when the held-out tail fits at
    # least one seq_len eval window, otherwise eval is disabled with a
    # warning. An explicit integer that cannot fit still raises — only
    # the auto default degrades silently.
    eval_fraction: float = 0.1
    eval_batches: int | None = None
    eval_every: int = 1
    log_dir: str = "./log"
    log_name: str = "lm"
    checkpoint_dir: str = "./checkpoint"
    resume: bool = False
    # Elastic resume — same semantics as TrainConfig.emergency_every /
    # TrainConfig.elastic (train/elastic.py): a step-cadence emergency
    # checkpoint slot carrying the exact continuation state (step cursor,
    # global step, recovery budgets), and startup mesh refit to the live
    # device count with resharded restore.
    emergency_every: int = 0
    elastic: bool = False
    # Guards (train/guards.py:GuardRunner) — same semantics as TrainConfig.
    check_finite_every: int = 0
    stall_budget_s: float | None = None
    # Cross-replica consistency sentinel cadence — same semantics as
    # TrainConfig.consistency_every (train/consistency.py). Params are
    # replicated over the data axis under the SPMD pipeline, so dp >= 2
    # gives real cross-replica detection; dp == 1 degrades to the
    # finiteness fingerprint.
    consistency_every: int = 0
    # Automatic recovery policy + fault-injection plan — same semantics as
    # TrainConfig.recovery (train/resilience.py, utils/faults.py).
    recovery: RecoveryConfig = dataclasses.field(
        default_factory=RecoveryConfig)
    # Live status/metrics exporter — same semantics as
    # TrainConfig.statusz_port (utils/statusz.py; DMP_STATUSZ_PORT
    # fallback, one exporter per process).
    statusz_port: int | None = None


class LMTrainer:
    def __init__(self, config: LMTrainConfig, spec: MeshSpec | None = None):
        if config.strategy not in ("spmd", "auto"):
            raise ValueError(
                f"LMTrainConfig.strategy must be 'spmd' or 'auto', got "
                f"{config.strategy!r} — no silent ignores")
        self.plan_decision = None
        if config.strategy == "auto" and spec is not None:
            raise ValueError(
                "strategy='auto' plans the mesh layout itself and cannot "
                "honor an explicit MeshSpec; resolve the plan first "
                "(autotune.plan_for_lm) or pass strategy='spmd' — no "
                "silent ignores")
        if config.strategy == "auto" and spec is None:
            # Cost-model-driven layout (autotune/, docs/AUTOTUNE.md):
            # enumerate every feasible (dp, pp, tp, sp, ep) factorization
            # of the LIVE device count, HBM-filter, rank alpha-beta, and
            # rewrite mesh/microbatches/sp_axis from the winner. An
            # elastic restart therefore RE-PLANS on the refitted mesh.
            from distributed_model_parallel_tpu.autotune.planner import (
                plan_for_lm,
            )
            from distributed_model_parallel_tpu.train.elastic import (
                live_device_count,
            )

            config, self.plan_decision = plan_for_lm(config,
                                                     live_device_count())
        self.elastic_decision = None
        if config.elastic and spec is None and self.plan_decision is None:
            # Elastic restart: refit the data axis to the live device count
            # (train/elastic.py); resume then reshards the checkpoint onto
            # the rebuilt mesh. strategy="auto" replans above instead.
            from distributed_model_parallel_tpu.train.elastic import (
                fit_mesh_to_devices,
                live_device_count,
            )

            mesh_cfg, self.elastic_decision = fit_mesh_to_devices(
                config.mesh, live_device_count(),
                batch_size=config.batch_size)
            config = dataclasses.replace(config, mesh=mesh_cfg)
        self.config = config
        self.spec = spec if spec is not None else make_mesh(config.mesh)
        cfg = config.model
        if cfg.max_seq_len < config.seq_len:
            raise ValueError("model max_seq_len < training seq_len")
        self.cfg = cfg
        if cfg.looped:
            raise NotImplementedError(
                f"LMTrainer trains a stack run once: a looped stack "
                f"(n_passes={cfg.n_passes}, loop_final_norm="
                f"{cfg.loop_final_norm}, exit_gate={cfg.exit_gate}) would "
                f"take the head on every pass's output and the looped "
                f"objective (the expected loss over exit steps under the "
                f"gate's distribution, with its entropy term), which are "
                f"not written (ROADMAP M8); serve it through serve.Engine")
        if config.optimizer.ema_decay is not None:
            raise ValueError(
                "ema_decay is implemented by the data-parallel Trainer "
                "(gspmd/fsdp), not the LM trainer — no silent ignores")
        if config.optimizer.fused:
            raise ValueError(
                "OptimizerConfig.fused runs the update over flat "
                "coalesced parameter buckets; the LM trainer's params are "
                "stage/tensor-sharded (spmd_pipeline.shard_params), so "
                "the flat concat would gather them to full size every "
                "step — use it on the replicated-param CNN trainer paths "
                "(gspmd/ddp) — no silent ignores")
        self.tx = make_optimizer(config.optimizer, config.steps_per_epoch,
                                 config.epochs)
        self._step = make_spmd_train_step(
            cfg, self.spec, self.tx,
            num_microbatches=config.num_microbatches,
            schedule=config.pipeline_schedule,
            virtual_stages=config.virtual_stages)

        host_params = tfm.init_params(jax.random.key(config.seed), cfg)
        if config.virtual_stages > 1:
            from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
                interleave_block_rows,
            )

            host_params["blocks"] = interleave_block_rows(
                host_params["blocks"], cfg.n_layers, self.spec.num_stages,
                config.virtual_stages)
        self.opt_state = jax.device_put(
            self.tx.init(host_params), NamedSharding(self.spec.mesh, P()))
        self.params = shard_params(host_params, cfg, self.spec)

        self.tokens = make_token_stream(cfg.vocab_size, config.n_tokens,
                                        config.seed)
        # Train/eval split: training samples only from the head of the
        # stream; eval scores fixed batches from the held-out tail.
        self._n_train = int(len(self.tokens) * (1.0 - config.eval_fraction))
        min_train = config.seq_len + 2
        if not (0.0 <= config.eval_fraction < 1.0):
            raise ValueError(
                f"eval_fraction must be in [0, 1), got {config.eval_fraction}")
        if self._n_train < min_train:
            raise ValueError(
                f"eval_fraction={config.eval_fraction} leaves only "
                f"{self._n_train} training tokens (< seq_len + 2)")
        self._eval_loss = None
        tail_fits = len(self.tokens) - config.seq_len - 1 > self._n_train
        if config.eval_batches is None:
            # Auto: eval when the tail fits a window, warn-and-skip when it
            # doesn't (long-context configs where 0.1*n_tokens < seq_len+1
            # must not become hard startup failures — ADVICE r3).
            self._n_eval_batches = 8 if tail_fits else 0
            if not tail_fits and config.eval_fraction > 0.0:
                import warnings

                warnings.warn(
                    f"held-out tail ({len(self.tokens) - self._n_train} "
                    f"tokens, eval_fraction={config.eval_fraction}) cannot "
                    f"fit one seq_len={config.seq_len} eval window; "
                    f"disabling eval (set eval_batches explicitly to make "
                    f"this an error)", stacklevel=2)
                # Nothing will ever read the carved-out tail — give it back
                # to training rather than silently dropping 10% of the
                # stream.
                self._n_train = len(self.tokens)
        else:
            self._n_eval_batches = config.eval_batches
        if self._n_eval_batches > 0 and config.eval_fraction > 0.0:
            # The held-out tail must fit at least one eval window, or
            # evaluate() would die mid-fit on an opaque rng bound error.
            if not tail_fits:
                raise ValueError(
                    f"eval tail ({len(self.tokens) - self._n_train} tokens, "
                    f"eval_fraction={config.eval_fraction}) cannot fit one "
                    f"seq_len={config.seq_len} eval window; raise "
                    f"eval_fraction/n_tokens or set eval_batches=0")
            from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
                make_spmd_eval_loss,
            )

            self._eval_loss = make_spmd_eval_loss(
                cfg, self.spec, num_microbatches=config.num_microbatches)
        self._rng = np.random.default_rng(config.seed + 1)
        from distributed_model_parallel_tpu.train.preemption import (
            PreemptionGuard,
        )

        self.preemption = PreemptionGuard()
        # Analytic model FLOPs per train step (utils/profiling): lets the
        # report CLI compute MFU from the telemetry stream alone (flops /
        # n_devices / step_time / chip peak).
        from distributed_model_parallel_tpu.utils.profiling import (
            lm_model_flops,
        )

        self.logger = RunLogger(
            config.log_dir, config.log_name,
            meta=dict(workload="lm",
                      batch_size=config.batch_size,
                      seq_len=config.seq_len,
                      tokens_per_step=config.batch_size * config.seq_len,
                      mesh=config.mesh.axis_sizes(),
                      pipeline_schedule=config.pipeline_schedule,
                      model_flops_per_step=lm_model_flops(
                          cfg, config.batch_size, config.seq_len)))
        # Span sink for this thread (utils/tracing.py) — resume/checkpoint
        # spans below land on this run's stream.
        tracing.install(self.logger.telemetry)
        # Live status exporter (utils/statusz.py) — see Trainer: start or
        # join the process's exporter, publish this run under /statusz.
        from distributed_model_parallel_tpu.utils import statusz

        statusz.maybe_serve(config.statusz_port)
        statusz.register_trainer(self, "lm")
        from distributed_model_parallel_tpu.train.resilience import (
            RecoverySupervisor,
        )
        from distributed_model_parallel_tpu.utils.faults import FaultInjector

        self.faults = FaultInjector(config.recovery.faults)
        from distributed_model_parallel_tpu.utils.faults import (
            validate_corruption_plan,
        )

        # Topology validation before the supervisor: its "arm the
        # sentinel" advice is useless on a dp=1 mesh.
        validate_corruption_plan(self.faults.plan, self.spec.num_data,
                                 context=f"dp={self.spec.num_data}")
        # Slice identity for the device-health sentinel feeds
        # (utils/health.py; no-ops outside orchestrated runs).
        self._device_ids = tuple(sorted(
            d.id for d in np.asarray(self.spec.mesh.devices).flat))
        self.ckpt = Checkpointer(config.checkpoint_dir,
                                 keep=config.recovery.keep_checkpoints,
                                 injector=self.faults,
                                 meta_fn=self._ckpt_meta)
        self.resilience = RecoverySupervisor(
            config.recovery, logger=self.logger, ckpt=self.ckpt,
            preemption=self.preemption, slot="lm-good", injector=self.faults,
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

        self.sentinel = ConsistencySentinel(
            config.consistency_every, self.spec, logger=self.logger,
            guards=self.guards,
            barrier_timeout_s=config.recovery.barrier_timeout_s)
        from distributed_model_parallel_tpu.train.elastic import (
            EmergencyCheckpointer,
        )

        self.emergency = EmergencyCheckpointer(
            self.ckpt, "lm-emergency", config.emergency_every,
            logger=self.logger)
        self.start_epoch = 0
        # Cooperative-scheduling hook (orchestrator/): called with this
        # trainer at every train-step boundary, before the preemption poll
        # — see Trainer.step_hook.
        self.step_hook = None
        # Exact-continuation position: the next (epoch, step) the training
        # loop will sample. Batches are derived statelessly from
        # (seed, epoch, step), so this pair IS the data-loader state
        # (train/elastic.py).
        self._pos_epoch = 0
        self._pos_step = 0
        self._global_step = 0
        if self.elastic_decision is not None and self.elastic_decision.changed:
            self.logger.log_line(self.elastic_decision.describe())
            self.logger.telemetry.event(self.elastic_decision.describe())
        if config.resume and any(self.ckpt.exists(n)
                                 for n in ("lm", "lm-preempt",
                                           "lm-emergency", "lm-good")):
            self._resume()
        if self.plan_decision is not None:
            # After _resume so an elastic re-plan is stamped with the
            # exact global step the run continues from.
            from distributed_model_parallel_tpu.autotune.planner import (
                emit_plan_record,
            )

            emit_plan_record(self.logger.telemetry, self.plan_decision,
                             global_step=self._global_step)
            self.logger.log_line(self.plan_decision.describe())

    # ------------------------------------------------------------------ data
    def sample_batch(self, epoch: int | None = None,
                     step: int | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """One training batch. With ``(epoch, step)`` the batch is derived
        statelessly from ``(seed, epoch, step)`` — the training loop's
        path, so a resumed run draws exactly the batches an uninterrupted
        run would have (train/elastic.py). Without them, the legacy
        consumed-rng stream (ad-hoc/interactive use)."""
        b, t = self.config.batch_size, self.config.seq_len
        if epoch is None or step is None:
            rng = self._rng
        else:
            rng = np.random.default_rng(
                (self.config.seed + 1, int(epoch), int(step)))
        starts = rng.integers(0, self._n_train - t - 1, size=b)
        idx = starts[:, None] + np.arange(t + 1)[None]
        chunk = self.tokens[idx]
        return chunk[:, :-1], chunk[:, 1:]

    def eval_batches(self):
        """Deterministic held-out batches from the stream's tail (same
        batches every epoch, so loss_val curves are comparable)."""
        b, t = self.config.batch_size, self.config.seq_len
        rng = np.random.default_rng(self.config.seed + 2)
        lo, hi = self._n_train, len(self.tokens) - t - 1
        for _ in range(self._n_eval_batches):
            starts = rng.integers(lo, hi, size=b)
            idx = starts[:, None] + np.arange(t + 1)[None]
            chunk = self.tokens[idx]
            yield chunk[:, :-1], chunk[:, 1:]

    def _canonical_params(self):
        """Params with blocks in canonical layer order. Under interleaved
        virtual stages the run's working layout is the interleaved storage
        order; the GPipe-forward eval loss composes layers in row order,
        so it must see the canonical stack (a layer-permuted model would
        evaluate silently wrong)."""
        if self.config.virtual_stages == 1:
            return self.params
        from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
            deinterleave_block_rows,
        )

        out = dict(self.params)
        blocks_c = deinterleave_block_rows(
            self.params["blocks"], self.cfg.n_layers, self.spec.num_stages,
            self.config.virtual_stages)
        # The row gather drops the NamedSharding; pin each leaf back to its
        # working-layout sharding (same shapes, so specs carry over).
        out["blocks"] = jax.tree.map(
            lambda c, o: jax.device_put(c, o.sharding),
            blocks_c, self.params["blocks"])
        return out

    def evaluate(self) -> float:
        """Mean held-out loss over the fixed eval batches.

        All batches are dispatched back-to-back and fetched with ONE
        host sync (vectorized numpy mean) — the per-batch ``float()``
        drain serialized upload/compute across eval batches through a
        remote device transport (one blocking round trip each)."""
        if self._eval_loss is None:
            raise ValueError("eval disabled (eval_batches=0 or "
                             "eval_fraction=0)")
        eval_params = self._canonical_params()
        # Bounded run-ahead (the Trainer.evaluate _max_inflight pattern):
        # a large explicit eval_batches must not hold every batch's
        # input buffers + in-flight computations on device at once.
        max_inflight = 8
        vals: list = []
        pending: list = []
        for toks, tgts in self.eval_batches():
            pending.append(self._eval_loss(eval_params, jnp.asarray(toks),
                                           jnp.asarray(tgts)))
            if len(pending) >= max_inflight:
                vals.extend(jax.device_get(pending))
                pending.clear()
        vals.extend(jax.device_get(pending))
        if not vals:
            return 0.0
        return float(np.mean(np.asarray(vals, dtype=np.float64)))

    # ----------------------------------------------------------- checkpoint
    def _ckpt_meta(self):
        """Manifest stamp: saving topology + exact position
        (train/checkpoint.py, train/elastic.py)."""
        return {"workload": "lm",
                "mesh": {**self.config.mesh.axis_sizes(),
                         "dcn_data": self.config.mesh.dcn_data},
                "n_devices": int(np.asarray(self.spec.mesh.devices).size),
                "global_step": self._global_step}

    def _resume_tree(self):
        from distributed_model_parallel_tpu.train import elastic

        return elastic.build_resume_tree(
            self._pos_epoch, self._pos_step, self.config.steps_per_epoch,
            self._global_step, self.resilience.budgets())

    def _ckpt_tree(self):
        # virtual_stages is part of the checkpoint identity: params AND
        # optimizer state rows live in the interleaved storage order, so a
        # resume under a different V would restore a layer-permuted model
        # whose shapes all match — detectable only by this marker.
        return {"params": self.params, "opt_state": self.opt_state,
                "epoch": jnp.asarray(self.start_epoch, jnp.int32),
                "virtual_stages": jnp.asarray(
                    self.config.virtual_stages, jnp.int32),
                "resume": self._resume_tree()}

    def _apply_resume_tree(self, restored: dict, *, budgets: bool) -> None:
        """Adopt the exact-continuation position; see Trainer for the
        ``budgets`` contract (False on in-run recovery restores)."""
        from distributed_model_parallel_tpu.train import elastic

        ri = restored.get("resume")
        if ri is None:
            return
        (self._pos_epoch, self._pos_step, self._global_step,
         retries, lr_scale) = elastic.unpack_resume_tree(ri)
        if budgets:
            self.resilience.restore_budgets(retries, lr_scale)
            if lr_scale != 1.0:
                self._apply_lr_shrink(lr_scale)

    def _resume(self):
        from distributed_model_parallel_tpu.train import elastic

        # Newest-valid slot wins: end-of-epoch "lm", the preemption save,
        # or a step-cadence emergency save — restored through
        # restore_resharded so a checkpoint from a different mesh degree
        # lands in this mesh's shardings. Template ladder: current tree,
        # then pre-elastic (no "resume" subtree), then pre-round-5 (no
        # virtual_stages marker either; its absence means V=1).
        tmpl = self._ckpt_tree()
        t2 = {k: v for k, v in tmpl.items() if k != "resume"}
        t3 = {k: v for k, v in t2.items() if k != "virtual_stages"}
        name, restored = elastic.elastic_restore(
            self.ckpt, (tmpl, t2, t3),
            # The supervisor's good slot is the last resort: it makes a
            # torn preemption/emergency save survivable (dmp_soak.py).
            ("lm", "lm-preempt", "lm-emergency", "lm-good"),
            on_fallback=self.resilience.note_fallback)
        ckpt_v = int(restored.get("virtual_stages", 1))
        if ckpt_v != self.config.virtual_stages:
            raise ValueError(
                f"checkpoint was written with virtual_stages={ckpt_v} "
                f"(blocks+opt-state rows in that interleaved storage "
                f"order) but this run has virtual_stages="
                f"{self.config.virtual_stages}; convert the blocks with "
                f"parallel.spmd_pipeline.deinterleave_block_rows/"
                f"interleave_block_rows (optimizer state rows too) or "
                f"resume with the matching V")
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.start_epoch = int(restored["epoch"])
        self._apply_resume_tree(restored, budgets=True)
        self.start_epoch = max(self.start_epoch, self._pos_epoch)
        # Provenance from the version actually read (a torn-newest
        # fallback may have restored an older one).
        from distributed_model_parallel_tpu.train.checkpoint import (
            read_manifest_meta,
        )

        saved_mesh = (read_manifest_meta(self.ckpt.last_restored_path)
                      if self.ckpt.last_restored_path else {}).get("mesh")
        current_mesh = self._ckpt_meta()["mesh"]
        self.logger.telemetry.resume(
            slot=name, epoch=self.start_epoch,
            loader_epoch=self._pos_epoch, batch_cursor=self._pos_step,
            global_step=self._global_step, mesh=current_mesh,
            **({"saved_mesh": saved_mesh}
               if saved_mesh and saved_mesh != current_mesh else {}))
        self.logger.log_line(
            f"resume: slot {name!r} -> epoch {self.start_epoch} "
            f"step {self._pos_step} (global step {self._global_step})"
            + (f", resharded from mesh {saved_mesh}"
               if saved_mesh and saved_mesh != current_mesh else ""))

    def _restore_good(self):
        """Recovery restore from the supervisor's "last good" slot
        (train/resilience.py), with torn-version fallback. Position rides
        along; budgets stay live (see Trainer._restore_good)."""
        restored = self.ckpt.restore(
            self._ckpt_tree(), self.resilience.slot, allow_fallback=True,
            on_fallback=self.resilience.note_fallback)
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self._apply_resume_tree(restored, budgets=False)

    def _apply_lr_shrink(self, factor: float) -> None:
        """Recovery-time LR shrink: rebuild the optimizer and the jitted
        train step at the scaled LR — opt_state structure is unchanged (the
        schedule is a closure), so the restored state carries over."""
        opt = dataclasses.replace(
            self.config.optimizer,
            learning_rate=self.config.optimizer.learning_rate * factor)
        self.config = dataclasses.replace(self.config, optimizer=opt)
        self.tx = make_optimizer(opt, self.config.steps_per_epoch,
                                 self.config.epochs)
        self._step = make_spmd_train_step(
            self.cfg, self.spec, self.tx,
            num_microbatches=self.config.num_microbatches,
            schedule=self.config.pipeline_schedule,
            virtual_stages=self.config.virtual_stages)

    # ----------------------------------------------------------------- loop
    def _poll_step_faults(self, step_m):
        """Serve planned step-site faults (utils/faults.py): poison this
        step's loss or the live params, silently corrupt one replica's
        params, or request a simulated preemption. Returns the (possibly
        poisoned) step metrics."""
        from distributed_model_parallel_tpu.utils.faults import (
            CORRUPTION_KINDS,
            corrupt_one_replica,
            poison,
        )

        for spec in self.faults.poll("step"):
            if spec.kind == "preempt":
                self.preemption.request()
            elif spec.kind == "nan_loss":
                step_m = poison(step_m)
            elif spec.kind == "nan_params":
                self.params = poison(self.params)
            elif spec.kind in CORRUPTION_KINDS:
                self.params = corrupt_one_replica(
                    self.params, self.spec, spec.kind, spec.param)
        return step_m

    def _run_sentinel(self, n_steps: int, *, flush: bool = False) -> None:
        """Advance the consistency sentinel (train/consistency.py) — or,
        with ``flush=True``, check any steps the cadence hasn't covered
        (end of epoch, before the good slot is stamped) — and splice a
        repaired params/opt_state pair back in place. No-quorum
        divergence raises into fit()'s recovery handler."""
        tree_fn = lambda: {"params": self.params,
                           "opt_state": self.opt_state}
        fixed = (self.sentinel.flush(tree_fn) if flush
                 else self.sentinel.after_sync(n_steps, tree_fn))
        if fixed is not None:
            self.params = fixed["params"]
            self.opt_state = fixed["opt_state"]

    def _train_one_epoch(self, epoch: int, epochs: int) -> dict | None:
        """One training epoch + eval. Returns the history record, or None
        when a preemption stopped the epoch mid-way (checkpoint already
        written). Raises NonFiniteError through to fit()'s recovery path."""
        meter = AverageMeter("loss")
        drop_meter = AverageMeter("moe_drop")
        timer = StepTimer()
        tokens_per_step = (self.config.batch_size
                           * self.config.seq_len)
        # Start of `epoch`, or the mid-epoch cursor a resumed run loaded
        # (train/elastic.py). Batches are stateless in (epoch, step), so
        # the continuation draws exactly what the uninterrupted run would.
        if epoch != self._pos_epoch:
            self._pos_epoch, self._pos_step = epoch, 0
        start = self._pos_step
        for step_i in range(start, self.config.steps_per_epoch):
            # The whole loop body, hook included: what its three children
            # leave over is the guards, the sentinel, the step's
            # telemetry, health and the emergency checkpoint's cadence.
            # Profiler-only: the stream has the step's ``step`` record.
            with span("train_step", stream=False, step=self._global_step):
                if self.step_hook is not None:
                    self.step_hook(self)
                if self.preemption.requested():
                    break
                with span("train_step.batch", stream=False):
                    toks, tgts = self.sample_batch(epoch, step_i)
                    timer.data_ready()
                    toks, tgts = jnp.asarray(toks), jnp.asarray(tgts)
                with span("train_step.dispatch", stream=False):
                    self.params, self.opt_state, step_m = self._step(
                        self.params, self.opt_state, toks, tgts)
                if self.faults.enabled:
                    step_m = self._poll_step_faults(step_m)
                with span("train_step.sync", stream=False), \
                        self.guards.watch():
                    # the per-step sync point
                    loss_host = float(step_m["loss"])
                if self.guards.enabled:
                    self.guards.after_sync({"loss": loss_host}, 1,
                                           params=self.params)
                if self.sentinel.enabled:
                    self._run_sentinel(1)
                meter.update(loss_host)
                if "moe_drop" in step_m:
                    drop_meter.update(float(step_m["moe_drop"]))
                self._pos_step = step_i + 1
                self._global_step += 1
                timer.step_done()
                # Per-step health signal (the LM loop syncs every step,
                # so this is a true per-step time; utils/health.py —
                # no-op outside orchestrated runs, first compile window
                # skipped).
                from distributed_model_parallel_tpu.utils import health

                health.observe_step_warmed(self, self._device_ids,
                                           timer.step.last, 1)
                # Per-step telemetry (the LM loop syncs every step, so
                # the per-step timing is real, not a window average).
                self.logger.telemetry.step(
                    epoch=epoch, step=step_i, loss=loss_host,
                    step_time_s=timer.step.last,
                    data_time_s=timer.data.last,
                    tokens_per_s=tokens_per_step
                    / max(timer.step.last, 1e-9))
                self.emergency.after_step(1, self._ckpt_tree)
        if self.sentinel.enabled:
            # Cover any tail steps the cadence missed before the epoch is
            # declared clean (or a preempt checkpoint is written) — an
            # epoch shorter than the cadence would otherwise never be
            # checked at all (train/consistency.py flush).
            self._run_sentinel(0, flush=True)
        if self.preemption.requested():
            # Partial epoch: save for resume at this epoch and stop
            # cleanly (train/preemption.py).
            from distributed_model_parallel_tpu.train.preemption import (
                checkpoint_on_preempt,
            )

            self.start_epoch = epoch
            checkpoint_on_preempt(self.preemption, self.ckpt,
                                  self._ckpt_tree(), "lm-preempt",
                                  self.logger, epoch,
                                  global_step=self._global_step)
            return None
        from distributed_model_parallel_tpu.train.trainer import (
            eval_now,
        )

        if (self._eval_loss is not None
                and eval_now(epoch, epochs, self.config.eval_every)):
            with span("evaluate", epoch=epoch):
                loss_val = self.evaluate()
        else:
            loss_val = None
        record = dict(epoch=epoch, loss_train=meter.avg,
                      loss_val=loss_val,
                      time_per_batch=timer.step.avg,
                      time_load_per_batch=timer.data.avg,
                      tokens_per_s=self.config.batch_size
                      * self.config.seq_len / max(timer.step.avg, 1e-9))
        if drop_meter.count:
            # MoE router observability: mean fraction of
            # token-choices dropped at capacity this epoch
            # (ops/moe._route — silent overflow made visible).
            record["moe_drop_rate"] = drop_meter.avg
        return record

    def fit(self, epochs: int | None = None) -> list[dict]:
        """Epoch loop with eval, per-epoch checkpointing, preemption-safe
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
                        record = self._train_one_epoch(epoch, epochs)
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
                if record is None:      # preempted mid-epoch
                    break
                self.logger.log_epoch(**record)
                self.logger.telemetry.memory()
                history.append(record)
                self.start_epoch = epoch + 1
                self.ckpt.save(self._ckpt_tree(), "lm")
                # Finite-checked epoch state = the recovery restore point.
                self.resilience.note_good(self._ckpt_tree)
                epoch += 1
        self.logger.finish(epochs_run=len(history))
        return history
