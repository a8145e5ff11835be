"""Training harness: jitted SPMD train/eval steps + the epoch driver.

Covers the reference's two driver scripts' harness behavior
(``data_parallel.py:99-172``, ``utils.py:34-210``): cross-entropy training
with SGD + cosine + warmup, top-1/5 accuracy, per-batch compute/data timing,
every-N-step prints, per-epoch text logging, best-acc checkpointing with
resume.

Data parallelism here is the GSPMD path: the batch is sharded over the mesh's
``data`` axis, parameters are replicated, and XLA inserts the gradient
allreduce — the TPU-native equivalent of both ``nn.DataParallel``'s
scatter/replicate/gather (reference ``Readme.md:17-143``) and DDP's bucketed
ring-allreduce (``Readme.md:144-157``). BatchNorm under this path sees the
global batch (SyncBN semantics); per-replica BN lives in the explicit
``shard_map`` DDP path (parallel/ddp.py).
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from distributed_model_parallel_tpu.config import TrainConfig
from distributed_model_parallel_tpu.data.loader import (
    BatchLoader,
    augment_batch,
    maybe_device_prefetch,
    maybe_prefetch,
    normalize,
    resize_batch,
    resolve_input_size,
)
from distributed_model_parallel_tpu.data.registry import ArrayDataset, load_dataset
from distributed_model_parallel_tpu.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.models import get_model
from distributed_model_parallel_tpu.models.staged import StagedModel
from distributed_model_parallel_tpu.train.checkpoint import Checkpointer
from distributed_model_parallel_tpu.train.logging_util import RunLogger
from distributed_model_parallel_tpu.train.metrics import AverageMeter, StepTimer, topk_correct
from distributed_model_parallel_tpu.train.optim import make_optimizer
from distributed_model_parallel_tpu.utils import health, tracing
from distributed_model_parallel_tpu.utils.tracing import span


def _filter_expected_batch_donation_warnings() -> None:
    """Silence jax's "donated buffers were not usable" warning ONLY for
    the uint8/int32 batch buffers the train steps donate BY DESIGN (no
    same-shaped output to alias with — ownership transfer still frees
    them at dispatch, see ``_build_steps``). Left loud, the known-noise
    warning trains users to ignore donation warnings — including a
    future REAL one where the f32 state alias drops (the 2x-live-memory
    regression ``utils/profiling.assert_donation`` exists to catch).
    The filter is shape-anchored: a dropped float buffer breaks the
    pattern and stays loud. Audits are unaffected (``donation_report``
    captures under ``simplefilter("always")``, which overrides this
    filter in-context). Installed at import; re-invoke after anything
    that resets the process filters (pytest does per test)."""
    warnings.filterwarnings(
        "ignore",
        message=r"Some donated buffers were not usable: "
                r"((uint8|int32)\[[\d,]*\](, )?)+\.")


_filter_expected_batch_donation_warnings()


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    model_state: Any          # BN running stats (tuple over units)
    opt_state: Any
    # Exponential moving average of params + model_state (None unless
    # OptimizerConfig.ema_decay is set); evaluation/checkpoint-selection
    # read these when present — the standard large-batch trick the
    # reference lacks. BN running stats are averaged alongside the weights
    # so evaluation never pairs averaged weights with live statistics.
    ema_params: Any = None
    ema_model_state: Any = None


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def eval_now(epoch: int, total_epochs: int, eval_every: int) -> bool:
    """Eval-cadence rule shared by the DP and pipeline trainers: every Nth
    epoch, and always the final one (so final-loss artifacts exist)."""
    return ((epoch + 1) % max(1, eval_every) == 0
            or epoch == total_epochs - 1)


def make_train_step(model: StagedModel, tx: optax.GradientTransformation,
                    *, mean, std, augment: bool = True,
                    dtype=jnp.float32, ema_decay: float | None = None,
                    resize_to: int | None = None) -> Callable:
    """Returns step(state, rng, images_u8, labels) -> (state, metrics).

    Augmentation + normalization run on-device so XLA fuses them with the
    forward pass; metrics are computed on-device as sums (psum-friendly).
    With ``ema_decay``, ``state.ema_params`` tracks
    ``d*ema + (1-d)*params`` after each update. ``resize_to`` upsamples the
    uint8 batch on-device before augmentation (the 224px finetune input
    path; data/loader.resize_batch).
    """

    def loss_fn(params, model_state, images, labels):
        logits, new_state = model.apply(params, model_state, images, train=True)
        loss = cross_entropy(logits, labels)
        return loss, (logits, new_state)

    def step(state: TrainState, rng: jax.Array, images_u8, labels):
        if resize_to is not None:
            images_u8 = resize_batch(images_u8, resize_to)
        images_u8 = augment_batch(rng, images_u8) if augment else images_u8
        images = normalize(images_u8, mean, std, dtype)
        (loss, (logits, new_model_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, state.model_state, images, labels)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_ema = state.ema_params
        new_ema_state = state.ema_model_state
        if ema_decay is not None:
            step_size = 1.0 - ema_decay
            if hasattr(new_opt_state, "mini_step"):
                # Gradient accumulation (optax.MultiSteps): only count real
                # optimizer updates — mini_step resets to 0 exactly when one
                # fires — so the EMA horizon matches the equivalent
                # big-batch run instead of shrinking by accum_steps.
                step_size = jnp.where(new_opt_state.mini_step == 0,
                                      step_size, 0.0)
            new_ema = optax.incremental_update(new_params, state.ema_params,
                                               step_size)
            # BN running stats averaged on the same horizon — evaluating
            # averaged weights against live statistics skews the metrics.
            new_ema_state = optax.incremental_update(
                new_model_state, state.ema_model_state, step_size)
        metrics = {"loss": loss, "batch": jnp.asarray(labels.shape[0], jnp.float32),
                   **topk_correct(logits, labels)}
        return (TrainState(step=state.step + 1, params=new_params,
                           model_state=new_model_state,
                           opt_state=new_opt_state,
                           ema_params=new_ema,
                           ema_model_state=new_ema_state), metrics)

    return step


def make_multi_step(model: StagedModel, tx: optax.GradientTransformation,
                    *, image_shape, mean, std, augment: bool = True,
                    dtype=jnp.float32, ema_decay: float | None = None,
                    resize_to: int | None = None) -> Callable:
    """K train steps per dispatched program (lax.scan) over a
    device-resident dataset.

    multi(state, rng, images_flat, labels_all, idx[K, B]) -> (state,
    stacked metrics). Each scan step gathers its batch from the on-device
    dataset by index — no host→device image traffic and no per-step
    dispatch, the two costs that dominate small-step training through a
    remote device transport. The per-step math is exactly
    ``make_train_step``'s.
    """
    step = make_train_step(model, tx, mean=mean, std=std, augment=augment,
                           dtype=dtype, ema_decay=ema_decay,
                           resize_to=resize_to)
    h, w, c = image_shape

    def multi(state: TrainState, rng: jax.Array, images_flat, labels_all, idx):
        rngs = jax.random.split(rng, idx.shape[0])

        def body(st, xs):
            r, ib = xs
            im = jnp.take(images_flat, ib, axis=0).reshape(
                ib.shape[0], h, w, c)
            lb = jnp.take(labels_all, ib, axis=0)
            return step(st, r, im, lb)

        return jax.lax.scan(body, state, (rngs, idx))

    return multi


def make_eval_step(model: StagedModel, *, mean, std, dtype=jnp.float32,
                   use_ema: bool = False,
                   resize_to: int | None = None) -> Callable:
    def step(state: TrainState, images_u8, labels):
        if resize_to is not None:
            images_u8 = resize_batch(images_u8, resize_to)
        images = normalize(images_u8, mean, std, dtype)
        params = state.ema_params if use_ema else state.params
        model_state = state.ema_model_state if use_ema else state.model_state
        logits, _ = model.apply(params, model_state, images,
                                train=False)
        return {"loss": cross_entropy(logits, labels),
                "batch": jnp.asarray(labels.shape[0], jnp.float32),
                **topk_correct(logits, labels)}

    return step


@dataclasses.dataclass
class EpochResult:
    loss: float
    acc1: float
    acc5: float
    step_time: float
    data_time: float


class Trainer:
    """Data-parallel epoch driver over a mesh (GSPMD path)."""

    def __init__(self, config: TrainConfig, spec: MeshSpec | None = None,
                 *, train_ds: ArrayDataset | None = None,
                 eval_ds: ArrayDataset | None = None):
        self.plan_decision = None
        if config.strategy == "auto" and spec is not None:
            raise ValueError(
                "strategy='auto' plans the mesh layout itself and cannot "
                "honor an explicit MeshSpec; resolve the plan first "
                "(autotune.plan_for_cnn) or pass a concrete strategy — "
                "no silent ignores")
        if config.strategy == "auto" and spec is None:
            # Cost-model-driven layout (autotune/, docs/AUTOTUNE.md):
            # probe the model, enumerate feasible (dp, pp) x strategy
            # layouts of the LIVE device count, rank with the alpha-beta
            # comm/compute model, and rewrite strategy + mesh from the
            # winner. On an elastic restart this REPLANS on the refitted
            # mesh instead of blindly shrinking dp.
            from distributed_model_parallel_tpu.autotune.planner import (
                plan_for_cnn,
            )
            from distributed_model_parallel_tpu.train.elastic import (
                live_device_count,
            )

            config, self.plan_decision = plan_for_cnn(config,
                                                      live_device_count())
        self.elastic_decision = None
        if config.elastic and spec is None and self.plan_decision is None:
            # Elastic restart: rebuild the mesh at the largest dp degree
            # the live device count supports (train/elastic.py) — the
            # degraded-slice restart path. An explicit `spec` means the
            # caller already chose a topology; strategy="auto" replans
            # above instead.
            from distributed_model_parallel_tpu.train.elastic import (
                fit_mesh_to_devices,
                live_device_count,
            )

            mesh_cfg, self.elastic_decision = fit_mesh_to_devices(
                config.mesh, live_device_count(),
                batch_size=config.data.batch_size)
            config = config.replace(mesh=mesh_cfg)
        self.config = config
        if config.optimizer.fused and config.strategy == "fsdp":
            raise ValueError(
                "OptimizerConfig.fused runs the update over flat "
                "coalesced parameter buckets, which would gather the "
                "ZeRO-sharded params/opt state back to full size on "
                "every step; use it with replicated-param strategies "
                "(gspmd/ddp) — no silent ignores")
        if config.grad_bucket_mb is not None and config.strategy != "ddp":
            raise ValueError(
                f"grad_bucket_mb routes the gradient allreduce through "
                f"ops/collectives.bucketed_psum, which needs the explicit "
                f"per-replica grad path (strategy='ddp'); "
                f"strategy={config.strategy!r} leaves the reduction to "
                f"XLA's partitioner — no silent ignores")
        if (config.grad_bucket_mb is not None
                and config.ddp_allreduce == "hierarchical"):
            raise ValueError(
                "grad_bucket_mb has no effect on the hierarchical "
                "transport (hierarchical_psum_tree flattens the whole "
                "tree into one two-level reduction, no size-capped "
                "buckets); use ddp_allreduce='psum'/'bucketed'/'ring' "
                "with it — no silent ignores")
        self.spec = spec if spec is not None else make_mesh(config.mesh)
        if train_ds is None or eval_ds is None:
            train_ds, eval_ds = load_dataset(config.data)
        self.train_ds, self.eval_ds = train_ds, eval_ds

        axis = self.spec.data_axis if config.model.batchnorm == "sync" else None
        self.model = get_model(config.model, axis_name=axis)

        # Multi-process (multi-host) runs: every process computes the same
        # global batch order; the loaders materialize only the local slice
        # and _shard_batch stitches the global array
        # (mesh.host_local_batch_to_global). Single-process runs are
        # untouched (shard_by_process degenerates to the whole batch).
        multiprocess = jax.process_count() > 1
        if multiprocess and config.device_resident_data:
            raise ValueError(
                "device_resident_data assumes a single-process runtime "
                "(the dataset upload and index gathers are per-process); "
                "use the streaming path on multi-host")
        self.train_loader = BatchLoader(
            train_ds, config.data.batch_size, shuffle=config.data.shuffle,
            seed=config.data.seed, use_native=config.data.use_native,
            num_workers=config.data.num_workers,
            shard_by_process=multiprocess)
        self.eval_loader = BatchLoader(
            eval_ds, min(config.data.eval_batch_size, len(eval_ds)),
            shuffle=False, use_native=config.data.use_native,
            num_workers=config.data.num_workers,
            shard_by_process=multiprocess)

        self.tx = make_optimizer(config.optimizer, len(self.train_loader),
                                 config.epochs)
        # On-device resize stage when the configured input size differs from
        # the dataset's native resolution (the 224px finetune input path):
        # the model initializes at the *target* size and every step upsamples
        # the uint8 batch before augmentation.
        resize_to, in_hw = resolve_input_size(train_ds.images.shape,
                                              config.data.image_size)
        sample = jnp.zeros((2, in_hw, in_hw, train_ds.images.shape[3]),
                           jnp.uint8)
        params, model_state = self.model.init(
            jax.random.key(config.seed),
            normalize(sample, train_ds.mean, train_ds.std))
        # Replicate state over the mesh; shard batches on the data axis.
        self._repl = self.spec.replicated()
        self._batch_sh = self.spec.batch_sharded()
        kw = dict(mean=train_ds.mean, std=train_ds.std, resize_to=resize_to)

        ema = config.optimizer.ema_decay
        if ema is not None and not (0.0 <= ema <= 1.0):
            raise ValueError(f"ema_decay must be in [0, 1], got {ema}")
        # Everything _build_steps needs to (re)construct the jitted step
        # functions — stored so a recovery-time LR shrink can rebuild them
        # without re-running state init (train/resilience.py).
        self._kw = kw
        self._in_hw = in_hw
        if config.strategy == "ddp":
            if config.device_resident_data:
                raise ValueError(
                    "device_resident_data is only supported with "
                    "strategy='gspmd' (the ddp path materializes per-replica "
                    "batches on host)")
            if ema is not None:
                raise ValueError(
                    "ema_decay is supported on the gspmd/fsdp strategies")
            # Explicit per-replica engine: BN state carries a leading
            # per-replica axis sharded over the data axis (parallel/ddp.py).
            from distributed_model_parallel_tpu.parallel.ddp import (
                replicate_model_state,
            )

            model_state = replicate_model_state(model_state, self.spec.num_data)
            state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                               model_state=model_state,
                               opt_state=self.tx.init(params))
            self._state_sh = TrainState(
                step=self._repl, params=self._repl,
                model_state=self.spec.batch_sharded(),
                opt_state=self._repl)
            self.state = jax.device_put(state, self._state_sh)
        elif config.strategy in ("gspmd", "fsdp"):
            if config.strategy == "fsdp":
                # ZeRO-3: params + optimizer state live sharded over `data`;
                # XLA's partitioner inserts the just-in-time all-gathers and
                # gradient reduce-scatters (parallel/fsdp.py). Shard params
                # *before* building optimizer state, and init that state
                # directly into its sharded layout (jit + out_shardings) so
                # the full-size tree never materializes on one device.
                from distributed_model_parallel_tpu.parallel.fsdp import (
                    tree_shardings,
                )

                params_sh = tree_shardings(params, self.spec)
                params = jax.device_put(params, params_sh)
                opt_sh = tree_shardings(jax.eval_shape(self.tx.init, params),
                                        self.spec)
                opt_state = jax.jit(self.tx.init, out_shardings=opt_sh)(params)
                self._state_sh = TrainState(
                    step=self._repl, params=params_sh,
                    model_state=self._repl, opt_state=opt_sh,
                    ema_params=params_sh if ema is not None else None,
                    ema_model_state=(self._repl if ema is not None else None))
            else:
                self._state_sh = self._repl
                opt_state = self.tx.init(params)
            # EMA starts at the initial weights/stats — as real copies:
            # params and ema_params live in one donated state, and donation
            # rejects the same buffer appearing twice.
            ema_params = (jax.tree.map(jnp.copy, params) if ema is not None
                          else None)
            ema_model_state = (jax.tree.map(jnp.copy, model_state)
                               if ema is not None else None)
            state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                               model_state=model_state, opt_state=opt_state,
                               ema_params=ema_params,
                               ema_model_state=ema_model_state)
            self.state = jax.device_put(state, self._state_sh)
            if config.device_resident_data:
                # Fast path: dataset lives on device; K steps per dispatch.
                if getattr(train_ds, "is_lazy", False):
                    raise ValueError(
                        "device_resident_data requires materialized pixels "
                        "but the dataset streams lazily from disk (auto "
                        "when decoded size exceeds the in-memory cap); set "
                        "DataConfig.lazy_decode=False to decode eagerly, "
                        "or drop device_resident_data")
                n = len(train_ds)
                self._dev_images = jax.device_put(
                    train_ds.images.reshape(n, -1), self._repl)
                self._dev_labels = jax.device_put(
                    np.asarray(train_ds.labels), self._repl)
        elif config.strategy == "spmd_pipeline":
            # Single-program GPipe over the `stage` mesh axis for staged
            # CNNs (parallel/spmd_cnn_pipeline.py) — the multi-host-capable
            # counterpart of PipelineTrainer's single-controller runtime,
            # driven by this harness because its step has the same
            # (state, rng, images, labels) -> (state, metrics) contract as
            # the GSPMD step. Params stay replicated (each device computes
            # only its own stage), so eval rides the ordinary batch-sharded
            # GSPMD forward.
            if config.device_resident_data:
                raise ValueError(
                    "device_resident_data is only supported with "
                    "strategy='gspmd'")
            if ema is not None:
                raise ValueError(
                    "ema_decay is supported on the gspmd/fsdp strategies")
            if self.spec.num_stages < 2:
                raise ValueError(
                    "strategy='spmd_pipeline' needs mesh.stage >= 2 "
                    "(use 'gspmd' for pure data parallelism)")
            if config.pipeline_schedule not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"strategy='spmd_pipeline' implements the gpipe and "
                    f"1f1b schedules, got "
                    f"{config.pipeline_schedule!r} (interleaved is a "
                    f"single-controller PipelineRunner schedule — no "
                    f"silent ignores)")
            if config.virtual_stages != 1 and \
                    config.pipeline_schedule != "1f1b":
                raise ValueError(
                    "strategy='spmd_pipeline' supports interleaved "
                    "virtual stages only under pipeline_schedule='1f1b' "
                    "(spmd_cnn_pipeline.make_cnn_1f1b_fwd_bwd); gpipe's "
                    "whole-program AD would gain nothing — no silent "
                    "ignores")
            boundaries = config.stage_boundaries
            # Under interleaved virtual stages the model splits into
            # D = S*V CHUNKS, so boundaries (explicit or auto) are chunk
            # boundaries — D+1 cut points, not S+1.
            n_chunks = self.spec.num_stages * config.virtual_stages
            if (boundaries is not None
                    and len(boundaries) != n_chunks + 1):
                raise ValueError(
                    f"stage_boundaries has {len(boundaries)} cut points "
                    f"but the pipeline splits into {n_chunks} chunks "
                    f"({self.spec.num_stages} stages x "
                    f"{config.virtual_stages} virtual) — provide "
                    f"{n_chunks + 1}")
            if boundaries is None and config.auto_partition:
                from distributed_model_parallel_tpu.parallel.auto_partition import (
                    auto_boundaries,
                    microbatch_rows,
                )

                micro = microbatch_rows(config.data.batch_size,
                                        config.num_microbatches,
                                        self.spec.num_data)
                boundaries = auto_boundaries(
                    self.model,
                    (micro, in_hw, in_hw, train_ds.images.shape[3]),
                    n_chunks)
            self._boundaries = boundaries
            self._state_sh = self._repl
            state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                               model_state=model_state,
                               opt_state=self.tx.init(params))
            self.state = jax.device_put(state, self._state_sh)
            # masked dispatch on CPU: conv backward inside lax.switch loses
            # intra-op threading on the XLA CPU backend (~35x slower —
            # spmd_cnn_pipeline.py); TPU keeps the switch default.
            self._dispatch = ("masked" if jax.devices()[0].platform == "cpu"
                              else "switch")
        else:
            raise KeyError(f"unknown strategy {config.strategy!r}")
        self._build_steps()

        self._max_inflight = max(1, config.max_inflight_steps)
        from distributed_model_parallel_tpu.train.preemption import (
            PreemptionGuard,
        )

        self.preemption = PreemptionGuard()
        self.logger = RunLogger(
            config.log_dir, config.log_name,
            meta=dict(workload="cnn", model=config.model.name,
                      strategy=config.strategy,
                      batch_size=config.data.batch_size,
                      mesh=config.mesh.axis_sizes(),
                      steps_per_dispatch=config.steps_per_dispatch
                      if config.device_resident_data else 1))
        # Span sink for this thread (utils/tracing.py): every span opened
        # while this trainer runs — including the resume/restore below and
        # checkpoint I/O deep in train/checkpoint.py — lands on this run's
        # stream (and inherits its tenant tag under the orchestrator).
        tracing.install(self.logger.telemetry)
        # Live status exporter (utils/statusz.py): start the process's
        # exporter when a port is configured (else join the running one —
        # orchestrated tenants land on the fleet's) and publish this
        # run's live state under /statusz. No-op when neither
        # statusz_port nor DMP_STATUSZ_PORT is set.
        from distributed_model_parallel_tpu.utils import statusz

        statusz.maybe_serve(config.statusz_port)
        statusz.register_trainer(self, "cnn")
        from distributed_model_parallel_tpu.train.resilience import (
            RecoverySupervisor,
        )
        from distributed_model_parallel_tpu.utils.faults import (
            FaultInjector,
            validate_corruption_plan,
        )

        # Slice identity for the device-health sentinel feeds
        # (utils/health.py; no-ops unless an orchestrator installed a
        # monitor): step windows, guarded syncs, checkpoint I/O and stall
        # escalations are all attributed to these devices.
        self._device_ids = tuple(sorted(
            d.id for d in np.asarray(self.spec.mesh.devices).flat))
        self.faults = FaultInjector(config.recovery.faults)
        if config.consistency_every and config.strategy == "fsdp":
            raise ValueError(
                "consistency_every needs state replicated over the data "
                "axis to compare; strategy='fsdp' shards params + "
                "optimizer state over it — no redundancy, no cross-replica "
                "check. No silent ignores")
        # Topology validation first: on a topology that CANNOT arm the
        # sentinel, the supervisor's "set consistency_every >= 1" advice
        # would send the user into the rejection above.
        validate_corruption_plan(
            self.faults.plan,
            # FSDP shards state over the data axis — zero replicated copies.
            0 if config.strategy == "fsdp" else self.spec.num_data,
            context=f"strategy={config.strategy!r}")
        self.ckpt = Checkpointer(config.checkpoint_dir,
                                 keep=config.recovery.keep_checkpoints,
                                 injector=self.faults,
                                 meta_fn=self._ckpt_meta)
        self.resilience = RecoverySupervisor(
            config.recovery, logger=self.logger, ckpt=self.ckpt,
            preemption=self.preemption, slot="good", injector=self.faults,
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
            self.ckpt, "emergency", config.emergency_every,
            logger=self.logger, wait=not config.async_checkpoint)
        self.best_acc = 0.0
        self.start_epoch = 0
        # Cooperative-scheduling hook (orchestrator/): when set, called with
        # this trainer at EVERY train-step boundary, before the preemption
        # poll — so an external scheduler can pause the run mid-epoch
        # (block in the hook), and a preemption it requests while the run
        # is paused is honored before the next step dispatches.
        self.step_hook: Callable[["Trainer"], None] | None = None
        # Per-step augmentation rng is derived from (base key, global step)
        # — stateless, so a resumed run replays the exact stream an
        # uninterrupted run would have used (train/elastic.py). The host
        # mirrors the on-device TrainState.step counter.
        self._rng_base = jax.random.key(config.seed + 1)
        self._global_step = 0
        # Trainer-authoritative loader position (epoch, consumed batches);
        # see _resume_tree for why the loader's own state is not trusted.
        self._loader_pos = (0, 0)
        if self.elastic_decision is not None and self.elastic_decision.changed:
            self.logger.log_line(self.elastic_decision.describe())
            self.logger.telemetry.event(self.elastic_decision.describe())
        if config.resume and any(self.ckpt.exists(n)
                                 for n in ("ckpt", "preempt", "emergency",
                                           "good")):
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

    def _build_steps(self) -> None:
        """(Re)build the jitted step functions from the current config and
        ``self.tx``. Called once at init and again by ``_apply_lr_shrink``
        after a recovery rebuilds the optimizer: state, shardings and the
        on-device dataset are untouched, so a restored ``opt_state`` stays
        structurally compatible (the LR lives in the schedule closure, not
        in the state)."""
        config = self.config
        kw = self._kw
        ema = config.optimizer.ema_decay
        self._multi_step = None
        if config.strategy == "ddp":
            from distributed_model_parallel_tpu.parallel.ddp import (
                make_ddp_eval_step,
                make_ddp_train_step,
            )

            bucket_bytes = config.ddp_bucket_bytes
            allreduce = config.ddp_allreduce
            if config.grad_bucket_mb is not None:
                # The Reducer's bucket_cap_mb knob: size-capped flat
                # buckets in reverse leaf order, fired as the backward
                # produces them (ops/collectives.bucketed_psum).
                bucket_bytes = int(config.grad_bucket_mb * 1024 * 1024)
                if allreduce == "psum":
                    allreduce = "bucketed"
            self._train_step = make_ddp_train_step(
                self.model, self.tx, self.spec,
                augment=config.data.augment,
                bucket_bytes=bucket_bytes,
                allreduce=allreduce, **kw)
            self._eval_step = make_ddp_eval_step(self.model, self.spec, **kw)
        elif config.strategy in ("gspmd", "fsdp"):
            # Full-step donation: the state (in-place param/opt update)
            # AND the input batch. The uint8/int32 batch buffers have no
            # same-shaped output to alias with, but donating them hands
            # ownership to the runtime so their device memory frees at
            # dispatch instead of at the next GC — with the device
            # prefetcher keeping depth extra batches resident, that is
            # the difference between depth+1 and 2*depth live batches.
            # utils/profiling.assert_donation is the trace-time proof the
            # state aliasing actually held (tests/test_perf_pipeline.py).
            self._train_step = jax.jit(
                make_train_step(self.model, self.tx, ema_decay=ema,
                                augment=config.data.augment, **kw),
                in_shardings=(self._state_sh, self._repl, self._batch_sh,
                              self._batch_sh),
                out_shardings=(self._state_sh, self._repl),
                donate_argnums=(0, 2, 3))
            self._eval_step = jax.jit(
                make_eval_step(self.model, use_ema=ema is not None, **kw),
                in_shardings=(self._state_sh, self._batch_sh, self._batch_sh),
                out_shardings=self._repl)
            if config.device_resident_data:
                from jax.sharding import NamedSharding, PartitionSpec as P

                idx_sh = NamedSharding(self.spec.mesh,
                                       P(None, self.spec.data_axis))
                self._multi_step = jax.jit(
                    make_multi_step(self.model, self.tx, ema_decay=ema,
                                    image_shape=self.train_ds.images.shape[1:],
                                    augment=config.data.augment, **kw),
                    in_shardings=(self._state_sh, self._repl, self._repl,
                                  self._repl, idx_sh),
                    out_shardings=(self._state_sh, self._repl),
                    donate_argnums=(0,))
        elif config.strategy == "spmd_pipeline":
            from distributed_model_parallel_tpu.parallel.spmd_cnn_pipeline import (
                make_spmd_cnn_train_step,
            )

            in_hw = self._in_hw
            self._train_step = jax.jit(
                make_spmd_cnn_train_step(
                    self.model, self.spec, self.tx,
                    sample_shape=(2, in_hw, in_hw,
                                  self.train_ds.images.shape[3]),
                    num_microbatches=config.num_microbatches,
                    boundaries=self._boundaries,
                    bn_momentum=config.model.bn_momentum,
                    augment=config.data.augment,
                    stage_dispatch=self._dispatch,
                    schedule=config.pipeline_schedule,
                    virtual_stages=config.virtual_stages, **kw),
                in_shardings=(self._state_sh, self._repl, self._batch_sh,
                              self._batch_sh),
                out_shardings=(self._state_sh, self._repl),
                donate_argnums=(0, 2, 3))
            self._eval_step = jax.jit(
                make_eval_step(self.model, use_ema=False, **kw),
                in_shardings=(self._state_sh, self._batch_sh,
                              self._batch_sh),
                out_shardings=self._repl)

    def _apply_lr_shrink(self, factor: float) -> None:
        """Recovery-time LR shrink: scale the configured LR, rebuild the
        optimizer (same opt_state structure — the schedule is a closure)
        and re-jit the step functions (train/resilience.py)."""
        opt = self.config.optimizer
        self.config = self.config.replace(
            optimizer=dataclasses.replace(
                opt, learning_rate=opt.learning_rate * factor))
        self.tx = make_optimizer(self.config.optimizer,
                                 len(self.train_loader), self.config.epochs)
        self._build_steps()

    # -- checkpointing (reference data_parallel.py:80-87,143-155) ------------
    def _ckpt_tree(self):
        return {"state": self.state,
                "best_acc": jnp.asarray(self.best_acc, jnp.float32),
                "epoch": jnp.asarray(self.start_epoch, jnp.int32),
                "resume": self._resume_tree()}

    def _ckpt_meta(self):
        """Manifest stamp written with every committed version: the saving
        topology + exact position, readable without restoring anything
        (train/checkpoint.py, train/elastic.py)."""
        return {"workload": "cnn",
                "mesh": {**self.config.mesh.axis_sizes(),
                         "dcn_data": self.config.mesh.dcn_data},
                "n_devices": int(np.asarray(self.spec.mesh.devices).size),
                "global_step": self._global_step}

    def _resume_tree(self):
        """The exact-continuation state riding along in every checkpoint:
        loader position, global step, and the supervisor's live budgets —
        what turns an epoch-granular restore into a mid-epoch one
        (train/elastic.py).

        The position comes from the TRAINER's own (epoch, consumed)
        bookkeeping, not the loader's: a prefetch worker that exhausts the
        underlying iterator before the consumer has dispatched anything
        auto-advances the loader's epoch on its own thread (data/loader.py)
        — only the trainer knows what was actually consumed. The loader is
        re-synced here so its state matches every checkpoint written."""
        from distributed_model_parallel_tpu.train import elastic

        ep, cur = self._loader_pos
        tree = elastic.build_resume_tree(ep, cur, len(self.train_loader),
                                         self._global_step,
                                         self.resilience.budgets())
        self.train_loader.position(int(tree["loader_epoch"]),
                                   int(tree["batch_cursor"]))
        return tree

    def _apply_resume_tree(self, restored: dict, *, budgets: bool) -> None:
        """Adopt a restored checkpoint's exact-continuation state. Legacy
        checkpoints (no "resume" subtree) degrade to the historical
        epoch-granular resume. ``budgets=False`` for in-run recovery
        restores: the LIVE retry budget/LR scale must not be refilled from
        a checkpoint written before the failure."""
        from distributed_model_parallel_tpu.train import elastic

        ri = restored.get("resume")
        if ri is None:
            self._global_step = int(jax.device_get(restored["state"].step))
            return
        ep, cur, gs, retries, lr_scale = elastic.unpack_resume_tree(ri)
        self.train_loader.load_state_dict({"epoch": ep, "batch_cursor": cur})
        self._loader_pos = (self.train_loader.epoch,
                            self.train_loader.cursor)
        self._global_step = gs
        if budgets:
            self.resilience.restore_budgets(retries, lr_scale)
            if lr_scale != 1.0:
                # Re-apply the cumulative recovery LR shrink the saving run
                # had in effect (the optimizer was rebuilt at base LR).
                self._apply_lr_shrink(lr_scale)

    def _resume(self):
        from distributed_model_parallel_tpu.train import elastic

        tmpl = self._ckpt_tree()
        # The checkpoint's TrainState may differ from the current config in
        # the optional EMA subtrees: runs resumed with ema_decay toggled,
        # and checkpoints from before ema_model_state existed (params-only
        # EMA layout). Try the current template first, then each alternate
        # layout; pre-elastic checkpoints additionally lack the "resume"
        # subtree, so every layout also gets a legacy template without it.
        st = tmpl["state"]
        layouts, seen = [], set()
        for layout in (
                st,
                st.replace(ema_params=None, ema_model_state=None),
                st.replace(ema_params=st.params,
                           ema_model_state=st.model_state),
                st.replace(ema_params=st.params, ema_model_state=None)):
            key = jax.tree.structure(layout)
            if key not in seen:          # the candidates overlap with tmpl
                seen.add(key)
                layouts.append(layout)
        templates = [{**tmpl, "state": lo} for lo in layouts]
        legacy = {k: v for k, v in tmpl.items() if k != "resume"}
        templates += [{**legacy, "state": lo} for lo in layouts]
        # Newest-valid slot wins — best-accuracy, preemption, step-cadence
        # emergency, or the recovery supervisor's per-epoch good slot —
        # restored through restore_resharded so a checkpoint from a
        # different mesh degree lands in THIS mesh's shardings; torn
        # versions/slots fall back (train/elastic.py). The good slot is
        # the last resort that makes a torn preemption save survivable
        # (the multi-tenant soak flushed this out: an injected tear_save
        # landing on a first-preemption checkpoint used to kill the
        # resume outright — scripts/dmp_soak.py).
        name, restored = elastic.elastic_restore(
            self.ckpt, templates, ("ckpt", "preempt", "emergency", "good"),
            on_fallback=self.resilience.note_fallback)
        rs = restored["state"]
        want_ema = self.config.optimizer.ema_decay is not None
        if want_ema:
            if rs.ema_params is None:
                # EMA newly enabled: seed the average at the restored state.
                rs = rs.replace(ema_params=jax.tree.map(jnp.copy, rs.params))
            if rs.ema_model_state is None:
                # Also covers the legacy params-only EMA layout.
                rs = rs.replace(
                    ema_model_state=jax.tree.map(jnp.copy, rs.model_state))
        elif rs.ema_params is not None or rs.ema_model_state is not None:
            rs = rs.replace(ema_params=None, ema_model_state=None)
        self.state = jax.device_put(rs, self._state_sh)
        self.best_acc = float(restored["best_acc"])
        self.start_epoch = int(restored["epoch"])
        self._apply_resume_tree(restored, budgets=True)
        # The best-acc slot's "epoch" leaf lags when later epochs brought
        # no accuracy improvement; the loader position is authoritative
        # for where training actually stood.
        self.start_epoch = max(self.start_epoch, self.train_loader.epoch)
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
            loader_epoch=self.train_loader.epoch,
            batch_cursor=self.train_loader.cursor,
            global_step=self._global_step,
            mesh=current_mesh,
            **({"saved_mesh": saved_mesh}
               if saved_mesh and saved_mesh != current_mesh else {}))
        self.logger.log_line(
            f"resume: slot {name!r} -> epoch {self.start_epoch} "
            f"batch {self.train_loader.cursor} "
            f"(global step {self._global_step})"
            + (f", resharded from mesh {saved_mesh}"
               if saved_mesh and saved_mesh != current_mesh else ""))

    def _save(self, epoch: int):
        self.start_epoch = epoch + 1
        self.ckpt.save(self._ckpt_tree(),
                       wait=not self.config.async_checkpoint)

    def _restore_good(self):
        """Recovery restore: pull the supervisor's "last good" slot (same
        tree layout as this run wrote it) back onto the devices, with
        torn-version fallback (train/resilience.py). The loader position
        and global step ride along so the retry replays exactly the
        batches the restored state had seen — budgets stay LIVE (a
        checkpoint written before the failure must not refill them)."""
        restored = self.ckpt.restore(
            self._ckpt_tree(), self.resilience.slot, allow_fallback=True,
            on_fallback=self.resilience.note_fallback)
        self.state = jax.device_put(restored["state"], self._state_sh)
        self.best_acc = float(restored["best_acc"])
        self._apply_resume_tree(restored, budgets=False)

    # -- epoch loops ---------------------------------------------------------
    def _shard_batch(self, images, labels):
        if jax.process_count() > 1:
            # Each process holds only its slice (BatchLoader shards by
            # process); stitch the global batch-sharded jax.Array.
            from distributed_model_parallel_tpu.mesh import (
                host_local_batch_to_global,
            )

            return host_local_batch_to_global((images, labels), self.spec,
                                              sharding=self._batch_sh)
        return (jax.device_put(images, self._batch_sh),
                jax.device_put(labels, self._batch_sh))

    def _prefetched(self, loader):
        return maybe_prefetch(loader, self.config.data.prefetch)

    def _input_stream(self, loader):
        """The full input pipeline: host-thread batch assembly
        (PrefetchLoader) feeding the device-resident prefetcher, which
        issues the next ``device_prefetch`` batches' sharded device_put
        (the old per-step transfer at the top of the epoch loop) while the
        current step runs. Yields device-resident (images, labels)."""
        return maybe_device_prefetch(self._prefetched(loader),
                                     self._shard_batch,
                                     self.config.data.device_prefetch)

    def _drain(self, pending: list, meters: dict, *,
               sentinel: bool = False) -> None:
        """Fetch queued device metrics and fold them into the meters.

        Metrics are held as device arrays between sync points so the host
        never blocks on a step it doesn't need yet — step k+1 dispatches
        while step k still runs (async dispatch). The reference instead
        syncs every batch via ``.item()`` on loss/accuracy (``utils.py:64-68``).
        Entries may be stacked over a leading K axis (multi-step dispatch).

        This is the trainer's sync point, so the guards (when configured)
        run here: the blocking fetch sits under the stall watchdog, and the
        fetched values (plus, at the coarser cadence, the params) get
        finiteness-checked (train/guards.py:GuardRunner). With
        ``sentinel=True`` (training drains only — eval never mutates
        state) the cross-replica consistency sentinel also advances and,
        at its cadence, fingerprints + repairs the live state
        (train/consistency.py).
        """
        with span("drain", n=len(pending)), self.guards.watch():
            host = jax.device_get(pending)
        if host and (self.guards.enabled
                     or (sentinel and self.sentinel.enabled)):
            # Entries may stack K steps (multi-step dispatch): count real
            # steps so the every-N cadence is dispatch-shape independent.
            n_steps = sum(np.atleast_1d(m["loss"]).shape[0] for m in host)
            if self.guards.enabled:
                self.guards.after_sync(
                    host, n_steps,
                    params=getattr(self.state, "params", None))
            if sentinel and self.sentinel.enabled and n_steps:
                self._run_sentinel(n_steps)
        # Vectorized meter fold: one weighted update per meter for the
        # whole drained window instead of a per-element Python float()
        # loop — at steps_per_dispatch x max_inflight entries per drain,
        # host bookkeeping must not shadow the async fetch.
        if host:
            loss = np.concatenate([np.atleast_1d(m["loss"]) for m in host])
            batch = np.concatenate([np.atleast_1d(m["batch"])
                                    for m in host]).astype(np.float64)
            c1 = np.concatenate([np.atleast_1d(m["correct@1"])
                                 for m in host])
            c5 = np.concatenate([np.atleast_1d(m["correct@5"])
                                 for m in host])
            b_tot = float(batch.sum())
            if b_tot > 0:
                # update(v, n) folds v*n into the running sum: the
                # batch-weighted mean at weight b_tot reproduces the
                # per-step update sequence's totals.
                meters["loss"].update(float((loss * batch).sum()) / b_tot,
                                      int(b_tot))
                meters["acc1"].update(float(c1.sum()) / b_tot * 100,
                                      int(b_tot))
                meters["acc5"].update(float(c5.sum()) / b_tot * 100,
                                      int(b_tot))
        pending.clear()

    def _sentinel_tree(self) -> dict:
        """The replicated-state subtree the consistency sentinel
        fingerprints: params + optimizer state (+ EMA and BN stats where
        present — per-replica DDP BN state is auto-excluded by the
        sentinel's data-axis sharding filter). Keys are TrainState field
        names so a repaired tree splices back via ``state.replace``."""
        t = {"params": self.state.params,
             "model_state": self.state.model_state,
             "opt_state": self.state.opt_state}
        if self.state.ema_params is not None:
            t["ema_params"] = self.state.ema_params
        if self.state.ema_model_state is not None:
            t["ema_model_state"] = self.state.ema_model_state
        return t

    def _run_sentinel(self, n_steps: int, *, flush: bool = False) -> None:
        """Advance the consistency sentinel (or, with ``flush=True``,
        check any steps the cadence hasn't covered — end of epoch, before
        the good slot is stamped); splice a repaired state back in place.
        No-quorum divergence / non-finite consensus raise out of here
        into fit()'s recovery handlers."""
        fixed = (self.sentinel.flush(self._sentinel_tree) if flush
                 else self.sentinel.after_sync(n_steps, self._sentinel_tree))
        if fixed is not None:
            self.state = self.state.replace(**fixed)

    def _poll_step_faults(self, pending: list) -> None:
        """Serve planned step-site faults (utils/faults.py): poison the
        just-computed metrics or the live params, silently corrupt one
        replica's params (bitflip/desync/grad_skew), or request a
        simulated preemption — the chaos hooks the recovery tests drive.
        No-op (one counter bump) when no fault plan is configured."""
        from distributed_model_parallel_tpu.utils.faults import (
            CORRUPTION_KINDS,
            corrupt_one_replica,
            poison,
        )

        for spec in self.faults.poll("step"):
            if spec.kind == "preempt":
                self.preemption.request()
            elif spec.kind == "nan_loss" and pending:
                pending[-1] = poison(pending[-1])
            elif spec.kind == "nan_params":
                self.state = self.state.replace(
                    params=poison(self.state.params))
            elif spec.kind in CORRUPTION_KINDS:
                self.state = self.state.replace(
                    params=corrupt_one_replica(
                        self.state.params, self.spec, spec.kind,
                        spec.param))

    def _health_window(self, n_steps: int, timer: StepTimer) -> None:
        """Report a drained step window's per-step wall time to the
        device-health sentinel (utils/health.py; no-op unless a monitor
        is installed — i.e. outside orchestrated runs). The first-window
        compile skip lives in the shared helper."""
        health.observe_step_warmed(self, self._device_ids,
                                   timer.step.last, n_steps)

    def train_epoch(self, epoch: int) -> EpochResult:
        if getattr(self, "_multi_step", None) is not None:
            return self._train_epoch_device_resident(epoch)
        meters = {k: AverageMeter(k) for k in ("loss", "acc1", "acc5")}
        timer = StepTimer()
        pending: list = []
        # Loader position: start of `epoch`, or the mid-epoch cursor a
        # resumed run loaded (train/elastic.py). `base + i` is the global
        # batch index within the epoch; _loader_pos after each dispatched
        # step keeps the resume position in lockstep with the train state
        # (the prefetch worker runs ahead and cannot be trusted).
        self.train_loader.set_epoch(epoch)
        base = self.train_loader.cursor
        self._loader_pos = (epoch, base)
        for i, (images, labels) in enumerate(self._input_stream(self.train_loader)):
            if self.step_hook is not None:
                self.step_hook(self)
            if self.preemption.requested():
                break
            gi = base + i
            timer.data_ready()
            sub = jax.random.fold_in(self._rng_base, self._global_step)
            self.state, metrics = self._train_step(self.state, sub, images, labels)
            self._global_step += 1
            self._loader_pos = (epoch, gi + 1)
            pending.append(metrics)
            if self.faults.enabled:
                self._poll_step_faults(pending)
            log_now = gi % self.config.log_every_n_steps == 0
            if log_now or len(pending) >= self._max_inflight:
                n = len(pending)
                self._drain(pending, meters, sentinel=True)  # sync point
                timer.window_done(n)
                self._health_window(n, timer)
            if log_now:
                # Per-WINDOW samples (meter .last, set by window_done), not
                # the epoch running mean: the report's step-time percentiles
                # must see real per-step variation or a straggler window
                # collapses into the average and disappears.
                self.logger.log_step(
                    epoch, gi, loss=meters["loss"].avg,
                    acc1=meters["acc1"].avg,
                    step_time_s=timer.step.last,
                    data_time_s=timer.data.last,
                    samples_per_s=self.config.data.batch_size
                    / max(timer.step.last, 1e-9))
            self.emergency.after_step(1, self._ckpt_tree)
        n = len(pending)
        self._drain(pending, meters, sentinel=True)
        timer.window_done(n)
        self._health_window(n, timer)
        if self.sentinel.enabled:
            self._run_sentinel(0, flush=True)
        return EpochResult(meters["loss"].avg, meters["acc1"].avg,
                           meters["acc5"].avg, timer.step.avg, timer.data.avg)

    def _train_epoch_device_resident(self, epoch: int) -> EpochResult:
        """Epoch over the on-device dataset: K steps per dispatched program.

        Batch composition is identical to the materializing path — both use
        ``BatchLoader.epoch_indices()`` — so switching the fast path on
        changes performance, not math.
        """
        meters = {k: AverageMeter(k) for k in ("loss", "acc1", "acc5")}
        timer = StepTimer()
        pending: list = []
        bs = self.train_loader.batch_size
        K = max(1, self.config.steps_per_dispatch)
        self.train_loader.set_epoch(epoch)
        # Resume cursor is always dispatch-aligned: saves only happen at
        # dispatch boundaries, so a resumed run re-chunks the remaining
        # steps exactly like the uninterrupted run would have.
        base = self.train_loader.cursor
        self._loader_pos = (epoch, base)
        idx = self.train_loader.epoch_indices(epoch)
        steps = len(idx) // bs
        idx = idx[:steps * bs].reshape(steps, bs)
        inflight = 0
        for i in range(base, steps, K):
            if self.step_hook is not None:
                self.step_hook(self)
            if self.preemption.requested():
                break
            chunk = np.ascontiguousarray(idx[i:i + K])
            timer.data_ready()
            sub = jax.random.fold_in(self._rng_base, self._global_step)
            self.state, metrics = self._multi_step(
                self.state, sub, self._dev_images, self._dev_labels,
                jnp.asarray(chunk))
            self._global_step += chunk.shape[0]
            self._loader_pos = (epoch, i + chunk.shape[0])
            pending.append(metrics)
            if self.faults.enabled:
                # One step-site poll per DISPATCH (K fused steps) — faults
                # cannot target an individual step inside the scan.
                self._poll_step_faults(pending)
            inflight += chunk.shape[0]
            # Log when a multiple of log_every_n_steps falls inside this
            # dispatch's [i, i+K) step window — same cadence as the
            # per-batch path.
            log_now = (-i) % self.config.log_every_n_steps < chunk.shape[0]
            if log_now or len(pending) >= self._max_inflight:
                self._drain(pending, meters, sentinel=True)
                timer.window_done(inflight)
                self._health_window(inflight, timer)
                inflight = 0
            if log_now:
                # Per-window samples, same rationale as the per-batch path.
                self.logger.log_step(
                    epoch, i, loss=meters["loss"].avg,
                    acc1=meters["acc1"].avg,
                    step_time_s=timer.step.last,
                    data_time_s=timer.data.last,
                    samples_per_s=self.config.data.batch_size
                    / max(timer.step.last, 1e-9))
            self.emergency.after_step(chunk.shape[0], self._ckpt_tree)
        self._drain(pending, meters, sentinel=True)
        timer.window_done(inflight)
        self._health_window(inflight, timer)
        if self.sentinel.enabled:
            self._run_sentinel(0, flush=True)
        return EpochResult(meters["loss"].avg, meters["acc1"].avg,
                           meters["acc5"].avg, timer.step.avg, timer.data.avg)

    def evaluate(self) -> EpochResult:
        meters = {k: AverageMeter(k) for k in ("loss", "acc1", "acc5")}
        timer = StepTimer()
        pending: list = []
        for images, labels in self._input_stream(self.eval_loader):
            timer.data_ready()
            pending.append(self._eval_step(self.state, images, labels))
            if len(pending) >= self._max_inflight:
                # Bound host run-ahead so in-flight eval batches can't pile
                # up in device memory on large eval sets.
                n = len(pending)
                self._drain(pending, meters)
                timer.window_done(n)
        n = len(pending)
        self._drain(pending, meters)
        timer.window_done(n)
        return EpochResult(meters["loss"].avg, meters["acc1"].avg,
                           meters["acc5"].avg, timer.step.avg, timer.data.avg)

    def fit(self, epochs: int | None = None) -> list[dict]:
        """Train with per-epoch eval + best-acc checkpointing
        (reference epoch loop data_parallel.py:160-172).

        SIGTERM/SIGINT (TPU preemption, Ctrl-C) request a graceful stop:
        the epoch loop breaks at the next step boundary, a checkpoint is
        written pointing resume at the interrupted epoch, and fit returns
        the completed history (train/preemption.py).

        With recovery enabled (``TrainConfig.recovery.max_retries > 0``) a
        NonFiniteError raised by the guards restores the supervisor's
        per-epoch "last good" checkpoint, optionally shrinks the LR, and
        retries the epoch — bounded by the retry budget
        (train/resilience.py). A no-quorum replica divergence from the
        consistency sentinel (train/consistency.py) takes the same
        restore-and-retry path, without the LR shrink.
        """
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
                        tr = self.train_epoch(epoch)
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
                    # Partial epoch: resume *at* this epoch (the standard
                    # redo-the-epoch convention); the dedicated slot never
                    # evicts the best-accuracy checkpoint.
                    from distributed_model_parallel_tpu.train.preemption import (
                        checkpoint_on_preempt,
                    )

                    self.start_epoch = epoch
                    checkpoint_on_preempt(self.preemption, self.ckpt,
                                          self._ckpt_tree(), "preempt",
                                          self.logger, epoch,
                                          global_step=self._global_step)
                    break
                if eval_now(epoch, epochs, self.config.eval_every):
                    with span("evaluate", epoch=epoch):
                        ev = self.evaluate()
                else:
                    ev = None
                record = dict(epoch=epoch, loss_train=tr.loss,
                              acc1_train=tr.acc1,
                              loss_val=ev.loss if ev else None,
                              acc1_val=ev.acc1 if ev else None,
                              time_per_batch=tr.step_time,
                              time_load_per_batch=tr.data_time)
                self.logger.log_epoch(**record)
                # Device memory watermark per epoch (no-op where the backend
                # reports none, e.g. CPU).
                self.logger.telemetry.memory()
                history.append(record)
                if ev is not None and ev.acc1 > self.best_acc:
                    self.best_acc = ev.acc1
                    self._save(epoch)
                # Epoch completed with finite metrics/params — persist it
                # as the recovery restore point (no-op unless enabled).
                self.resilience.note_good(self._ckpt_tree)
                epoch += 1
        self.ckpt.wait_until_finished()
        self.logger.finish(epochs_run=len(history))
        return history
