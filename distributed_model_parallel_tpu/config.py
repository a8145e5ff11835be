"""Typed configuration for the framework.

The reference scatters configuration across argparse defaults and inline
literals (and some flags are silently ignored — reference
``model_parallel.py:89-97`` re-hard-codes batch size 512 / 12 workers over the
``-b``/``-j`` flags; see SURVEY.md §1 "Notable coupling"). Here every knob
lives in one dataclass tree with no hidden hard-coding; entry scripts parse CLI
overrides into these dataclasses.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axis sizes of 1 disable an axis.

    Replaces the reference's ``--world-size`` + ``mp.spawn`` + NCCL process
    group (``model_parallel.py:19-24,57,162``): on TPU the "backend choice" is
    mesh/axis configuration, not a transport plugin (SURVEY.md §2.4).
    """

    data: int = 1          # data-parallel axis ("dp")
    stage: int = 1         # pipeline-stage axis ("pp")
    model: int = 1         # tensor-parallel axis ("tp")
    seq: int = 1           # sequence/context-parallel axis ("sp")
    expert: int = 1        # expert-parallel axis ("ep"), reserved

    # Multi-host layout: how many of the `data` ways cross the DCN (slow,
    # host-to-host) boundary. Must divide `data`. With dcn_data > 1 the data
    # axis is laid out host-major — the dcn_data host granules are the outer
    # factor — so XLA decomposes the gradient allreduce hierarchically
    # (ICI-local reduce-scatter, small DCN exchange, ICI all-gather). Other
    # axes (stage/model/seq/expert) always stay within a host's ICI domain.
    dcn_data: int = 1

    # Axis names as they appear in PartitionSpecs / collectives.
    data_axis: str = "data"
    stage_axis: str = "stage"
    model_axis: str = "model"
    seq_axis: str = "seq"
    expert_axis: str = "expert"

    @property
    def num_devices(self) -> int:
        return self.data * self.stage * self.model * self.seq * self.expert

    def axis_sizes(self) -> dict[str, int]:
        return {
            self.data_axis: self.data,
            self.stage_axis: self.stage,
            self.model_axis: self.model,
            self.seq_axis: self.seq,
            self.expert_axis: self.expert,
        }


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """SGD + cosine annealing + linear warmup.

    Mirrors the reference's recipe: ``SGD(lr, momentum=0.9, weight_decay=1e-4)``
    + ``CosineAnnealingLR(T_max=90)`` + ``UntunedLinearWarmup`` over ~10 epochs
    (reference ``data_parallel.py:89-96``, ``model_parallel.py:105-108``).
    """

    name: str = "sgd"
    learning_rate: float = 0.4
    momentum: float = 0.9
    weight_decay: float = 1e-4
    nesterov: bool = False
    cosine_decay_steps: int | None = None   # if None: derived from epochs
    warmup_steps: int = 0
    grad_clip_norm: float | None = None
    # Gradient accumulation: average grads over k consecutive calls and apply
    # one optimizer update per k (optax.MultiSteps). A size-b batch at
    # accum_steps=k matches a size-k*b batch step exactly (mean-loss grads).
    accum_steps: int = 1
    # Exponential moving average of the weights (e.g. 0.999); evaluation and
    # best-acc selection use the averaged weights. None disables.
    ema_decay: float | None = None
    # Fused optimizer update (ops/pallas_optim.py): apply
    # SGD+momentum+weight-decay+LR in ONE Pallas TPU kernel over flat
    # coalesced parameter buckets instead of optax's per-leaf elementwise
    # op chain (pure-XLA fallback off-TPU, parity-tested against the optax
    # path). Only valid with name="sgd" — other optimizers reject it
    # loudly. Composes with grad_clip_norm and accum_steps; the LR
    # schedule stays a closure, so recovery-time lr_shrink rebuilds keep
    # the opt_state structure (docs/PERFORMANCE.md).
    fused: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Model selection + model-family specific knobs."""

    name: str = "mobilenetv2"               # registry key
    num_classes: int = 10
    # BatchNorm behavior: "local" = per-replica stats (nn.DataParallel / plain
    # DDP semantics), "sync" = cross-replica stats (SyncBatchNorm), "none" =
    # the no-BN variant (reference model/mobilenetv2.py:84-148).
    batchnorm: str = "local"
    bn_momentum: float = 0.9
    bn_epsilon: float = 1e-5
    dtype: str = "float32"                  # compute dtype ("bfloat16" on TPU)
    param_dtype: str = "float32"
    extra: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Dataset + loader settings.

    The reference's transforms: random crop 32 pad 4, horizontal flip,
    normalize with CIFAR-10 stats (``data_parallel.py:31-40``); loaders bs 512
    train / 1000 test (``data_parallel.py:44-51``).
    """

    name: str = "cifar10"                   # registry key
    root: str = "./data"
    batch_size: int = 512
    eval_batch_size: int = 1000
    image_size: int = 32
    num_workers: int = 2
    shuffle: bool = True
    augment: bool = True
    seed: int = 0
    synthetic_ok: bool = True               # fall back to synthetic data offline
    synthetic_train_size: int = 2048
    synthetic_eval_size: int = 512
    # Native resolution of GENERATED synthetic images (None = image_size).
    # Set below image_size to exercise the on-device resize input stage the
    # way a real small-native dataset does (CIFAR pixels upsampled to a
    # 224px backbone, reference Readme.md:186-196).
    synthetic_native_size: int | None = None
    prefetch: int = 2                       # host-thread prefetch depth (0 = off)
    # Device-resident input prefetch (data/loader.DevicePrefetchLoader):
    # keep this many batches ahead of the consumed one already uploaded —
    # the sharded jax.device_put for batch k+1..k+depth is issued while
    # step k runs, so the step never waits on the host→device wire. 0
    # disables (the epoch loop falls back to a per-step device_put).
    # Composes with `prefetch` (host thread assembles, this stage
    # uploads); exact-resume semantics are untouched — the loader cursor
    # is consumer-driven (BatchLoader.position), and run-ahead uploads
    # are never counted as consumed (docs/PERFORMANCE.md).
    device_prefetch: int = 2
    use_native: bool = False                # C++ row-gather batch assembly
    # File-backed datasets (ImageFolder / CUB): True streams pixels from
    # disk per batch (host memory = the path list), False decodes the
    # whole split up front, None auto-picks by decoded size
    # (registry.LAZY_AUTO_BYTES) — the reference's torchvision loaders
    # are lazy the same way (dataset_collection.py:36-47).
    lazy_decode: bool | None = None


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Automatic failure recovery (train/resilience.RecoverySupervisor).

    Default-off (``max_retries=0``): every detection keeps its historical
    fail-fast behavior. With ``max_retries > 0`` the supervisor maintains a
    per-epoch "last good" checkpoint slot and, on a non-finite loss/params
    detection (requires ``check_finite_every > 0``), restores it, optionally
    shrinks the learning rate, and retries the epoch — up to the budget.
    Restores verify the per-checkpoint integrity manifest and fall back to
    the previous committed version when the newest is torn
    (train/checkpoint.Checkpointer.restore ``allow_fallback``).
    """

    # Bounded retry budget for restore-and-resume recoveries; 0 disables the
    # supervisor (detections raise, as before).
    max_retries: int = 0
    # Multiply the learning rate by this factor on every non-finite recovery
    # (1.0 = keep it). Trainers that cannot rebuild their optimizer mid-run
    # reject values != 1.0 loudly — no silent ignores.
    lr_shrink: float = 1.0
    # Committed checkpoint versions retained per slot (Checkpointer keep-K):
    # >= 2 gives torn-newest restores something to fall back to.
    keep_checkpoints: int = 2
    # Escalate a stall-budget overrun (see TrainConfig.stall_budget_s) to a
    # graceful checkpoint-and-exit instead of only logging. The watchdog's
    # periodic "still blocked" lines appear either way.
    stall_exit: bool = False
    # Watchdog log cadence while a sync is blocked (None = budget/2, capped
    # to [0.05s, 30s]).
    watchdog_interval_s: float | None = None
    # Hard bound on a consistency check's blocking operations: the host
    # rendezvous before its cross-host collectives (multi-process runs)
    # AND the fingerprint fetch itself (any run, including single-process).
    # A wedged or missing participant then surfaces as a typed "straggler"
    # failure record + StragglerTimeoutError — fatal unless caught — instead
    # of hanging the very check meant to catch divergence (mesh.
    # barrier_with_timeout). Size it well above a slow-but-healthy
    # steady-state fetch; the FIRST check automatically gets a 10x grace
    # for one-time compile + cross-host compile skew. None = unbounded
    # (the stall watchdog still logs/escalates).
    barrier_timeout_s: float | None = None
    # Deterministic fault-injection plan (utils/faults.py): FaultSpec
    # entries or "kind@at[:param]" strings, e.g. ("nan_loss@1",). Empty =
    # no chaos.
    faults: Sequence[Any] = ()


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Top-level run configuration."""

    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)

    epochs: int = 100                       # reference data_parallel.py:160
    seed: int = 0
    # Data-parallel engine: "gspmd" = sharded jit (XLA infers the allreduce);
    # "ddp" = explicit shard_map per-replica programs with psum gradient
    # averaging and per-replica BatchNorm (parallel/ddp.py); "fsdp" = ZeRO-3
    # parameter+optimizer sharding over the data axis (parallel/fsdp.py);
    # "spmd_pipeline" = single-jit GPipe/1F1B over the stage axis
    # (parallel/spmd_cnn_pipeline.py); "auto" = cost-model-driven layout
    # (autotune/, docs/AUTOTUNE.md): probe the model, enumerate feasible
    # layouts of the LIVE device count, HBM-filter, rank with the
    # alpha-beta comm/compute model, rewrite strategy + mesh from the
    # winner and emit a typed `plan` telemetry record; elastic restarts
    # re-plan on the refitted mesh instead of blindly shrinking dp.
    strategy: str = "gspmd"
    ddp_bucket_bytes: int | None = None     # None = per-leaf psum
    ddp_allreduce: str = "psum"             # "psum" | "bucketed" | "ring"
    # Bucketed gradient allreduce cap in MiB — the DDP Reducer's
    # bucket_cap_mb knob (reference Readme.md:148-157). With
    # strategy="ddp" this routes the gradient averaging through
    # ops/collectives.bucketed_psum (reverse-leaf-order size-capped flat
    # buckets, so early buckets fire while the backward still runs and
    # XLA overlaps the collectives with compute). Only meaningful on the
    # explicit DDP path: the gspmd/fsdp strategies leave the reduction to
    # XLA's partitioner, so setting it there raises — no silent ignores.
    # Overrides ddp_bucket_bytes when both are set.
    grad_bucket_mb: float | None = None
    log_dir: str = "./log"
    log_name: str = "train"
    checkpoint_dir: str = "./checkpoint"
    resume: bool = False                    # reference data_parallel.py:21-22,80-87
    # Elastic resume (train/elastic.py): step-cadence "emergency" checkpoint
    # slot carrying the full resume state — train state, loader position
    # (epoch + batch cursor), global step, recovery budgets — so a
    # preempted run continues at the exact step instead of replaying the
    # epoch. The preemption save writes the same tree. 0 = only preemption/
    # epoch-boundary saves; N > 0 also saves every N steps. The slot is
    # distinct from the per-epoch best/good slots and exempt from their
    # keep-K rotation (per-slot retention, train/checkpoint.py).
    emergency_every: int = 0
    # On startup, shrink the mesh's data axis to the largest degree the
    # live device count and batch size allow (a preempted TPU job often
    # comes back on a degraded slice); resume then reshards the checkpoint
    # onto the new mesh (Checkpointer.restore_resharded). Non-data axes
    # never shrink — too few devices for them is still an error.
    elastic: bool = False
    # Asynchronous checkpointing: persist on a background thread so the next
    # epoch doesn't stall behind filesystem writes; fit() drains at the end.
    async_checkpoint: bool = False
    log_every_n_steps: int = 30             # reference data_parallel.py:116
    # Run the eval pass every N epochs (always on the final epoch). The
    # reference evals every epoch (data_parallel.py:160-172) — keep 1 for
    # parity; raise it when eval wall-clock dominates short epochs.
    eval_every: int = 1
    max_inflight_steps: int = 8             # bound on host run-ahead (async dispatch)
    # Numerical/stall guards (train/guards.py:GuardRunner): N > 0 checks
    # drained metrics for NaN/Inf at every sync and the full params every N
    # steps (raises NonFiniteError); stall_budget_s arms a wall-clock
    # watchdog around blocking drains (logs, never raises). Both close the
    # reference's silent-failure gap (SURVEY.md §5: a dead rank blocks
    # forever on dist.recv, distributed_layers.py:20).
    check_finite_every: int = 0
    stall_budget_s: float | None = None
    # Cross-replica consistency sentinel (train/consistency.py): every N
    # steps fingerprint params + optimizer state on device (per-leaf
    # finiteness / L2 / checksum), compare across the data-parallel axis,
    # and repair a minority-outlier replica in place by re-broadcasting
    # from a majority-good one (no quorum -> good-slot restore via the
    # recovery supervisor). 0 = off. Detects the silent data corruption
    # and replica drift the finiteness guards are blind to. Requires
    # replicated state: strategy "fsdp" (params sharded over data) rejects
    # it loudly.
    consistency_every: int = 0
    # Automatic recovery policy + fault-injection plan
    # (train/resilience.py, utils/faults.py). Off by default.
    recovery: RecoveryConfig = dataclasses.field(
        default_factory=RecoveryConfig)
    # Live status/metrics exporter (utils/statusz.py): serve /metrics
    # (Prometheus text), /statusz (JSON fleet state) and /healthz on
    # 127.0.0.1:<port> from a daemon thread (0 = ephemeral port). One
    # exporter per process — under the orchestrator the tenants register
    # providers on the fleet's exporter instead of opening their own.
    # None falls back to DMP_STATUSZ_PORT; unset both = true no-op.
    statusz_port: int | None = None
    # Device-resident fast path (gspmd strategy): upload the train set to the
    # accelerators once and run steps_per_dispatch train steps per jitted
    # program (lax.scan over on-device index gathers) — amortizes dispatch
    # overhead and removes per-step host->device image traffic.
    device_resident_data: bool = False
    steps_per_dispatch: int = 1
    # Pipeline-specific knobs (used when mesh.stage > 1).
    num_microbatches: int = 1               # 1 == reference's naive schedule
    stage_boundaries: Sequence[int] | None = None  # unit indices; None = balanced
    # Compute stage_boundaries from XLA per-unit FLOP costs (minimax
    # partition, parallel/auto_partition.py) instead of equal unit counts.
    auto_partition: bool = False
    pipeline_schedule: str = "gpipe"        # "gpipe" | "1f1b"
    virtual_stages: int = 1                 # >1 = Megatron interleaved chunks

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)
