#!/usr/bin/env python
"""Data-parallel training driver.

CLI parity with the reference's ``data_parallel.py`` (flags ``--lr``,
``--resume``; ``data_parallel.py:19-23``) plus the knobs its pipeline script
exposed (``model_parallel.py:15-42``: dataset, batch size, workers, wd,
momentum, epochs) — all honored, none silently ignored (the reference ignores
``-b``/``-j``/``-type``, SURVEY.md §1).

Examples:
  python scripts/train_data_parallel.py --lr 0.4 --batch-size 512
  python scripts/train_data_parallel.py --resume --sync-bn --ddp
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from scripts._cpu_devices import force_cpu_devices

force_cpu_devices(("--num-devices",))

from distributed_model_parallel_tpu.config import (
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    TrainConfig,
)
from distributed_model_parallel_tpu.mesh import best_effort_distributed_init


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("data", nargs="?", default="./data", help="dataset root")
    p.add_argument("--dataset-type", "-type", default="cifar10",
                   choices=["cifar10", "imagenet", "cub200", "place365",
                            "synthetic"])
    p.add_argument("--model", default="mobilenetv2")
    p.add_argument("--lr", default=0.4, type=float)
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture an XLA profiler trace of the run into DIR")
    p.add_argument("--device-data", action="store_true",
                   help="device-resident dataset fast path (gspmd only)")
    p.add_argument("--steps-per-dispatch", default=1, type=int,
                   help="train steps per jitted program with --device-data")
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "adam", "adamw", "adafactor", "lamb",
                            "lars"],
                   help="lars/lamb: layerwise-adaptive large-batch training; "
                        "adafactor: sub-linear optimizer memory")
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--wd", default=1e-4, type=float)
    p.add_argument("--epochs", default=100, type=int)
    p.add_argument("--batch-size", "-b", default=512, type=int)
    p.add_argument("--workers", "-j", default=2, type=int)
    p.add_argument("--warmup-epochs", default=10, type=int)
    p.add_argument("--ema-decay", default=None, type=float,
                   help="weight EMA decay (e.g. 0.999); eval and best-acc "
                        "selection use the averaged weights")
    p.add_argument("--accum-steps", default=1, type=int,
                   help="gradient accumulation: one optimizer update per k "
                        "batches (size-b batch at k == size-k*b batch)")
    p.add_argument("--resume", "-r", action="store_true")
    p.add_argument("--emergency-every", default=0, type=int, metavar="N",
                   help="elastic resume: write the emergency checkpoint "
                        "slot (full mid-epoch resume state: loader cursor, "
                        "global step, recovery budgets) every N steps so a "
                        "preempted run continues at the exact step "
                        "(0 = only the preemption save; train/elastic.py)")
    p.add_argument("--elastic", action="store_true",
                   help="on startup, shrink the data axis to the largest "
                        "degree the live device count and batch size allow "
                        "(degraded-slice restart) and reshard the resumed "
                        "checkpoint onto the rebuilt mesh")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="persist checkpoints on a background thread")
    p.add_argument("--sync-bn", action="store_true",
                   help="SyncBatchNorm semantics (BASELINE config 3)")
    p.add_argument("--no-bn", action="store_true",
                   help="train without BatchNorm (the reference's "
                        "MobileNetV2_nobn large-batch study)")
    p.add_argument("--ddp", action="store_true",
                   help="explicit shard_map DDP engine (per-replica BN, "
                        "psum grad averaging) instead of GSPMD")
    p.add_argument("--fsdp", action="store_true",
                   help="FSDP/ZeRO-3: shard params + optimizer state over "
                        "the data axis (XLA inserts JIT all-gather / grad "
                        "reduce-scatter)")
    p.add_argument("--bucket-mb", type=int, default=0,
                   help="DDP gradient bucket size in MiB (0 = per-leaf psum)")
    p.add_argument("--allreduce", default="psum",
                   choices=["psum", "bucketed", "ring", "hierarchical"],
                   help="DDP gradient allreduce implementation "
                        "(hierarchical needs --dcn-data > 1)")
    p.add_argument("--image-size", default=32, type=int,
                   help="train/eval input resolution; when it differs from "
                        "the dataset's native size the batch is resized "
                        "on-device (224 = the reference finetune recipe)")
    p.add_argument("--no-augment", action="store_true")
    p.add_argument("--prefetch", default=2, type=int,
                   help="host prefetch depth (0 disables)")
    p.add_argument("--device-prefetch", default=2, type=int, metavar="N",
                   help="device-resident input prefetch: keep N batches' "
                        "sharded uploads in flight ahead of the running "
                        "step (0 = per-step device_put; "
                        "docs/PERFORMANCE.md)")
    p.add_argument("--grad-bucket-mb", default=None, type=float,
                   metavar="MB",
                   help="bucketed gradient allreduce cap (DDP only): "
                        "route grads through flat reverse-order buckets "
                        "overlapping the backward (the Reducer's "
                        "bucket_cap_mb; overrides --bucket-mb)")
    p.add_argument("--fused-opt", action="store_true",
                   help="fused Pallas SGD optimizer kernel "
                        "(ops/pallas_optim.py; sgd only, pure-XLA "
                        "fallback off-TPU)")
    p.add_argument("--native-loader", action="store_true",
                   help="assemble batches with the C++ row-gather")
    p.add_argument("--bf16", action="store_true", help="bfloat16 compute")
    p.add_argument("--num-devices", default=0, type=int,
                   help="data-parallel width (0 = all visible devices)")
    p.add_argument("--check-finite-every", default=0, type=int,
                   help="check drained metrics every sync and the params "
                        "every N steps for NaN/Inf (0 = off)")
    p.add_argument("--stall-budget", default=None, type=float, metavar="S",
                   help="arm the live stall watchdog around blocking syncs")
    p.add_argument("--consistency-every", default=0, type=int, metavar="N",
                   help="cross-replica consistency sentinel: every N steps "
                        "fingerprint params+opt state on device, compare "
                        "across the data axis, and repair a minority-bad "
                        "replica by re-broadcast (0 = off; "
                        "train/consistency.py)")
    p.add_argument("--barrier-timeout", default=None, type=float,
                   metavar="S",
                   help="hard bound (seconds) on each consistency check's "
                        "blocking ops — the multi-host rendezvous AND the "
                        "fingerprint fetch (any run) — so a wedged/missing "
                        "participant is reported as a straggler instead of "
                        "hanging")
    p.add_argument("--recovery-retries", default=0, type=int,
                   help="automatic recovery: restore the last good "
                        "checkpoint and retry the epoch on non-finite "
                        "detections, up to N times (0 = fail fast; needs "
                        "--check-finite-every)")
    p.add_argument("--recovery-lr-shrink", default=1.0, type=float,
                   help="multiply the LR by this factor on every "
                        "non-finite recovery (e.g. 0.5)")
    p.add_argument("--stall-exit", action="store_true",
                   help="escalate a stall-budget overrun to a graceful "
                        "checkpoint-and-exit")
    p.add_argument("--inject-faults", default=None, metavar="PLAN",
                   help="deterministic chaos plan, e.g. "
                        "'nan_loss@1,stall@0:0.5' (utils/faults.py)")
    p.add_argument("--dcn-data", default=1, type=int,
                   help="how many data-parallel ways cross the host (DCN) "
                        "boundary; must divide the data width. Lays the mesh "
                        "host-major so XLA reduces gradients hierarchically")
    p.add_argument("--log-name", default=None)
    return p.parse_args()


def main():
    args = parse_args()
    # Crash flight recorder (utils/flightrec.py): DMP_FLIGHT_RECORDER=
    # <dir> tees every telemetry record into a bounded ring and arms an
    # unhandled-exception hook that fsyncs the failure record, closes
    # the live streams, and dumps a postmortem bundle (ring tail +
    # all-thread stacks + span stacks + device memory + health scores).
    from distributed_model_parallel_tpu.utils import flightrec

    flightrec.install_from_env()
    best_effort_distributed_init()
    # One attempt at the backend; anything but a TPU is refused unless
    # JAX_PLATFORMS=cpu asked for it (utils/device_contact.py).
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributed_model_parallel_tpu.utils.device_contact import (
        require_devices,
    )

    enable_compile_cache()
    require_devices("train-data-parallel")
    import jax

    if args.ddp and args.fsdp:
        sys.exit("--ddp and --fsdp are mutually exclusive engines")
    if args.sync_bn and args.no_bn:
        sys.exit("--sync-bn and --no-bn are mutually exclusive")
    if args.sync_bn and args.model.endswith("_nobn"):
        sys.exit(f"--sync-bn conflicts with the BN-free model {args.model!r}")
    if not args.ddp and args.grad_bucket_mb is not None:
        sys.exit("--grad-bucket-mb routes gradients through bucketed_psum, "
                 "which needs the explicit DDP path; add --ddp")
    if not args.ddp and (args.allreduce != "psum" or args.bucket_mb):
        print("warning: --allreduce/--bucket-mb select the explicit DDP "
              "gradient transport; without --ddp the GSPMD path lets XLA "
              "insert the allreduce and these flags have no effect",
              file=sys.stderr)
    n = args.num_devices or len(jax.devices())
    steps_per_epoch = max(1, 50000 // args.batch_size)
    from distributed_model_parallel_tpu.config import RecoveryConfig
    from distributed_model_parallel_tpu.utils.faults import parse_faults

    recovery = RecoveryConfig(
        max_retries=args.recovery_retries,
        lr_shrink=args.recovery_lr_shrink,
        stall_exit=args.stall_exit,
        barrier_timeout_s=args.barrier_timeout,
        faults=parse_faults(args.inject_faults) if args.inject_faults
        else ())
    config = TrainConfig(
        model=ModelConfig(name=args.model,
                          batchnorm=("none" if args.no_bn
                                     else "sync" if args.sync_bn else "local"),
                          dtype="bfloat16" if args.bf16 else "float32"),
        data=DataConfig(name=args.dataset_type, root=args.data,
                        image_size=args.image_size,
                        batch_size=args.batch_size, num_workers=args.workers,
                        augment=not args.no_augment, prefetch=args.prefetch,
                        device_prefetch=args.device_prefetch,
                        use_native=args.native_loader),
        optimizer=OptimizerConfig(
            name=args.optimizer,
            learning_rate=args.lr, momentum=args.momentum,
            weight_decay=args.wd,
            warmup_steps=args.warmup_epochs * steps_per_epoch,
            accum_steps=args.accum_steps,
            ema_decay=args.ema_decay,
            fused=args.fused_opt),
        mesh=MeshConfig(data=n, dcn_data=args.dcn_data),
        epochs=args.epochs,
        resume=args.resume,
        emergency_every=args.emergency_every,
        elastic=args.elastic,
        async_checkpoint=args.async_checkpoint,
        device_resident_data=args.device_data,
        steps_per_dispatch=args.steps_per_dispatch,
        strategy="ddp" if args.ddp else ("fsdp" if args.fsdp else "gspmd"),
        ddp_bucket_bytes=args.bucket_mb * 1024 * 1024 or None,
        ddp_allreduce=args.allreduce,
        grad_bucket_mb=args.grad_bucket_mb,
        check_finite_every=args.check_finite_every,
        stall_budget_s=args.stall_budget,
        consistency_every=args.consistency_every,
        recovery=recovery,
        log_name=args.log_name or f"data_para_{args.batch_size}",
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer
    trainer = Trainer(config)
    if args.profile:
        # XLA profiler trace (TensorBoard/Perfetto); use a short --epochs run
        # — the trace covers the whole fit.
        from distributed_model_parallel_tpu.utils.profiling import trace
        with trace(args.profile):
            trainer.fit()
    else:
        trainer.fit()


if __name__ == "__main__":
    main()
