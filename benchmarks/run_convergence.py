#!/usr/bin/env python
"""Converged end-to-end training: final-loss parity across strategies.

The reference publishes *converged* results — 93.3% MP / 93.8% DP at 90
epochs (``Readme.md:283-285``) — and BASELINE.json's north star demands
"identical final loss" across parallelism strategies. This driver runs the
full 90-epoch MobileNetV2 bs-512 recipe under every strategy family at a
fixed seed and commits the per-epoch curves:

* ``gspmd``      — GSPMD data-parallel Trainer (the DP baseline).
* ``ddp``        — explicit per-replica shard_map engine.
* ``fsdp``       — ZeRO-3 sharded params/optimizer.
* ``pipe_naive`` — PipelineRunner, 1 microbatch (the reference's 1-in-flight
  schedule); on one chip this is the short-chain equivalence run the
  hardware allows (stage machinery exercised end to end, S=num devices).
* ``pipe_gpipe8`` — PipelineRunner, GPipe with 8 microbatches.

Parity semantics: with ``--no-augment`` (default here) the train step is
deterministic given the batch order, and every engine consumes the same
``BatchLoader`` shuffle stream (same data seed) — so final losses must
agree to float tolerance; any real divergence is an engine bug. With
augmentation the crop/flip rng plumbing is engine-specific (DP uses the
step rng directly; DDP folds in the replica index; the pipeline splits
per microbatch), exactly like torch DP-vs-DDP, so augmented runs are
reported as curves, not bit parity. GPipe-8 additionally normalizes each
microbatch with its own BatchNorm statistics (standard grad-accumulation
semantics), giving a small documented deviation.

Dataset: real CIFAR-10 when present under ``--data-root``; otherwise the
deterministic synthetic stand-in at CIFAR scale (50k/10k) — parity across
strategies is a property of the engines, not the pixels.

Writes benchmarks/<--out> (default convergence.json); RESULTS.md
at the repo root narrates the committed artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--model", default="mobilenetv2")
    p.add_argument("--lr", type=float, default=0.4)      # bs-512 linear rule
    p.add_argument("--warmup-epochs", type=int, default=10)
    p.add_argument("--train-size", type=int, default=50_000)
    p.add_argument("--eval-size", type=int, default=10_000)
    p.add_argument("--data-root", default="./data")
    p.add_argument("--augment", action="store_true",
                   help="reference recipe augmentation (disables the exact "
                        "cross-engine parity property; see module docstring)")
    p.add_argument("--strategies",
                   default="gspmd,ddp,fsdp,pipe_naive,pipe_gpipe8")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    p.add_argument("--device-resident", action="store_true",
                   help="gspmd/fsdp: dataset lives on device, K steps per "
                        "dispatch — the fast path for the full-scale "
                        "headline run (the host-streaming path pays a "
                        "per-step batch upload)")
    p.add_argument("--out", default="convergence.json",
                   help="output filename under benchmarks/")
    p.add_argument("--eval-every", type=int, default=1,
                   help="eval pass every N epochs (final epoch always "
                        "evals); raise when eval dominates short epochs")
    return p.parse_args()


def build_config(args, strategy):
    from distributed_model_parallel_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, OptimizerConfig, TrainConfig)

    n_dev = 1  # one real chip; strategies run their machinery at width 1
    data = DataConfig(
        name="cifar10", root=args.data_root, batch_size=args.batch_size,
        eval_batch_size=1000, augment=args.augment, seed=args.seed,
        synthetic_train_size=args.train_size,
        synthetic_eval_size=args.eval_size)
    steps_per_epoch = args.train_size // args.batch_size
    kw = dict(
        model=ModelConfig(name=args.model),
        data=data,
        optimizer=OptimizerConfig(
            learning_rate=args.lr,
            warmup_steps=args.warmup_epochs * steps_per_epoch),
        epochs=args.epochs,
        seed=args.seed,
        eval_every=args.eval_every,
        log_dir="/tmp/dmp_conv_log", checkpoint_dir=f"/tmp/dmp_conv_ckpt_{strategy}",
        log_every_n_steps=10_000,
    )
    if strategy in ("gspmd", "ddp", "fsdp"):
        kw.update(strategy=strategy, mesh=MeshConfig(data=n_dev))
        if args.device_resident:
            kw.update(device_resident_data=True, steps_per_dispatch=10)
    elif strategy == "pipe_naive":
        kw.update(mesh=MeshConfig(data=1, stage=n_dev), num_microbatches=1)
    elif strategy == "pipe_gpipe8":
        kw.update(mesh=MeshConfig(data=1, stage=n_dev), num_microbatches=8)
    else:
        raise KeyError(strategy)
    if args.device_resident and strategy not in ("gspmd", "fsdp"):
        raise ValueError(
            f"--device-resident is a gspmd/fsdp fast path; strategy "
            f"{strategy!r} streams batches from host (no silent ignores)")
    return TrainConfig(**kw)


def run_strategy(args, strategy):
    from distributed_model_parallel_tpu.train.pipeline_trainer import (
        PipelineTrainer,
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer

    cfg = build_config(args, strategy)
    cls = PipelineTrainer if strategy.startswith("pipe") else Trainer
    t0 = time.perf_counter()
    trainer = cls(cfg)
    history = trainer.fit(epochs=args.epochs)
    wall = time.perf_counter() - t0
    return {
        "strategy": strategy,
        "epochs": args.epochs,
        "final_loss_train": history[-1]["loss_train"],
        "final_loss_val": history[-1].get("loss_val"),
        "final_acc1_val": history[-1].get("acc1_val"),
        "best_acc1_val": max((h.get("acc1_val") or 0.0) for h in history),
        "wall_s": round(wall, 1),
        "curve": [{"epoch": h["epoch"], "loss_train": h["loss_train"],
                   "loss_val": h.get("loss_val"),
                   "acc1_val": h.get("acc1_val")} for h in history],
    }


def main():
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse_args()
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax

    real_data = os.path.isdir(os.path.join(args.data_root,
                                           "cifar-10-batches-py"))
    out_rows = []
    for strategy in args.strategies.split(","):
        print(f"=== {strategy} ===", file=sys.stderr, flush=True)
        row = run_strategy(args, strategy)
        out_rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "curve"}),
              flush=True)

    meta = {
        "ts": time.time(),
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "dataset": ("cifar-10-batches-py" if real_data
                    else f"synthetic-{args.train_size}/{args.eval_size}"),
        "recipe": {"model": args.model, "epochs": args.epochs,
                   "batch_size": args.batch_size, "lr": args.lr,
                   "warmup_epochs": args.warmup_epochs,
                   "augment": args.augment, "seed": args.seed},
        "results": out_rows,
    }
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       args.out)
    with open(out, "w") as f:
        json.dump(meta, f, indent=2)
    print(f"wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
