#!/usr/bin/env python
"""Parity benchmark suite: reproduce the reference's headline tables.

Reference tables (BASELINE.md / ``Readme.md:283-293``): MobileNetV2/CIFAR-10
time-per-batch, model-parallel vs data-parallel at 2- and 4-way, bs 256/512 —
where the naive 1-in-flight pipeline loses to DP by ~4x (the result this
framework must reproduce for the degenerate schedule, while the micro-batched
schedule closes the gap; SURVEY.md §7 "hard parts" (5)).

Writes one JSON object per config to stdout and benchmarks/results.json.

On a single TPU chip, multi-way rows run on virtual CPU devices
(--platform cpu) — relative MP-vs-DP behavior is meaningful there; absolute
chip throughput comes from bench.py.
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
    p.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    p.add_argument("--device-count", type=int, default=8,
                   help="virtual device count when --platform cpu")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--model", default="mobilenetv2")
    p.add_argument("--ways", default="2,4")
    p.add_argument("--microbatches", default="8",
                   help="comma list: one gpipe row per count (e.g. 2,4,8)")
    return p.parse_args()


def main():
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse_args()
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.device_count)
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, OptimizerConfig, TrainConfig)
    from distributed_model_parallel_tpu.data.registry import load_dataset
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from distributed_model_parallel_tpu.train.pipeline_trainer import PipelineTrainer
    from distributed_model_parallel_tpu.utils.profiling import time_step

    bs = args.batch_size
    results = []
    ways = [int(w) for w in args.ways.split(",")]
    n_dev = len(jax.devices())

    def run(name, trainer_cls, mesh, microbatches=1):
        cfg = TrainConfig(
            model=ModelConfig(name=args.model),
            data=DataConfig(name="synthetic", batch_size=bs,
                            eval_batch_size=bs, synthetic_train_size=bs * 2,
                            synthetic_eval_size=bs),
            optimizer=OptimizerConfig(learning_rate=0.4, warmup_steps=0),
            mesh=mesh,
            num_microbatches=microbatches,
            log_dir="/tmp/dmp_parity_log", checkpoint_dir="/tmp/dmp_parity_ckpt",
        )
        t = trainer_cls(cfg)
        images, labels = next(iter(t.train_loader))
        rng = jax.random.key(0)
        if trainer_cls is Trainer:
            def step():
                nonlocal rng
                rng, sub = jax.random.split(rng)
                # Shard per call: the step donates its batch buffers, so
                # a once-sharded batch dies at the first dispatch.
                im, lb = t._shard_batch(images, labels)
                t.state, m = t._train_step(t.state, sub, im, lb)
                return m["loss"]
        else:
            def step():
                nonlocal rng
                rng, sub = jax.random.split(rng)
                return t.runner.train_step(sub, images, labels)["loss"]

        stats = time_step(lambda: step(), warmup=2, iters=args.steps)
        row = {
            "config": name, "batch_size": bs,
            "time_per_batch_s": round(stats["mean_s"], 4),
            "samples_per_s": round(bs / stats["mean_s"], 1),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    for w in ways:
        if w > n_dev:
            print(json.dumps({"config": f"{w}-way", "skipped":
                              f"only {n_dev} devices"}), flush=True)
            continue
        run(f"data_parallel_{w}way", Trainer, MeshConfig(data=w))
        run(f"model_parallel_{w}way_naive", PipelineTrainer,
            MeshConfig(data=1, stage=w), microbatches=1)
        for m in (int(x) for x in args.microbatches.split(",")):
            run(f"model_parallel_{w}way_gpipe{m}", PipelineTrainer,
                MeshConfig(data=1, stage=w), microbatches=m)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "results.json")
    platform = jax.devices()[0].platform
    meta = {"ts": time.time(), "platform": platform,
            "host_cpus": os.cpu_count(), "results": results}
    if platform == "cpu":
        # A virtual CPU mesh time-slices one host: stage/replica programs
        # SERIALIZE on the host cores (fully so when host_cpus == 1), so
        # wall-clock rows measure total work + per-program dispatch, never
        # pipeline overlap. Relative DP-vs-MP shape is meaningful; GPipe-vs-
        # naive differences are dispatch overhead, not bubble fraction.
        meta["caveat"] = (
            f"virtual CPU mesh on {os.cpu_count()} host core(s): no "
            f"inter-device overlap exists; schedule comparisons reflect "
            f"dispatch overhead only — see docs/design.md §4")
    with open(out, "w") as f:
        json.dump(meta, f, indent=2)
    print(f"wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
