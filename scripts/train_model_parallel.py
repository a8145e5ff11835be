#!/usr/bin/env python
"""Pipeline (model-parallel) training driver.

CLI parity with the reference's ``model_parallel.py`` (``:15-42``) with the
mesh replacing ``--dist-url``/``--dist-backend``/``--world-size`` +
``mp.spawn`` (SURVEY.md §2.4): ``--stages`` is the pipeline depth,
``--microbatches 1`` reproduces the reference's naive 1-batch-in-flight
schedule, larger values give GPipe. Stage boundaries are configurable data
(``--boundaries 0,4,10,16,19`` = the reference's hard-coded 4-GPU split,
``model_parallel.py:102-144``), not per-rank code.

Example:
  python scripts/train_model_parallel.py --stages 4 --batch-size 512 --lr 0.4
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._cpu_devices import force_cpu_devices

force_cpu_devices((("--stages", "--world-size"), "--dp"))

from distributed_model_parallel_tpu.config import (
    DataConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    TrainConfig,
)


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("data", nargs="?", default="./data")
    p.add_argument("--dataset-type", "-type", default="cifar10",
                   choices=["cifar10", "imagenet", "cub200", "place365",
                            "synthetic"])
    p.add_argument("--model", default="mobilenetv2")
    p.add_argument("--stages", "--world-size", default=4, type=int)
    p.add_argument("--microbatches", default=1, type=int,
                   help="1 = reference's naive schedule; >1 = GPipe/1F1B")
    p.add_argument("--schedule", default="gpipe", choices=["gpipe", "1f1b"])
    p.add_argument("--virtual-stages", default=1, type=int,
                   help=">1 = Megatron interleaved placement: each device "
                        "owns that many non-contiguous layer chunks")
    p.add_argument("--boundaries", default=None,
                   help="comma-separated unit boundaries, e.g. 0,4,10,16,19")
    p.add_argument("--auto-partition", action="store_true",
                   help="choose boundaries by minimax over XLA per-unit "
                        "FLOPs instead of equal unit counts")
    p.add_argument("--lr", default=0.4, type=float)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--wd", default=1e-4, type=float)
    p.add_argument("--epochs", default=100, type=int)
    p.add_argument("--batch-size", "-b", default=512, type=int)
    p.add_argument("--warmup-epochs", default=10, type=int)
    p.add_argument("--resume", "-r", action="store_true")
    p.add_argument("--emergency-every", default=0, type=int, metavar="N",
                   help="elastic resume: write the emergency checkpoint "
                        "slot (exact mid-epoch resume state) every N steps "
                        "(0 = only the preemption save; train/elastic.py)")
    p.add_argument("--image-size", default=32, type=int,
                   help="train/eval input resolution; when it differs from "
                        "the dataset's native size the batch is resized "
                        "on-device (224 = the reference finetune recipe)")
    p.add_argument("--no-augment", action="store_true")
    p.add_argument("--log-name", default=None)
    p.add_argument("--engine", default="runner", choices=["runner", "spmd"],
                   help="'runner' = single-controller PipelineRunner (one "
                        "program per stage, schedules incl. 1F1B/virtual "
                        "stages); 'spmd' = single-program shard_map+ppermute "
                        "pipeline over a data x stage mesh "
                        "(parallel/spmd_cnn_pipeline.py) — the multi-host "
                        "path; --dp sets its data-parallel width")
    p.add_argument("--dp", default=1, type=int,
                   help="data-axis width for --engine spmd (total devices "
                        "= dp * stages)")
    return p.parse_args()


def main():
    args = parse_args()
    # Crash flight recorder opt-in (utils/flightrec.py): ring tee +
    # unhandled-exception postmortem hook under DMP_FLIGHT_RECORDER.
    from distributed_model_parallel_tpu.utils import flightrec

    flightrec.install_from_env()
    # One attempt at the backend; anything but a TPU is refused unless
    # JAX_PLATFORMS=cpu asked for it (utils/device_contact.py).
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributed_model_parallel_tpu.utils.device_contact import (
        require_devices,
    )

    enable_compile_cache()
    require_devices("train-model-parallel")
    boundaries = (None if args.boundaries is None else
                  [int(x) for x in args.boundaries.split(",")])
    if boundaries is not None and args.auto_partition:
        print("warning: explicit --boundaries override --auto-partition",
              file=sys.stderr)
    steps_per_epoch = max(1, 50000 // args.batch_size)
    config = TrainConfig(
        model=ModelConfig(name=args.model),
        data=DataConfig(name=args.dataset_type, root=args.data,
                        image_size=args.image_size,
                        batch_size=args.batch_size,
                        augment=not args.no_augment),
        optimizer=OptimizerConfig(
            learning_rate=args.lr, momentum=args.momentum,
            weight_decay=args.wd,
            warmup_steps=args.warmup_epochs * steps_per_epoch),
        mesh=MeshConfig(data=args.dp, stage=args.stages),
        epochs=args.epochs,
        resume=args.resume,
        emergency_every=args.emergency_every,
        strategy=("spmd_pipeline" if args.engine == "spmd" else "gspmd"),
        num_microbatches=args.microbatches,
        stage_boundaries=boundaries,
        auto_partition=args.auto_partition,
        pipeline_schedule=args.schedule,
        virtual_stages=args.virtual_stages,
        log_name=args.log_name or f"{args.batch_size}",
    )
    if args.engine == "runner" and args.dp != 1:
        raise SystemExit(
            "--dp is an --engine spmd knob; the single-controller runner "
            "pipelines over stages only (PipelineTrainer ignores the data "
            "axis — refusing to silently drop your requested data "
            "parallelism)")
    if args.engine == "spmd":
        if args.virtual_stages != 1:
            raise SystemExit(
                "--engine spmd runs one stage per device; virtual stages "
                "are a runner-engine schedule (interleaving only beats "
                "GPipe under 1F1B ordering, and the SPMD 1F1B is "
                "single-level)")
        from distributed_model_parallel_tpu.train.trainer import Trainer

        Trainer(config).fit()
        return
    from distributed_model_parallel_tpu.train.pipeline_trainer import (
        PipelineTrainer,
    )
    PipelineTrainer(config).fit()


if __name__ == "__main__":
    main()
