#!/usr/bin/env python
"""Parallelism-plan CLI: rank mesh layouts for a workload, optionally
validate the top candidates with short measured steps.

One command over the autotuner core (``autotune/``, docs/AUTOTUNE.md):

* ``--dry-run`` — pure analytic planning (enumerate -> HBM filter ->
  alpha-beta rank), no device programs built. Prints ONE JSON object:
  the chosen plan, the ranked feasible list, and the rejections. Exits
  nonzero (rc 2) with a parseable ``{"error": "no-feasible-plan", ...}``
  record when the constraints admit no layout — the CI smoke pins both
  contracts (tests/test_autotune.py).
* ``--measure K`` — additionally time the analytic top-K candidates with
  short real steps of an ``LMTrainer`` on each plan's own mesh
  (``autotune/measure.lm_step_for_plan``), letting the measurement
  overrule the model. Needs the devices to actually exist
  (``--devices`` spawns virtual CPU devices via scripts/_cpu_devices.py
  when JAX_PLATFORMS=cpu).

Examples:
  JAX_PLATFORMS=cpu python scripts/dmp_plan.py --workload lm --devices 8 \\
      --batch 16 --seq 128 --dry-run
  JAX_PLATFORMS=cpu python scripts/dmp_plan.py --workload lm --devices 8 \\
      --batch 16 --seq 128 --d-model 64 --measure 3
  python scripts/dmp_plan.py --workload cnn --model mobilenetv2 \\
      --devices 8 --batch 512 --dry-run
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._cpu_devices import force_cpu_devices  # noqa: E402

force_cpu_devices(("--devices",))


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", choices=("lm", "cnn"), default="lm")
    p.add_argument("--devices", type=int, default=8,
                   help="device count to plan for (analytic planning is "
                        "pure math; --measure needs them to exist)")
    p.add_argument("--batch", type=int, default=64)
    # LM model geometry (tiny-by-default so the dryrun is CPU-cheap but
    # compute-dominant enough that the bubble/overlap terms matter).
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--moe-experts", type=int, default=0)
    # CNN workload.
    p.add_argument("--model", default="tinycnn",
                   help="CNN model registry key (--workload cnn)")
    p.add_argument("--image-size", type=int, default=32)
    # Planner knobs.
    p.add_argument("--hbm-gb", type=float, default=None,
                   help="per-device HBM override, GB (default: "
                        "backend-reported / device-kind table / unfiltered)")
    p.add_argument("--top", type=int, default=None,
                   help="truncate the printed ranked list (default: all)")
    p.add_argument("--dry-run", action="store_true",
                   help="analytic only — no device programs built")
    p.add_argument("--measure", type=int, default=0, metavar="K",
                   help="time the analytic top-K with short real steps; "
                        "measured-best wins")
    p.add_argument("--measure-steps", type=int, default=2)
    return p.parse_args(argv)


def _build_workload(args):
    from distributed_model_parallel_tpu.autotune import search

    if args.workload == "lm":
        from distributed_model_parallel_tpu.models import transformer as tfm

        model = tfm.TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.heads, n_layers=args.layers, d_ff=args.d_ff,
            max_seq_len=args.seq, pos_embedding="rope",
            moe_experts=args.moe_experts)
        return search.lm_workload(model, args.batch, args.seq), model
    from distributed_model_parallel_tpu.config import DataConfig, ModelConfig

    model_cfg = ModelConfig(name=args.model)
    data_cfg = DataConfig(name="synthetic", batch_size=args.batch,
                          image_size=args.image_size)
    return search.cnn_workload(model_cfg, data_cfg), model_cfg


def _lm_measure_fn(args, model_cfg):
    """Per-plan measured seconds/step of an LMTrainer on the plan's mesh
    (autotune/measure.lm_step_for_plan)."""
    from distributed_model_parallel_tpu.autotune import (
        lm_step_for_plan,
        time_step_fn,
    )

    def measure(plan):
        step = lm_step_for_plan(model_cfg, plan, batch=args.batch,
                                seq=args.seq)
        return time_step_fn(step, warmup=1, iters=args.measure_steps)

    return measure


def main(argv=None) -> None:
    args = parse_args(argv)
    from distributed_model_parallel_tpu.autotune import (
        InfeasiblePlanError,
        memory,
        planner,
    )

    hbm = (args.hbm_gb * 1e9 if args.hbm_gb is not None
           else memory.device_hbm_bytes())
    workload, model_cfg = _build_workload(args)
    measure_fn = None
    if args.measure > 0 and args.dry_run:
        raise SystemExit(
            "--measure times candidates with real device steps, which "
            "--dry-run promises not to run; pick one — no silent ignores")
    if args.measure > 0:
        if args.workload != "lm":
            raise SystemExit(
                "--measure times LM candidates only; use --workload lm "
                "(the cnn path ranks analytically)")
        import jax

        if len(jax.devices()) < args.devices:
            raise SystemExit(
                f"--measure needs {args.devices} live devices, have "
                f"{len(jax.devices())} (on CPU, pass --devices before "
                f"jax initializes — scripts/_cpu_devices.py)")
        measure_fn = _lm_measure_fn(args, model_cfg)
    try:
        decision = planner.plan_parallelism(
            workload, args.devices, hbm_bytes=hbm,
            measure_fn=measure_fn, measure_top=args.measure)
    except InfeasiblePlanError as e:
        print(json.dumps({"error": "no-feasible-plan",
                          "workload": args.workload,
                          "n_devices": args.devices,
                          "detail": str(e)}))
        sys.exit(2)
    out = decision.telemetry_payload()
    ranked = [r.payload() for r in decision.ranked]
    out["ranked"] = ranked[:args.top] if args.top else ranked
    out["rejected"] = [{**p.payload(), "reason": why}
                       for p, why in decision.rejected]
    print(json.dumps(out))
    print(f"[dmp_plan] {decision.describe()}", file=sys.stderr)


if __name__ == "__main__":
    main()
