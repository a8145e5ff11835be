"""LM parity artifact: the same seed/config trained under every parallelism
factorization must converge to the same loss.

Trains the flagship Transformer LM (train/lm_trainer.py) for a few hundred
steps under single-device, dp, pp, tp, sp, and hybrid dp x pp x tp meshes —
identical model config, identical init seed, identical host-side batch
stream — and records the final-window mean loss per row in one JSON
(benchmarks/lm_parity.json). Factorizations change only reduction order and
collective placement, so the losses must agree to float tolerance; a row
that drifts indicates a broken sharding, not noise.

Run on the 8-virtual-CPU-device mesh for multi-axis rows; re-run with
``--rows single --merge`` on the real chip to append a hardware anchor:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/run_lm_parity.py
    python benchmarks/run_lm_parity.py --rows single --merge
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


ROWS = {
    "single": dict(mesh=dict(), model=dict()),
    "dp2": dict(mesh=dict(data=2), model=dict()),
    "pp2": dict(mesh=dict(stage=2), model=dict(), microbatches=2),
    "tp2": dict(mesh=dict(model=2), model=dict(tp_axis="model")),
    "sp2_ring": dict(mesh=dict(seq=2), model=dict(sp_axis="seq",
                                                  sp_impl="ring")),
    "sp2_ulysses": dict(mesh=dict(seq=2), model=dict(sp_axis="seq",
                                                     sp_impl="ulysses")),
    "dp2_pp2_tp2": dict(mesh=dict(data=2, stage=2, model=2),
                        model=dict(tp_axis="model"), microbatches=2),
    # The hand-scheduled 1F1B backward must land on the same losses as the
    # whole-program-AD GPipe rows (same config as pp2 but schedule="1f1b").
    "pp2_1f1b": dict(mesh=dict(stage=2), model=dict(), microbatches=2,
                     schedule="1f1b"),
    "dp2_pp2_tp2_1f1b": dict(mesh=dict(data=2, stage=2, model=2),
                             model=dict(tp_axis="model"), microbatches=2,
                             schedule="1f1b"),
}


def run_row(name: str, row: dict, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        OptimizerConfig,
    )
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    cfg = LMTrainConfig(
        model=tfm.TransformerConfig(
            vocab_size=512, d_model=128, n_heads=4, n_layers=4, d_ff=512,
            max_seq_len=128, pos_embedding="rope", **row["model"]),
        mesh=MeshConfig(**row["mesh"]),
        optimizer=OptimizerConfig(learning_rate=0.05, warmup_steps=20,
                                  weight_decay=0.0),
        batch_size=8, seq_len=128,
        num_microbatches=row.get("microbatches", 1),
        pipeline_schedule=row.get("schedule", "gpipe"),
        steps_per_epoch=steps, epochs=1, seed=0,
        log_dir="/tmp/lm_parity_log", checkpoint_dir="/tmp/lm_parity_ckpt_"
        + name)
    t = LMTrainer(cfg)
    losses = []
    t0 = time.perf_counter()
    for _ in range(steps):
        toks, tgts = t.sample_batch()
        t.params, t.opt_state, step_m = t._step(
            t.params, t.opt_state, jnp.asarray(toks), jnp.asarray(tgts))
        losses.append(float(step_m["loss"]))
    dt = time.perf_counter() - t0
    tail = losses[-20:]
    rec = dict(row=name, mesh=row["mesh"],
               microbatches=row.get("microbatches", 1), steps=steps,
               first_loss=round(losses[0], 6),
               final_loss=round(losses[-1], 6),
               final_window_mean=round(sum(tail) / len(tail), 6),
               wall_s=round(dt, 1),
               platform=jax.devices()[0].platform,
               device_kind=getattr(jax.devices()[0], "device_kind", ""))
    print(json.dumps(rec), flush=True)
    return rec


def main() -> None:
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", nargs="*", default=None,
                    help="subset of row names (default: all that fit the "
                    "visible device count)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--out", default=str(Path(__file__).parent
                                         / "lm_parity.json"))
    ap.add_argument("--merge", action="store_true",
                    help="merge rows into an existing artifact instead of "
                    "overwriting")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force the CPU backend with N virtual devices "
                    "(overrides any platform baked in at interpreter "
                    "startup, e.g. by sitecustomize)")
    args = ap.parse_args()

    import jax

    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)

    n_dev = len(jax.devices())
    names = args.rows or [
        n for n, r in ROWS.items()
        if int(__import__("math").prod(r["mesh"].values() or [1])) <= n_dev]
    results = [run_row(n, ROWS[n], args.steps) for n in names]

    out = Path(args.out)
    doc = {"note": "Same seed/config/batch-stream trained under each "
                   "parallelism factorization (benchmarks/run_lm_parity.py); "
                   "final losses must agree — factorizations only reorder "
                   "reductions. final_window_mean averages the last 20 "
                   "steps.",
           "results": []}
    if args.merge and out.exists():
        doc = json.loads(out.read_text())
        keep = {(r["row"], r["platform"]): r for r in doc["results"]}
        keep.update({(r["row"], r["platform"]): r for r in results})
        doc["results"] = list(keep.values())
    else:
        doc["results"] = results
    doc["ts"] = time.time()
    out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"wrote {out} ({len(doc['results'])} rows)")


if __name__ == "__main__":
    main()
