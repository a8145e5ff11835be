"""MoE capacity_factor x aux-weight x z-loss sweep.

The bench's one-number drop rate is measured a few steps from init, where
an untrained router routes everything to the same top experts; what
matters is the STEADY-STATE drop once the load-balance loss has spread
the routing. This sweep trains the LM-MoE config for a fixed step budget
per grid point and records the drop-rate trajectory, final drop, and
throughput, so the capacity choice is evidence, not folklore.

Writes benchmarks/moe_sweep_r5.json. Run ON CHIP:
  python benchmarks/run_moe_sweep.py            # ~grid x 60 steps
"""

from __future__ import annotations

import itertools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from distributed_model_parallel_tpu.config import MeshConfig  # noqa: E402
from distributed_model_parallel_tpu.models import transformer as tfm  # noqa: E402
from distributed_model_parallel_tpu.train.lm_trainer import (  # noqa: E402
    LMTrainConfig,
    LMTrainer,
)
from distributed_model_parallel_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from distributed_model_parallel_tpu.utils.profiling import (  # noqa: E402
    fetch,
    fetch_overhead,
    lm_model_flops,
    peak_flops_per_chip,
)

SEQ = 8192
BATCH = 2
STEPS = 60


def run_point(cf: float, aux_w: float, z_w: float) -> dict:
    cfg = LMTrainConfig(
        model=tfm.TransformerConfig(
            vocab_size=32_000, d_model=1024, n_heads=8, n_layers=8,
            d_ff=4096, max_seq_len=SEQ, pos_embedding="rope",
            moe_experts=8, moe_top_k=2, moe_capacity_factor=cf,
            moe_aux_weight=aux_w, moe_z_weight=z_w,
            remat=True, remat_policy="dots", dtype=jnp.bfloat16),
        batch_size=BATCH, seq_len=SEQ, n_tokens=4 * BATCH * (SEQ + 1),
        eval_batches=0, mesh=MeshConfig(data=1),
        log_dir="/tmp/dmp_moe_sweep_log",
        checkpoint_dir="/tmp/dmp_moe_sweep_ckpt",
    )
    t = LMTrainer(cfg)
    toks, tgts = t.sample_batch()
    toks, tgts = jnp.asarray(toks), jnp.asarray(tgts)

    drops = []

    def step():
        t.params, t.opt_state, m = t._step(t.params, t.opt_state, toks, tgts)
        return m

    m = step()
    fetch(m)                             # compile + warm
    drops.append(round(float(m["moe_drop"]), 4))
    t_fetch = fetch_overhead()
    t0 = time.perf_counter()
    for i in range(STEPS):
        m = step()
        if (i + 1) % 15 == 0:
            drops.append(round(float(m["moe_drop"]), 4))
    fetch(m)
    dt = max(1e-9, time.perf_counter() - t0 - t_fetch) / STEPS
    toks_s = BATCH * SEQ / dt
    flops = lm_model_flops(cfg.model, BATCH, SEQ)
    peak = peak_flops_per_chip()
    row = {
        "capacity_factor": cf, "aux_weight": aux_w, "z_weight": z_w,
        "drop_rate_trajectory": drops,
        "final_drop_rate": drops[-1],
        "tokens_per_s": round(toks_s, 1),
        "mfu": round(flops / dt / peak, 4) if peak else None,
        "final_loss": round(float(m["loss"]), 4),
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    enable_compile_cache()
    grid = list(itertools.product(
        [1.0, 1.25, 1.5, 2.0],       # capacity_factor
        [0.01, 0.05],                # load-balance aux weight
        [0.0, 1e-3],                 # router z-loss weight
    ))
    rows = [run_point(cf, a, z) for cf, a, z in grid]
    ok = [r for r in rows
          if r["capacity_factor"] <= 1.5 and r["final_drop_rate"] < 0.02]
    best = (max(ok, key=lambda r: r["tokens_per_s"]) if ok
            else min(rows, key=lambda r: r["final_drop_rate"]))
    out = {
        "config": {"seq": SEQ, "batch": BATCH, "steps": STEPS,
                   "experts": 8, "top_k": 2,
                   "model": "d1024 L8 ff4096 bf16 remat=dots"},
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "rows": rows,
        "recommended": best,
        "note": ("drop_rate_trajectory samples step ~1 then every 15 steps: "
                 "the init-collapsed router (every token picks the same "
                 "top-2) balances within tens of steps under the aux loss, "
                 "so capacity should be provisioned for the steady state, "
                 "not for step 0. 'recommended' = fastest grid point with "
                 "cf<=1.5 and steady-state drop <2%."),
    }
    path = pathlib.Path(__file__).parent / "moe_sweep_r5.json"
    path.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {path}; recommended: cf={best['capacity_factor']} "
          f"aux={best['aux_weight']} z={best['z_weight']} "
          f"drop={best['final_drop_rate']} tok/s={best['tokens_per_s']}")


if __name__ == "__main__":
    main()
