"""Peak-memory evidence for the 1F1B SPMD pipeline schedule.

Compiles the GPipe (whole-program-AD) and 1F1B (hand-interleaved) train
steps on an 8-virtual-device CPU mesh and records XLA ``memory_analysis()``
per schedule: the GPipe backward can only start after all M microbatches'
forwards, so every microbatch's residuals are live at the peak; 1F1B stashes
at most 2S-1 stage inputs and recomputes the stage forward in the backward
(before it, the only host-spanning schedule was the most memory-hungry
one).

Writes benchmarks/pipeline_memory.json. Run:
  python benchmarks/run_1f1b_memory.py
(forces an 8-device CPU platform itself; no flags needed).
"""

import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from distributed_model_parallel_tpu.config import MeshConfig  # noqa: E402
from distributed_model_parallel_tpu.mesh import make_mesh  # noqa: E402
from distributed_model_parallel_tpu.models import transformer as tfm  # noqa: E402
from distributed_model_parallel_tpu.parallel.spmd_pipeline import (  # noqa: E402
    make_spmd_train_step,
    shard_params,
)


def measure(schedule: str, cfg, spec, M: int, B: int, T: int,
            V: int = 1) -> dict:
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        interleave_block_rows,
    )

    tx = optax.sgd(0.1)
    step = make_spmd_train_step(cfg, spec, tx, num_microbatches=M,
                                schedule=schedule, virtual_stages=V)
    host = tfm.init_params(jax.random.key(0), cfg)
    if V > 1:
        host["blocks"] = interleave_block_rows(
            host["blocks"], cfg.n_layers, spec.num_stages, V)
    params = shard_params(host, cfg, spec)
    opt_state = tx.init(params)
    toks = jnp.zeros((B, T), jnp.int32)
    lowered = step.lower(params, opt_state, toks, toks)
    mem = lowered.compile().memory_analysis()
    out = {
        "schedule": schedule,
        "temp_bytes": int(mem.temp_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "generated_code_bytes": int(mem.generated_code_size_in_bytes),
    }
    print(f"{schedule}: temp={out['temp_bytes'] / 1e6:.1f} MB "
          f"args={out['argument_bytes'] / 1e6:.1f} MB")
    return out


def main() -> None:
    T = 512
    results = []
    for stages, M, remat in ((4, 8, False), (4, 16, False), (4, 32, False),
                             (2, 8, False), (4, 16, True), (4, 32, True)):
        ndata = 8 // stages
        B = M * ndata            # local batch = M -> microbatch of 1
        cfg = tfm.TransformerConfig(
            vocab_size=512, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
            max_seq_len=T, pos_embedding="rope",
            remat=remat, remat_policy="full")
        spec = make_mesh(MeshConfig(data=ndata, stage=stages))
        row = {"mesh": f"data={ndata} stage={stages}", "M": M,
               "batch": B, "seq": T, "remat": remat,
               "model": "L8 d512 h8 ff2048 v512"}
        for schedule in ("gpipe", "1f1b"):
            row[schedule] = measure(schedule, cfg, spec, M, B, T)
        row["temp_ratio_gpipe_over_1f1b"] = round(
            row["gpipe"]["temp_bytes"] / row["1f1b"]["temp_bytes"], 3)
        results.append(row)

    # Interleaved virtual stages (V=2) next to their V=1 1F1B baseline:
    # same model, same mesh, M % S == 0. The stash ring grows 2S-1 ->
    # 2VS-1 buffers (more activation memory — the known Megatron
    # interleaving trade) while the bubble shrinks (S-1)/(M+S-1) ->
    # (S-1)/(V*M+V*S-1) of the fine-tick schedule.
    for stages, M in ((4, 8), (2, 8)):
        ndata = 8 // stages
        B = M * ndata
        cfg = tfm.TransformerConfig(
            vocab_size=512, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
            max_seq_len=T, pos_embedding="rope")
        spec = make_mesh(MeshConfig(data=ndata, stage=stages))
        row = {"mesh": f"data={ndata} stage={stages}", "M": M,
               "batch": B, "seq": T, "remat": False,
               "model": "L8 d512 h8 ff2048 v512",
               "1f1b_v1": measure("1f1b", cfg, spec, M, B, T),
               "1f1b_v2_interleaved": measure("1f1b", cfg, spec, M, B, T,
                                              V=2)}
        S = stages
        row["bubble_frac_v1"] = round((S - 1) / (M + S - 1), 4)
        row["bubble_frac_v2"] = round((S - 1) / (2 * M + 2 * S - 1), 4)
        row["temp_ratio_v2_over_v1"] = round(
            row["1f1b_v2_interleaved"]["temp_bytes"]
            / row["1f1b_v1"]["temp_bytes"], 3)
        results.append(row)

    out = {
        "note": ("XLA memory_analysis() of the compiled SPMD train step on "
                 "an 8-virtual-CPU-device mesh. temp_bytes is the per-"
                 "device transient (activation/residual) pool — the number "
                 "the schedule controls; argument bytes (params+opt state) "
                 "are schedule-independent. 1F1B stashes <= 2S-1 stage "
                 "inputs and recomputes stage forwards in the backward; "
                 "GPipe under whole-program AD keeps all M microbatches' "
                 "residuals live. The remat=True rows answer the obvious "
                 "follow-up: even with per-block activation recompute "
                 "shrinking GPipe's per-tick saves to block inputs, its "
                 "liveness still scales with M while 1F1B's stays flat. "
                 "The 1f1b_v2_interleaved rows (round 5) measure the "
                 "Megatron virtual-stage trade in the SAME engine: "
                 "bubble_frac_v2 < bubble_frac_v1 per the fine-tick "
                 "schedule, stash ring 2S-1 -> 2VS-1 slots, per-tick "
                 "recompute 1/V the layers (which is why V=2 can measure "
                 "LOWER transients at S=4 despite the bigger ring)."),
        "results": results,
    }
    path = pathlib.Path(__file__).parent / "pipeline_memory.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
