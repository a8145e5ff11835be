"""Pipeline-schedule structure evidence on the virtual mesh.

One physical chip cannot time a real stage axis, but everything about the
compiled schedules EXCEPT wall-clock is measurable on the 8-virtual-CPU
mesh: per-device transient memory, the number of inter-stage hop
collectives XLA actually emitted (collective-permutes in the optimized
HLO — the wire protocol the schedule implies), and the tick structure
(warmup/steady/drain counts, bubble fraction). This artifact captures
GPipe vs 1F1B at pp=2 and pp=4 across microbatch counts so the first
multi-chip round only needs to fill in measured step time.

Real-chip command, once >=2 chips are visible (per-chip tokens/s + MFU
land in the one-line bench output):

  DMP_BENCH_WORKLOAD=lm DMP_BENCH_PP=4 DMP_BENCH_MICRO=8 \
  DMP_BENCH_SCHEDULE=1f1b python bench.py     # and SCHEDULE=gpipe

Writes benchmarks/schedule_structure_r5.json. Run anywhere:
  python benchmarks/run_schedule_structure.py
(forces an 8-device CPU platform itself; no flags needed).
"""

import json
import os
import pathlib
import re
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

B, T = 32, 512     # local batch = B / (8/pp) must divide every M below


def _tick_structure(schedule: str, S: int, M: int) -> dict:
    """The schedule's tick counts, from its definition (spmd_pipeline.py):
    GPipe = M+S-1 forward ticks then whole-program AD backward; 1F1B =
    S-1 warmup + M steady (fwd+bwd fused) + S-1 drain."""
    if schedule == "gpipe":
        fwd_ticks = M + S - 1
        return {"fwd_ticks": fwd_ticks, "steady_ticks": 0,
                "total_ticks": fwd_ticks,   # backward mirrors via AD
                "bubble_frac": round((S - 1) / (M + S - 1), 4)}
    return {"warmup_ticks": S - 1, "steady_ticks": M,
            "drain_ticks": S - 1, "total_ticks": M + 2 * (S - 1),
            "bubble_frac": round((S - 1) / (M + S - 1), 4)}


def measure(schedule: str, S: int, M: int) -> dict:
    cfg = tfm.TransformerConfig(
        vocab_size=512, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
        max_seq_len=T, pos_embedding="rope")
    spec = make_mesh(MeshConfig(data=8 // S, stage=S))
    tx = optax.sgd(0.1)
    step = make_spmd_train_step(cfg, spec, tx, num_microbatches=M,
                                schedule=schedule)
    params = shard_params(tfm.init_params(jax.random.key(0), cfg), cfg, spec)
    opt_state = tx.init(params)
    toks = jnp.zeros((B, T), jnp.int32)
    compiled = step.lower(params, opt_state, toks, toks).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    # Inter-stage hops the compiled program actually contains. A
    # collective-permute inside a while body executes trip-count times;
    # count both for the honest dispatch story.
    cp_static = len(re.findall(r"collective-permute(?:-start)?\(", hlo))
    # "%w = (tuple type with spaces) while(...)" — match on the op itself.
    n_while = len(re.findall(r" while\(", hlo))
    row = {
        "schedule": schedule, "pp": S, "M": M,
        "tick_structure": _tick_structure(schedule, S, M),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "collective_permute_sites": cp_static,
        "while_loops": n_while,
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    rows = []
    for S in (2, 4):
        for M in (4, 8):
            for schedule in ("gpipe", "1f1b"):
                rows.append(measure(schedule, S, M))
    out = {
        "config": {"batch": B, "seq": T, "model": "L8 d512 h8 ff2048 v512",
                   "mesh": "data=(8/pp) stage=pp, 8 virtual CPU devices"},
        "rows": rows,
        "note": ("collective_permute_sites counts instruction SITES in the "
                 "optimized HLO; sites inside a while body run trip-count "
                 "times (while_loops reported alongside). temp_bytes is "
                 "the per-device transient pool - the schedule-controlled "
                 "number (see pipeline_memory.json for the M-scaling "
                 "study). Wall-clock per schedule needs >=2 physical "
                 "chips; the exact command is in this file's docstring."),
    }
    path = pathlib.Path(__file__).parent / "schedule_structure_r5.json"
    path.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
