"""Per-kernel flash-attention breakdown + block sweep.

Times the forward, dq, and dk/dv kernels SEPARATELY at seq 8192 head-dim 128
bf16 across block shapes, attributing the fwd+bwd gap to its kernels.
Achieved TFLOPS per kernel counts that kernel's ACTUAL matmul work over the
causal band (per attended pair per head: fwd 4D, dq 6D — score recompute +
dp + ds·k, dkv 8D — score recompute + dv + dp + ds·q), while the headline
"model TFLOPS" number divides the MFU-convention model FLOPs (12D per pair,
recompute excluded) by the total fwd+bwd time — the number
grad_sweep_r3_hd128.json's 97 TFLOPS quotes.

Writes benchmarks/kernel_profile.json. Run ON CHIP:
  python benchmarks/run_kernel_profile.py
"""

import itertools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from distributed_model_parallel_tpu.ops.pallas_attention import (  # noqa: E402
    _bwd_dkv_call,
    _bwd_dq_call,
    _bwd_prep,
    _flash_impl,
    _plan,
)
from distributed_model_parallel_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from distributed_model_parallel_tpu.utils.profiling import (  # noqa: E402
    time_fn_in_scan,
)

B, T, H, D = 1, 8192, 8, 128
PAIRS = T * (T + 1) // 2


def main() -> None:
    enable_compile_cache()
    rng = jax.random.key(0)
    ks = jax.random.split(rng, 4)
    q, k, v, g = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                  for kk in ks)
    o, lse = _flash_impl(q, k, v, True, 512, 1024, None)
    t_pad, d_pad, _, _, _ = _plan(T, D, True, 512, 1024, None)
    prep = _bwd_prep(q, k, v, o, lse, g, t_pad, d_pad)
    scale = D ** -0.5

    blocks = [256, 512, 1024, 2048]
    rows = []

    def record(kind, bq, bk, dt, kernel_flops):
        tf = kernel_flops / dt / 1e12
        rows.append({"kernel": kind, "block_q": bq, "block_k": bk,
                     "ms": round(dt * 1e3, 3),
                     "kernel_tflops": round(tf, 1)})
        print(rows[-1], flush=True)

    # ---- forward kernel sweep (4D per pair per head)
    fwd_flops = 4 * B * H * PAIRS * D
    for bq, bk in itertools.product(blocks, blocks):
        try:
            dt = time_fn_in_scan(
                lambda q, k, v, bq=bq, bk=bk: _flash_impl(
                    q, k, v, True, bq, bk, None)[0], q, k, v, iters=10)
            record("fwd", bq, bk, dt, fwd_flops)
        except Exception as e:
            print(f"fwd {bq}x{bk}: {type(e).__name__}", flush=True)

    # ---- dq kernel sweep (6D per pair per head)
    dq_flops = 6 * B * H * PAIRS * D
    for bq, bk in itertools.product(blocks, blocks):
        try:
            dt = time_fn_in_scan(
                lambda qf, *rest, bq=bq, bk=bk: _bwd_dq_call(
                    qf, *rest, bq=bq, bk=bk, d_pad=d_pad, causal=True,
                    scale=scale, window=None, interp=False,
                    out_dtype=jnp.bfloat16), *prep, iters=10)
            record("dq", bq, bk, dt, dq_flops)
        except Exception as e:
            print(f"dq {bq}x{bk}: {type(e).__name__}", flush=True)

    # ---- dkv kernel sweep (8D per pair per head)
    dkv_flops = 8 * B * H * PAIRS * D
    for bq, bk in itertools.product(blocks, blocks):
        try:
            dt = time_fn_in_scan(
                lambda qf, *rest, bq=bq, bk=bk: _bwd_dkv_call(
                    qf, *rest, bq=bq, bk=bk, d_pad=d_pad, causal=True,
                    scale=scale, window=None, interp=False,
                    k_dtype=jnp.bfloat16, v_dtype=jnp.bfloat16)[0],
                *prep, iters=10)
            record("dkv", bq, bk, dt, dkv_flops)
        except Exception as e:
            print(f"dkv {bq}x{bk}: {type(e).__name__}", flush=True)

    best = {}
    for kind in ("fwd", "dq", "dkv"):
        cand = [r for r in rows if r["kernel"] == kind]
        if cand:
            best[kind] = min(cand, key=lambda r: r["ms"])
    total_ms = sum(b["ms"] for b in best.values())
    model_flops = 12 * B * H * PAIRS * D
    out = {
        "config": {"batch": B, "seq": T, "heads": H, "head_dim": D,
                   "dtype": "bfloat16", "causal": True},
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "rows": rows,
        "best_per_kernel": best,
        "best_total_ms": round(total_ms, 3),
        "model_tflops_at_best": round(model_flops / (total_ms / 1e3) / 1e12,
                                      1),
        "note": ("kernel_tflops counts each kernel's actual causal-band "
                 "matmul work (fwd 4D / dq 6D / dkv 8D per pair per head); "
                 "model_tflops_at_best is the MFU-convention number (12D, "
                 "recompute excluded) over the sum of the three best "
                 "kernel times — the delta pass and unpad reshapes add "
                 "~2-3% on top in the end-to-end vjp."),
    }
    path = pathlib.Path(__file__).parent / "kernel_profile.json"
    path.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {path}: best={ {k: (v['block_q'], v['block_k']) for k, v in best.items()} } "
          f"model TFLOPS {out['model_tflops_at_best']}")


if __name__ == "__main__":
    main()
