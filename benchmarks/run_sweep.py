#!/usr/bin/env python
"""Sweep benchmarks: batch-size scaling and flash-vs-XLA attention.

Two sweeps, mirroring the reference's experiment-log studies:

1. **Batch-size sweep** — the reference's large-batch study trains at
   bs 128/256/512/1024 with linearly scaled lr (``Readme.md:180-211``,
   settings ``:186-196``). Here we sweep the same batch sizes through the
   jitted DP train step and record time/batch + samples/s (accuracy sweeps
   need the real dataset + hours of training; throughput is the
   hardware-meaningful part of the table).

2. **Attention sweep** — flash (pallas, ``ops/pallas_attention.py``) vs plain
   XLA attention across sequence lengths, causal, bfloat16. The reference has
   no attention (CNN-only, SURVEY.md §5 long-context: absent); this sweep
   covers the long-context subsystem this framework adds.

Writes one JSON object per row to stdout and benchmarks/sweep_results.json.
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
    p.add_argument("--model", default="mobilenetv2")
    p.add_argument("--batch-sizes", default="128,256,512,1024")
    p.add_argument("--seq-lens", default="512,1024,2048")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--skip-attention", action="store_true")
    p.add_argument("--skip-batch", action="store_true")
    p.add_argument("--window", type=int, default=None,
                   help="attention sweep: sliding-window width for the "
                        "flash impl (reproduces the banded-compute numbers)")
    p.add_argument("--grad", action="store_true",
                   help="attention sweep times fwd+bwd (training step "
                        "shape) instead of forward only; compares the "
                        "FlashAttention-2 backward kernels against the "
                        "XLA-recompute backward (bwd_impl='xla')")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="attention sweep compute dtype (the flash-vs-XLA "
                        "crossover is dtype-dependent; feeds the dispatch "
                        "table in ops/pallas_attention.py)")
    p.add_argument("--head-dim", type=int, default=64,
                   help="attention sweep head dimension (dispatch-table "
                        "axis)")
    p.add_argument("--heads", type=int, default=8,
                   help="attention sweep head count")
    p.add_argument("--out", default="sweep_results.json",
                   help="output JSON filename under benchmarks/ (e.g. "
                        "dispatch_sweep.json for dispatch-table evidence)")
    return p.parse_args()


def batch_sweep(args, results):
    import jax
    from distributed_model_parallel_tpu.config import (
        DataConfig, MeshConfig, ModelConfig, OptimizerConfig, TrainConfig)
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from distributed_model_parallel_tpu.utils.profiling import time_step

    n_dev = len(jax.devices())
    for bs in (int(b) for b in args.batch_sizes.split(",")):
        # Linear lr scaling, as the reference's sweep does (lr 0.05 at bs 128
        # up to 0.4 at bs 1024, Readme.md:186-205).
        lr = 0.05 * bs / 128
        cfg = TrainConfig(
            model=ModelConfig(name=args.model),
            data=DataConfig(name="synthetic", batch_size=bs,
                            eval_batch_size=bs, synthetic_train_size=bs * 2,
                            synthetic_eval_size=bs),
            optimizer=OptimizerConfig(learning_rate=lr, warmup_steps=0),
            mesh=MeshConfig(data=n_dev),
            log_dir="/tmp/dmp_sweep_log", checkpoint_dir="/tmp/dmp_sweep_ckpt",
        )
        t = Trainer(cfg)
        images, labels = next(iter(t.train_loader))
        rng = jax.random.key(0)

        def step():
            nonlocal rng
            rng, sub = jax.random.split(rng)
            # Shard per call: the train step donates its batch buffers,
            # so a once-sharded batch would be invalidated after the
            # first dispatch (and the per-step upload is part of the
            # streaming step cost being measured).
            im, lb = t._shard_batch(images, labels)
            t.state, m = t._train_step(t.state, sub, im, lb)
            return m["loss"]

        stats = time_step(step, warmup=2, iters=args.steps)
        row = {"sweep": "batch_size", "model": args.model, "batch_size": bs,
               "lr": lr, "time_per_batch_s": round(stats["mean_s"], 4),
               "samples_per_s": round(bs / stats["mean_s"], 1)}
        results.append(row)
        print(json.dumps(row), flush=True)


def attention_sweep(args, results):
    import jax
    import jax.numpy as jnp
    from distributed_model_parallel_tpu.ops.pallas_attention import flash_attention
    from distributed_model_parallel_tpu.utils.profiling import time_fn_in_scan

    on_tpu = jax.devices()[0].platform == "tpu"
    batch, heads, head_dim = 4, args.heads, args.head_dim
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    for seq in (int(s) for s in args.seq_lens.split(",")):
        # [B, T, H, D] — the layout flash_attention takes.
        q = jax.random.normal(jax.random.key(0), (batch, seq, heads, head_dim),
                              dtype)
        k = jax.random.normal(jax.random.key(1), q.shape, dtype)
        v = jax.random.normal(jax.random.key(2), q.shape, dtype)

        def xla_attn(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s / (head_dim ** 0.5)
            mask = jnp.tril(jnp.ones((seq, seq), bool))
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        impls = {"xla": xla_attn}
        if on_tpu:
            impls["flash_pallas"] = (
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                window=args.window))
            if args.grad:
                impls["flash_pallas_xla_bwd"] = (
                    lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                    bwd_impl="xla"))
        if args.grad:
            def as_grad(f):
                def grad_fn(q, k, v):
                    def loss(q, k, v):
                        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
                    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                return grad_fn
            impls = {name: as_grad(f) for name, f in impls.items()}
        for impl_name, fn in impls.items():
            # In-scan timing: attention runs fused inside larger programs in
            # real use, so kernel time (not per-program dispatch) is the
            # comparable quantity.
            try:
                dt = time_fn_in_scan(fn, q, k, v, iters=args.steps)
            except Exception as e:
                # e.g. XLA fails to compile the materialized T^2 scores at
                # long seq — record the failure, keep sweeping.
                row = {"sweep": "attention", "impl": impl_name,
                       "seq_len": seq, "dtype": args.dtype,
                       "head_dim": head_dim, "heads": heads,
                       "grad": bool(args.grad),
                       "failed": type(e).__name__}
                results.append(row)
                print(json.dumps(row), flush=True)
                continue
            # causal: ~half the FLOPs of full attention; bwd ~2.5x fwd
            flops = 2 * 2 * batch * heads * seq * seq * head_dim / 2
            if args.grad:
                flops *= 3.5
            row = {"sweep": "attention", "impl": impl_name, "seq_len": seq,
                   "dtype": args.dtype, "head_dim": head_dim,
                   "heads": heads, "grad": bool(args.grad),
                   "time_s": round(dt, 5),
                   "tflops": round(flops / dt / 1e12, 2)}
            if args.window is not None and impl_name == "flash_pallas":
                # Only this impl receives the window (the xla paths have no
                # banded formulation). FLOPs model above assumes the full
                # causal triangle; banded rows report time only.
                row["window"] = args.window
                row.pop("tflops")
            results.append(row)
            print(json.dumps(row), flush=True)
    if not on_tpu:
        print(json.dumps({"sweep": "attention",
                          "note": "flash_pallas skipped (needs TPU)"}),
              flush=True)


def main():
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse_args()
    if args.window is not None and args.window < 1:
        sys.exit("--window must be >= 1")
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.device_count)
    import jax

    results = []
    if not args.skip_batch:
        batch_sweep(args, results)
    if not args.skip_attention:
        attention_sweep(args, results)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       args.out)
    with open(out, "w") as f:
        json.dump({"ts": time.time(), "platform": jax.devices()[0].platform,
                   "results": results}, f, indent=2)
    print(f"wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
