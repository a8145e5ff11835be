#!/usr/bin/env python
"""LM generation CLI: restore a train_lm.py checkpoint and decode.

Single-host decoding routes through the serving engine (serve/ — the
continuous-batching paged-KV path, here in its one-request degenerate
case): the prompt prefills in fixed-size chunks against the paged cache,
so ANY prompt length runs the same two compiled programs and repeated CLI
calls hit jax's compile cache instead of re-jitting per prompt length
(the pre-engine CLI re-traced the whole decode for every distinct
prompt/gen shape). Greedy, temperature, top-k and nucleus (top-p)
sampling; sampled streams are per-request (seeded) and differ from the
pre-engine CLI's batch-keyed draws. Sharded decoding (--dp/--tp > 1) and
MoE checkpoints stay on models/transformer.generate — the engine is
replicated and rejects batch-coupled MoE routing.

Model-shape flags must match the training run; the checkpoint is read
from --checkpoint-dir (falling back to randomly initialized weights,
clearly announced, so the decode path can be exercised without a
training run).

Example:
  python scripts/train_lm.py --layers 2 --d-model 64 --steps 50
  python scripts/generate.py --layers 2 --d-model 64 \
      --prompt 5,17,42 --gen-steps 32 --temperature 0.8 --top-p 0.9
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from scripts._cpu_devices import force_cpu_devices

force_cpu_devices(("--dp", "--tp"))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dp", type=int, default=1,
                   help="batch-shard decoding over this many devices")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel decoding: heads (and the KV "
                        "cache) split over this many devices, the "
                        "training layout — no gather-to-one-device")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill the prompt in N-token slices against the "
                        "growing KV cache (peak attention memory O(N*T) "
                        "instead of O(T0^2) — the long-prompt lever). On "
                        "the engine path this is the compiled chunk size "
                        "(default 32): prompts pad to a chunk multiple, so "
                        "every prompt length reuses one program")
    p.add_argument("--page-size", type=int, default=16,
                   help="KV-cache page size (tokens) on the engine path")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--max-seq-len", type=int, default=128)
    p.add_argument("--rope", action="store_true",
                   help="rotary positions; must match the training run")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="grouped-query k/v heads; must match the training "
                        "run")
    p.add_argument("--attn-window", type=int, default=None,
                   help="sliding-window width; must match the training run")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="experts per block; must match the training run")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="routing fan-out; must match the training run "
                        "(shapes restore either way, but a mismatched k "
                        "routes differently than the trained model)")
    p.add_argument("--checkpoint-dir", default="./checkpoint")
    p.add_argument("--pp-stages", type=int, default=1,
                   help="stage count of the TRAINING run — only needed to "
                        "de-interleave a checkpoint trained with "
                        "--virtual-stages > 1 (decode itself runs "
                        "layer-stacked)")
    p.add_argument("--prompt", default="1,2,3",
                   help="comma-separated token ids (the LM trains on a "
                        "synthetic integer stream; there is no text "
                        "tokenizer)")
    p.add_argument("--gen-steps", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy argmax decoding")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    if args.moe_experts and not (1 <= args.moe_top_k <= args.moe_experts):
        raise SystemExit(
            f"--moe-top-k must be in [1, --moe-experts={args.moe_experts}]")
    if args.prefill_chunk is not None and args.prefill_chunk < 1:
        raise SystemExit(f"--prefill-chunk must be >= 1, got "
                         f"{args.prefill_chunk}")
    if args.page_size < 1:
        raise SystemExit(f"--page-size must be >= 1, got {args.page_size}")
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.train.checkpoint import Checkpointer
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributed_model_parallel_tpu.utils.device_contact import (
        require_devices,
    )

    enable_compile_cache()
    require_devices("generate")
    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, d_ff=args.d_ff,
        max_seq_len=max(args.max_seq_len, 128),
        moe_experts=args.moe_experts, moe_top_k=args.moe_top_k,
        tp_axis="model" if args.tp > 1 else None,
        pos_embedding="rope" if args.rope else "learned",
        n_kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        attn_impl="flash" if args.attn_window is not None else "auto")
    params = tfm.init_params(jax.random.key(args.seed), cfg)

    ckpt = Checkpointer(args.checkpoint_dir)
    if ckpt.exists("lm"):
        # Restore only the params subtree of the LM checkpoint; shape flags
        # must match the training run.
        try:
            restored = ckpt.restore_subtree({"params": params}, "lm")
        except ValueError as e:
            raise SystemExit(
                f"checkpoint under {args.checkpoint_dir} does not match the "
                f"model flags (--layers/--d-model/... must equal the "
                f"training run's): {e}") from e
        params = restored["params"]
        # Orbax partial restore leaves abstract placeholders for target
        # leaves the checkpoint lacks (e.g. --kv-heads against a fused-
        # wqkv checkpoint) — catch that here instead of deep in jit.
        if any(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(params)):
            raise SystemExit(
                f"checkpoint under {args.checkpoint_dir} does not match "
                f"the model flags (e.g. --kv-heads/--moe-experts change "
                f"the parameter tree); flags must equal the training "
                f"run's")
        # A 1f1b run with interleaved virtual stages checkpoints its block
        # rows in interleaved storage order (marker saved alongside) —
        # composing them in row order here would run a layer-permuted
        # model that generates garbage with no error. Convert back.
        try:
            v_marker = ckpt.restore_subtree(
                {"virtual_stages": jnp.zeros((), jnp.int32)}, "lm")
            ckpt_v = int(v_marker["virtual_stages"])
        except Exception:
            ckpt_v = 1                 # pre-marker checkpoint: always V=1
        if ckpt_v > 1:
            from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
                deinterleave_block_rows,
            )

            if args.pp_stages < 2:
                raise SystemExit(
                    f"checkpoint was trained with virtual_stages={ckpt_v}; "
                    f"pass --pp-stages equal to the training stage count "
                    f"so the block rows can be de-interleaved")
            params["blocks"] = deinterleave_block_rows(
                params["blocks"], cfg.n_layers, args.pp_stages, ckpt_v)
            print(f"de-interleaved blocks (virtual_stages={ckpt_v}, "
                  f"S={args.pp_stages})", file=sys.stderr)
        print(f"restored LM checkpoint from {args.checkpoint_dir}",
              file=sys.stderr)
    else:
        print(f"no LM checkpoint under {args.checkpoint_dir}; using random "
              f"init (run scripts/train_lm.py first for a trained model)",
              file=sys.stderr)

    prompt_ids = [int(x) for x in args.prompt.split(",")]
    bad = [t for t in prompt_ids if not (0 <= t < cfg.vocab_size)]
    if bad:
        raise SystemExit(f"prompt tokens {bad} outside vocab [0, "
                         f"{cfg.vocab_size})")
    prompt = jnp.asarray([prompt_ids], jnp.int32)
    if args.dp > 1 or args.tp > 1:
        from distributed_model_parallel_tpu.config import MeshConfig
        from distributed_model_parallel_tpu.mesh import make_mesh

        if args.dp > 1:
            prompt = jnp.tile(prompt, (args.dp, 1))  # one row per replica
        spec = make_mesh(MeshConfig(data=args.dp, model=args.tp))
        out = tfm.generate_sharded(
            params, cfg, prompt, args.gen_steps, spec,
            rng=jax.random.key(args.seed + 1),
            temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p,
            prefill_chunk=args.prefill_chunk)
        tokens = [int(t) for t in out[0]]
    elif args.moe_experts:
        # MoE routing is batch-coupled (capacity drops depend on
        # co-resident tokens) — the engine rejects it; the single-batch
        # generate path stays correct for one request.
        print("MoE checkpoint: decoding via models.transformer.generate "
              "(the serving engine rejects batch-coupled MoE routing)",
              file=sys.stderr)
        out = tfm.generate(params, cfg, prompt, args.gen_steps,
                           rng=jax.random.key(args.seed + 1),
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p,
                           prefill_chunk=args.prefill_chunk)
        tokens = [int(t) for t in out[0]]
    else:
        # Engine path (single-request degenerate case of continuous
        # batching): fixed prefill chunk + fixed decode program, so any
        # prompt length — and any later CLI call against the same model
        # shape — reuses the same two compiled programs.
        from distributed_model_parallel_tpu.serve import (
            Engine,
            ServeConfig,
        )

        chunk = args.prefill_chunk if args.prefill_chunk else 32
        serve = ServeConfig(
            n_slots=1, page_size=args.page_size,
            n_pages=-(-cfg.max_seq_len // args.page_size) + 1,
            max_seq_len=cfg.max_seq_len,
            prefill_chunk=min(chunk, cfg.max_seq_len),
            temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p)
        engine = Engine(params, cfg, serve)
        print(f"engine decode: paged KV (page={serve.page_size}, "
              f"pool={serve.n_pages} pages), prefill chunk "
              f"{serve.prefill_chunk} — prompt lengths bucket to one "
              f"compiled program", file=sys.stderr)
        req = engine.submit(prompt_ids, args.gen_steps,
                            seed=args.seed + 1)
        engine.run()
        if req.error:
            raise SystemExit(f"engine failed: {req.error}")
        tokens = prompt_ids + req.generated
    print(",".join(str(t) for t in tokens))


if __name__ == "__main__":
    main()
