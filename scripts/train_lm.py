#!/usr/bin/env python
"""Transformer LM training driver over a dp x pp x tp x sp mesh.

Example (8 virtual CPU devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python scripts/train_lm.py --dp 2 --pp 2 --tp 2 --layers 4 --steps 20
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from scripts._cpu_devices import force_cpu_devices

force_cpu_devices(("--dp", "--pp", "--tp", "--sp", "--ep"))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel ways (shards --moe-experts)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="experts per block (0 = dense FFN)")
    p.add_argument("--moe-top-k", type=int, default=2)
    p.add_argument("--moe-z-weight", type=float, default=0.0,
                   help="router z-loss weight (ST-MoE logit-drift "
                        "regularizer; 0 = off)")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of a learned "
                        "table (relative positions; extrapolates)")
    p.add_argument("--attn-window", type=int, default=None,
                   help="sliding-window attention width (flash kernels, "
                        "O(T*W) compute); incompatible with --sp")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="grouped-query attention: k/v head count (must "
                        "divide --heads; 1 = multi-query). Shrinks the "
                        "decode KV cache by heads/kv-heads")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (activation recompute — "
                        "the long-context memory lever)")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots"],
                   help="with --remat: 'dots' saves matmul outputs and "
                        "recomputes only elementwise ops (less recompute, "
                        "slightly more HBM)")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help="chunked cross-entropy head: compute logits in "
                        "N-token slices so [B, T, vocab] never "
                        "materializes — the head-side long-context memory "
                        "lever (0 = dense head)")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--schedule", default="gpipe", choices=["gpipe", "1f1b"],
                   help="SPMD pipeline schedule: gpipe holds all "
                        "microbatches' activations through the backward; "
                        "1f1b interleaves forward/backward so peak "
                        "activation memory is bounded by the stage count "
                        "(benchmarks/pipeline_memory.json)")
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="Megatron interleaved virtual stages for the 1f1b "
                        "schedule (device s owns V model chunks; bubble "
                        "shrinks ~V-fold; microbatches must divide by the "
                        "stage count)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--emergency-every", default=0, type=int, metavar="N",
                   help="elastic resume: write the emergency checkpoint "
                        "slot (exact mid-epoch resume state) every N steps "
                        "(0 = only the preemption save; train/elastic.py)")
    p.add_argument("--elastic", action="store_true",
                   help="on startup, shrink the data axis to the largest "
                        "degree the live device count and batch size allow "
                        "and reshard the resumed checkpoint onto the "
                        "rebuilt mesh")
    p.add_argument("--check-finite-every", default=0, type=int,
                   help="check loss every step and params every N steps "
                        "for NaN/Inf (0 = off)")
    p.add_argument("--consistency-every", default=0, type=int, metavar="N",
                   help="cross-replica consistency sentinel: every N steps "
                        "fingerprint params+opt state on device, compare "
                        "across the dp axis, and repair a minority-bad "
                        "replica by re-broadcast (0 = off; "
                        "train/consistency.py)")
    p.add_argument("--barrier-timeout", default=None, type=float,
                   metavar="S",
                   help="hard bound (seconds) on each consistency check's "
                        "blocking ops — the multi-host rendezvous AND the "
                        "fingerprint fetch (any run) — so a wedged/missing "
                        "participant is reported as a straggler instead of "
                        "hanging")
    p.add_argument("--recovery-retries", default=0, type=int,
                   help="restore the last good checkpoint and retry the "
                        "epoch on non-finite detections, up to N times")
    p.add_argument("--recovery-lr-shrink", default=1.0, type=float,
                   help="multiply the LR by this factor on every recovery")
    p.add_argument("--inject-faults", default=None, metavar="PLAN",
                   help="deterministic chaos plan, e.g. 'nan_loss@3' "
                        "(utils/faults.py)")
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
    require_devices("train-lm")
    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        OptimizerConfig,
        RecoveryConfig,
    )
    from distributed_model_parallel_tpu.utils.faults import parse_faults
    from distributed_model_parallel_tpu.models.transformer import TransformerConfig
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    if args.layers % max(args.pp, 1):
        raise SystemExit("--layers must be divisible by --pp")
    if args.ep > 1 and args.moe_experts % args.ep:
        raise SystemExit("--moe-experts must be divisible by --ep")
    if args.moe_experts and not (1 <= args.moe_top_k <= args.moe_experts):
        raise SystemExit(
            f"--moe-top-k must be in [1, --moe-experts={args.moe_experts}]")
    if args.attn_window is not None and args.attn_window < 1:
        raise SystemExit("--attn-window must be >= 1")
    config = LMTrainConfig(
        model=TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.heads,
            n_layers=args.layers, d_ff=args.d_ff,
            max_seq_len=max(args.seq_len, 128),
            tp_axis="model" if args.tp > 1 else None,
            sp_axis="seq" if args.sp > 1 else None,
            moe_experts=args.moe_experts, moe_top_k=args.moe_top_k,
            moe_z_weight=args.moe_z_weight,
            ep_axis="expert" if args.ep > 1 else None,
            pos_embedding="rope" if args.rope else "learned",
            n_kv_heads=args.kv_heads,
            attn_window=args.attn_window,
            remat=args.remat, remat_policy=args.remat_policy,
            loss_chunk=args.loss_chunk,
            attn_impl="flash" if args.attn_window is not None else "auto"),
        mesh=MeshConfig(data=args.dp, stage=args.pp, model=args.tp,
                        seq=args.sp, expert=args.ep),
        optimizer=OptimizerConfig(learning_rate=args.lr, weight_decay=0.0,
                                  warmup_steps=10),
        batch_size=args.batch_size, seq_len=args.seq_len,
        num_microbatches=args.microbatches,
        pipeline_schedule=args.schedule,
        virtual_stages=args.virtual_stages,
        steps_per_epoch=args.steps, epochs=args.epochs, resume=args.resume,
        emergency_every=args.emergency_every, elastic=args.elastic,
        check_finite_every=args.check_finite_every,
        consistency_every=args.consistency_every,
        recovery=RecoveryConfig(
            max_retries=args.recovery_retries,
            lr_shrink=args.recovery_lr_shrink,
            barrier_timeout_s=args.barrier_timeout,
            faults=parse_faults(args.inject_faults) if args.inject_faults
            else ()),
    )
    LMTrainer(config).fit()


if __name__ == "__main__":
    main()
