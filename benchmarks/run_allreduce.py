#!/usr/bin/env python
"""Gradient-allreduce microbenchmark (the BASELINE.json µs metric).

Times one full gradient-tree allreduce — the DDP Reducer's work item
(reference ``Readme.md:148-157``) — for a real model's gradient shapes
across every transport this framework offers: per-leaf ``psum``, flat
bucketed coalesced psum, the explicit bandwidth-optimal neighbor ring, and
(on two-level meshes) hierarchical ICI/DCN staging.

Writes one JSON line per (transport, dtype) to stdout and
``benchmarks/allreduce.json``.

Hardware honesty: with one real TPU chip an allreduce is a self-copy, so
absolute ICI µs cannot be measured in this environment; run with
``--platform cpu --device-count 8`` for *relative* transport comparison and
on a real multi-chip slice for absolute numbers. Timing uses the forced-sync
fetch harness (``utils/profiling.py``) like every published number here.
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
    p.add_argument("--device-count", type=int, default=8)
    p.add_argument("--dcn-data", type=int, default=1,
                   help=">1 adds the hierarchical transport to the sweep")
    p.add_argument("--model", default="resnet50",
                   help="gradient shapes come from this model's params")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--bucket-mb", type=int, default=25)
    return p.parse_args()


def main():
    from distributed_model_parallel_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    args = parse_args()
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.device_count)
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_model_parallel_tpu.config import MeshConfig, ModelConfig
    from distributed_model_parallel_tpu.mesh import make_mesh
    from distributed_model_parallel_tpu.models import get_model
    from distributed_model_parallel_tpu.ops.collectives import (
        bucketed_psum,
        hierarchical_psum_tree,
        psum_mean,
    )
    from distributed_model_parallel_tpu.ops.ring_reduce import ring_psum_tree
    from distributed_model_parallel_tpu.utils.profiling import fetch, fetch_overhead

    n = len(jax.devices())
    spec = make_mesh(MeshConfig(data=n, dcn_data=args.dcn_data))
    axis = spec.data_axis

    model = get_model(ModelConfig(name=args.model))
    params, _ = model.init(jax.random.key(0),
                           jnp.zeros((2, 32, 32, 3), jnp.float32))
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    grads = jax.tree.map(
        lambda x: jnp.asarray(jax.random.normal(jax.random.key(1), x.shape),
                              dtype), params)
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(grads))

    transports = {
        "psum": lambda g: psum_mean(g, axis),
        "bucketed": lambda g: bucketed_psum(
            g, axis, bucket_bytes=args.bucket_mb * 1024 * 1024),
    }
    if spec.dcn_axis is None:
        # Same bucket size as the bucketed transport — the ring is also a
        # bucketed algorithm, and comparing transports at different bucket
        # sizes would confound the sweep.
        transports["ring"] = lambda g: ring_psum_tree(
            g, axis, bucket_bytes=args.bucket_mb * 1024 * 1024)
    else:
        transports["hierarchical"] = lambda g: hierarchical_psum_tree(
            g, spec.ici_data_axis, spec.dcn_axis, mean=True)

    t_fetch = fetch_overhead()
    results = []
    for name, fn in transports.items():
        reduced = jax.jit(jax.shard_map(
            fn, mesh=spec.mesh, in_specs=P(), out_specs=P(),
            check_vma=False))
        out = reduced(grads)                   # compile
        fetch(jax.tree.leaves(out)[0])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = reduced(grads)
        fetch(jax.tree.leaves(out)[0])
        dt = max(1e-9, time.perf_counter() - t0 - t_fetch) / args.iters
        row = {"transport": name, "model": args.model, "dtype": args.dtype,
               # All transports now flatten/reduce in the gradient's native
               # dtype (collectives.py), so payload bytes are equal across
               # rows — no upcast confound.
               "wire_dtype": args.dtype,
               "devices": n, "dcn_data": args.dcn_data,
               "grad_bytes": nbytes, "allreduce_us": round(dt * 1e6, 1),
               "platform": jax.devices()[0].platform}
        if row["platform"] == "cpu":
            row["caveat"] = (
                "virtual CPU mesh: collectives are shared-memory copies; "
                "rows rank transports relatively, they are NOT ICI timings "
                "or transport guidance for TPU hardware")
        print(json.dumps(row), flush=True)
        results.append(row)

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "allreduce.json")
    with open(out_path, "w") as f:
        json.dump({"ts": time.time(), "results": results}, f, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
