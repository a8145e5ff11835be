"""Collective/communication layer.

TPU-native replacements for the native communication machinery the reference
consumes (SURVEY.md §2.2/§2.4):

* ``dist.send``/``dist.recv`` P2P with a 3-message dynamic-shape wire protocol
  (reference ``distributed_layers.py:11-13,20-24,42-45,52,58-60``) →
  ``ppermute_shift``: shapes are static under ``jit`` so the shape negotiation
  disappears; a stage-to-stage transfer is one collective-permute over ICI.
* the DDP ``Reducer``'s bucketed NCCL ring-allreduce fired from autograd hooks
  (reference ``Readme.md:14,148-157``) → ``psum_mean`` (XLA schedules
  overlap with the backward) and ``bucketed_psum`` (explicit flat-bucket
  allreduce — fewer, larger collectives, the Reducer's actual trick).
* ``comm.scatter``/``broadcast_coalesced``/``comm.gather`` used by
  DataParallel (``Readme.md:20,28-30,49-56,109-143``) → sharding-based
  ``scatter``/``replicate``/``gather`` in ``parallel/data_parallel.py``.

All functions taking ``axis_name`` must be called inside ``shard_map`` (or
another named-axis context) over that axis.

Every wrapper accounts its communication volume into the telemetry
registry (``utils/telemetry.record_collective``) **at trace time** — once
per compilation, tagged by kind and mesh axis, with per-device wire bytes
AND per-device message counts under the ring cost model
(``wire_bytes_estimate`` / ``wire_ops_estimate`` — the beta and alpha
terms of an alpha-beta comm model; the parallelism autotuner's cost model
is built on the same two estimators, so its analytic schedule and this
trace-time accounting are one currency, autotune/cost_model.py).
``scripts/dmp_report.py`` renders the totals; see the telemetry module
docstring for the per-compile (not per-step) semantics.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.utils.telemetry import record_collective


def _tree_bytes(tree: Any) -> int:
    """Static payload size of a pytree (works on tracers: shape/dtype only)."""
    return sum(l.size * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def flatten_padded(tree: Any, n_shards: int, dtype=jnp.float32) -> jax.Array:
    """Concatenate all leaves (cast to ``dtype``, f32 by default) into one
    flat vector padded to a multiple of ``n_shards`` — the canonical
    pre-shape for contiguous scatter/gather collectives. Shared by the ZeRO
    optimizer sharding (parallel/zero.py, which wants the f32 master copy)
    and the hierarchical allreduce below (which passes the native gradient
    dtype so the wire payload matches the per-leaf transports)."""
    flat = jnp.concatenate(
        [l.astype(dtype).reshape(-1) for l in jax.tree.leaves(tree)])
    pad = (-flat.size) % n_shards
    return jnp.pad(flat, (0, pad))


def unflatten_like(flat: jax.Array, tree: Any) -> Any:
    """Inverse of ``flatten_padded`` (drops padding, restores dtypes)."""
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def psum_mean(tree: Any, axis_name: str) -> Any:
    """Gradient averaging over the data axis — DDP's allreduce-mean."""
    n = jax.lax.psum(1, axis_name)
    record_collective("psum", axis_name, _tree_bytes(tree), n)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_name) / n, tree)


def ppermute_shift(x: jax.Array, axis_name: str, *, shift: int = 1) -> jax.Array:
    """Rotate values around a mesh axis ring: src i -> dst (i+shift) % n.

    The TPU-native equivalent of the reference's rank-to-rank activation
    send/recv (``distributed_layers.py:7-62``); on hardware this rides the ICI
    ring neighbor links.
    """
    n = jax.lax.axis_size(axis_name)
    record_collective("ppermute", axis_name, _tree_bytes(x), n)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def all_gather_concat(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """Gather shards along ``axis`` (DataParallel's output ``gather``)."""
    n = jax.lax.axis_size(axis_name)
    record_collective("all_gather", axis_name, _tree_bytes(x) * n, n)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def reduce_scatter_mean(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """psum_scatter-mean: each shard gets one slice of the reduced result —
    the building block of ZeRO-style sharded optimizers and of halving
    allreduce traffic when parameters are sharded."""
    n = jax.lax.axis_size(axis_name)
    record_collective("reduce_scatter", axis_name, _tree_bytes(x), n)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True) / n


# ----------------------------------------------------------------------------
# Bucketed allreduce: the DDP Reducer capability (reference Readme.md:148-157).
# ----------------------------------------------------------------------------

def plan_buckets(tree: Any, bucket_bytes: int = 25 * 1024 * 1024
                 ) -> list[list[int]]:
    """Group flattened leaf indices into size-capped buckets, in reverse leaf
    order (the Reducer fills buckets in (roughly) reverse parameter order so
    early buckets become ready first during backward)."""
    leaves = jax.tree.leaves(tree)
    buckets: list[list[int]] = [[]]
    used = 0
    for idx in reversed(range(len(leaves))):
        nbytes = leaves[idx].size * np.dtype(leaves[idx].dtype).itemsize
        if buckets[-1] and used + nbytes > bucket_bytes:
            buckets.append([])
            used = 0
        buckets[-1].append(idx)
        used += nbytes
    return buckets


def bucketed_psum(tree: Any, axis_name: str, *,
                  bucket_bytes: int = 25 * 1024 * 1024,
                  mean: bool = True, reduce_fn: Any = None,
                  accum_dtype: Any = None) -> Any:
    """Allreduce a gradient pytree in flat coalesced buckets.

    Each bucket is flattened+concatenated into one vector, reduced with a
    single ``psum``, then split back — mirroring
    ``_broadcast_coalesced``/Reducer bucketing (``Readme.md:49-56,148-157``)
    with XLA free to overlap bucket collectives with compute.

    ``reduce_fn(flat, axis_name) -> flat`` swaps the transport (default
    ``lax.psum``; see ``ops/ring_reduce.ring_psum_tree`` for the explicit
    ring).

    Reduction dtype: by default each bucket is flattened in its own
    *promoted leaf dtype* (bf16 gradients reduce as bf16, like torch DDP; a
    stray f32 leaf upcasts only its own bucket) so the wire payload matches
    the per-leaf ``psum`` transport byte-for-byte. Note the conflation this
    implies: the accumulation across replicas then also happens at bf16
    precision, and the error grows with replica count. ``accum_dtype=
    jnp.float32`` decouples them — reduce (and mean-divide) in f32,
    downcast to the leaf dtype after — at the cost of a 2x wire payload
    for bf16 buckets (the XLA collective carries the accumulation dtype);
    the same trade torch DDP exposes via fp32-reduce comm hooks.
    """
    if reduce_fn is None:
        reduce_fn = jax.lax.psum
    leaves, treedef = jax.tree.flatten(tree)
    n = jax.lax.psum(1, axis_name) if mean else 1
    n_axis = jax.lax.axis_size(axis_name)
    out: list[Any] = [None] * len(leaves)
    for bucket in plan_buckets(tree, bucket_bytes):
        wire_dtype = (jnp.dtype(accum_dtype) if accum_dtype is not None
                      else jnp.result_type(*(leaves[i] for i in bucket)))
        flat = jnp.concatenate(
            [leaves[i].astype(wire_dtype).reshape(-1) for i in bucket])
        record_collective("bucketed_psum", axis_name,
                          flat.size * wire_dtype.itemsize, n_axis)
        red = reduce_fn(flat, axis_name)
        if mean:
            red = red / n
        offset = 0
        for i in bucket:
            size = leaves[i].size
            out[i] = red[offset:offset + size].reshape(
                leaves[i].shape).astype(leaves[i].dtype)
            offset += size
    return jax.tree.unflatten(treedef, out)


def hierarchical_psum(x: jax.Array, inner_axis: str, outer_axis: str, *,
                      mean: bool = False) -> jax.Array:
    """Two-level allreduce: reduce-scatter over ``inner_axis`` (ICI), psum
    over ``outer_axis`` (DCN), all-gather back over ``inner_axis``.

    Semantically equal to ``psum(x, (inner, outer))``; the staging is the
    bandwidth play for multi-host meshes — each host moves only 1/|inner| of
    the payload across the slow DCN hop, with the fast ICI links doing the
    full-size scatter/gather. (The same trick as NCCL's hierarchical rings,
    which is what DDP's Reducer rides on multi-node GPU clusters,
    ``Readme.md:148-157``.) Requires ``x``'s leading dim divisible by
    |inner|; use ``hierarchical_psum_tree`` for arbitrary pytrees.
    """
    n_in = jax.lax.axis_size(inner_axis)
    n_out = jax.lax.axis_size(outer_axis)
    record_collective("reduce_scatter", inner_axis, _tree_bytes(x), n_in)
    shard = jax.lax.psum_scatter(x, inner_axis, scatter_dimension=0,
                                 tiled=True)
    record_collective("psum", outer_axis, _tree_bytes(shard), n_out)
    shard = jax.lax.psum(shard, outer_axis)
    record_collective("all_gather", inner_axis, _tree_bytes(x), n_in)
    out = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    if mean:
        out = out / (jax.lax.psum(1, inner_axis) * jax.lax.psum(1, outer_axis))
    return out


def hierarchical_psum_tree(tree: Any, inner_axis: str, outer_axis: str, *,
                           mean: bool = False) -> Any:
    """Hierarchical allreduce of a gradient pytree: flatten + pad to one
    vector (so the scatter is contiguous and every leaf shape is legal),
    two-level reduce, split back. Like ``hierarchical_psum`` (and
    ``lax.psum``) this sums by default; pass ``mean=True`` for DDP-style
    gradient averaging. The flat vector uses the promoted leaf dtype, not
    f32 — same wire-payload rule as ``bucketed_psum``."""
    flat = flatten_padded(tree, jax.lax.axis_size(inner_axis),
                          dtype=jnp.result_type(*jax.tree.leaves(tree)))
    red = hierarchical_psum(flat, inner_axis, outer_axis, mean=mean)
    return unflatten_like(red, tree)


_BARRIER_CACHE: dict = {}


def mesh_barrier(spec: Any) -> float:
    """Device-level rendezvous over EVERY axis of ``spec.mesh``: a
    scalar psum that cannot complete until all devices (and, on a
    multi-process mesh, all hosts) participate — then blocks until done.

    The building block the consistency sentinel's pre-check barrier uses
    on multiprocess runs: wrapped in ``mesh.barrier_with_timeout`` it
    turns a wedged or missing host into a reported straggler instead of
    an eternal hang in the first cross-host collective
    (train/consistency.py). Returns the world size (= psum of 1), which
    doubles as a cheap sanity check.
    """
    import jax

    mesh = spec.mesh
    # pop + reinsert keeps insertion order = recency, so the bound below
    # evicts the LEAST-recently-used entry, never a hot mesh's barrier.
    fn = _BARRIER_CACHE.pop(mesh, None)
    if fn is None:
        names = tuple(mesh.axis_names)
        n = int(np.prod(mesh.devices.shape))
        record_collective("psum", names, 4, n)

        def body():
            return jax.lax.psum(jnp.ones((), jnp.float32), names)

        from jax.sharding import PartitionSpec as P

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(), out_specs=P(), check_vma=False))
    _BARRIER_CACHE[mesh] = fn
    if len(_BARRIER_CACHE) > 8:              # bound the compiled-fn cache
        _BARRIER_CACHE.pop(next(iter(_BARRIER_CACHE)))
    out = fn()
    out.block_until_ready()
    return float(out)


def unused_param_mask(grads: Any) -> Any:
    """Per-leaf boolean: True where a gradient is identically zero.

    The capability analog of DDP's ``find_unused_parameters``
    (``Readme.md:153-157``): JAX autodiff already produces zero gradients for
    parameters not on the loss path (no hang to avoid — there are no autograd
    hooks waiting), so "detection" reduces to reporting which leaves were
    untouched, useful for debugging partially-frozen models.

    Caveat: this is a *value* test, not a graph-reachability test — a
    parameter that IS on the loss path but happens to receive an exactly-zero
    gradient at this step (e.g. behind a relu that is off for the whole
    batch) is also flagged. Treat a True as "no gradient signal this step";
    for a structural unused-parameter check, inspect the jaxpr of the loss
    instead (a leaf is structurally unused iff the grad jaxpr pipes a
    symbolic zero to it, which this debugging aid deliberately does not
    compute — it would force a retrace per call).
    """
    return jax.tree.map(lambda g: jnp.all(g == 0), grads)
