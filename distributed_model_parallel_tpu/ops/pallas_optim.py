"""Fused SGD optimizer update as a single Pallas TPU kernel.

The optax path (train/optim.py) lowers the reference recipe —
``add_decayed_weights`` → momentum ``trace`` → ``scale_by_learning_rate``
— to a chain of per-leaf elementwise HLO ops: for a CNN with ~160
parameter leaves that is ~500 tiny kernels per step, each reading and
writing its operands through HBM. The CNN step is bandwidth-bound
(benchmarks/step_profile_r5.json), so every avoided HBM round trip is
wall time.

This module fuses the whole update into ONE elementwise Pallas kernel per
flat parameter bucket (``ops/collectives.plan_buckets`` — the same
reverse-leaf-order size-capped coalescing the DDP Reducer uses for its
allreduce): params, momentum and gradients stream through VMEM in
(rows, 128)-lane blocks, the VPU applies

    g'     = g + weight_decay * p
    m'     = momentum * m + g'
    delta  = -lr * (g' + momentum * m')   (nesterov)
           | -lr * m'                     (classic)

and each value makes exactly one HBM round trip. The momentum buffer
aliases its output (``input_output_aliases``) so it updates in place.

Exposed as an ``optax.GradientTransformation`` (``fused_sgd``) so it
drops into every trainer through ``make_optimizer`` — selectable via
``OptimizerConfig(fused=True)``. The LR schedule stays a host closure
over the on-device step count, so recovery-time lr_shrink rebuilds
(train/resilience.py) keep the opt_state structure, exactly like the
optax path. Off-TPU the same bucket math runs as pure XLA (fallback) —
and the kernel itself runs under the pallas interpreter for CPU parity
tests, the ``ops/pallas_attention.py`` idiom.

Parity: bit-identical to the optax chain for float32 trees on the
fallback path, and elementwise-equal within float32 rounding on the
kernel path (tests/test_pallas_optim.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_model_parallel_tpu.ops.collectives import plan_buckets

_LANES = 128            # TPU lane width: flat buckets reshape to (rows, 128)
_BLOCK_ROWS = 512       # rows per grid step: 512*128*4B = 256 KiB per operand


class FusedSGDState(NamedTuple):
    """Optimizer state: applied-update count (drives the LR schedule,
    like optax's ScaleByScheduleState) + the momentum buffer (params-like
    f32, like optax's TraceState; ``None`` when momentum is 0.0 — plain
    SGD carries no trace, matching the optax path's memory footprint)."""

    count: jnp.ndarray
    momentum: Any


def _fused_sgd_kernel(lr_ref, p_ref, m_ref, g_ref, d_ref, om_ref, *,
                      momentum: float, weight_decay: float, nesterov: bool):
    """One (BLOCK_ROWS, LANES) f32 tile of the fused update (momentum
    variant). Outputs: the update delta (added to params by
    ``optax.apply_updates``) and the new momentum (aliased over the old
    one, so it never leaves HBM twice)."""
    lr = lr_ref[0]
    g = g_ref[...]
    if weight_decay:
        g = g + weight_decay * p_ref[...]
    m = momentum * m_ref[...] + g
    om_ref[...] = m
    d = g + momentum * m if nesterov else m
    d_ref[...] = -lr * d


def _plain_sgd_kernel(lr_ref, p_ref, g_ref, d_ref, *,
                      weight_decay: float):
    """Momentum-free tile: no trace buffer exists at all (plain SGD
    carries no state beyond the count, like optax)."""
    g = g_ref[...]
    if weight_decay:
        g = g + weight_decay * p_ref[...]
    d_ref[...] = -lr_ref[0] * g


def _run_kernel(lr, p_flat, m_flat, g_flat, *, momentum, weight_decay,
                nesterov, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p_flat.size
    # Pad the flat bucket so it reshapes to (rows, 128) with rows an exact
    # multiple of the block height (itself a multiple of the 8-sublane f32
    # tile) — no ragged last grid step.
    rows0 = -(-n // _LANES)
    block_rows = min(_BLOCK_ROWS, -(-rows0 // 8) * 8)
    rows = -(-rows0 // block_rows) * block_rows
    pad = rows * _LANES - n
    shape2d = (rows, _LANES)
    grid = (rows // block_rows,)

    def pad2d(x):
        return jnp.pad(x, (0, pad)).reshape(shape2d)

    block = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    out2d = jax.ShapeDtypeStruct(shape2d, jnp.float32)
    lr_arr = jnp.asarray([lr], jnp.float32)
    if m_flat is None:
        delta = pl.pallas_call(
            partial(_plain_sgd_kernel, weight_decay=weight_decay),
            grid=grid,
            in_specs=[scalar, block, block],
            out_specs=block,
            out_shape=out2d,
            # the gradient buffer (dead after this kernel) aliases the
            # delta output.
            input_output_aliases={2: 0},
            interpret=interpret,
        )(lr_arr, pad2d(p_flat), pad2d(g_flat))
        return delta.reshape(-1)[:n], None
    out = pl.pallas_call(
        partial(_fused_sgd_kernel, momentum=momentum,
                weight_decay=weight_decay, nesterov=nesterov),
        grid=grid,
        in_specs=[scalar, block, block, block],
        out_specs=[block, block],
        out_shape=[out2d, out2d],
        # momentum-in aliases momentum-out; the gradient buffer (dead
        # after this kernel) aliases the delta.
        input_output_aliases={3: 0, 2: 1},
        interpret=interpret,
    )(lr_arr, pad2d(p_flat), pad2d(m_flat), pad2d(g_flat))
    delta, new_m = (x.reshape(-1)[:n] for x in out)
    return delta, new_m


def _run_xla(lr, p_flat, m_flat, g_flat, *, momentum, weight_decay,
             nesterov):
    """Pure-XLA fallback: the same flat-bucket math, same operation order
    as the kernel (and as the optax chain — bitwise parity on f32).
    ``m_flat`` is None iff momentum is 0.0 (no trace state)."""
    g = g_flat + weight_decay * p_flat if weight_decay else g_flat
    if m_flat is None:
        return -lr * g, None
    m = momentum * m_flat + g
    d = g + momentum * m if nesterov else m
    return -lr * d, m


def fused_sgd(learning_rate: Union[float, Callable], *,
              momentum: float = 0.0, weight_decay: float = 0.0,
              nesterov: bool = False,
              bucket_bytes: int = 64 * 1024 * 1024,
              use_pallas: bool | None = None
              ) -> optax.GradientTransformation:
    """SGD + momentum + weight decay + LR scaling as one fused kernel over
    flat parameter buckets — the drop-in equivalent of
    ``optax.chain(add_decayed_weights(wd), sgd(lr, momentum, nesterov))``.

    ``learning_rate`` may be a float or a schedule (called with the
    applied-update count, like optax). ``use_pallas``: None = auto (the
    kernel on TPU, the pure-XLA flat-bucket fallback elsewhere); True
    forces the kernel (interpret mode off-TPU — slow, for parity tests);
    False forces the fallback. Buckets are ``plan_buckets`` groups, so
    the coalescing matches the DDP bucketed allreduce's layout.

    Non-f32 leaves are updated in f32 and cast back to the leaf dtype on
    write-out (the f32-master-weights convention); the momentum buffer is
    always f32.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    interpret = jax.default_backend() != "tpu"

    has_momentum = bool(momentum)

    def init_fn(params):
        return FusedSGDState(
            count=jnp.zeros((), jnp.int32),
            # Plain SGD carries no trace — don't allocate (and round-trip
            # through HBM) a params-sized buffer that is always zero.
            momentum=(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
                if has_momentum else None))

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("fused_sgd needs params (weight decay + the "
                             "fused write-back read them)")
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        lr = jnp.asarray(lr, jnp.float32)
        g_leaves, treedef = jax.tree.flatten(updates)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = (treedef.flatten_up_to(state.momentum)
                    if has_momentum else None)
        out_d: list = [None] * len(g_leaves)
        out_m: list = [None] * len(g_leaves)
        run = (partial(_run_kernel, interpret=interpret) if use_pallas
               else _run_xla)
        for bucket in plan_buckets(updates, bucket_bytes):
            sizes = [g_leaves[i].size for i in bucket]
            p_flat = jnp.concatenate(
                [p_leaves[i].astype(jnp.float32).reshape(-1)
                 for i in bucket])
            m_flat = (jnp.concatenate(
                [m_leaves[i].reshape(-1) for i in bucket])
                if has_momentum else None)
            g_flat = jnp.concatenate(
                [g_leaves[i].astype(jnp.float32).reshape(-1)
                 for i in bucket])
            delta, new_m = run(lr, p_flat, m_flat, g_flat,
                               momentum=momentum,
                               weight_decay=weight_decay,
                               nesterov=nesterov)
            off = 0
            for i, size in zip(bucket, sizes):
                out_d[i] = delta[off:off + size].reshape(
                    g_leaves[i].shape).astype(p_leaves[i].dtype)
                if has_momentum:
                    out_m[i] = new_m[off:off + size].reshape(
                        g_leaves[i].shape)
                off += size
        return (jax.tree.unflatten(treedef, out_d),
                FusedSGDState(count=optax.safe_int32_increment(state.count),
                              momentum=(jax.tree.unflatten(treedef, out_m)
                                        if has_momentum else None)))

    return optax.GradientTransformation(init_fn, update_fn)
