"""Automatic cost-balanced pipeline stage partitioning.

The reference hard-codes its per-rank layer split in the launcher — rank 0
gets the stem + first blocks, middle ranks get ``layers[6r-3:6r+3]``, the last
rank gets the head (``model_parallel.py:99-157``) — so rebalancing means
editing code, and nothing guarantees the stages are actually balanced. Here
stage boundaries are already plain data over a ``StagedModel``
(``models/staged.py``); this module *computes* them: per-unit costs come from
XLA's own compiled cost model (``lowered.compile().cost_analysis()`` FLOPs,
with a parameter+activation-bytes fallback), and boundaries are chosen to
minimize the bottleneck stage cost — the pipeline's steady-state throughput is
set by its slowest stage, so minimax (not equal-count) is the right objective.

**Public contract:** ``unit_costs``, ``cost_balanced_boundaries``,
``auto_boundaries``, ``microbatch_rows`` and ``compiled_flops_probe`` are
stable API, not pipeline-internal helpers — the parallelism autotuner
(``autotune/``, docs/AUTOTUNE.md) builds its compute term on them, and
``parallel/__init__`` re-exports them. Pinned properties: ``unit_costs``
returns one strictly-positive float per unit, in unit order, at the given
sample shape; ``cost_balanced_boundaries`` is a deterministic exact
minimax DP whose ties keep the latest cut (front-loaded stages).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.models.staged import StagedModel

__all__ = [
    "auto_boundaries",
    "compiled_flops_probe",
    "cost_balanced_boundaries",
    "microbatch_rows",
    "unit_costs",
]


def compiled_flops_probe(fn, *args) -> float | None:
    """XLA's FLOP estimate for ``fn(*args)``, or None if unavailable
    (loop bodies counted once, custom calls zero: valid for the
    loop-free, kernel-free per-unit programs this module costs)."""
    try:
        analysis = jax.jit(fn).lower(*args).compile().cost_analysis()
        flops = analysis.get("flops", None)
        if flops is None or not np.isfinite(flops) or flops < 0:
            return None
        return float(flops)
    except Exception:
        return None


def unit_costs(model: StagedModel, sample_shape: Sequence[int],
               *, train: bool = True) -> list[float]:
    """Per-unit relative cost of one forward pass at ``sample_shape``.

    Threads the activation shape through the unit chain with ``eval_shape``
    (so each unit is costed at its true input shape), compiling each unit
    once on whatever backend is active — the FLOP count is
    backend-independent. Falls back to parameter-count + activation-element
    proxies for units XLA cannot cost.

    Stability pin (consumed by ``autotune/search.cnn_workload`` and the
    pipeline balancer alike): returns ``model.num_units`` floats, each
    ``>= 1.0``, in unit order.
    """
    x = jnp.zeros(tuple(sample_shape), jnp.float32)
    params, state = model.init(jax.random.key(0), x)
    costs: list[float] = []
    for i in range(model.num_units):
        def fwd(p, s, a, _i=i):
            y, _ = model.apply_unit(_i, p, s, a, train=train)
            return y
        flops = compiled_flops_probe(fwd, params[i], state[i], x)
        out = jax.eval_shape(fwd, params[i], state[i], x)
        if flops is None:
            n_params = sum(l.size for l in jax.tree.leaves(params[i]))
            flops = 2.0 * n_params * np.prod(sample_shape[:1]) + out.size
        costs.append(max(flops, 1.0))
        x = jnp.zeros(out.shape, out.dtype)
    return costs


def cost_balanced_boundaries(costs: Sequence[float],
                             num_stages: int) -> list[int]:
    """Contiguous minimax partition of ``costs`` into ``num_stages`` stages.

    Returns boundaries like ``balanced_boundaries`` (length num_stages+1,
    b[0]=0, b[-1]=len(costs), strictly increasing). O(S·N²) exact DP —
    N is the unit count (19 for MobileNetV2), so this is microseconds.
    """
    n = len(costs)
    if not (1 <= num_stages <= n):
        raise ValueError(f"cannot split {n} units into {num_stages} stages")
    prefix = np.concatenate([[0.0], np.cumsum(costs)])

    def seg(i: int, j: int) -> float:      # cost of units [i, j)
        return prefix[j] - prefix[i]

    INF = float("inf")
    # best[s][i] = minimal bottleneck cost splitting units [0, i) into s stages
    best = np.full((num_stages + 1, n + 1), INF)
    cut = np.zeros((num_stages + 1, n + 1), np.int64)
    best[0][0] = 0.0
    for s in range(1, num_stages + 1):
        for i in range(s, n + 1):
            for j in range(s - 1, i):
                v = max(best[s - 1][j], seg(j, i))
                # `<=` keeps the *latest* cut among minimax ties, pushing
                # extra units onto the earliest stages — the same
                # front-loading convention as balanced_boundaries (and the
                # reference's split, which gives rank 0 the stem plus the
                # first blocks, model_parallel.py:102-104).
                if v <= best[s][i]:
                    best[s][i] = v
                    cut[s][i] = j
    bounds = [n]
    for s in range(num_stages, 0, -1):
        bounds.append(int(cut[s][bounds[-1]]))
    return bounds[::-1]


def auto_boundaries(model: StagedModel, sample_shape: Sequence[int],
                    num_stages: int, *, train: bool = True) -> list[int]:
    """Measure unit costs and return the minimax stage boundaries."""
    return cost_balanced_boundaries(
        unit_costs(model, sample_shape, train=train), num_stages)


def microbatch_rows(batch_size: int, num_microbatches: int,
                    data_shards: int = 1) -> int:
    """Rows of ONE microbatch as a pipeline stage sees it — the batch shape
    ``auto_boundaries`` should profile at. The single home for this
    arithmetic: the single-controller runner feeds the whole global batch
    through one replica (``data_shards=1``); the SPMD pipeline splits it
    over the ``data`` axis first."""
    return max(1, batch_size // (max(1, data_shards)
                                 * max(1, num_microbatches)))
