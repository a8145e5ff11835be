"""Measured validation: time short real steps of the top-K candidates.

The analytic ranking is only as good as its coefficients, so the planner
can close the loop with measurements: ``scripts/dmp_plan.py --measure K``
builds each of the analytic top-K plans through **bench.py's shared
workload builders** (``build_lm_bench`` with a per-plan mesh override —
the measured program IS the bench program, so the numbers are comparable
with the bench's) and times a handful of dispatched steps with the same
fetch-bracketed discipline as ``utils/profiling.time_step`` (see that
module's docstring).

This module holds only the timing harness; the bench-builder plumbing
lives in ``scripts/dmp_plan.py`` (the repo-root ``bench`` module is a
script, not a package member).
"""

from __future__ import annotations

import time
from typing import Callable, Sequence

from distributed_model_parallel_tpu.autotune.plan import ParallelPlan

__all__ = ["measure_plans", "time_step_fn"]


def time_step_fn(step: Callable[[], object], *, warmup: int = 1,
                 iters: int = 2) -> float:
    """Seconds per call of ``step()`` (one train step): ``warmup`` calls
    (compile + warm), then ``iters`` back-to-back calls bracketed by ONE
    host fetch, minus the separately-measured fetch round trip."""
    from distributed_model_parallel_tpu.utils.profiling import (
        fetch,
        fetch_overhead,
    )

    out = None
    for _ in range(max(1, warmup)):
        out = step()
    fetch(out)
    t_fetch = fetch_overhead()
    t0 = time.perf_counter()
    for _ in range(max(1, iters)):
        out = step()
    fetch(out)
    return max(1e-9, time.perf_counter() - t0 - t_fetch) / max(1, iters)


def measure_plans(plans: Sequence[ParallelPlan],
                  build_step: Callable[[ParallelPlan], Callable[[], object]],
                  *, warmup: int = 1, iters: int = 2) -> list[dict]:
    """Measure each plan through ``build_step(plan) -> step()`` (a fresh
    per-plan program — mesh layout is compile-time). Returns one row per
    plan, measurement order preserved; a candidate whose build/compile
    fails records its error instead of killing the sweep (the analytic
    ranking still stands for it)."""
    rows: list[dict] = []
    for p in plans:
        row = dict(p.payload())
        try:
            row["measured_s"] = time_step_fn(build_step(p), warmup=warmup,
                                             iters=iters)
        except Exception as e:  # noqa: BLE001 - reported, not fatal
            row["error"] = f"{type(e).__name__}: {e}"
        rows.append(row)
    return rows
