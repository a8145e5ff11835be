"""Measured validation: time short real steps of the top-K candidates.

The analytic ranking is only as good as its coefficients, so the planner
can close the loop with measurements: ``scripts/dmp_plan.py --measure K``
builds each of the analytic top-K plans as an ``LMTrainer`` on the plan's
own mesh (:func:`lm_step_for_plan`) and times a handful of dispatched
steps with the same fetch-bracketed discipline as
``utils/profiling.time_step`` (see that module's docstring).
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Callable, Sequence

from distributed_model_parallel_tpu.autotune.plan import (
    ParallelPlan,
    mesh_from_plan,
)

__all__ = ["lm_step_for_plan", "measure_plans", "time_step_fn"]


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


def lm_step_for_plan(model, plan: ParallelPlan, *, batch: int,
                     seq: int) -> Callable[[], object]:
    """``step()`` running one LM train step of ``model`` under ``plan``:
    an ``LMTrainer`` on the plan's mesh (tensor/sequence/expert axes
    switched on in the model config as the plan says), one sampled batch
    fed to every call. ``step()`` returns the step's device metrics."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.autotune.planner import (
        lm_model_for_plan,
    )
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    tmp = tempfile.gettempdir()
    t = LMTrainer(LMTrainConfig(
        model=lm_model_for_plan(model, plan),
        batch_size=batch, seq_len=seq, n_tokens=4 * batch * (seq + 1),
        # No held-out eval: at small batch the default 10% tail cannot
        # fit one seq_len eval window.
        eval_batches=0,
        mesh=mesh_from_plan(plan),
        num_microbatches=plan.num_microbatches,
        log_dir=os.path.join(tmp, "dmp_plan_log"),
        checkpoint_dir=os.path.join(tmp, "dmp_plan_ckpt")))
    toks, tgts = (jnp.asarray(a) for a in t.sample_batch())

    def step():
        t.params, t.opt_state, m = t._step(t.params, t.opt_state,
                                           toks, tgts)
        return m

    return step


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
