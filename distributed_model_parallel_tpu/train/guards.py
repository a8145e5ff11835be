"""Training guards: replica-divergence and non-finite detection.

The reference has no race/failure detection at all (SURVEY.md §5): DDP's
implicit guarantee that replicas stay in lockstep is trusted blindly, and a
dead rank simply hangs the NCCL ring. The single-controller SPMD model
removes whole classes of those failures (there is one program; collectives
cannot mismatch), so the remaining failure surface is numerical and
placement drift — which these guards check cheaply:

* ``assert_replicated`` — verifies a pytree whose arrays claim to be
  replicated really is bitwise-identical across devices (the invariant DDP
  maintains by construction and silently corrupts when broken; here it can
  only break through user error like donating a stale buffer, and a test
  can check it directly).
* ``check_finite`` — raises on NaN/Inf in a pytree (e.g. loss explosion),
  replacing silent divergence with a loud failure; cheap enough to run every
  N steps. The whole pytree is fetched with ONE ``jax.device_get`` (one host
  sync total, not one per leaf) and the scan raises at the first non-finite
  leaf.
* ``StallDetector`` — the original post-hoc step-budget flag, kept for
  standalone use. The trainers now run the *live*
  ``train/resilience.Watchdog`` instead: it logs "still blocked after Ns"
  lines while the sync is still wedged (the observable symptom of a dead
  collective, which in the reference just blocks forever on ``dist.recv``,
  ``distributed_layers.py:20``) and can escalate to checkpoint-and-exit.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import numpy as np


class ReplicaDivergenceError(AssertionError):
    pass


def assert_replicated(tree: Any, *, atol: float = 0.0, name: str = "tree") -> None:
    """Check every array's shards are identical across its devices.

    ``atol=0`` (the default) compares BIT PATTERNS, matching the
    consistency sentinel's fingerprint semantics: ``-0.0`` vs ``+0.0``
    diverges (a sign-bit SDC), while replicas that all hold the same NaN
    bytes are identical (a non-finite incident, not a replication one —
    ``check_finite`` is the guard for that). ``atol > 0`` falls back to
    a value comparison via ``np.allclose``."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not hasattr(leaf, "addressable_shards"):
            continue
        shards = leaf.addressable_shards
        if len(shards) < 2:
            continue
        if shards[0].data.shape != leaf.shape:
            continue  # actually sharded, not replicated
        ref = np.asarray(shards[0].data)
        for s in shards[1:]:
            got = np.asarray(s.data)
            if atol == 0.0:
                same = ref.tobytes() == got.tobytes()
                detail = "bit patterns differ"
            else:
                same = np.allclose(ref, got, atol=atol, rtol=0.0)
                detail = (f"max abs diff {np.abs(ref - got).max()}"
                          if not same else "")
            if not same:
                raise ReplicaDivergenceError(
                    f"{name}{jax.tree_util.keystr(path)} diverges between "
                    f"device {shards[0].device} and {s.device} ({detail})")


class NonFiniteError(FloatingPointError):
    pass


def check_finite(tree: Any, *, name: str = "tree") -> None:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    if not flat:
        return
    # ONE device->host fetch for the whole tree: per-leaf device_get would
    # pay one blocking round trip per leaf (hundreds for a real model).
    host = jax.device_get([leaf for _path, leaf in flat])
    for (path, _leaf), arr in zip(flat, host):
        arr = np.asarray(arr)
        if not np.isfinite(arr).all():
            # Short-circuit on the first bad leaf — no point scanning the
            # rest of an already-condemned tree.
            raise NonFiniteError(
                f"{name}{jax.tree_util.keystr(path)} contains "
                f"{np.isnan(arr).sum()} NaN / {np.isinf(arr).sum()} Inf values")


class StallDetector:
    """Flags steps exceeding ``budget_s``. Usage:

        stall = StallDetector(budget_s=60)
        with stall.step():
            train_step(...)
        if stall.stalled: ...
    """

    def __init__(self, budget_s: float):
        self.budget_s = budget_s
        self.stalled = False
        self.worst_s = 0.0

    class _Ctx:
        def __init__(self, outer):
            self.outer = outer

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            dt = time.perf_counter() - self.t0
            self.outer.worst_s = max(self.outer.worst_s, dt)
            if dt > self.outer.budget_s:
                self.outer.stalled = True
            return False

    def step(self) -> "_Ctx":
        return self._Ctx(self)


class GuardRunner:
    """Config-driven guard harness the trainers wire in (off by default).

    ``TrainConfig.check_finite_every=N`` turns on finiteness checking: every
    drained metrics window is checked (those values are already on host — the
    check is free), and every N steps the parameters are fetched and checked
    too (a device→host sync, hence the coarser, explicit cadence).
    ``TrainConfig.stall_budget_s=S`` arms a live
    :class:`~distributed_model_parallel_tpu.train.resilience.Watchdog`
    around every blocking drain: while the sync is still blocked it logs
    "still blocked after Ns" lines, and an overrun flips ``stall.stalled``
    and (when the recovery supervisor wires ``on_stall`` with
    ``recovery.stall_exit``) escalates to a graceful checkpoint-and-exit —
    it never raises mid-sync, because wall-clock slowness can be transport
    noise while NaN is always a bug. ``injector`` serves planned ``stall``
    faults inside the watched region (utils/faults.py).
    """

    def __init__(self, *, check_finite_every: int = 0,
                 stall_budget_s: float | None = None, logger=None,
                 watchdog_interval_s: float | None = None,
                 on_stall=None, injector=None,
                 device_ids: tuple = ()):
        self.every = check_finite_every
        # Slice attribution for the device-health sentinel feeds
        # (utils/health.py): every watched sync's wall time is an
        # observation for these devices.
        self.device_ids = tuple(device_ids)
        if stall_budget_s:
            from distributed_model_parallel_tpu.train.resilience import (
                Watchdog,
            )

            self.stall = Watchdog(stall_budget_s,
                                  interval_s=watchdog_interval_s,
                                  logger=logger, on_escalate=on_stall)
        else:
            self.stall = None
        self.logger = logger
        self.injector = (injector if injector is not None
                         and injector.enabled else None)
        self._seen = 0
        self._next_params_check = check_finite_every

    @property
    def enabled(self) -> bool:
        return self.every > 0 or self.stall is not None

    def watch(self, what: str = "sync"):
        """Context manager wrapping a blocking sync point. ``what`` labels
        the watchdog's "still blocked" lines (the consistency sentinel
        passes "consistency-fingerprint" so a divergence check wedged on a
        dead mesh is attributed to the check, not a training sync)."""
        import contextlib

        from distributed_model_parallel_tpu.utils import health

        if (self.stall is None and self.injector is None
                and health.installed() is None):
            return contextlib.nullcontext()
        return self._watched(what)

    def _watched(self, what: str):
        import contextlib
        import time

        from distributed_model_parallel_tpu.utils import health

        @contextlib.contextmanager
        def ctx():
            wd = (self.stall.watch(what) if self.stall is not None
                  else contextlib.nullcontext())
            t0 = time.perf_counter()
            try:
                with wd:
                    if self.injector is not None:
                        # Injected stalls sleep INSIDE the watched region,
                        # so the watchdog observes them like a real wedged
                        # sync. Polling is keyed by ``what``: the
                        # sentinel's "consistency-fingerprint" fetches
                        # advance their own occurrence counter, so arming
                        # the sentinel never shifts which training drain a
                        # planned ``stall@N`` fires at (stall specs target
                        # site "sync" only).
                        self.injector.maybe_stall(what)
                    yield
            finally:
                # Every watched sync's wall time feeds the device-health
                # sentinel (no-op unless a monitor is installed): the
                # sentinel's labeled fetches land in the per-replica
                # "fetch" signal, training drains in "sync".
                dt = time.perf_counter() - t0
                if what == "consistency-fingerprint":
                    health.observe_fetch(self.device_ids, dt)
                else:
                    health.observe_sync(self.device_ids, dt)
        return ctx()

    def after_sync(self, host_metrics: Any, n_steps: int,
                   params: Any = None) -> None:
        """Run after a drain: ``host_metrics`` are the already-fetched
        values (checked every time), ``params`` the live model params
        (checked when the step counter crosses the N-step cadence)."""
        if self.every <= 0:
            return
        check_finite(host_metrics, name="metrics")
        self._seen += n_steps
        if params is not None and self._seen >= self._next_params_check:
            self._next_params_check = self._seen + self.every
            check_finite(params, name="params")
