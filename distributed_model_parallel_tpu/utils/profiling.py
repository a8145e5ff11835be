"""Tracing / profiling.

The reference's entire observability story is ``time.time()`` deltas averaged
per epoch (``utils.py:41,48,64-74``; SURVEY.md §5). Equivalent meters live in
``train/metrics.py`` (StepTimer). This module adds the TPU-native upgrade:
``jax.profiler`` traces viewable in TensorBoard/Perfetto, plus a lightweight
step-latency profiler for benchmarking jitted step functions.

**Why timing ends in a host fetch:** JAX dispatches asynchronously, so
wall-clock around a call that nothing waited for measures the enqueue. A
device→host copy of the result cannot lie on any transport — the bytes
only exist once the program ran. ``time_step`` therefore times a whole
loop of calls bracketed by one host fetch, and subtracts the
separately-measured fetch round-trip cost.
"""

from __future__ import annotations

import contextlib
import re
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


# Published bf16 peak matmul throughput per chip (FLOP/s), keyed by
# device_kind prefix. Used to turn measured step time + a step's FLOPs
# into model-FLOPs-utilization (MFU) — an absolute efficiency number,
# unlike throughput ratios against a historical baseline.
# Source: Google Cloud TPU documentation, one page per generation; the
# chip this repo is measured on is "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
# HBM bandwidth, 16 GB HBM per chip.
TPU_PEAK_FLOPS: dict[str, float] = {
    "TPU v6": 918e12,        # v6e (Trillium)
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # bare "v5" = v5p
    "TPU v4 lite": 137.5e12,  # v4i
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 46e12,
}


def match_device_kind(table: dict, device=None, *, kind: str | None = None):
    """Longest-prefix lookup of ``device.device_kind`` in ``table`` (so
    "TPU v5 lite..." hits a "TPU v5 lite" row, not "TPU v5"). Shared by the
    peak-FLOPs table here and the flash dispatch table
    (ops/pallas_attention.py). Returns the value or None.

    Pass ``kind`` to look up a recorded device_kind string without a live
    backend (scripts/dmp_report.py reads it from a telemetry stream)."""
    if kind is None:
        device = device if device is not None else jax.devices()[0]
        kind = getattr(device, "device_kind", "") or ""
    for prefix in sorted(table, key=len, reverse=True):
        if kind.startswith(prefix):
            return table[prefix]
    return None


def peak_flops_per_chip(device=None) -> float | None:
    """bf16 peak FLOP/s for ``device`` (default: devices()[0]). None on the
    CPU only, where no utilization is reported; a TPU that is not in the
    table is an error, never a default."""
    device = device if device is not None else jax.devices()[0]
    if device.platform == "cpu":
        return None
    peak = match_device_kind(TPU_PEAK_FLOPS, device)
    if peak is None:
        raise ValueError(
            f"no published bf16 peak FLOP/s for device_kind "
            f"{device.device_kind!r} ({device.platform}); add its row, "
            f"with the source, to utils/profiling.py")
    return peak


# ---------------------------------------------------------------------------
# Buffer-donation audit: trace-time proof that donation held.
# ---------------------------------------------------------------------------

class DonationError(AssertionError):
    """An expected buffer donation was dropped (or never set up) by XLA.

    Dropped donation is a *silent* perf/memory regression: the step still
    computes the same numbers, it just holds two copies of the state —
    which is exactly how an OOM or a 2x live-memory surprise ships.
    """


# One alias entry of the HLO module header's input_output_alias field,
# e.g. ``{0}: (0, {}, may-alias)`` — (output index): (param number,
# param index, kind).
_ALIAS_ENTRY_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\(\s*(\d+)\s*,\s*\{[\d,\s]*\}\s*,\s*"
    r"(may-alias|must-alias)\s*\)")


# The avals in jax's "Some donated buffers were not usable: uint8[3,3],
# float32[8]." lowering warning.
_DROPPED_AVAL_RE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")


def aot_compile(jitted: Callable, *args, **kwargs):
    """``jitted.lower(*args).compile()`` with lowering warnings captured:
    returns ``(compiled, warnings_list)`` for :func:`donation_report`.
    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct``s."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jitted.lower(*args, **kwargs).compile()
    return compiled, list(caught)


def donation_report(compiled, caught=()) -> dict:
    """What happened to a compiled program's donated buffers:
    ``{"n_aliased", "aliased_params", "dropped"}``.

    * ``n_aliased`` — input→output alias pairs XLA committed to (the
      ``input_output_alias`` field of the compiled module header): these
      buffers are genuinely reused in place.
    * ``dropped`` — donations XLA could NOT use (jax's "Some donated
      buffers were not usable" lowering warning from ``caught``, captured
      instead of printed), as the warned shape strings, e.g.
      ``["uint8[512,32,32,3]"]``. Caveat: the warning fires at *lowering*
      — a jit whose lowering was already cached (the function was called
      before) re-raises nothing, so dropped-detection needs a fresh
      jitted fn (or the trainers' build-time audit).
    """
    dropped: list[str] = []
    for w in caught:
        msg = str(w.message)
        if "donated buffers were not usable" in msg:
            dropped += _DROPPED_AVAL_RE.findall(msg) or [msg]
    # The alias field's nested braces defeat a simple field-isolating
    # regex; the entry pattern's literal "may-alias)" is unambiguous in
    # the whole module header, so match entries directly. The header is
    # everything before the first computation body.
    header = compiled.as_text().split("ENTRY", 1)[0]
    entries = _ALIAS_ENTRY_RE.findall(header)
    return {
        "n_aliased": len(entries),
        "aliased_params": sorted({int(p) for p, _ in entries}),
        "dropped": dropped,
    }


def donation_audit(jitted: Callable, *args, **kwargs) -> dict:
    """AOT-compile ``jitted(*args)`` and return its :func:`donation_report`.
    A real (cache-miss) XLA compile of the program — use at trace/startup
    time, not per step."""
    return donation_report(*aot_compile(jitted, *args, **kwargs))


def assert_donation(jitted: Callable, *args, min_aliased: int = 1,
                    allow_dropped: tuple[str, ...] = (), **kwargs) -> dict:
    """Fail loudly when an expected donation was dropped by XLA.

    Asserts the compiled program carries at least ``min_aliased``
    input→output buffer aliases AND that every dropped donation matches an
    ``allow_dropped`` prefix (e.g. ``("uint8", "int32")`` for the batch
    buffers, which have no same-shaped output to alias with but are still
    donated so the runtime frees them at dispatch). Returns the
    :func:`donation_audit` report on success; raises :class:`DonationError`
    otherwise. The CI smoke (tests/test_perf_pipeline.py) pins both
    failure modes on toy functions.
    """
    report = donation_audit(jitted, *args, **kwargs)
    unexpected = [d for d in report["dropped"]
                  if not any(d.startswith(p) for p in allow_dropped)]
    if unexpected:
        raise DonationError(
            f"XLA dropped donation for {unexpected} (aliased "
            f"{report['n_aliased']} buffers) — an expected in-place "
            f"update silently became a copy; see donation_audit()")
    if report["n_aliased"] < min_aliased:
        raise DonationError(
            f"expected >= {min_aliased} donated input→output aliases, "
            f"compiled program has {report['n_aliased']} — donation is "
            f"not set up (missing donate_argnums?)")
    return report


def lm_model_flops(cfg, batch: int, seq: int, causal: bool = True) -> float:
    """Analytic model FLOPs (forward + backward) of one Transformer LM
    train step at ``batch`` sequences of ``seq`` tokens.

    XLA's cost analysis cannot produce this number for the real program
    (scan bodies counted once, pallas custom calls counted zero), so MFU
    uses the standard analytic count:

    * dense matmuls: ``6 * N_mm * tokens`` where ``N_mm`` is the matmul
      parameter count touched per token (q/kv/o projections, MLP or the
      top-k routed expert slice plus router, LM head; embedding lookups
      and elementwise work excluded) — fwd ``2N`` + bwd ``4N``.
    * attention scores/values: fwd ``4*B*H*pairs*hd`` + bwd twice that,
      where ``pairs`` is the number of attended (q, k) positions —
      ``T*(T+1)/2`` causal, banded under a sliding window.
    * backward recompute (remat or the FA2 in-kernel score rebuild) is
      EXCLUDED: that work is implementation overhead, not model FLOPs
      (this is MFU, not HFU).
    """
    d, hd = cfg.d_model, cfg.head_dim
    H, kv = cfg.n_heads, cfg.kv_heads
    L, f, V = cfg.n_layers, cfg.d_ff, cfg.vocab_size
    attn_proj = d * H * hd + d * kv * 2 * hd + H * hd * d
    if cfg.moe_experts:
        mlp = cfg.moe_top_k * 2 * d * f + d * cfg.moe_experts
    else:
        mlp = 2 * d * f
    n_mm = L * (attn_proj + mlp) + d * V
    tokens = batch * seq
    dense = 6 * n_mm * tokens
    if cfg.attn_window is not None:
        w = min(cfg.attn_window, seq)
        # query i attends keys (i-w, i]: min(i+1, w) positions
        pairs = seq * w - w * (w - 1) // 2
    elif causal:
        pairs = seq * (seq + 1) // 2
    else:
        pairs = seq * seq
    attn = 12 * batch * H * pairs * hd * L
    return float(dense + attn)


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/dmp_trace"):
    """Capture an XLA/TPU profiler trace for the enclosed region."""
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def fetch(out) -> None:
    """Force device→host transfer of one leaf of ``out`` (true sync point).

    Devices execute enqueued programs in order, so fetching the last
    program's output waits for everything before it too.
    """
    leaves = jax.tree.leaves(out)
    if leaves:
        np.asarray(leaves[-1])


def fetch_overhead() -> float:
    """Seconds for one device→host round trip of an already-computed value
    (pure transport latency)."""
    a = jax.jit(lambda v: v + 1)(jax.numpy.zeros(()))
    b = jax.jit(lambda v: v + 2)(jax.numpy.zeros(()))
    fetch(a)   # waits for both trivial programs; warms the transport path
    t0 = time.perf_counter()
    fetch(b)   # executed but not host-cached: a pure round trip
    return time.perf_counter() - t0


def _warn_if_swamped(total: float, t_fetch: float, who: str) -> bool:
    """A timed loop shorter than the (single-sample) fetch round-trip means
    the measurement is noise — say so rather than report inflated numbers."""
    if total <= t_fetch:
        import sys
        print(f"[{who}] WARNING: timed loop ({total * 1e3:.1f} ms) <= fetch "
              f"round-trip ({t_fetch * 1e3:.1f} ms); measurement invalid — "
              f"raise iters or use a bigger workload", file=sys.stderr)
        return False
    return True


def time_fn_in_scan(fn: Callable, *args, iters: int = 20) -> float:
    """True device seconds per call of a pure array function.

    Runs ``iters`` calls inside ONE jitted ``lax.scan`` — no per-call
    dispatch at all — bracketed by a single host fetch. Use for kernel
    comparisons (e.g. attention implementations), where per-program
    dispatch overhead is not part of what's being measured; ``time_step``
    measures dispatched-call latency instead. The first argument must be a
    float array; a data dependency through the scan carry defeats CSE.
    Iteration count auto-scales (up to 16x) until the timed loop clearly
    exceeds the fetch round-trip, so fast kernels still measure validly
    over a high-latency transport.
    """
    first = args[0]

    def measure(n: int) -> tuple[float, float]:
        @jax.jit
        def run(first):
            def body(acc, _):
                out = fn(first + acc.astype(first.dtype) * 0, *args[1:])
                # Every output leaf must reach the carry — depending on just
                # one would let XLA dead-code-eliminate the computation of
                # the others (e.g. the dk/dv kernel of a multi-output
                # backward), timing only part of the work.
                dep = sum((jnp.sum(leaf) * 1e-20).astype(jnp.float32)
                          for leaf in jax.tree.leaves(out))
                return acc + dep, ()

            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                                  length=n)
            return acc

        fetch(run(first))          # compile + warm
        t_fetch = fetch_overhead()
        t0 = time.perf_counter()
        fetch(run(first))
        return time.perf_counter() - t0, t_fetch

    n = iters
    for attempt in range(3):
        total, t_fetch = measure(n)
        if total > 2 * t_fetch or attempt == 2:
            break
        n *= 4                     # too fast to resolve — lengthen the loop
    _warn_if_swamped(total, t_fetch, "time_fn_in_scan")
    return max(1e-9, total - t_fetch) / n


def time_step(fn: Callable, *args, warmup: int = 2, iters: int = 10,
              **kwargs) -> dict:
    """Steady-state per-call latency of a jitted callable (seconds).

    Times ``iters`` back-to-back calls bracketed by a single host fetch of
    the final output (see module docstring for why), then subtracts the
    measured fetch round-trip. Only aggregate keys are returned — per-call
    percentiles are unknowable under single-fetch timing, so none are
    fabricated.
    """
    out = None
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    fetch(out)
    t_fetch = fetch_overhead()

    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    fetch(out)
    total = time.perf_counter() - t0
    # Floor: a noisy fetch-overhead sample larger than a fast timed loop
    # must not produce 0 (callers divide by this).
    valid = _warn_if_swamped(total, t_fetch, "time_step")
    per_call = max(1e-9, total - t_fetch) / iters
    return {
        "mean_s": per_call,
        "total_s": total,
        "fetch_overhead_s": t_fetch,
        "iters": iters,
        "valid": valid,
    }
