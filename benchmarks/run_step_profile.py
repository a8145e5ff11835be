"""Hardware-profiler breakdown of a dispatched train/decode program.

The >1.0 demand-side ``hbm_frac_of_peak`` is not a saturation
measurement. This runner captures a REAL ``jax.profiler`` trace
of a dispatched program (the exact workload bench.py times — shared
builders, not a copy), parses the device plane (utils/xplane.py), and
commits:

* device-busy fraction (module device time / wall time between modules)
* per-category device-time breakdown (conv-fusions vs elementwise vs copies)
* top-N individual ops with device microseconds
* the profiler's own device peaks (TFLOP/s, HBM GB/s)

Workload entry list (DMP_PROFILE_WORKLOAD, default ``cnn``):

* ``cnn``    — bs-512 MobileNetV2 multi-step dispatch (bench.py main);
               writes benchmarks/step_profile_r5.json (historical path)
* ``lm``     — the long-context Transformer train step (bench.build_lm_bench;
               DMP_BENCH_SEQ/BATCH/... apply)
* ``moe``    — same, with every FFN a routed MoE (DMP_BENCH_MOE_EXPERTS,
               default 8 here)
* ``decode`` — the KV-cache greedy decode program (bench.build_decode_bench)

Non-cnn workloads write benchmarks/step_profile_<workload>.json. Each run
also appends a telemetry record (utils/telemetry; DMP_TELEMETRY overrides
the stream path). Run ON CHIP:
  python benchmarks/run_step_profile.py            # mobilenetv2 bs512
  DMP_BENCH_MODEL=resnet50 python benchmarks/run_step_profile.py
  DMP_PROFILE_WORKLOAD=lm DMP_BENCH_SEQ=8192 python benchmarks/run_step_profile.py
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import build_cnn_bench  # noqa: E402
from distributed_model_parallel_tpu.utils import xplane  # noqa: E402
from distributed_model_parallel_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from distributed_model_parallel_tpu.utils.profiling import fetch  # noqa: E402

TRACE_DIR = "/tmp/dmp_step_trace"

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "pred": 1, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2}
_SHAPE_RE = re.compile(
    r"\b(bf16|f32|f16|s32|u32|s64|u64|pred|s8|u8|s16|u16)\[([\d,]*)\]")


def _op_hbm_bytes(instr_text: str) -> int:
    """Sum of operand+result logical bytes for ONE execution of an HLO op,
    parsed from the instruction text.

    This is the op's data-footprint estimate, not a DMA counter: each
    listed buffer counts once (an op reading a buffer twice moves fewer
    HBM bytes than 2x), and VMEM-resident reuse makes real HBM traffic
    lower still — so per-op achieved_gbs can exceed the physical peak and
    means "footprint/time", an upper bound on the op's HBM need. The big
    NHWC activations here tile with zero padding (batch 512 = 4x128 lanes),
    so logical bytes ~= physical bytes for the arrays that matter."""
    total = 0
    for m in _SHAPE_RE.finditer(instr_text):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def _op_roofline(rows, n_steps: int, hbm_peak_gbs: float | None) -> dict:
    """Per-op footprint rate (analytic operand bytes / MEASURED device
    time) for every op >=20us/step, plus the time-weighted average.

    Device time is a hardware measurement (the TPU runtime's op timeline);
    bytes are analytic (_op_hbm_bytes), so a rate above peak means VMEM
    reuse, not impossible DMA. The saturation evidence is the combination:
    back-to-back module execution + per-op rates clustered at the HBM
    peak across ops covering ~90% of the step."""
    table = []
    for r in xplane.exclude_envelopes(rows):
        t_us = r.total_ps / 1e6 / n_steps
        if t_us < 20:
            continue
        b = _op_hbm_bytes(r.example)
        # Bytes are per ONE execution, so the rate divides by per-execution
        # time (total/count) — an op running once per dispatch rather than
        # once per step would otherwise read 10x too fast.
        t_exec_s = r.total_ps / 1e12 / max(1, r.count)
        table.append({
            "op": r.name,
            "us_per_step": round(t_us, 1),
            "executions": r.count,
            "mb": round(b / 1e6, 1),
            "achieved_gbs": round(b / 1e9 / t_exec_s, 0) if t_exec_s else 0,
        })
    table.sort(key=lambda d: -d["us_per_step"])
    cov = sum(d["us_per_step"] for d in table)
    weighted = (sum(d["us_per_step"] * d["achieved_gbs"] for d in table) / cov
                if cov else 0)
    return {
        "ops": table[:40],
        "covered_us_per_step": round(cov, 0),
        "time_weighted_achieved_gbs": round(weighted, 0),
        "hbm_peak_gbs": hbm_peak_gbs,
        "weighted_frac_of_peak": (round(weighted / hbm_peak_gbs, 3)
                                  if hbm_peak_gbs else None),
    }


def _build_workload(workload: str):
    """Entry list: (dispatch, steps_per_dispatch, hlo_fn, tag). The
    builders are bench.py's own, so the profiled program IS the timed
    program (shared construction, not a copy)."""
    if workload == "cnn":
        model_name = os.environ.get("DMP_BENCH_MODEL", "mobilenetv2")
        batch = int(os.environ.get("DMP_BENCH_BATCH", "512"))
        spd = int(os.environ.get("DMP_BENCH_SPD", "10"))
        trainer, dispatch = build_cnn_bench(model_name, batch, spd)

        def hlo():
            sub = jax.random.key(1)
            idx = jnp.zeros((spd, batch), jnp.int64)
            return trainer._multi_step.lower(
                trainer.state, sub, trainer._dev_images,
                trainer._dev_labels, idx).compile().as_text()

        return (dispatch, spd, batch, "samples", hlo,
                f"{model_name}_bs{batch}_spd{spd}")

    if workload in ("lm", "moe"):
        if workload == "moe" and not os.environ.get("DMP_BENCH_MOE_EXPERTS"):
            os.environ["DMP_BENCH_MOE_EXPERTS"] = "8"
        from bench import build_lm_bench

        t, step, info = build_lm_bench()
        toks, tgts = info["step_args"]

        def hlo():
            return t._step.lower(t.params, t.opt_state, toks,
                                 tgts).compile().as_text()

        return (step, 1, info["batch"] * info["seq"], "tokens", hlo,
                f"lm_{info['tag']}seq{info['seq']}_bs{info['batch']}")

    if workload == "decode":
        from bench import build_decode_bench

        gen, gen_args, info = build_decode_bench()

        def hlo():
            return gen.lower(*gen_args).compile().as_text()

        # One dispatched program generates gen_steps tokens: per-"step"
        # numbers below are per decoded token.
        return (lambda: gen(*gen_args), info["gen_steps"], info["batch"],
                "tokens", hlo,
                f"decode_bs{info['batch']}p{info['prompt_len']}"
                f"g{info['gen_steps']}")

    raise SystemExit(f"unknown DMP_PROFILE_WORKLOAD={workload!r} "
                     f"(entry list: cnn, lm, moe, decode)")


def main() -> None:
    enable_compile_cache()
    workload = os.environ.get("DMP_PROFILE_WORKLOAD", "cnn")
    dispatch, spd, units_per_step, unit, hlo_fn, tag = (
        _build_workload(workload))

    for _ in range(2):                      # compile + warm
        fetch(dispatch())
    print("[profile] warm; tracing...", file=sys.stderr, flush=True)

    n_dispatch = 4
    t0 = time.perf_counter()
    with xplane.trace_to(TRACE_DIR):
        m = None
        for _ in range(n_dispatch):
            m = dispatch()
        fetch(m)
    wall = time.perf_counter() - t0

    # Optimized HLO of the dispatched program, to attribute fusions.
    hlo_text = hlo_fn()

    space = xplane.load_xspace(TRACE_DIR)
    plane = xplane.device_plane(space)
    peaks = xplane.plane_peaks(plane)
    mods = xplane.module_events(plane)
    # Loop envelopes (%while) contain every inner op — excluded, or the
    # category fractions and op totals double-count the entire scan body.
    rows = xplane.exclude_envelopes(xplane.op_breakdown(plane, hlo_text))
    cats = xplane.category_totals(rows)
    n_steps = n_dispatch * spd
    roofline = _op_roofline(rows, n_steps,
                            peaks.get("peak_hbm_bw_gigabytes_per_second"))

    # Keep only the steady-state traced modules (the multi_step program —
    # ignore tiny helper programs like rng split if they appear).
    main_mods = [md for md in mods if md.duration_ps > 1e9]  # >1 ms
    if not main_mods:
        raise SystemExit(
            "no XLA module events >1ms in the trace — device events were "
            "not captured (host-only trace?); nothing to analyze")
    mod_total_s = sum(md.duration_ps for md in main_mods) / 1e12
    device_s_per_step = mod_total_s / len(main_mods) / spd
    # Gap between consecutive module executions = dispatch overhead.
    gaps = [(b.start_ps - (a.start_ps + a.duration_ps)) / 1e12
            for a, b in zip(main_mods, main_mods[1:])]
    op_total_s = sum(r.total_ps for r in rows) / 1e12

    units_per_s_device = units_per_step / device_s_per_step

    top = [{
        "op": r.name, "category": r.category,
        "total_us": round(r.total_ps / 1e6, 1),
        "per_step_us": round(r.total_ps / 1e6 / n_steps, 2),
        "count": r.count,
    } for r in rows[:30]]

    out = {
        "workload": tag,
        "workload_kind": workload,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "profiler_peaks": peaks,
        "wall_s": round(wall, 3),
        "n_dispatch": n_dispatch, "steps_per_dispatch": spd,
        "module_device_s_total": round(mod_total_s, 4),
        "device_s_per_step": round(device_s_per_step, 6),
        f"{unit}_per_s_per_chip_device_time": round(units_per_s_device, 1),
        "device_busy_frac_of_wall": round(mod_total_s / wall, 3),
        "intermodule_gaps_ms": [round(g * 1e3, 2) for g in gaps],
        "op_time_s_total": round(op_total_s, 4),
        "category_totals_s": {k: round(v, 4) for k, v in cats.items()},
        "category_frac_of_op_time": {
            k: round(v / op_total_s, 4) for k, v in cats.items()},
        "roofline": roofline,
        "top_ops": top,
        "note": ("device_duration_ps from the TPU runtime's own timeline — "
                 "hardware-measured, not cost-analysis estimates. "
                 "category_totals classifies each fusion by its fused "
                 "content from the optimized HLO (conv-fusion / "
                 "elementwise-fusion / reduce-fusion / copy...)."),
    }
    # cnn keeps its historical artifact path (round-5 evidence appends to
    # it); the new entry-list workloads get their own files.
    fname = ("step_profile_r5.json" if workload == "cnn"
             else f"step_profile_{workload}.json")
    path = pathlib.Path(__file__).parent / fname
    if path.exists():
        existing = json.loads(path.read_text())
        if not isinstance(existing, list):
            existing = [existing]
    else:
        existing = []
    existing.append(out)
    path.write_text(json.dumps(existing, indent=1) + "\n")

    # Tag the run's telemetry stream so the report CLI can cite which
    # profile artifact covers it.
    from distributed_model_parallel_tpu.utils.telemetry import TelemetryRun

    telemetry = TelemetryRun(
        os.environ.get("DMP_TELEMETRY",
                       "/tmp/dmp_profile_log/profile_telemetry.jsonl"),
        run=f"profile-{workload}",
        meta=dict(workload=workload, tag=tag, artifact=str(path)))
    telemetry.step(step=0, step_time_s=device_s_per_step,
                   **{f"{unit}_per_s": units_per_s_device})
    telemetry.record("profile", workload=tag,
                     device_s_per_step=device_s_per_step,
                     device_busy_frac_of_wall=round(mod_total_s / wall, 3))
    telemetry.memory()
    telemetry.finish()

    print(json.dumps({k: out[k] for k in (
        "workload", "device_s_per_step",
        f"{unit}_per_s_per_chip_device_time", "device_busy_frac_of_wall",
        "category_frac_of_op_time")}, indent=1))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
