#!/usr/bin/env python
"""Run report: one command that answers "why is this step slow".

Joins a run's telemetry JSONL (utils/telemetry.TelemetryRun — written by
every trainer via RunLogger and by the serving engine) with an optional
xplane trace directory (utils/xplane op breakdown) and prints:

* step-time percentiles (p50/p90/p99) and throughput from ``step`` records;
* comm/compute overlap from the xplane device timeline (``--trace``): the
  comm-hidden fraction — how much of the collective time the backward
  actually covered;
* serving SLOs (``serve`` records from serve/engine.py — per-request
  TTFT / queue-wait / per-token-latency percentiles, tokens/s, slot
  utilization, page-pool occupancy per engine run) on streams written by
  any engine with a telemetry stream attached;
* MFU against the profiling.py peak tables — or an honest "MFU unavailable"
  line when the device has no peak entry (CPU) or the run recorded no FLOPs;
* communication volume AND message counts per collective kind x mesh axis
  (trace-time ring-model estimates from ops/collectives.py — the beta and
  alpha terms the autotuner's cost model prices with);
* the parallelism-plan timeline (``plan`` records from the autotuner,
  autotune/planner.py): chosen layout, cost breakdown, alternatives, and
  the global step each (re-)plan landed at;
* the span-time rollup (``span`` records, utils/tracing.py) — the
  zoomable version is scripts/dmp_trace.py (docs/TRACING.md);
* device memory watermarks and recompilation counts;
* the failure/recovery/divergence timeline (injected faults, non-finite
  restores, stall escalations, torn-checkpoint fallbacks, cross-replica
  divergence detections + repairs — train/resilience.py,
  train/consistency.py);
* on fleet reports: the device-health timeline (score transitions,
  quarantines, proactive migrations, grow-backs — utils/health.py);
* top-N device ops + per-category device time from the xplane trace
  (``--trace``), degrading to an actionable one-liner when the tensorflow
  proto bindings are absent.

Usage:
  python scripts/dmp_report.py log/lm.jsonl
  python scripts/dmp_report.py log/train.jsonl --trace /tmp/dmp_step_trace
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_model_parallel_tpu.utils.telemetry import (  # noqa: E402
    join_request_traces,
    read_records,
)


def percentile(xs: list[float], q: float) -> float:
    """Linear-interpolated percentile of a non-empty list (no numpy dep in
    the report path — the stream is host data)."""
    ys = sorted(xs)
    if len(ys) == 1:
        return ys[0]
    pos = q / 100.0 * (len(ys) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (pos - lo) * (ys[hi] - ys[lo])


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024 or unit == "TB":
            return f"{b:.1f} {unit}"
        b /= 1024
    return f"{b:.1f} TB"


def _fmt_s(s: float) -> str:
    return f"{s * 1e3:.2f} ms" if s < 1 else f"{s:.3f} s"


def _by_kind(records: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for r in records:
        # Legacy streams (pre-telemetry RunLogger) had no "kind": treat
        # records carrying an epoch as epoch records so old logs still
        # render a (reduced) report.
        kind = r.get("kind") or ("epoch" if "epoch" in r else "event")
        out.setdefault(kind, []).append(r)
    return out


def _steps_section(lines: list[str], steps: list[dict]) -> list[float]:
    """Append the step-timing section; returns the step-time list so the
    efficiency section reuses the same filtered values."""
    lines.append(f"== steps ({len(steps)} records) ==")
    times = [r["step_time_s"] for r in steps
             if isinstance(r.get("step_time_s"), (int, float))]
    if times:
        lines.append(
            f"step time   p50 {_fmt_s(percentile(times, 50))}   "
            f"p90 {_fmt_s(percentile(times, 90))}   "
            f"p99 {_fmt_s(percentile(times, 99))}   "
            f"mean {_fmt_s(sum(times) / len(times))}")
    else:
        lines.append("step time   (no step_time_s keys recorded)")
    data = [r["data_time_s"] for r in steps
            if isinstance(r.get("data_time_s"), (int, float))]
    if data:
        tot = sum(data) + sum(times)
        lines.append(
            f"data time   mean {_fmt_s(sum(data) / len(data))}"
            + (f"   (data/compute split {sum(data) / tot:.1%} data)"
               if tot > 0 else ""))
    for key, unit in (("tokens_per_s", "tokens/s"),
                      ("samples_per_s", "samples/s")):
        vals = [r[key] for r in steps
                if isinstance(r.get(key), (int, float))]
        if vals:
            lines.append(f"throughput  mean {sum(vals) / len(vals):,.1f} "
                         f"{unit}   max {max(vals):,.1f} {unit}")
    return times


def _mfu_section(lines: list[str], meta: dict, device: dict,
                 times: list[float]) -> None:
    from distributed_model_parallel_tpu.utils.profiling import (
        TPU_PEAK_FLOPS,
        match_device_kind,
    )

    lines.append("== efficiency ==")
    kind = device.get("device_kind", "") or device.get("platform", "?")
    n_dev = max(1, int(device.get("n_devices", 1) or 1))
    peak = match_device_kind(TPU_PEAK_FLOPS, kind=kind)
    # Global analytic FLOPs from the LM trainer's run meta.
    flops_global = meta.get("model_flops_per_step")
    if not times:
        lines.append("MFU unavailable (no step-time records)")
    elif peak is None:
        lines.append(f"MFU unavailable (no peak-FLOPs table entry for "
                     f"device_kind={kind!r} — expected on CPU)")
    elif not flops_global:
        lines.append("MFU unavailable (run recorded no FLOPs-per-step; the "
                     "LM trainer records them)")
    else:
        t50 = percentile(times, 50)
        per_chip = flops_global / n_dev
        lines.append(f"MFU {per_chip / t50 / peak:.3f}  "
                     f"({per_chip / 1e12:.2f} TF/chip/step at p50 "
                     f"{_fmt_s(t50)} vs {peak / 1e12:.0f} TF/s peak "
                     f"[{kind}])")


def _serving_section(lines: list[str], by_kind: dict) -> None:
    """Serving SLOs from the engine's typed ``serve`` records
    (serve/engine.py): per-request TTFT / queue wait / per-token latency
    percentiles over the completed requests, failures, and each engine
    run's summary line (policy, tokens/s, slot utilization, page-pool
    occupancy) — one summary per engine run, so runs of two policies on
    one stream read side by side."""
    recs = by_kind.get("serve") or []
    sheds = by_kind.get("shed") or []
    brownouts = by_kind.get("brownout") or []
    if not recs and not sheds and not brownouts:
        return
    completed = [r for r in recs if r.get("event") == "completed"]
    failed = [r for r in recs if r.get("event") == "failed"]
    summaries = [r for r in recs if r.get("event") == "summary"]
    lines.append(f"== serving ({len(completed)} completed, "
                 f"{len(failed)} failed"
                 + (f", {len(sheds)} shed" if sheds else "") + ") ==")
    # Overload protection (docs/SERVING.md): typed sheds by reason and
    # the brownout ladder's travel — absent entirely on a run that
    # never shed (the common case stays terse).
    if sheds:
        by_reason: dict[str, int] = {}
        for r in sheds:
            by_reason[str(r.get("reason"))] = (
                by_reason.get(str(r.get("reason")), 0) + 1)
        lines.append("shed: " + ", ".join(
            f"{reason} {n}" for reason, n in sorted(by_reason.items())))
    if brownouts:
        max_level = max((r.get("level", 0) for r in brownouts), default=0)
        final = brownouts[-1].get("level")
        lines.append(
            f"brownout: {len(brownouts)} transitions, max level "
            f"{max_level}, final level {final} "
            f"({', '.join(brownouts[-1].get('applied') or []) or 'clear'})")
    breakers = by_kind.get("breaker") or []
    if breakers:
        opens = sum(1 for r in breakers if r.get("state") == "open")
        last: dict[str, str] = {}
        for r in breakers:
            last[str(r.get("replica"))] = str(r.get("state"))
        lines.append("breaker: " + f"{opens} opens   " + "  ".join(
            f"{k}={v}" for k, v in sorted(last.items())))
    # One percentile block PER POLICY: a stream may hold the
    # per-request records of a continuous and a static run, and a
    # blended percentile would describe neither.
    policies = sorted({str(r.get("policy")) for r in completed})
    for policy in policies:
        rows = [r for r in completed if str(r.get("policy")) == policy]
        prefix = f"[{policy}] " if len(policies) > 1 else ""
        for key, label in (("ttft_s", "TTFT"),
                           ("queue_wait_s", "queue wait"),
                           ("token_latency_s", "token latency")):
            vals = [r[key] for r in rows
                    if isinstance(r.get(key), (int, float))]
            if vals:
                lines.append(
                    f"{prefix}{label:14s} "
                    f"p50 {_fmt_s(percentile(vals, 50))}   "
                    f"p99 {_fmt_s(percentile(vals, 99))}   "
                    f"max {_fmt_s(max(vals))}")
    for s in summaries:
        occ = s.get("page_occupancy") or {}
        tps = s.get("tokens_per_s")
        util = s.get("slot_utilization")
        hit = s.get("cache_hit_rate")
        accept = s.get("draft_accept_rate")
        shed_n = s.get("requests_shed")
        lines.append(
            f"engine[{s.get('policy')}]: "
            f"{s.get('tokens_generated')} tokens"
            + (f" at {tps:,.1f} tokens/s" if isinstance(tps, (int, float))
               else "")
            + (f", slot utilization {util:.2f}"
               if isinstance(util, (int, float)) else "")
            + (f", page occupancy mean {occ.get('mean'):.2f} "
               f"max {occ.get('max'):.2f}"
               if isinstance(occ.get("mean"), (int, float)) else "")
            + (f", {shed_n} shed ({s.get('requests_rejected', 0)} "
               f"rejected)" if shed_n else ""))
        # Prefix-cache + speculative-decoding line only when either
        # lever was on (docs/SERVING.md) — a plain engine stays terse.
        if s.get("prefix_cache") or s.get("spec_k"):
            parts = []
            if s.get("prefix_cache"):
                parts.append(
                    f"cache hit {hit:.2f}"
                    if isinstance(hit, (int, float)) else "cache hit -")
                parts.append(f"{s.get('prefill_tokens_saved', 0)} prefill "
                             f"tokens saved")
                parts.append(f"{s.get('cached_prefix_pages', 0)} cached "
                             f"pages ({s.get('prefix_evictions', 0)} "
                             f"evicted)")
            if s.get("spec_k"):
                parts.append(
                    f"draft accept {accept:.2f} "
                    f"({s.get('draft_tokens_accepted', 0)}"
                    f"/{s.get('draft_tokens_proposed', 0)} at "
                    f"k={s.get('spec_k')})"
                    if isinstance(accept, (int, float))
                    else f"draft accept - (k={s.get('spec_k')})")
            lines.append("  " + ", ".join(parts))
    for r in failed:
        lines.append(f"  FAILED {r.get('request')}: {r.get('error')} "
                     f"({str(r.get('detail', ''))[:80]})")


def _fleet_serving_section(lines: list[str], by_kind: dict) -> None:
    """Multi-replica fleet serving (serve/fleet.py): router assignment
    counts from the typed ``router`` records, live migrations from the
    ``migration`` records, cell lifecycle events (typed ``cell``
    records, serve/cells.py) and the fleet summary's replica + per-cell
    tables — the post-mortem view of a replica- or cell-kill drill."""
    routed = by_kind.get("router") or []
    migs = by_kind.get("migration") or []
    fleet_sums = [r for r in by_kind.get("serve") or []
                  if r.get("event") == "summary"
                  and r.get("policy") == "fleet"]
    if not routed and not migs and not fleet_sums:
        return
    lines.append(f"== fleet serving ({len(routed)} routed, "
                 f"{len(migs)} migrated) ==")
    per: dict[str, int] = {}
    reasons: dict[str, int] = {}
    for r in routed:
        per[str(r.get("replica"))] = per.get(str(r.get("replica")), 0) + 1
        reasons[str(r.get("reason"))] = (
            reasons.get(str(r.get("reason")), 0) + 1)
    if per:
        lines.append("router: " + "  ".join(
            f"{name}={n}" for name, n in sorted(per.items()))
            + "   (" + ", ".join(f"{k} {v}"
                                 for k, v in sorted(reasons.items())) + ")")
    shown = migs[:12]
    for m in shown:
        lines.append(
            f"  migrated {m.get('request')}: {m.get('from_replica')} -> "
            f"{m.get('to_replica')} at {m.get('tokens_committed')} "
            f"committed tokens ({m.get('state')}, {m.get('pages')} pages, "
            f"round {m.get('round')})")
    if len(migs) > len(shown):
        lines.append(f"  ... and {len(migs) - len(shown)} more migrations")
    cell_recs = by_kind.get("cell") or []
    if cell_recs:
        ev: dict[str, int] = {}
        for c in cell_recs:
            ev[str(c.get("event"))] = ev.get(str(c.get("event")), 0) + 1
        lines.append("cell events: " + ", ".join(
            f"{k} x{v}" for k, v in sorted(ev.items())))
    for s in fleet_sums:
        reps = s.get("replicas") or {}
        states = "  ".join(
            f"{name}={info.get('state')}"
            + (f"(x{info.get('kills')} kills)" if info.get("kills") else "")
            for name, info in sorted(reps.items()))
        lines.append(
            f"fleet: {s.get('live_replicas')}/{s.get('n_replicas')} "
            f"replicas live, {s.get('requests_migrated', 0)} requests "
            f"migrated over {s.get('migrations', 0)} moves, "
            f"{s.get('replica_kills', 0)} kills   {states}")
        cb = s.get("cells") or {}
        if cb:
            layout = cb.get("layout") or {}
            live = cb.get("live") or []
            extra = ""
            if cb.get("cell_kills"):
                extra += f", {cb['cell_kills']} cell kills"
            if cb.get("partitioned"):
                extra += f", partitioned {','.join(cb['partitioned'])}"
            lines.append(
                f"  cells: {len(live)}/{len(layout)} live ("
                + "  ".join(f"{c}[{len(m)}]"
                            for c, m in sorted(layout.items()))
                + ")" + extra)
        # Per-tenant SLO attainment from the fleet summary's metering
        # rollup (utils/metering.py): goodput fraction = in-deadline
        # tokens / tokens, next to the tenant's shed count.
        mt = s.get("metering") or {}
        for name, row in (mt.get("by_tenant") or {}).items():
            gf = row.get("goodput_fraction")
            lines.append(
                f"  tenant {name:<12} {row.get('requests', 0):>4} req   "
                f"goodput "
                + (f"{gf:6.1%}" if isinstance(gf, (int, float))
                   else "     -")
                + f"   sheds {row.get('sheds', 0)}   chip "
                  f"{row.get('chip_s', 0.0):.4f}s")


def _rtrace_summary(by_kind: dict) -> dict | None:
    """Fold the ``rtrace`` plane into the joined-timeline summary both
    report forms share: timeline/orphan counts, the terminal-event
    breakdown, linked migration hops, and fleet-wide per-phase seconds.
    None when the stream carries no request traces."""
    recs = by_kind.get("rtrace") or []
    if not recs:
        return None
    traces = join_request_traces(recs)
    terminals: dict[str, int] = {}
    phases: dict[str, float] = {}
    orphans = hops = 0
    for t in traces.values():
        if t["orphan"]:
            orphans += 1
        if t["terminal"]:
            terminals[t["terminal"]] = terminals.get(t["terminal"], 0) + 1
        hops += len(t["hops"])
        for p, s in t["phases"].items():
            phases[p] = phases.get(p, 0.0) + s
    return {
        "traces": len(traces),
        "orphans": orphans,
        "terminals": dict(sorted(terminals.items())),
        "migration_hops": hops,
        "phase_seconds": {p: round(s, 4)
                          for p, s in sorted(phases.items())},
    }


def _rtrace_section(lines: list[str], by_kind: dict) -> None:
    """Request-trace rollup (``rtrace`` records, utils/tracing.py):
    joined per-request timelines, terminal accounting and fleet-wide
    phase attribution. The zoomable per-request waterfall is
    ``scripts/dmp_xray.py``; this is the at-a-glance version."""
    s = _rtrace_summary(by_kind)
    if s is None:
        return
    lines.append(f"== request traces ({s['traces']} timelines) ==")
    terms = "  ".join(f"{k}={v}" for k, v in s["terminals"].items())
    lines.append(f"terminals: {terms or '(none)'}   orphans: "
                 f"{s['orphans']}   migration hops: {s['migration_hops']}")
    if s["phase_seconds"]:
        lines.append("phase seconds: " + "  ".join(
            f"{p}={v:.4f}s" for p, v in s["phase_seconds"].items()))
    lines.append("  (per-request waterfall: "
                 "python scripts/dmp_xray.py <stream> --worst 5)")


def _capacity_data(records: list[dict], by_kind: dict) -> dict | None:
    """Capacity observatory fold (serve/capacity.py over the ``meter``
    and ``utilization`` records, utils/metering.py). None when the
    stream carries no metering plane — training-only reports stay
    terse."""
    if not (by_kind.get("meter") or by_kind.get("utilization")):
        return None
    from distributed_model_parallel_tpu.serve.capacity import (
        build_capacity,
    )
    return build_capacity(records)


def _capacity_section(lines: list[str], records: list[dict],
                      by_kind: dict) -> None:
    """Fleet capacity rollup: billed cost per tenant, per-replica duty
    cycles, and sustainable-throughput headroom. The zoomable version
    (duty bars, what-if projections, billing-invariant gate) is
    ``scripts/dmp_capacity.py``."""
    cap = _capacity_data(records, by_kind)
    if cap is None:
        return
    lines.append(f"== capacity ({cap['meter_records']} meter records) ==")
    lines.append(
        f"observed {cap['tokens_per_s']:.1f} tok/s   sustainable "
        f"{cap['sustainable_tokens_per_s']:.1f} tok/s   headroom "
        f"{cap['headroom_tokens_per_s']:.1f} tok/s"
        + (f" ({cap['headroom_fraction']:.0%})"
           if cap.get("headroom_fraction") is not None else "")
        + f"   billed chip {cap['billed_chip_s']:.4f}s page "
          f"{cap['billed_page_s']:.4f}s   metering overhead "
          f"{cap['metering_overhead']['fraction']:.2%}")
    for name, row in cap["replicas"].items():
        duty = row["duty"]
        lines.append(
            f"  {name:<6} busy {duty['busy']:>4.0%}  stalled "
            f"{duty['stalled']:>4.0%}  brownout {duty['brownout']:>4.0%}  "
            f"idle {duty['idle']:>4.0%}  quarantined "
            f"{duty['quarantined']:>4.0%}  sustainable "
            f"{row['sustainable_tokens_per_s']:.1f} tok/s")
    for name, row in cap["tenants"].items():
        lines.append(
            f"  tenant {name:<12} {row['requests']:>4} req   chip "
            f"{row['chip_s']:.4f}s   page {row['page_s']:.4f}s   "
            f"{row['tokens']} tokens   {row['sheds']} sheds   "
            f"{row['hops']} hops")
    lines.append("  (observatory: python scripts/dmp_capacity.py "
                 "<stream> --what-if 2 --gate)")


def _plan_section(lines: list[str], by_kind: dict) -> None:
    """Parallelism-plan records (autotune/planner.emit_plan_record): which
    layout the autotuner chose, at which global step, and the nearest
    alternatives — so a re-planned elastic restart is auditable."""
    plans = by_kind.get("plan") or []
    if not plans:
        return
    lines.append(f"== parallelism plan ({len(plans)} planned) ==")
    for r in plans:
        axes = r.get("axes") or {}
        degrees = "x".join(f"{k}{v}" for k, v in axes.items()
                           if isinstance(v, (int, float)) and v > 1) or "dp1"
        cost = r.get("cost") or {}
        # "measured" only when a measurement actually succeeded —
        # error-only measured rows mean the analytic ranking stood.
        how = ("measured" if any("measured_s" in m
                                 for m in r.get("measured") or [])
               else "analytic")
        lines.append(
            f"  step {r.get('global_step', 0):>6}: "
            f"{r.get('strategy', '?')}[{degrees}] "
            f"M={r.get('num_microbatches', 1)} on "
            f"{r.get('n_devices', '?')} devices ({r.get('reason', '?')}, "
            f"{how}; {r.get('n_feasible', '?')} feasible / "
            f"{r.get('n_rejected', 0)} rejected)")
        if cost.get("total_s") is not None:
            lines.append(
                f"      predicted {_fmt_s(cost['total_s'])}/step "
                f"(compute {_fmt_s(cost.get('compute_s', 0))} x bubble "
                f"{cost.get('bubble', 1):.2f}, comm "
                f"{_fmt_s(cost.get('comm_s', 0))}, hidden "
                f"{_fmt_s(cost.get('comm_hidden_s', 0))})")
        # Alternatives = the analytic top minus the CHOSEN plan (which is
        # not necessarily top[0] — a measurement may have overruled it;
        # the model's preferred-but-rejected layout is then the most
        # interesting line here).
        chosen_key = (r.get("strategy"), axes, r.get("num_microbatches"))
        alts = [a for a in (r.get("top") or [])
                if (a.get("strategy"), a.get("axes"),
                    a.get("num_microbatches")) != chosen_key]
        for alt in alts[:3]:
            a = alt.get("axes") or {}
            ad = "x".join(f"{k}{v}" for k, v in a.items()
                          if isinstance(v, (int, float)) and v > 1) or "dp1"
            at = (alt.get("cost") or {}).get("total_s")
            lines.append(f"      alt {alt.get('strategy', '?')}[{ad}]"
                         + (f" {_fmt_s(at)}/step" if at else ""))


def _spans_section(lines: list[str], by_kind: dict) -> None:
    """Span-time rollup (``span`` records, utils/tracing.py): total and
    mean duration per span name — where the run's instrumented host time
    went. The zoomable view is ``scripts/dmp_trace.py``; this is the
    at-a-glance version."""
    spans = by_kind.get("span") or []
    if not spans:
        return
    totals: dict[str, list] = {}
    for r in spans:
        d = r.get("dur_s")
        if isinstance(d, (int, float)):
            totals.setdefault(str(r.get("name")), []).append(float(d))
    lines.append(f"== spans ({len(spans)} records, "
                 f"{len(totals)} names) ==")
    ranked = sorted(totals.items(), key=lambda kv: -sum(kv[1]))
    for name, ds in ranked[:12]:
        lines.append(f"  {name:20s} {_fmt_s(sum(ds)):>10s} total "
                     f"x{len(ds):<5d} mean {_fmt_s(sum(ds) / len(ds))}")
    lines.append("  (export the zoomable timeline: "
                 "python scripts/dmp_trace.py <stream> -o trace.json)")


def _comm_section(lines: list[str], by_kind: dict) -> None:
    snaps = by_kind.get("metrics") or []
    counters = snaps[-1].get("counters", {}) if snaps else {}
    comm = {k: v for k, v in counters.items()
            if k.startswith("collective_wire_bytes_est")}
    lines.append("== communication (trace-time estimates, per compile) ==")
    if not comm:
        lines.append("(no collective traffic recorded)")
    else:
        for key in sorted(comm):
            tags = key[key.index("{") + 1:-1]
            traces = counters.get(f"collective_traces{{{tags}}}", 0)
            ops = counters.get(f"collective_ops_est{{{tags}}}")
            # Message counts next to bytes: the alpha term of an
            # alpha-beta comm model (autotune/cost_model.py) — many small
            # collectives read differently from one big one here.
            ops_txt = f", {ops:.0f} msgs" if ops is not None else ""
            lines.append(f"{tags:40s} {_fmt_bytes(comm[key]):>12s} wire "
                         f"({traces:.0f} traces{ops_txt})")
    n_compiles = counters.get("jax_compiles")
    if n_compiles is not None:
        secs = counters.get("jax_compile_seconds", 0.0)
        lines.append(f"compilations: {n_compiles:.0f} "
                     f"({secs:.1f}s total backend compile time)")


def _memory_section(lines: list[str], by_kind: dict) -> None:
    mems = by_kind.get("memory") or []
    if not mems:
        return
    lines.append("== device memory ==")
    peak_by_dev: dict = {}
    for rec in mems:
        for d in rec.get("devices", []):
            cur = peak_by_dev.get(d.get("id"), 0)
            peak_by_dev[d.get("id")] = max(
                cur, d.get("peak_bytes_in_use", d.get("bytes_in_use", 0)))
    for dev_id, peak in sorted(peak_by_dev.items()):
        lines.append(f"device {dev_id}: peak {_fmt_bytes(peak)} in use")


def _resilience_section(lines: list[str], by_kind: dict,
                        t0: float | None = None) -> None:
    """Failure / recovery / divergence timeline: every detected failure
    (non-finite, stall, torn checkpoint, failed save, preemption, replica
    divergence) next to the recovery action the supervisor or consistency
    sentinel took (train/resilience.py, train/consistency.py), in event
    order. ``t0`` overrides the timeline origin (the fleet report passes
    the campaign start — a resumed tenant's stream holds several
    ``run_start`` records, and the last one would put earlier attempts'
    events at negative offsets)."""
    fails = by_kind.get("failure") or []
    recs = by_kind.get("recovery") or []
    cons = by_kind.get("consistency") or []
    resumes = by_kind.get("resume") or []
    if not fails and not recs and not cons and not resumes:
        return
    starts = by_kind.get("run_start") or []
    if t0 is None and starts:
        t0 = starts[-1].get("ts")
    if t0 is None:
        t0 = min((r.get("ts") for r in fails + recs + cons + resumes
                  if isinstance(r.get("ts"), (int, float))), default=0.0)
    header = f"== resilience ({len(fails)} failures, {len(recs)} recoveries"
    if cons:
        header += f", {len(cons)} consistency"
    if resumes:
        header += f", {len(resumes)} resumes"
    lines.append(header + ") ==")
    events = sorted(fails + recs + cons + resumes,
                    key=lambda r: r.get("ts") or 0.0)
    for r in events:
        dt = (r["ts"] - t0) if isinstance(r.get("ts"), (int, float)) else 0.0
        if r.get("kind") == "resume":
            extra = " ".join(
                f"{k}={r[k]}" for k in ("epoch", "batch_cursor",
                                        "global_step", "saved_mesh")
                if r.get(k) is not None)
            lines.append(f"  [+{dt:7.1f}s] resume    "
                         f"{str(r.get('slot')):<24}"
                         + (f" {extra}" if extra else ""))
        elif r.get("kind") == "consistency":
            extra = " ".join(
                f"{k}={r[k]}" for k in ("replicas", "groups", "outliers",
                                        "leaves", "check")
                if r.get(k) is not None)
            lines.append(f"  [+{dt:7.1f}s] consistency "
                         f"{str(r.get('status')):<22}"
                         + (f" {extra}" if extra else ""))
        elif r.get("kind") == "failure" or "error" in r:
            extra = " ".join(
                f"{k}={r[k]}" for k in ("epoch", "stage", "attempts",
                                        "retries_left")
                if r.get(k) is not None)
            detail = str(r.get("detail", ""))[:100]
            lines.append(f"  [+{dt:7.1f}s] failure   "
                         f"{str(r.get('error')):<24}"
                         + (f" {extra}" if extra else "")
                         + (f"  ({detail})" if detail else ""))
        else:
            extra = " ".join(
                f"{k}={r[k]}" for k in ("slot", "epoch", "retries_left",
                                        "lr_scale")
                if r.get(k) is not None)
            lines.append(f"  [+{dt:7.1f}s] recovery  "
                         f"{str(r.get('action')):<24}"
                         + (f" {extra}" if extra else ""))


def _trace_section(lines: list[str], trace_dir: str, top: int) -> None:
    from distributed_model_parallel_tpu.utils import xplane

    lines.append(f"== xplane trace ({trace_dir}) ==")
    try:
        xplane._pb2()
    except xplane.XplaneProtosUnavailable as e:
        lines.append(f"trace analysis skipped: {e}")
        return
    try:
        plane = xplane.device_plane(xplane.load_xspace(trace_dir))
    except (FileNotFoundError, ValueError) as e:
        lines.append(f"trace analysis skipped: {e}")
        return
    mods = xplane.module_events(plane)
    rows = xplane.exclude_envelopes(xplane.op_breakdown(plane))
    mod_s = sum(m.duration_ps for m in mods) / 1e12
    lines.append(f"{len(mods)} module executions, {mod_s:.4f}s device time")
    totals = xplane.category_totals(rows)
    for cat, sec in totals.items():
        lines.append(f"  {cat:24s} {sec * 1e3:10.2f} ms")
    # Comm/compute overlap from the measured device timeline: module wall
    # time vs summed op time. If collectives were fully serialized the
    # module wall ≈ compute + comm; fully hidden ≈ compute alone — so the
    # exposed share is the wall's excess over compute, capped at the comm
    # total. This is how "bucketed allreduce overlaps the backward" stops
    # being an assertion (reference Readme.md:148-157) and becomes a
    # number.
    comm_s = totals.get("allreduce", 0.0)
    if comm_s > 0 and mod_s > 0:
        compute_s = sum(totals.values()) - comm_s
        exposed = min(comm_s, max(0.0, mod_s - compute_s))
        lines.append(
            f"comm overlap: {comm_s * 1e3:.2f} ms collective device time, "
            f"{exposed * 1e3:.2f} ms exposed on the critical path → "
            f"comm-hidden fraction {1 - exposed / comm_s:.1%}")
    lines.append(f"top {top} ops:")
    for r in rows[:top]:
        lines.append(f"  {r.total_ps / 1e9:9.3f} ms x{r.count:6d} "
                     f"{r.category:18s} {r.name}")


def build_report(records: list[dict], *, trace_dir: str | None = None,
                 top: int = 15) -> str:
    """Render the report text for one telemetry stream."""
    by_kind = _by_kind(records)
    lines: list[str] = []

    starts = by_kind.get("run_start") or [{}]
    start = starts[-1]
    device = start.get("device", {}) or {}
    meta = start.get("meta", {}) or {}
    lines.append("== run ==")
    lines.append(
        f"run {start.get('run', '?')}   device "
        f"{device.get('platform', '?')} x{device.get('n_devices', '?')} "
        f"({device.get('device_kind', '?')})   jax {start.get('jax', '?')}")
    if meta:
        lines.append("meta " + " ".join(
            f"{k}={v}" for k, v in sorted(meta.items())
            if not isinstance(v, (dict, list))))
    for f in by_kind.get("failure", []):
        lines.append(f"FAILURE: {f.get('error')} — {f.get('detail', '')}")

    steps = by_kind.get("step", [])
    times = _steps_section(lines, steps)
    _mfu_section(lines, meta, device, times)
    _serving_section(lines, by_kind)
    _fleet_serving_section(lines, by_kind)
    _capacity_section(lines, records, by_kind)
    _rtrace_section(lines, by_kind)
    _plan_section(lines, by_kind)
    _spans_section(lines, by_kind)
    _comm_section(lines, by_kind)
    _memory_section(lines, by_kind)
    _resilience_section(lines, by_kind)

    epochs = by_kind.get("epoch", [])
    if epochs:
        lines.append(f"== epochs ({len(epochs)}) ==")
        last = epochs[-1]
        keys = [k for k in ("epoch", "loss_train", "acc1_train", "loss_val",
                            "acc1_val", "time_per_batch", "tokens_per_s")
                if last.get(k) is not None]
        lines.append("last: " + "  ".join(
            f"{k}={last[k]:.4g}" if isinstance(last[k], float)
            else f"{k}={last[k]}" for k in keys))

    ends = by_kind.get("run_end")
    if ends:
        lines.append(f"run wall time: {ends[-1].get('wall_s', 0):.1f}s")
    else:
        lines.append("(no run_end record — run still in flight or killed)")

    if trace_dir:
        _trace_section(lines, trace_dir, top)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Machine-readable report (--json): the same answers as data, not text
# ---------------------------------------------------------------------------

def _pcts(vals: list[float]) -> dict | None:
    if not vals:
        return None
    return {"p50": percentile(vals, 50), "p90": percentile(vals, 90),
            "p99": percentile(vals, 99), "max": max(vals),
            "mean": sum(vals) / len(vals), "n": len(vals)}


def build_report_data(records: list[dict]) -> dict:
    """The report as one JSON-ready dict — sections as keys — so CI and
    the cockpit consume reports without screen-scraping. The section
    keys and the inner shapes of ``headline`` / ``resilience`` /
    ``serving`` are a pinned schema
    (tests/test_report_json.py): additions are fine, renames and
    removals are breaking."""
    by_kind = _by_kind(records)
    start = (by_kind.get("run_start") or [{}])[-1]
    steps = by_kind.get("step") or []
    times = [r["step_time_s"] for r in steps
             if isinstance(r.get("step_time_s"), (int, float))]
    throughput = None
    for key, unit in (("tokens_per_s", "tokens/s"),
                      ("samples_per_s", "samples/s")):
        vals = [r[key] for r in steps
                if isinstance(r.get(key), (int, float))]
        if vals:
            throughput = {"unit": unit, "mean": sum(vals) / len(vals),
                          "max": max(vals)}
            break
    headline = {
        "n_steps": len(steps),
        "step_time_s": _pcts(times),
        "throughput": throughput,
    }
    resilience_events = sorted(
        (by_kind.get("failure") or []) + (by_kind.get("recovery") or [])
        + (by_kind.get("consistency") or []) + (by_kind.get("resume") or [])
        + (by_kind.get("fault") or []) + (by_kind.get("postmortem") or []),
        key=lambda r: r.get("ts") or 0.0)
    resilience = {
        "failures": len(by_kind.get("failure") or []),
        "recoveries": len(by_kind.get("recovery") or []),
        "consistency": len(by_kind.get("consistency") or []),
        "resumes": len(by_kind.get("resume") or []),
        "postmortems": [r.get("bundle")
                        for r in by_kind.get("postmortem") or []],
        "events": resilience_events,
    }
    serve = by_kind.get("serve") or []
    completed = [r for r in serve if r.get("event") == "completed"]
    policies: dict[str, dict] = {}
    for policy in sorted({str(r.get("policy")) for r in completed}):
        rows = [r for r in completed if str(r.get("policy")) == policy]
        policies[policy] = {
            key: _pcts([r[key] for r in rows
                        if isinstance(r.get(key), (int, float))])
            for key in ("ttft_s", "queue_wait_s", "token_latency_s")}
    serving = {
        "completed": len(completed),
        "failed": len([r for r in serve if r.get("event") == "failed"]),
        "policies": policies,
        "summaries": [r for r in serve if r.get("event") == "summary"],
        # Overload protection (docs/SERVING.md): the typed shed records
        # and brownout-ladder transitions, verbatim.
        "shed": by_kind.get("shed") or [],
        "brownout": by_kind.get("brownout") or [],
        "breaker": by_kind.get("breaker") or [],
    }
    spans: dict[str, dict] = {}
    for r in by_kind.get("span") or []:
        d = r.get("dur_s")
        if isinstance(d, (int, float)):
            cell = spans.setdefault(str(r.get("name")),
                                    {"total_s": 0.0, "count": 0})
            cell["total_s"] += float(d)
            cell["count"] += 1
    alerts = by_kind.get("alert") or []
    snaps = by_kind.get("metrics") or []
    ends = by_kind.get("run_end") or []
    return {
        "run": {"run": start.get("run"), "device": start.get("device"),
                "jax": start.get("jax"), "meta": start.get("meta")},
        "headline": headline,
        "resilience": resilience,
        "serving": serving,
        "rtrace": _rtrace_summary(by_kind),
        "capacity": _capacity_data(records, by_kind),
        "plan": by_kind.get("plan") or [],
        "spans": spans,
        "alerts": alerts,
        "counters": (snaps[-1].get("counters") or {}) if snaps else {},
        "epochs": {"count": len(by_kind.get("epoch") or []),
                   "last": (by_kind.get("epoch") or [None])[-1]},
        "wall_s": ends[-1].get("wall_s") if ends else None,
    }


def build_fleet_data(records: list[dict]) -> dict:
    """The fleet report as data: tenant table, fault ledger, health and
    alert timelines, unrecovered ledger."""
    tenants = sorted({r["tenant"] for r in records if r.get("tenant")})
    lifecycle = [r for r in records if r.get("kind") == "tenant"]
    out_tenants: dict[str, dict] = {}
    for tenant in tenants:
        recs = [r for r in records if r.get("tenant") == tenant]
        by_kind = _by_kind(recs)
        states = [r for r in lifecycle if r.get("name") == tenant]
        out_tenants[tenant] = {
            "state": states[-1].get("event") if states else None,
            "failures": len(by_kind.get("failure") or []),
            "recoveries": len(by_kind.get("recovery") or []),
            "resumes": len(by_kind.get("resume") or []),
            "epochs": len(by_kind.get("epoch") or []),
            "postmortems": [r.get("bundle")
                            for r in by_kind.get("postmortem") or []],
        }
    ledger = pair_faults(records)
    return {
        "tenants": out_tenants,
        "ledger": ledger,
        "unpaired": [r for r in ledger if not r["paired"]],
        "unrecovered": [{"name": r.get("name"), "error": r.get("error")}
                        for r in lifecycle if r.get("event") == "failed"],
        "health": [r for r in records if r.get("kind") == "health"],
        "alerts": [r for r in records if r.get("kind") == "alert"],
    }


# ---------------------------------------------------------------------------
# Fleet report: merged multi-tenant streams (orchestrator/ + dmp_soak.py)
# ---------------------------------------------------------------------------

# Which detection (failure error / consistency status) and recovery
# (recovery action / consistency status) records close the loop for each
# injected fault kind — the pairing the fault ledger audits. A fault is
# "paired" when a detection AND an action matching these sets appear in
# its tenant's stream after the injection.
FAULT_PAIRING: dict[str, tuple[frozenset, frozenset]] = {
    "nan_loss": (frozenset({"non-finite"}), frozenset({"restored"})),
    "nan_params": (frozenset({"non-finite"}), frozenset({"restored"})),
    "preempt": (frozenset({"preempted"}),
                frozenset({"checkpoint-and-exit"})),
    "stall": (frozenset({"stall"}), frozenset({"checkpoint-and-exit"})),
    "save_fail": (frozenset({"checkpoint-save-failed"}),
                  frozenset({"save-retried", "save-skipped"})),
    "tear_save": (frozenset({"checkpoint-torn"}),
                  frozenset({"checkpoint-fallback"})),
    # Silent corruption: detection is the sentinel's divergence (or, for
    # a consensus-poisoning drill, non-finite); the closing action is an
    # in-place replica re-broadcast, or a good-slot restore when there
    # was no quorum.
    "bitflip": (frozenset({"divergence", "non-finite"}),
                frozenset({"repaired", "replica-rebroadcast", "restored"})),
    "desync": (frozenset({"divergence", "non-finite"}),
               frozenset({"repaired", "replica-rebroadcast", "restored"})),
    "grad_skew": (frozenset({"divergence", "non-finite"}),
                  frozenset({"repaired", "replica-rebroadcast",
                             "restored"})),
}


def _detection_key(r: dict) -> str | None:
    if r.get("kind") == "failure":
        return r.get("error")
    if r.get("kind") == "consistency" and r.get("status") != "repaired":
        return r.get("status")
    return None


def _action_key(r: dict) -> str | None:
    if r.get("kind") == "recovery":
        return r.get("action")
    if r.get("kind") == "consistency" and r.get("status") == "repaired":
        return "repaired"
    return None


def pair_faults(records: list[dict]) -> list[dict]:
    """Pair every injected fault (typed ``fault`` record,
    train/resilience.py) with the detection and recovery that followed it
    in the same tenant's stream. Returns one ledger row per injection:
    ``{tenant, fault, site, detected, action, paired}``. Detections and
    actions are consumed in order, so two faults cannot claim the same
    recovery."""
    from distributed_model_parallel_tpu.utils.faults import (
        DEGRADATION_KINDS,
    )

    by_tenant: dict[str, list[dict]] = {}
    for r in records:
        by_tenant.setdefault(r.get("tenant") or "", []).append(r)
    ledger: list[dict] = []
    for tenant, recs in sorted(by_tenant.items()):
        used: set[int] = set()

        def _claim(start: int, match, accept: frozenset) -> tuple:
            for j in range(start, len(recs)):
                if j in used:
                    continue
                key = match(recs[j])
                if key is not None and key in accept:
                    used.add(j)
                    return j, key
            return len(recs), None

        for i, r in enumerate(recs):
            if r.get("kind") != "fault":
                continue
            kind = r.get("fault")
            if kind in DEGRADATION_KINDS:
                # Persistent degradations (slow_device/flaky_sync) are
                # not event faults with a detection/recovery pair — their
                # audit trail is the device-health timeline (quarantine,
                # migration, grow-back records), gated by the
                # degradation soak, not by this ledger.
                continue
            det_set, act_set = FAULT_PAIRING.get(
                kind, (frozenset(), frozenset()))
            dj, detected = _claim(i + 1, _detection_key, det_set)
            _, action = _claim(dj + 1 if detected else i + 1,
                               _action_key, act_set)
            ledger.append({
                "tenant": tenant, "fault": kind, "site": r.get("site"),
                "detected": detected, "action": action,
                "paired": detected is not None and action is not None,
            })
    return ledger


def _health_section(lines: list[str], records: list[dict],
                    t0: float) -> None:
    """Device-health timeline (utils/health.py): score transitions,
    quarantines and probation reinstates from the typed ``health``
    records, interleaved with the proactive migrations (tenant
    preemptions with reason ``device-degraded``) and grow-backs they
    caused — the self-healing story as one sequence."""
    health = [r for r in records if r.get("kind") == "health"]
    moves = [r for r in records if r.get("kind") == "tenant"
             and (str(r.get("reason", "")).startswith("device-degraded")
                  or str(r.get("reason", "")) == "grow-back"
                  or r.get("event") == "grow-back")]
    if not health and not moves:
        return
    n_q = sum(1 for r in health if r.get("event") == "quarantine")
    n_r = sum(1 for r in health if r.get("event") == "reinstate")
    lines.append(f"== device health ({len(health)} events, "
                 f"{n_q} quarantines, {n_r} reinstates) ==")
    for r in sorted(health + moves, key=lambda r: r.get("ts") or 0.0):
        dt = (r["ts"] - t0) if isinstance(r.get("ts"), (int, float)) else 0.0
        if r.get("kind") == "health":
            extra = " ".join(
                f"{k}={r[k]}" for k in ("signal", "score", "value",
                                        "baseline", "probation_ticks")
                if r.get(k) is not None)
            lines.append(f"  [+{dt:7.1f}s] {str(r.get('event')):<12} "
                         f"devices={r.get('devices')}"
                         + (f" {extra}" if extra else ""))
        elif r.get("event") == "grow-back":
            lines.append(f"  [+{dt:7.1f}s] grow-back    "
                         f"{r.get('name')}: {len(r.get('devices') or [])} "
                         f"-> {r.get('target_devices')} devices at step "
                         f"{r.get('global_step')}")
        else:
            lines.append(f"  [+{dt:7.1f}s] migration    "
                         f"{r.get('name')}: preempted off "
                         f"{r.get('devices') if r.get('devices') is not None else 'its slice'}"
                         f" ({r.get('reason')}) at step "
                         f"{r.get('global_step')}")


def build_fleet_report(records: list[dict]) -> str:
    """Render the fleet-level report for a merged multi-tenant record
    stream (utils/telemetry.merge_streams): the orchestration timeline,
    the device-health timeline (quarantines, migrations, grow-backs),
    one resilience timeline per tenant, per-tenant recovery/repair/resume
    counts, the injected-fault ledger, and the unrecovered-failure
    ledger."""
    lines: list[str] = []
    tenants = sorted({r["tenant"] for r in records if r.get("tenant")})
    lifecycle = [r for r in records if r.get("kind") == "tenant"]
    topology = [r for r in records if r.get("kind") == "event"
                and "topology" in str(r.get("message", ""))]
    lines.append(f"== fleet ({len(tenants)} tenants) ==")
    t0 = min((r.get("ts") for r in records
              if isinstance(r.get("ts"), (int, float))), default=0.0)
    for r in sorted(lifecycle + topology, key=lambda r: r.get("ts") or 0.0):
        dt = (r["ts"] - t0) if isinstance(r.get("ts"), (int, float)) else 0.0
        if r.get("kind") == "event":
            lines.append(f"  [+{dt:7.1f}s] {r.get('message')}")
        else:
            extra = " ".join(
                f"{k}={r[k]}" for k in ("devices", "global_step", "reason",
                                        "attempt", "error")
                if r.get(k) is not None)
            lines.append(f"  [+{dt:7.1f}s] {str(r.get('name')):<12} "
                         f"{str(r.get('event')):<20}"
                         + (f" {extra}" if extra else ""))

    _health_section(lines, records, t0)

    for tenant in tenants:
        recs = [r for r in records if r.get("tenant") == tenant]
        by_kind = _by_kind(recs)
        counts = {
            "failures": len(by_kind.get("failure") or []),
            "recoveries": len(by_kind.get("recovery") or []),
            "repairs": len([c for c in by_kind.get("consistency") or []
                            if c.get("status") == "repaired"]),
            "resumes": len(by_kind.get("resume") or []),
            "epochs": len(by_kind.get("epoch") or []),
        }
        lines.append(f"== tenant {tenant} ==")
        lines.append("  " + "  ".join(f"{k}={v}"
                                      for k, v in counts.items()))
        sub: list[str] = []
        _resilience_section(sub, by_kind, t0)
        lines += ["  " + s for s in sub]

    ledger = pair_faults(records)
    if ledger:
        lines.append(f"== fault ledger ({len(ledger)} injected) ==")
        for row in ledger:
            status = "ok" if row["paired"] else "UNPAIRED"
            lines.append(
                f"  {row['tenant']:<12} {row['fault']:<12} "
                f"detected={row['detected'] or '-':<24} "
                f"action={row['action'] or '-':<22} {status}")
    unpaired = [r for r in ledger if not r["paired"]]
    unrecovered = [r for r in lifecycle if r.get("event") == "failed"]
    lines.append(f"== unrecovered ({len(unrecovered)} tenant failures, "
                 f"{len(unpaired)} unpaired faults) ==")
    for r in unrecovered:
        lines.append(f"  {r.get('name')}: {r.get('error')}")
    for r in unpaired:
        lines.append(f"  {r['tenant']}: fault {r['fault']} never "
                     f"{'detected' if r['detected'] is None else 'recovered'}")
    if not unrecovered and not unpaired:
        lines.append("  (none — every injected fault was detected and "
                     "recovered, no tenant died)")
    return "\n".join(lines)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description="Render a run report from a telemetry JSONL stream")
    p.add_argument("jsonl", nargs="+",
                   help="telemetry stream(s) (RunLogger's "
                        "{log_dir}/{name}.jsonl); several "
                        "streams (or --fleet) render the merged "
                        "multi-tenant fleet report")
    p.add_argument("--fleet", action="store_true",
                   help="force the fleet report even for one stream "
                        "(e.g. just the orchestrator's fleet.jsonl)")
    p.add_argument("--trace", default=None,
                   help="xplane trace directory (utils/xplane.trace_to / "
                        "jax.profiler.start_trace) to join in")
    p.add_argument("--top", type=int, default=15,
                   help="top device ops to print from the trace")
    p.add_argument("--json", action="store_true",
                   help="emit the report as machine-readable JSON "
                        "(sections as keys; stable schema for the "
                        "headline/resilience/serving sections) "
                        "instead of the text renderer")
    args = p.parse_args(argv)
    for path in args.jsonl:
        if not os.path.exists(path):
            raise SystemExit(f"no such telemetry file: {path}")
    if args.fleet or len(args.jsonl) > 1:
        from distributed_model_parallel_tpu.utils.telemetry import (
            merge_streams,
        )

        if args.trace:
            raise SystemExit("--trace joins a single-run report, not the "
                             "fleet view; render the tenant's own stream")
        records = merge_streams(args.jsonl)
        if not records:
            raise SystemExit("no parseable records in any stream")
        if args.json:
            import json

            print(json.dumps(build_fleet_data(records), indent=2,
                             default=str))
            return
        print(build_fleet_report(records))
        return
    records = read_records(args.jsonl[0])
    if not records:
        raise SystemExit(f"{args.jsonl[0]} holds no parseable records")
    if args.json:
        import json

        if args.trace:
            raise SystemExit("--trace joins the text report; the JSON "
                             "schema carries stream data only")
        print(json.dumps(build_report_data(records), indent=2,
                         default=str))
        return
    print(build_report(records, trace_dir=args.trace, top=args.top))


if __name__ == "__main__":
    main()
