#!/usr/bin/env python
"""Export telemetry streams to Chrome-trace / Perfetto JSON.

One zoomable timeline from the typed records the stack already writes:

* ``span`` records (utils/tracing.py) become complete ("X") events —
  trainer epochs/drains/evals, checkpoint I/O, engine prefill chunks and
  decode rounds, orchestrator rounds — nested by the span stack's
  parent/child structure (same thread track, time containment);
* ``serve`` completed records become per-request lifecycle bars:
  queue → prefill → decode segments reconstructed from the record's
  queue_wait/ttft/wall accounting, one row per request;
* point records (failure, recovery, fault, consistency, resume, tenant,
  health, plan) become instant events on their lane;
* ``step`` records become counter tracks (step_time_ms, throughput).

Lanes: one Chrome "process" per tenant (untagged records share the
run's own lane), one "thread" per recording thread — so a fleet merge
renders every tenant's timeline stacked in one view, and the exported
file loads directly in ``chrome://tracing`` / https://ui.perfetto.dev
next to an xplane device trace.

Usage:
  python scripts/dmp_trace.py log/lm.jsonl -o /tmp/lm_trace.json
  python scripts/dmp_trace.py fleet/fleet.jsonl t0/log/t0.jsonl -o fleet.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_model_parallel_tpu.utils.telemetry import (  # noqa: E402
    merge_streams,
    read_records,
)

# Point-record kinds rendered as instant events, with the field that
# names the event in the UI.
INSTANT_KINDS = {
    "failure": "error",
    "recovery": "action",
    "fault": "fault",
    "consistency": "status",
    "resume": "slot",
    "tenant": "event",
    "health": "event",
    "event": "message",
    "plan": "strategy",
}


class _Lanes:
    """Stable pid/tid assignment: one pid per tenant lane, one tid per
    (lane, thread) pair, with Chrome metadata naming both."""

    def __init__(self, events: list):
        self.events = events
        self._pids: dict[str, int] = {}
        self._tids: dict[tuple[str, str], int] = {}

    def pid(self, lane: str) -> int:
        if lane not in self._pids:
            self._pids[lane] = len(self._pids)
            self.events.append({"ph": "M", "name": "process_name",
                                "pid": self._pids[lane], "ts": 0,
                                "args": {"name": lane}})
        return self._pids[lane]

    def tid(self, lane: str, thread: str) -> int:
        key = (lane, thread)
        if key not in self._tids:
            self._tids[key] = len(self._tids) + 1
            self.events.append({"ph": "M", "name": "thread_name",
                                "pid": self.pid(lane),
                                "tid": self._tids[key], "ts": 0,
                                "args": {"name": thread}})
        return self._tids[key]


def _lane(r: dict, default: str) -> str:
    return str(r.get("tenant") or default)


def build_trace(records: list[dict]) -> dict:
    """Chrome trace object ({"traceEvents": [...]}) for a record list
    (one stream's records, or a ts-ordered fleet merge)."""
    runs = [r for r in records if r.get("kind") == "run_start"]
    default_lane = str((runs[0].get("run") if runs else None) or "run")
    # Time origin: earliest wall-clock instant in the stream (span starts
    # included — a span can begin before the first point record lands).
    t_candidates = [r["ts"] for r in records
                    if isinstance(r.get("ts"), (int, float))]
    t_candidates += [r["t0"] for r in records if r.get("kind") == "span"
                     and isinstance(r.get("t0"), (int, float))]
    t_candidates += [r["ts"] - r["wall_s"] for r in records
                     if r.get("kind") == "serve"
                     and r.get("event") == "completed"
                     and isinstance(r.get("ts"), (int, float))
                     and isinstance(r.get("wall_s"), (int, float))]
    base = min(t_candidates, default=0.0)

    def us(t: float) -> float:
        return round((t - base) * 1e6, 1)

    events: list[dict] = []
    lanes = _Lanes(events)
    req_tids: dict[tuple[str, str], int] = {}
    for r in records:
        kind = r.get("kind")
        lane = _lane(r, default_lane)
        if kind == "span" and isinstance(r.get("t0"), (int, float)) \
                and isinstance(r.get("dur_s"), (int, float)):
            args = {k: v for k, v in r.items()
                    if k not in ("kind", "ts", "t0", "dur_s", "name",
                                 "thread", "tenant")}
            events.append({
                "ph": "X", "name": str(r.get("name")),
                "cat": "span", "ts": us(r["t0"]),
                "dur": round(r["dur_s"] * 1e6, 1),
                "pid": lanes.pid(lane),
                "tid": lanes.tid(lane, str(r.get("thread") or "main")),
                "args": args,
            })
        elif kind == "serve" and r.get("event") == "completed" \
                and isinstance(r.get("ts"), (int, float)) \
                and isinstance(r.get("wall_s"), (int, float)):
            # Reconstruct the request lifecycle from the SLO accounting:
            # arrival = completion ts - wall; queue wait, TTFT and the
            # decode tail partition the bar. One Chrome thread row per
            # request keeps concurrent requests visually parallel.
            rid = str(r.get("request"))
            key = (lane, rid)
            if key not in req_tids:
                req_tids[key] = lanes.tid(lane, f"request {rid}")
            tid = req_tids[key]
            arrive = r["ts"] - r["wall_s"]
            qw = r.get("queue_wait_s") or 0.0
            ttft = r.get("ttft_s")
            segs = [("queue", arrive, qw)]
            if isinstance(ttft, (int, float)) and ttft >= qw:
                segs.append(("prefill", arrive + qw, ttft - qw))
                segs.append(("decode", arrive + ttft,
                             max(0.0, r["wall_s"] - ttft)))
            pid = lanes.pid(lane)
            for name, t0, dur in segs:
                if dur <= 0:
                    continue
                events.append({
                    "ph": "X", "name": name, "cat": "serve-request",
                    "ts": us(t0), "dur": round(dur * 1e6, 1),
                    "pid": pid, "tid": tid,
                    "args": {"request": rid,
                             "new_tokens": r.get("new_tokens"),
                             "prompt_tokens": r.get("prompt_tokens"),
                             "policy": r.get("policy")},
                })
        elif kind == "step" and isinstance(r.get("ts"), (int, float)):
            pid = lanes.pid(lane)
            if isinstance(r.get("step_time_s"), (int, float)):
                events.append({
                    "ph": "C", "name": "step_time_ms", "pid": pid,
                    "ts": us(r["ts"]),
                    "args": {"ms": round(r["step_time_s"] * 1e3, 3)}})
            for k in ("samples_per_s", "tokens_per_s"):
                if isinstance(r.get(k), (int, float)):
                    events.append({
                        "ph": "C", "name": k, "pid": pid,
                        "ts": us(r["ts"]), "args": {k: round(r[k], 1)}})
        elif kind in INSTANT_KINDS and isinstance(r.get("ts"),
                                                  (int, float)):
            label = r.get(INSTANT_KINDS[kind])
            events.append({
                "ph": "i", "name": f"{kind}:{label}", "cat": kind,
                "ts": us(r["ts"]), "s": "p",
                "pid": lanes.pid(lane),
                "args": {k: v for k, v in r.items()
                         if k not in ("kind", "ts", "tenant")
                         and isinstance(v, (str, int, float, bool))},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"origin_unix_s": base,
                          "exporter": "scripts/dmp_trace.py"}}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description="Export telemetry stream(s) to Chrome-trace JSON")
    p.add_argument("jsonl", nargs="+",
                   help="telemetry stream(s); several merge into one "
                        "tenant-laned fleet timeline")
    p.add_argument("-o", "--out", default=None,
                   help="output path (default: stdout)")
    args = p.parse_args(argv)
    for path in args.jsonl:
        if not os.path.exists(path):
            raise SystemExit(f"no such telemetry file: {path}")
    records = (merge_streams(args.jsonl) if len(args.jsonl) > 1
               else read_records(args.jsonl[0]))
    if not records:
        raise SystemExit("no parseable records in any stream")
    trace = build_trace(records)
    out = json.dumps(trace)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out)
        n_span = sum(1 for e in trace["traceEvents"]
                     if e.get("cat") == "span")
        print(f"{args.out}: {len(trace['traceEvents'])} events "
              f"({n_span} spans) — load in chrome://tracing or "
              f"https://ui.perfetto.dev")
    else:
        print(out)


if __name__ == "__main__":
    main()
