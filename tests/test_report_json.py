"""scripts/dmp_report.py --json: the machine-readable report. Pins the
section keys and the inner shapes of the headline / resilience /
serving sections (the schema CI and the cockpit consume —
additive changes only), the fleet --json variant, and a
scripts/dmp_top.py --once rendering smoke."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from distributed_model_parallel_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    """One synthetic stream exercising every section."""
    tmp = tmp_path_factory.mktemp("reportjson")
    path = str(tmp / "run.jsonl")
    run = telemetry.TelemetryRun(
        path, run="demo", track_compiles=False,
        device={"platform": "cpu", "n_devices": 8},
        meta={"workload": "lm", "batch_size": 8})
    for i in range(10):
        run.step(epoch=0, step=i, step_time_s=0.01 + 0.001 * i,
                 tokens_per_s=1e5, loss=2.0)
    run.failure("non-finite", detail="nan at step 3")
    run.recovery(action="restored", slot="good")
    run.record("resume", slot="emergency", global_step=4)
    for policy in ("continuous", "static"):
        run.record("serve", event="completed", request="r0", policy=policy,
                   prompt_tokens=4, new_tokens=8, queue_wait_s=0.01,
                   ttft_s=0.2, token_latency_s=0.005)
    run.record("serve", event="summary", policy="continuous",
               tokens_generated=8, tokens_per_s=100.0,
               page_occupancy={"mean": 0.4, "max": 0.6})
    # A kind no section reads (older streams hold such records).
    run.record("gate", ok=False, regressions=[], ledger="L.jsonl")
    run.record("alert", rule="step_time_drift", subject="demo",
               state="firing", value=0.5, threshold=0.1)
    run.record("postmortem", reason="test", bundle="/tmp/pm", n_records=3)
    run.finish()
    return path


def test_report_json_section_keys_are_stable(stream):
    report = _load("dmp_report")
    data = report.build_report_data(telemetry.read_records(stream))
    assert {"run", "headline", "resilience", "serving", "rtrace",
            "capacity", "plan", "spans", "alerts", "counters", "epochs",
            "wall_s"} <= set(data)
    # No meter/utilization records in this stream: the capacity
    # observatory stays out of the way.
    assert data["capacity"] is None


def test_headline_section_schema(stream):
    report = _load("dmp_report")
    data = report.build_report_data(telemetry.read_records(stream))
    h = data["headline"]
    assert h["n_steps"] == 10
    assert {"p50", "p90", "p99", "max", "mean", "n"} == set(
        h["step_time_s"])
    assert h["throughput"] == {"unit": "tokens/s", "mean": 1e5,
                               "max": 1e5}


def test_resilience_section_schema(stream):
    report = _load("dmp_report")
    data = report.build_report_data(telemetry.read_records(stream))
    r = data["resilience"]
    assert {"failures", "recoveries", "consistency", "resumes",
            "postmortems", "events"} == set(r)
    assert r["failures"] == 1 and r["recoveries"] == 1
    assert r["resumes"] == 1
    assert r["postmortems"] == ["/tmp/pm"]
    # events: ts-ordered, every resilience kind folded in
    kinds = [e["kind"] for e in r["events"]]
    assert kinds == sorted(kinds, key=lambda k: 0) or len(kinds) == 4
    assert {"failure", "recovery", "resume", "postmortem"} <= set(kinds)


def test_serving_section_schema(stream):
    report = _load("dmp_report")
    data = report.build_report_data(telemetry.read_records(stream))
    s = data["serving"]
    assert {"completed", "failed", "policies", "summaries",
            "shed", "brownout", "breaker"} == set(s)
    assert s["completed"] == 2 and s["failed"] == 0
    # one percentile block per policy, never blended
    assert set(s["policies"]) == {"continuous", "static"}
    block = s["policies"]["continuous"]
    assert {"ttft_s", "queue_wait_s", "token_latency_s"} == set(block)
    assert block["ttft_s"]["p50"] == 0.2
    assert len(s["summaries"]) == 1


def test_record_kinds_without_a_section_are_ignored(stream):
    """The stream holds a ``gate`` record, a kind no section reads: it
    becomes no key of the JSON report and no section of the text one."""
    report = _load("dmp_report")
    records = telemetry.read_records(stream)
    assert any(r.get("kind") == "gate" for r in records)
    assert "gate" not in report.build_report_data(records)
    assert "regression gate" not in report.build_report(records)


def test_capacity_section_schema(tmp_path):
    """A metered stream grows the shape-pinned ``capacity`` key
    (serve/capacity.build_capacity — additive changes only)."""
    report = _load("dmp_report")
    path = str(tmp_path / "cap.jsonl")
    run = telemetry.TelemetryRun(path, run="cap", track_compiles=False,
                                 device={"platform": "cpu"})
    run.record("rtrace", trace="t1", request="a", event="completed")
    run.record("meter", trace="t1", request="a", tenant="web",
               replica="r0", event="completed", hop=0, chip_s=0.5,
               page_s=1.0, resident_s=1.0, prefill_chunks=1,
               decode_rounds=8, tokens=8)
    run.record("utilization", replica="r0", busy_s=0.6, stalled_s=0.1,
               brownout_s=0.0, idle_s=0.3, quarantined_s=0.0,
               wall_s=1.0, iterations=10, meter_write_s=0.001)
    run.record("serve", event="summary", policy="fleet", wall_s=1.0,
               n_replicas=1, tokens_generated=8)
    run.finish()
    data = report.build_report_data(telemetry.read_records(path))
    cap = data["capacity"]
    assert {"wall_s", "n_replicas", "tokens", "tokens_per_s",
            "billed_chip_s", "billed_page_s", "meter_records",
            "tenants", "replicas", "sustainable_tokens_per_s",
            "headroom_tokens_per_s", "headroom_fraction",
            "metering_overhead"} <= set(cap)
    assert cap["meter_records"] == 1
    assert cap["tenants"]["web"]["chip_s"] == 0.5
    assert cap["tenants"]["web"]["requests"] == 1
    r0 = cap["replicas"]["r0"]
    assert r0["duty"]["busy"] == 0.6
    assert {"meter_write_s", "iteration_wall_s",
            "fraction"} == set(cap["metering_overhead"])
    # 8 tok/s observed at 60% busy duty -> ~13.3 tok/s sustainable.
    assert cap["sustainable_tokens_per_s"] > cap["tokens_per_s"] == 8.0


def test_bare_stream_has_empty_sections(tmp_path):
    report = _load("dmp_report")
    path = str(tmp_path / "bare.jsonl")
    telemetry.TelemetryRun(path, run="bare", track_compiles=False,
                           device={"platform": "cpu"}).finish()
    data = report.build_report_data(telemetry.read_records(path))
    assert data["headline"]["step_time_s"] is None
    assert data["serving"]["completed"] == 0


def test_report_json_cli_roundtrip(stream):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dmp_report.py"),
         stream, "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1500:]
    data = json.loads(proc.stdout)
    assert data["run"]["run"] == "demo"
    assert data["headline"]["n_steps"] == 10


def test_fleet_json_tenant_table_and_ledger(tmp_path):
    report = _load("dmp_report")
    path = str(tmp_path / "t0.jsonl")
    run = telemetry.TelemetryRun(path, run="t0", track_compiles=False,
                                 device={"platform": "cpu"}, tenant="t0")
    run.record("fault", fault="nan_loss", site="step", index=1)
    run.failure("non-finite", detail="x")
    run.recovery(action="restored", slot="good")
    run.finish()
    fleet = str(tmp_path / "fleet.jsonl")
    frun = telemetry.TelemetryRun(fleet, run="fleet",
                                  track_compiles=False,
                                  device={"platform": "cpu"})
    frun.record("tenant", name="t0", event="completed")
    frun.record("alert", rule="step_time_drift", subject="t0",
                state="firing", value=1.0, threshold=0.1)
    frun.finish()
    data = report.build_fleet_data(
        telemetry.merge_streams([fleet, path]))
    assert {"tenants", "ledger", "unpaired", "unrecovered", "health",
            "alerts"} == set(data)
    assert data["tenants"]["t0"]["failures"] == 1
    assert data["ledger"][0]["paired"] is True
    assert data["unrecovered"] == []
    assert data["alerts"][0]["rule"] == "step_time_drift"


def test_dmp_top_once_renders_fleet_state(stream):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dmp_top.py"),
         stream, "--once"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1500:]
    out = proc.stdout
    assert "demo" in out
    assert "ALERT firing  step_time_drift[demo]" in out
    assert "POSTMORTEM  /tmp/pm" in out
    assert "tok/s" in out                       # throughput rendered
