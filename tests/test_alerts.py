"""utils/alerts.py: the SLO alert engine — rule semantics (step-time
drift fire/resolve, multiwindow burn rate, gauge ceiling, health
floor), dedup (one record per transition), per-tenant scoping, the
stream live-tail ingest, and a given drift reference."""

import pytest

from distributed_model_parallel_tpu.utils import alerts, telemetry
from distributed_model_parallel_tpu.utils.alerts import (
    AlertEngine,
    BurnRate,
    GaugeCeiling,
    HealthFloor,
    StepTimeDrift,
)


def _step(engine, ts, t, tenant="v"):
    engine.observe({"ts": ts, "kind": "step", "step_time_s": t,
                    "tenant": tenant})


# ---------------------------------------------------------------------------
# step-time drift
# ---------------------------------------------------------------------------

def test_drift_fires_once_and_resolves_once():
    eng = AlertEngine([StepTimeDrift(window=3, baseline_n=3, factor=3.0,
                                     min_drift_s=0.05)])
    ts = 0.0
    for _ in range(4):
        ts += 1
        _step(eng, ts, 0.01)
    assert eng.tick() == []                  # healthy baseline
    for _ in range(3):
        ts += 1
        _step(eng, ts, 0.5)                  # 50x the baseline
    ev = eng.tick()
    assert [e["state"] for e in ev] == ["firing"]
    assert ev[0]["rule"] == "step_time_drift" and ev[0]["subject"] == "v"
    assert ev[0]["value"] > ev[0]["threshold"]
    assert eng.tick() == []                  # deduped while still firing
    assert eng.firing == [{"rule": "step_time_drift", "subject": "v"}]
    for _ in range(3):
        ts += 1
        _step(eng, ts, 0.01)                 # healed (migrated tenant)
    ev = eng.tick()
    assert [e["state"] for e in ev] == ["resolved"]
    assert eng.firing == []


def test_drift_needs_full_window_before_judging():
    eng = AlertEngine([StepTimeDrift(window=4, baseline_n=2)])
    _step(eng, 1.0, 5.0)
    assert eng.tick() == []                  # one sample is not evidence


def test_drift_absolute_floor_ignores_microsecond_jitter():
    # 3x a 1ms baseline is still < the 50ms floor: no alert.
    eng = AlertEngine([StepTimeDrift(window=2, baseline_n=2, factor=3.0,
                                     min_drift_s=0.05)])
    ts = 0.0
    for t in (0.001, 0.001, 0.004, 0.004):
        ts += 1
        _step(eng, ts, t)
    assert eng.tick() == []


def test_drift_is_per_tenant():
    eng = AlertEngine([StepTimeDrift(window=2, baseline_n=2,
                                     min_drift_s=0.05)])
    ts = 0.0
    for _ in range(3):
        ts += 1
        _step(eng, ts, 0.01, tenant="slow")
        _step(eng, ts, 0.01, tenant="fast")
    for _ in range(2):
        ts += 1
        _step(eng, ts, 1.0, tenant="slow")
        _step(eng, ts, 0.01, tenant="fast")
    ev = eng.tick()
    assert [(e["subject"], e["state"]) for e in ev] == [("slow", "firing")]


def test_drift_uses_given_reference():
    eng = AlertEngine([StepTimeDrift(window=2, reference_s=0.1,
                                     factor=2.0, min_drift_s=0.05)])
    ts = 0.0
    for t in (0.5, 0.5):                      # 5x the given band
        ts += 1
        _step(eng, ts, t)
    ev = eng.tick()
    assert ev and ev[0]["state"] == "firing" and ev[0]["reference"] == 0.1


# ---------------------------------------------------------------------------
# burn rate
# ---------------------------------------------------------------------------

def _serve(engine, ts, ttft, tenant="s"):
    engine.observe({"ts": ts, "kind": "serve", "event": "completed",
                    "ttft_s": ttft, "tenant": tenant})


def test_burn_rate_needs_both_windows():
    rule = BurnRate(metric="ttft_s", target_s=0.1, budget=0.3, burn=1.5,
                    short_s=10, long_s=100, min_requests=2)
    eng = AlertEngine([rule])
    # Long window full of violations, short window healthy: no fire.
    for i in range(6):
        _serve(eng, 1000.0 + i, 0.5)
    for i in range(4):
        _serve(eng, 1095.0 + i, 0.01)         # recent requests healthy
    assert eng.tick(now=1099.0) == []
    # Now the short window burns too.
    for i in range(4):
        _serve(eng, 1100.0 + i, 0.5)
    ev = eng.tick(now=1104.0)
    assert ev and ev[0]["state"] == "firing"
    assert ev[0]["rule"] == "serve_burn_rate_ttft_s"
    assert ev[0]["metric"] == "ttft_s"


def test_burn_rate_resolves_when_violations_age_out():
    rule = BurnRate(metric="ttft_s", target_s=0.1, budget=0.5, burn=1.5,
                    short_s=10, long_s=50, min_requests=2)
    eng = AlertEngine([rule])
    for i in range(4):
        _serve(eng, 100.0 + i, 0.5)
    assert eng.tick(now=104.0)[0]["state"] == "firing"
    for i in range(4):
        _serve(eng, 160.0 + i, 0.01)          # old violations aged out
    ev = eng.tick(now=164.0)
    assert ev and ev[0]["state"] == "resolved"


# ---------------------------------------------------------------------------
# gauge ceiling + health floor (signal-fed, global scope)
# ---------------------------------------------------------------------------

def test_gauge_ceiling_from_signal_and_summary_record():
    eng = AlertEngine([GaugeCeiling(ceiling=0.9)])
    eng.set_signal("page_occupancy", 0.95)
    ev = eng.tick(now=1.0)
    assert ev and ev[0]["state"] == "firing" and ev[0]["subject"] == ""
    eng.set_signal("page_occupancy", 0.2)
    assert eng.tick(now=2.0)[0]["state"] == "resolved"
    # Without the live signal, the engine falls back to the last serve
    # summary record's occupancy aggregate.
    eng2 = AlertEngine([GaugeCeiling(ceiling=0.9)])
    eng2.observe({"ts": 1.0, "kind": "serve", "event": "summary",
                  "page_occupancy": {"mean": 0.5, "max": 0.99}})
    ev = eng2.tick()
    assert ev and ev[0]["state"] == "firing"


def test_health_floor_fires_on_worst_device():
    eng = AlertEngine([HealthFloor(floor=0.5)])
    eng.set_signal("health_scores", {0: 1.0, 3: 0.25})
    ev = eng.tick(now=1.0)
    assert ev and ev[0]["state"] == "firing" and ev[0]["device"] == 3
    eng.set_signal("health_scores", {0: 1.0, 3: 0.9})
    assert eng.tick(now=2.0)[0]["state"] == "resolved"


# ---------------------------------------------------------------------------
# sink + live-tail ingest
# ---------------------------------------------------------------------------

def test_transitions_land_as_typed_alert_records(tmp_path):
    run = telemetry.TelemetryRun(str(tmp_path / "fleet.jsonl"), run="f",
                                 track_compiles=False,
                                 device={"platform": "cpu"})
    eng = AlertEngine([HealthFloor(floor=0.5)], sink=run)
    eng.set_signal("health_scores", {1: 0.1})
    eng.tick(now=1.0)
    eng.set_signal("health_scores", {1: 1.0})
    eng.tick(now=2.0)
    recs = [r for r in telemetry.read_records(str(tmp_path / "fleet.jsonl"))
            if r["kind"] == "alert"]
    assert [(r["rule"], r["state"]) for r in recs] == [
        ("device_health_floor", "firing"),
        ("device_health_floor", "resolved")]


def test_watch_poll_ingests_streams_across_rotation(tmp_path):
    path = str(tmp_path / "t.jsonl")
    run = telemetry.TelemetryRun(path, run="t", track_compiles=False,
                                 device={"platform": "cpu"},
                                 tenant="v", max_bytes=4096)
    eng = AlertEngine([StepTimeDrift(window=3, baseline_n=3,
                                     min_drift_s=0.05)])
    eng.watch(path)
    eng.watch(path)                           # idempotent
    for i in range(20):
        run.step(step=i, step_time_s=0.01,
                 pad="x" * 300)               # forces a rotation mid-run
    eng.poll()
    assert eng.tick() == []
    for i in range(3):
        run.step(step=20 + i, step_time_s=0.8)
    eng.poll()
    ev = eng.tick()
    assert ev and ev[0]["state"] == "firing" and ev[0]["subject"] == "v"
    assert len(telemetry.stream_parts(path)) >= 2


def test_default_rules_cover_the_four_slo_families():
    names = {r.name for r in alerts.default_rules()}
    assert names == {"step_time_drift", "serve_burn_rate_ttft_s",
                     "serve_burn_rate_token_latency_s",
                     "page_pool_saturation", "device_health_floor"}


def test_two_burn_rate_rules_keep_separate_state():
    """ttft + token-latency burn rules on one engine must not share a
    state cell (each would double-count the other's samples)."""
    eng = AlertEngine([
        BurnRate(metric="ttft_s", target_s=0.1, budget=0.3, burn=1.5,
                 short_s=10, long_s=50, min_requests=2),
        BurnRate(metric="token_latency_s", target_s=10.0, budget=0.3,
                 burn=1.5, short_s=10, long_s=50, min_requests=2),
    ])
    for i in range(4):   # ttft violates, token latency is fine
        eng.observe({"ts": 100.0 + i, "kind": "serve",
                     "event": "completed", "ttft_s": 0.5,
                     "token_latency_s": 0.001, "tenant": "s"})
    ev = eng.tick(now=104.0)
    assert [(e["rule"], e["state"]) for e in ev] == [
        ("serve_burn_rate_ttft_s", "firing")]


def test_duplicate_rule_names_rejected():
    with pytest.raises(ValueError, match="duplicate alert rule names"):
        AlertEngine([HealthFloor(), HealthFloor()])
