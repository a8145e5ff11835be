"""Fleet reporting and failure-contract satellites: tenant-tagged
telemetry and stream merging (utils/telemetry.py), the fault-pairing
ledger and fleet report (scripts/dmp_report.py), and the
no-accelerator / failed-run exit contract."""

import jax
import pytest

from distributed_model_parallel_tpu.utils.telemetry import (
    TelemetryRun,
    merge_streams,
    read_records,
    tenant_scope,
)
from scripts.dmp_report import (
    build_fleet_report,
    build_report,
    pair_faults,
)


# ---------------------------------------------------------------------------
# tenant tagging + merge
# ---------------------------------------------------------------------------

def test_tenant_scope_tags_every_record(tmp_path):
    path = str(tmp_path / "a.jsonl")
    with tenant_scope("t0"):
        run = TelemetryRun(path, run="r")
        run.step(step=0, step_time_s=0.1)
        run.failure("non-finite")
    recs = read_records(path)
    assert recs and all(r.get("tenant") == "t0" for r in recs)
    # outside any scope: no tag
    path2 = str(tmp_path / "b.jsonl")
    run2 = TelemetryRun(path2, run="r2")
    run2.step(step=0)
    assert all("tenant" not in r for r in read_records(path2))


def test_tenant_scope_is_thread_local(tmp_path):
    import threading

    paths = {}

    def open_stream(name):
        with tenant_scope(name):
            run = TelemetryRun(str(tmp_path / f"{name}.jsonl"), run=name)
            run.event("hello")
            paths[name] = run.path

    threads = [threading.Thread(target=open_stream, args=(f"t{i}",))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for name, path in paths.items():
        assert all(r.get("tenant") == name for r in read_records(path))


def test_merge_streams_orders_and_skips_missing(tmp_path):
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    with tenant_scope("a"):
        TelemetryRun(a, run="a").event("one")
    with tenant_scope("b"):
        TelemetryRun(b, run="b").event("two")
    merged = merge_streams([a, b, str(tmp_path / "missing.jsonl")])
    assert merged
    ts = [r["ts"] for r in merged]
    assert ts == sorted(ts)
    assert {r["tenant"] for r in merged} == {"a", "b"}


# ---------------------------------------------------------------------------
# fault-pairing ledger
# ---------------------------------------------------------------------------

def _rec(kind, tenant="t", ts=0.0, **kw):
    return {"kind": kind, "tenant": tenant, "ts": ts, **kw}


def test_pair_faults_pairs_detection_and_action():
    records = [
        _rec("fault", ts=1, fault="nan_loss", site="step"),
        _rec("failure", ts=2, error="non-finite"),
        _rec("recovery", ts=3, action="restored"),
    ]
    ledger = pair_faults(records)
    assert len(ledger) == 1
    assert ledger[0]["paired"]
    assert ledger[0]["detected"] == "non-finite"
    assert ledger[0]["action"] == "restored"


def test_pair_faults_flags_undetected_and_unrecovered():
    records = [
        _rec("fault", ts=1, fault="nan_loss", site="step"),
        # a detection that does NOT match the kind's pairing
        _rec("failure", ts=2, error="stall"),
    ]
    ledger = pair_faults(records)
    assert len(ledger) == 1 and not ledger[0]["paired"]
    # corruption repaired in place: consistency records close the loop
    records = [
        _rec("fault", ts=1, fault="bitflip", site="step"),
        _rec("consistency", ts=2, status="divergence"),
        _rec("consistency", ts=3, status="repaired"),
    ]
    assert pair_faults(records)[0]["paired"]


def test_pair_faults_does_not_share_recoveries():
    """Two injections cannot claim one recovery record."""
    records = [
        _rec("fault", ts=1, fault="nan_loss", site="step"),
        _rec("fault", ts=2, fault="nan_loss", site="step"),
        _rec("failure", ts=3, error="non-finite"),
        _rec("recovery", ts=4, action="restored"),
    ]
    ledger = pair_faults(records)
    assert [row["paired"] for row in ledger] == [True, False]


def test_build_fleet_report_renders_tenants_and_ledger():
    records = [
        {"kind": "tenant", "ts": 1, "name": "t", "event": "submitted"},
        {"kind": "tenant", "ts": 2, "name": "t", "event": "admitted",
         "devices": [0, 1]},
        _rec("fault", ts=3, fault="preempt", site="step"),
        _rec("failure", ts=4, error="preempted"),
        _rec("recovery", ts=5, action="checkpoint-and-exit"),
        _rec("resume", ts=6, slot="preempt", global_step=4),
        {"kind": "tenant", "ts": 7, "name": "t", "event": "completed"},
    ]
    out = build_fleet_report(records)
    assert "== tenant t ==" in out
    assert "fault ledger (1 injected)" in out
    assert "ok" in out
    assert "(none — every injected fault was detected and recovered" in out


def test_fleet_report_renders_health_timeline():
    records = [
        {"kind": "tenant", "ts": 1, "name": "v", "event": "admitted",
         "devices": [0, 1, 2, 3]},
        {"kind": "health", "ts": 2, "event": "degrading",
         "devices": [0, 1, 2, 3], "signal": "step", "score": 0.75,
         "value": 1.6, "baseline": 0.02},
        {"kind": "health", "ts": 3, "event": "quarantine", "devices": [3],
         "score": 0.25},
        {"kind": "tenant", "ts": 4, "name": "v",
         "event": "preempt-requested", "reason": "device-degraded",
         "global_step": 10},
        {"kind": "health", "ts": 5, "event": "reinstate", "devices": [3],
         "score": 1.0, "probation_ticks": 3},
        {"kind": "tenant", "ts": 6, "name": "v", "event": "grow-back",
         "devices": [6, 7], "target_devices": 4, "global_step": 12},
    ]
    out = build_fleet_report(records)
    assert "== device health (3 events, 1 quarantines, 1 reinstates) ==" \
        in out
    assert "degrading" in out and "signal=step" in out
    assert "quarantine" in out and "reinstate" in out
    assert "migration    v: preempted off" in out
    assert "grow-back    v: 2 -> 4 devices at step 12" in out


def test_pair_faults_skips_persistent_degradations():
    """slow_device/flaky_sync are not event faults: their audit trail is
    the health timeline, so the ledger must not report them unpaired."""
    from scripts.dmp_report import pair_faults

    records = [
        _rec("fault", ts=1, fault="slow_device", site="step", index=6),
        _rec("fault", ts=2, fault="flaky_sync", site="sync", index=1),
        _rec("fault", ts=3, fault="nan_loss", site="step", index=2),
        _rec("failure", ts=4, error="non-finite"),
        _rec("recovery", ts=5, action="restored"),
    ]
    ledger = pair_faults(records)
    assert [row["fault"] for row in ledger] == ["nan_loss"]
    assert ledger[0]["paired"]


# ---------------------------------------------------------------------------
# failure contract: no accelerator / a failed run -> non-zero exit, never
# a record that a driver could read as a result
# ---------------------------------------------------------------------------

def test_first_contact_fails_when_backend_does_not_come_up(monkeypatch,
                                                           capsys):
    from distributed_model_parallel_tpu.utils import device_contact

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu': UNAVAILABLE")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(SystemExit) as ei:
        device_contact.require_devices("bench")
    assert ei.value.code == device_contact.EXIT_NO_ACCELERATOR != 0
    err = capsys.readouterr().err
    assert "[bench] no usable accelerator" in err
    assert "Unable to initialize backend" in err


def test_first_contact_refuses_cpu_unless_asked(monkeypatch, capsys):
    """JAX hands out the CPU when it finds no chip: that is refused
    unless the caller set JAX_PLATFORMS=cpu itself (as this suite does)."""
    from distributed_model_parallel_tpu.utils import device_contact

    assert device_contact.require_devices("t")[0].platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as ei:
        device_contact.require_devices("train-lm")
    assert ei.value.code == device_contact.EXIT_NO_ACCELERATOR
    err = capsys.readouterr().err
    assert "[train-lm] no usable accelerator" in err
    assert "JAX found no TPU (platform 'cpu'" in err


# ---------------------------------------------------------------------------
# report robustness (satellite): degenerate and mixed-schema streams must
# render every section gracefully — no KeyError, no format crash
# ---------------------------------------------------------------------------

def test_build_report_on_empty_stream():
    out = build_report([])
    assert "== run ==" in out and "no run_end record" in out


def test_build_report_on_run_start_only():
    out = build_report([{"ts": 1.0, "kind": "run_start", "run": "r",
                         "device": {"platform": "cpu", "n_devices": 8},
                         "meta": {"workload": "cnn"}}])
    assert "== steps (0 records) ==" in out
    assert "MFU unavailable" in out


def test_build_report_mixed_schema_records_render():
    """Records missing their conventional payload keys (foreign streams,
    future schema drift) must degrade to '?'/None rendering, never
    crash a section."""
    records = [
        {"ts": 1.0, "kind": "run_start"},                  # no run/device
        {"ts": 2.0, "kind": "step"},                       # no timings
        {"ts": 2.5, "kind": "step", "step_time_s": 0.1},
        {"ts": 3.0, "kind": "failure"},                    # no error field
        {"ts": 3.5, "kind": "recovery"},                   # no action
        {"ts": 4.0, "kind": "consistency"},                # no status
        {"ts": 4.5, "kind": "resume"},                     # no slot
        {"ts": 5.0, "kind": "serve", "event": "summary"},  # no totals
        {"ts": 5.5, "kind": "span", "name": "x"},          # no dur_s
        {"ts": 7.0, "kind": "plan"},                       # no axes
        {"ts": 7.5, "kind": "epoch", "epoch": 0},
        {"ts": 8.0, "kind": "memory"},                     # no devices
        {"ts": 8.5, "kind": "metrics"},                    # no counters
    ]
    out = build_report(records)
    assert "failure" in out and "== efficiency ==" in out


def test_build_fleet_report_mixed_schema_renders():
    records = [
        {"ts": 1.0, "kind": "tenant"},                     # no name/event
        {"ts": 1.5, "kind": "tenant", "tenant": "a", "name": "a",
         "event": "admitted"},
        {"ts": 2.0, "kind": "fault", "tenant": "a", "fault": "nan_loss"},
        {"ts": 2.5, "kind": "health"},                     # no devices
        {"ts": 3.0, "kind": "failure", "tenant": "a"},     # no error
        {"ts": 3.5, "kind": "event"},                      # no message
    ]
    out = build_fleet_report(records)
    assert "== fleet" in out and "== fault ledger" in out
