"""Cross-run perf regression gate (utils/baseline.py +
scripts/dmp_gate.py): artifact ingestion/seeding, the noise-band math,
the regressed-vs-parity exit codes the acceptance criteria pin, span
attribution, and bench.py's automatic warn/strict posture."""

import json
import time
from pathlib import Path

import pytest

from distributed_model_parallel_tpu.utils import baseline
from scripts import dmp_gate

REPO = Path(__file__).resolve().parent.parent

CNN_METRIC = "mobilenetv2_cifar10_bs512_train_samples_per_sec_per_chip"


def _write_stream(path, *, value=27000.0, step_time=0.019, mfu=0.083,
                  metric=CNN_METRIC, spans=None):
    """A minimal bench-shaped telemetry stream."""
    recs = [{"ts": time.time(), "kind": "run_start", "run": "bench-cnn",
             "meta": {"workload": "cnn"}}]
    for i in range(4):
        recs.append({"ts": time.time(), "kind": "step", "step": i,
                     "step_time_s": step_time,
                     "samples_per_s": value})
    for name, dur in (spans or {}).items():
        recs.append({"ts": time.time(), "kind": "span", "name": name,
                     "t0": time.time() - dur, "dur_s": dur, "sid": 1,
                     "parent": None, "depth": 0, "thread": "main"})
    recs.append({"ts": time.time(), "kind": "bench", "metric": metric,
                 "value": value, "unit": "samples/s/chip", "mfu": mfu})
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def _write_bench_artifacts(tmp_path) -> str:
    """Five driver-shaped bench artifacts (``{"n","cmd","rc","tail",
    "parsed"}``, pretty-printed as the driver writes them) under
    ``tmp_path``: four green runs of the CNN cell and one rc-1 run with no
    measurement. Returns their glob."""
    cmd = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
    for n, value in enumerate((27924.53, 26119.01, 27092.34, 26566.17), 1):
        parsed = {"metric": CNN_METRIC, "value": value,
                  "unit": "samples/s/chip"}
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps(
            {"n": n, "cmd": cmd, "rc": 0,
             "tail": "[bench] devices: [TPU v5 lite0]\n"
                     + json.dumps(parsed) + "\n",
             "parsed": parsed}, indent=2))
    (tmp_path / "BENCH_r05.json").write_text(json.dumps(
        {"n": 5, "cmd": cmd, "rc": 1,
         "tail": "RuntimeError: Unable to initialize backend 'tpu'\n",
         "parsed": None}, indent=2))
    return str(tmp_path / "BENCH_r0*.json")


# ---------------------------------------------------------------------------
# seeding from bench artifacts
# ---------------------------------------------------------------------------

def test_ingest_green_bench_artifact(tmp_path):
    _write_bench_artifacts(tmp_path)
    (e,) = baseline.ingest_artifact(str(tmp_path / "BENCH_r01.json"))
    assert e["green"] and e["metric"] == CNN_METRIC
    assert e["metrics"]["throughput"] == pytest.approx(27924.53)
    assert e["source"] == "BENCH_r01.json"


def test_ingest_failed_artifact_is_not_green(tmp_path):
    _write_bench_artifacts(tmp_path)
    (e,) = baseline.ingest_artifact(str(tmp_path / "BENCH_r05.json"))
    assert not e["green"] and e["metrics"] == {}


def test_ingest_multichip_artifact():
    (e,) = baseline.ingest_artifact(str(REPO / "MULTICHIP_r01.json"))
    assert e["key"] == "multichip" and isinstance(e["green"], bool)


def test_committed_ledger_seeded_from_artifacts():
    """The repo ships a ledger pre-seeded from BENCH_r01-r05 +
    MULTICHIP_r01-r05 — the gate has history from its first run."""
    entries = baseline.load_ledger(str(REPO / "BASELINE_LEDGER.jsonl"))
    sources = {e.get("source") for e in entries}
    assert {f"BENCH_r0{i}.json" for i in range(1, 6)} <= sources
    assert any(s.startswith("MULTICHIP_") for s in sources)
    greens = [e for e in entries if e["green"]
              and e.get("metric") == CNN_METRIC]
    assert len(greens) >= 4          # r01-r04 measured; r05 is the hole
    assert not any(e["green"] for e in entries
                   if e["source"] == "BENCH_r05.json")


def test_seeding_is_idempotent(tmp_path):
    ledger = str(tmp_path / "ledger.jsonl")
    artifacts = _write_bench_artifacts(tmp_path)
    n1 = dmp_gate.seed(ledger, [artifacts])
    n2 = dmp_gate.seed(ledger, [artifacts])
    assert n1 == 5 and n2 == 0
    assert len(baseline.load_ledger(ledger)) == 5


# ---------------------------------------------------------------------------
# the acceptance pins: regressed stream fails, parity re-run passes
# ---------------------------------------------------------------------------

def test_gate_parity_passes_and_regression_fails(tmp_path, capsys):
    ledger = str(tmp_path / "ledger.jsonl")
    dmp_gate.seed(ledger, [_write_bench_artifacts(tmp_path),
                           str(REPO / "MULTICHIP_r0*.json")])
    # 1. parity run vs the seeded history: passes, --update records it
    #    (now the ledger also has step_time_p50_s history).
    parity = _write_stream(tmp_path / "parity.jsonl")
    rc = dmp_gate.main([parity, "--ledger", ledger, "--update"])
    assert rc == 0
    # 2. synthetically regressed re-run: step_time_s inflated 2x and
    #    throughput halved vs the ledger -> nonzero exit, typed gate
    #    record on the stream naming the offending metric.
    bad = _write_stream(tmp_path / "bad.jsonl", value=13500.0,
                        step_time=0.038)
    rc = dmp_gate.main([bad, "--ledger", ledger])
    assert rc == 1
    gates = [r for r in baseline.load_ledger(bad) if r["kind"] == "gate"]
    assert gates and not gates[-1]["ok"]
    regressed = {v["metric"] for v in gates[-1]["regressions"]}
    assert f"{CNN_METRIC}:throughput" in regressed
    assert f"{CNN_METRIC}:step_time_p50_s" in regressed
    # 3. parity re-run still passes, with its own green gate record.
    again = _write_stream(tmp_path / "again.jsonl")
    rc = dmp_gate.main([again, "--ledger", ledger])
    assert rc == 0
    gates = [r for r in baseline.load_ledger(again) if r["kind"] == "gate"]
    assert gates and gates[-1]["ok"]


def test_artifact_vs_stream_sniffing(tmp_path):
    """Compact (single-line) artifacts and long-first-line streams must
    both classify correctly — pretty-printing is not the format
    contract."""
    compact = tmp_path / "compact.json"
    compact.write_text(json.dumps(
        {"n": 9, "rc": 0, "parsed": {"metric": CNN_METRIC,
                                     "value": 27000.0, "unit": "x"}}))
    assert dmp_gate._is_artifact(str(compact))
    artifacts = _write_bench_artifacts(tmp_path)
    pretty = tmp_path / "BENCH_r01.json"
    assert dmp_gate._is_artifact(str(pretty))
    long_first = tmp_path / "long.jsonl"
    long_first.write_text(
        json.dumps({"ts": 1.0, "kind": "run_start", "run": "r",
                    "meta": {"pad": "x" * 4096}}) + "\n"
        + json.dumps({"ts": 2.0, "kind": "step"}) + "\n")
    assert not dmp_gate._is_artifact(str(long_first))
    # ...and the compact artifact actually gates
    ledger = str(tmp_path / "l.jsonl")
    dmp_gate.seed(ledger, [artifacts])
    assert dmp_gate.main([str(compact), "--ledger", ledger]) == 0


def test_gate_rc2_when_nothing_to_gate(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text(json.dumps({"ts": 1.0, "kind": "run_start",
                                "run": "x"}) + "\n")
    assert dmp_gate.main([str(path), "--ledger",
                          str(tmp_path / "none.jsonl")]) == 2


def test_no_baseline_passes_with_note(tmp_path, capsys):
    stream = _write_stream(tmp_path / "s.jsonl", metric="brand_new_metric")
    rc = dmp_gate.main([stream, "--ledger", str(tmp_path / "l.jsonl")])
    assert rc == 0
    assert "no green baseline" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# noise-band math + attribution
# ---------------------------------------------------------------------------

def _entry(value, *, key="m", metric="m", span_shares=None, **metrics):
    return {"ts": 0.0, "key": key, "metric": metric, "green": True,
            "source": "t", "plan": None, "unit": None,
            "metrics": {"throughput": value, **metrics},
            "span_shares": span_shares}


def test_noise_band_median_mad_and_floor():
    # history 100,100,102,98 -> median 100, MAD 1, tol = max(3*1.4826, 5)
    ledger = [_entry(v) for v in (100.0, 100.0, 102.0, 98.0)]
    pt = {"metric": "m", "key": "m", "unit": None, "plan": None,
          "metrics": {"throughput": 94.0}, "span_shares": None,
          "phases": None}
    res = baseline.gate_points([pt], ledger, k=3.0, rel_floor=0.05)
    assert not res["ok"]
    (v,) = res["regressions"]
    assert v["baseline"] == pytest.approx(100.0)
    assert v["tolerance"] == pytest.approx(5.0)   # rel floor wins over MAD
    # within the band: passes
    pt["metrics"]["throughput"] = 95.5
    assert baseline.gate_points([pt], ledger)["ok"]
    # lower-is-better direction: inflated step time trips
    ledger = [_entry(100.0, step_time_p50_s=0.02) for _ in range(4)]
    pt["metrics"] = {"step_time_p50_s": 0.04}
    res = baseline.gate_points([pt], ledger)
    assert not res["ok"]
    assert res["regressions"][0]["metric"] == "m:step_time_p50_s"


def test_attribution_names_the_span_that_grew(tmp_path):
    ledger = [_entry(100.0,
                     span_shares={"drain": 0.5, "checkpoint_save": 0.5})]
    pt = {"metric": "m", "key": "m", "unit": None, "plan": None,
          "metrics": {"throughput": 50.0},
          "span_shares": {"drain": 0.1, "checkpoint_save": 0.9},
          "phases": None}
    res = baseline.gate_points([pt], ledger)
    attr = res["regressions"][0]["attribution"]
    assert attr["span"] == "checkpoint_save"
    assert attr["share"] == pytest.approx(0.9)
    assert attr["baseline_share"] == pytest.approx(0.5)


def test_attribution_falls_back_to_phases():
    ledger = [dict(_entry(100.0),
                   phases={"host_input_s": 0.01, "device_s": 0.01})]
    pt = {"metric": "m", "key": "m", "unit": None, "plan": None,
          "metrics": {"throughput": 50.0}, "span_shares": None,
          "phases": {"host_input_s": 0.03, "device_s": 0.01}}
    res = baseline.gate_points([pt], ledger)
    attr = res["regressions"][0]["attribution"]
    assert attr["phase"] == "host_input_s"


def test_plan_keying_separates_layouts():
    """A dp8 baseline must not gate a dp4 run: different plan payloads
    get different keys, and the metric-name fallback only reaches
    PLAN-LESS legacy entries (the seeded r01-r05 artifacts) — never an
    entry measured under a different layout."""
    plan8 = {"strategy": "ddp", "axes": {"dp": 8}}
    plan4 = {"strategy": "ddp", "axes": {"dp": 4}}
    assert baseline.entry_key("m", plan8) != baseline.entry_key("m", plan4)
    ledger = [dict(_entry(100.0), key=baseline.entry_key("m", plan8),
                   plan=plan8)]
    pt = {"metric": "m", "key": baseline.entry_key("m", plan4),
          "unit": None, "plan": plan4, "metrics": {"throughput": 50.0},
          "span_shares": None, "phases": None}
    # A dp8-plan entry must NOT become the dp4 run's baseline: no
    # verdict at all, reported as no-baseline.
    res = baseline.gate_points([pt], ledger)
    assert res["ok"] and res["no_baseline"] == [pt["key"]]
    # Plan-less legacy entries DO reach the same point via the fallback.
    legacy = [_entry(100.0)]          # metric "m", plan None
    res = baseline.gate_points([pt], legacy)
    assert not res["ok"]


def test_cli_gates_only_the_last_run_of_an_appended_stream(tmp_path):
    """bench's default stream path appends across invocations: the CLI
    must gate (and --update) only the records after the LAST run_start,
    or stale runs skew the p50 and duplicate ledger entries."""
    path = tmp_path / "appended.jsonl"
    _write_stream(path, value=100.0, step_time=0.5)     # stale slow run
    stale = path.read_text()
    _write_stream(path, value=27000.0, step_time=0.019)  # fresh run
    path.write_text(stale + path.read_text())
    ledger = str(tmp_path / "l.jsonl")
    assert dmp_gate.main([str(path), "--ledger", ledger,
                          "--update"]) == 0
    entries = baseline.load_ledger(ledger)
    assert len(entries) == 1                 # one run, one entry
    assert entries[0]["metrics"]["throughput"] == pytest.approx(27000.0)
    assert entries[0]["metrics"]["step_time_p50_s"] == pytest.approx(0.019)


def test_mixed_unit_fleet_stream_does_not_pool_throughput():
    """samples/s and tokens/s must never blend into one 'throughput'
    median — a fleet merge of CNN + LM tenants gates on step time
    only."""
    recs = [{"ts": 1.0, "kind": "run_start", "run": "fleet", "meta": {}},
            {"ts": 2.0, "kind": "step", "step_time_s": 0.02,
             "samples_per_s": 27000.0},
            {"ts": 3.0, "kind": "step", "step_time_s": 0.2,
             "tokens_per_s": 2000.0}]
    (pt,) = baseline.extract_points(recs)
    assert "throughput" not in pt["metrics"]
    assert "step_time_p50_s" in pt["metrics"]


def test_extract_points_from_plain_trainer_stream(tmp_path):
    recs = [{"ts": 1.0, "kind": "run_start", "run": "train",
             "meta": {"workload": "cnn", "mesh": {"data": 8}}}]
    recs += [{"ts": 2.0, "kind": "step", "step_time_s": 0.02,
              "samples_per_s": 1600.0} for _ in range(3)]
    (pt,) = baseline.extract_points(recs)
    assert pt["metrics"]["step_time_p50_s"] == pytest.approx(0.02)
    assert pt["metrics"]["throughput"] == pytest.approx(1600.0)
    assert pt["metric"] == "run_train_cnn"


# ---------------------------------------------------------------------------
# bench.py integration: warn by default, strict fails
# ---------------------------------------------------------------------------

def _bench_run(tmp_path, monkeypatch, ledger_entries, *, mode):
    import bench
    from distributed_model_parallel_tpu.utils.telemetry import TelemetryRun

    ledger = tmp_path / "ledger.jsonl"
    baseline.append_entries(str(ledger), ledger_entries)
    monkeypatch.setenv("DMP_BENCH_LEDGER", str(ledger))
    monkeypatch.setenv("DMP_BENCH_GATE", mode)
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="bench-cnn",
                       track_compiles=False)
    run.step(step=0, step_time_s=0.04, samples_per_s=13500.0)
    run.record("bench", metric=CNN_METRIC, value=13500.0,
               unit="samples/s/chip")
    return bench._maybe_gate(run)


def test_bench_gate_warn_only_by_default(tmp_path, monkeypatch):
    import bench

    result = _bench_run(tmp_path, monkeypatch,
                        [_entry(27000.0, key=CNN_METRIC, metric=CNN_METRIC)],
                        mode="warn")
    assert result is not None and not result["ok"]
    bench._enforce_gate(result)          # warn mode: no SystemExit


def test_bench_gate_strict_exits_nonzero(tmp_path, monkeypatch):
    import bench

    result = _bench_run(tmp_path, monkeypatch,
                        [_entry(27000.0, key=CNN_METRIC, metric=CNN_METRIC)],
                        mode="strict")
    assert result is not None and not result["ok"]
    with pytest.raises(SystemExit):
        bench._enforce_gate(result)


def test_bench_gate_off_skips(tmp_path, monkeypatch):
    assert _bench_run(tmp_path, monkeypatch, [], mode="off") is None


def test_bench_gate_internal_error_never_kills_bench(tmp_path,
                                                     monkeypatch):
    import bench
    from distributed_model_parallel_tpu.utils.telemetry import TelemetryRun

    monkeypatch.setenv("DMP_BENCH_LEDGER", str(tmp_path / "l.jsonl"))
    monkeypatch.setenv("DMP_BENCH_GATE", "strict")
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="bench-cnn",
                       track_compiles=False)
    monkeypatch.setattr(baseline, "gate_points",
                        lambda *a, **k: 1 / 0)
    run.record("bench", metric=CNN_METRIC, value=1.0, unit="x")
    assert bench._maybe_gate(run) is None   # logged, not raised
