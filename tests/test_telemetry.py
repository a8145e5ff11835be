"""utils/telemetry.py: registry semantics, the JSONL event stream, the
collectives comm accounting, trainer integration on a tiny CPU run, and a
scripts/dmp_report.py smoke test over the resulting stream.
"""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.utils import telemetry
from tests.conftest import tiny_train_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_dmp_report():
    spec = importlib.util.spec_from_file_location(
        "dmp_report", os.path.join(REPO, "scripts", "dmp_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("steps")
    c.inc()
    c.inc(2.5)
    assert reg.counter("steps").value == 3.5       # same object by key
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("lr")
    g.set(0.4)
    assert reg.gauge("lr").value == 0.4

    h = reg.histogram("t", bounds=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["min"] == 0.05 and snap["max"] == 5.0
    assert snap["sum"] == pytest.approx(6.05)
    # p50 must land in the (0.1, 1.0] bucket that holds the two 0.5s.
    assert 0.1 <= snap["p50"] <= 1.0


def test_histogram_single_sample_reports_sample():
    h = telemetry.Histogram(bounds=[1.0, 10.0])
    h.observe(3.0)
    # Clamped to observed min/max — not a bucket bound.
    assert h.percentile(50) == pytest.approx(3.0)
    assert h.percentile(99) == pytest.approx(3.0)


def test_tags_key_separate_metrics_and_type_conflicts_raise():
    reg = telemetry.MetricsRegistry()
    reg.counter("bytes", axis="data").inc(10)
    reg.counter("bytes", axis="stage").inc(20)
    snap = reg.snapshot()
    assert snap["counters"]["bytes{axis=data}"] == 10
    assert snap["counters"]["bytes{axis=stage}"] == 20
    with pytest.raises(telemetry.AlreadyRegisteredError):
        reg.gauge("bytes", axis="data")
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_wire_bytes_estimates():
    # Ring-algorithm cost model: allreduce 2(n-1)/n, gather/scatter (n-1)/n,
    # ppermute the full shard.
    assert telemetry.wire_bytes_estimate("psum", 800, 8) == \
        pytest.approx(2 * 7 / 8 * 800)
    assert telemetry.wire_bytes_estimate("all_gather", 800, 8) == \
        pytest.approx(7 / 8 * 800)
    assert telemetry.wire_bytes_estimate("ppermute", 800, 8) == 800


def test_record_collective_never_raises_on_tracers():
    # A dynamic axis size (tracer) must skip the sample, not break tracing.
    class NotAnInt:
        def __int__(self):
            raise TypeError("traced")

    telemetry.record_collective("psum", "data", 100, NotAnInt())


# ---------------------------------------------------------------------------
# Event stream round trip
# ---------------------------------------------------------------------------

def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    reg = telemetry.MetricsRegistry()
    run = telemetry.TelemetryRun(path, run="unit", meta={"batch_size": 32},
                                 registry_=reg, track_compiles=False)
    # numpy scalars must coerce to JSON floats.
    run.step(epoch=0, step=1, loss=np.float32(2.5), step_time_s=0.01,
             samples_per_s=3200.0)
    run.event("preemption requested")
    reg.counter("jax_compiles").inc(3)
    run.finish(epochs_run=1)
    run.finish()                       # idempotent: one run_end only

    records = telemetry.read_records(path)
    kinds = [r["kind"] for r in records]
    assert kinds == ["run_start", "step", "event", "metrics", "run_end"]
    start, step, event, metrics, end = records
    assert start["meta"]["batch_size"] == 32
    assert "device" in start and "ts" in start
    assert step["loss"] == 2.5 and isinstance(step["loss"], float)
    assert step["samples_per_s"] == 3200.0
    assert event["message"] == "preemption requested"
    assert metrics["counters"]["jax_compiles"] == 3
    assert end["epochs_run"] == 1 and end["wall_s"] >= 0


def test_metrics_counters_are_deltas_since_stream_open(tmp_path):
    # The registry is process-global: a second run in the same process
    # must not re-report the first run's comm volume / compile counts.
    reg = telemetry.MetricsRegistry()
    reg.counter("jax_compiles").inc(5)          # "previous run"
    run = telemetry.TelemetryRun(str(tmp_path / "r2.jsonl"), run="second",
                                 registry_=reg, track_compiles=False)
    reg.counter("jax_compiles").inc(2)          # this run's compiles
    run.step(step=0, step_time_s=0.25)          # feeds the histogram too
    run.finish()
    records = telemetry.read_records(run.path)
    (metrics,) = [r for r in records if r["kind"] == "metrics"]
    assert metrics["counters"]["jax_compiles"] == 2
    assert metrics["histograms"]["step_time_s"]["count"] == 1


def test_counter_increments_attributed_to_tenant_scope():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("work_units")
    with telemetry.tenant_scope("a"):
        c.inc(2)
    with telemetry.tenant_scope("b"):
        c.inc(3)
    c.inc(5)                                    # unscoped: fleet-only
    assert reg.snapshot()["counters"]["work_units"] == 10
    assert reg.snapshot(tenant="a")["counters"]["work_units"] == 2
    assert reg.snapshot(tenant="b")["counters"]["work_units"] == 3
    assert reg.snapshot(tenant="nobody")["counters"]["work_units"] == 0


def test_tenant_tagged_stream_reports_per_tenant_counter_deltas(tmp_path):
    """The OBSERVABILITY.md caveat this replaces: a co-resident tenant's
    final metrics record used to carry fleet-total counter deltas; with
    per-tenant attribution it carries only the increments made inside
    ITS tenant_scope."""
    reg = telemetry.MetricsRegistry()
    reg.counter("jax_compiles").inc(4)          # pre-campaign noise
    with telemetry.tenant_scope("a"):
        run_a = telemetry.TelemetryRun(str(tmp_path / "a.jsonl"), run="a",
                                       registry_=reg, track_compiles=False)
        reg.counter("jax_compiles").inc(2)      # tenant a's compiles
    with telemetry.tenant_scope("b"):
        run_b = telemetry.TelemetryRun(str(tmp_path / "b.jsonl"), run="b",
                                       registry_=reg, track_compiles=False)
        reg.counter("jax_compiles").inc(7)      # tenant b's compiles
    run_a.finish()
    run_b.finish()
    (ma,) = [r for r in telemetry.read_records(run_a.path)
             if r["kind"] == "metrics"]
    (mb,) = [r for r in telemetry.read_records(run_b.path)
             if r["kind"] == "metrics"]
    assert ma["counters"]["jax_compiles"] == 2
    assert mb["counters"]["jax_compiles"] == 7


def test_tenant_counter_attribution_is_thread_local():
    import threading

    reg = telemetry.MetricsRegistry()
    c = reg.counter("steps")

    def work(name, n):
        with telemetry.tenant_scope(name):
            for _ in range(n):
                c.inc()

    threads = [threading.Thread(target=work, args=("a", 30)),
               threading.Thread(target=work, args=("b", 50))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.snapshot(tenant="a")["counters"]["steps"] == 30
    assert reg.snapshot(tenant="b")["counters"]["steps"] == 50
    assert reg.snapshot()["counters"]["steps"] == 80


def test_read_records_skips_truncated_tail(tmp_path):
    path = tmp_path / "r.jsonl"
    path.write_text('{"ts": 1, "kind": "step"}\n{"ts": 2, "ki')
    (rec,) = telemetry.read_records(str(path))
    assert rec["kind"] == "step"


def test_torn_tail_counts_and_never_poisons_a_fleet_merge(tmp_path,
                                                          capsys):
    """Satellite: a run killed mid-write must cost a warning counter,
    not a JSONDecodeError that poisons the whole fleet merge."""
    import sys

    good = tmp_path / "good.jsonl"
    good.write_text('{"ts": 1, "kind": "run_start", "run": "a"}\n'
                    '{"ts": 2, "kind": "step", "step_time_s": 0.1}\n')
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"ts": 1, "kind": "run_start", "run": "b"}\n'
                    '{"ts": 3, "kind": "step", "step_time_s"')
    before = telemetry.registry().counter("telemetry_torn_lines").value
    merged = telemetry.merge_streams([str(good), str(torn)])
    assert len(merged) == 3                  # the torn line is dropped
    after = telemetry.registry().counter("telemetry_torn_lines").value
    assert after == before + 1
    assert "torn" in capsys.readouterr().err
    # ...and the report renders the merge without raising.
    from scripts.dmp_report import build_fleet_report, build_report

    build_fleet_report(merged)
    build_report(telemetry.read_records(str(torn)))


def test_stream_rotation_and_globbed_readback(tmp_path):
    """Satellite: TelemetryRun(max_bytes=...) rotates the live file to
    {stem}.N.jsonl parts; read_records/merge_streams glob the parts back
    in order so a rotated long-run stream reads as one stream.

    Hermetic registry: finish() snapshots every metric name the process
    has ever created into ONE ``metrics`` line, and a single line larger
    than max_bytes cannot be split — against the process-global registry
    this test's part-size assertion would depend on how many metrics the
    rest of the suite registered before it ran."""
    path = str(tmp_path / "run.jsonl")
    run = telemetry.TelemetryRun(path, run="long", track_compiles=False,
                                 max_bytes=4096,
                                 registry_=telemetry.MetricsRegistry())
    n = 60
    for i in range(n):
        # Non-ASCII payload: rotation must count written BYTES (the em
        # dash is 3 UTF-8 bytes), or parts overshoot max_bytes.
        run.step(step=i, step_time_s=0.01, note="x—" * 40)
    run.finish()
    parts = telemetry.stream_parts(path)
    assert len(parts) > 1, "stream never rotated"
    assert parts[-1] == path
    assert all(f".{i + 1}.jsonl" in parts[i] for i in range(len(parts) - 1))
    import os

    assert all(os.path.getsize(p) <= 4096 for p in parts[:-1])
    records = telemetry.read_records(path)
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    steps = [r["step"] for r in records if r["kind"] == "step"]
    assert steps == list(range(n))           # ordered across parts
    # merge_streams sees the whole logical stream through the base path
    assert len(telemetry.merge_streams([path])) == len(records)
    # a shell glob lists the base AND its parts: the parts are already
    # folded into the base read, so merging the expanded list must not
    # double-count them
    assert len(telemetry.merge_streams(sorted(parts))) == len(records)
    # a part path passed explicitly reads just that part
    assert telemetry.read_records(parts[0])


def test_rotation_rejects_degenerate_max_bytes(tmp_path):
    import pytest

    with pytest.raises(ValueError):
        telemetry.TelemetryRun(str(tmp_path / "r.jsonl"), run="r",
                               track_compiles=False, max_bytes=100)


def test_run_end_wall_s_is_monotonic_not_wall_clock(tmp_path,
                                                    monkeypatch):
    """Satellite: an NTP step mid-run must not skew wall_s — the
    duration pair uses time.monotonic(), only the per-record ts stamps
    stay on the wall clock."""
    import time as time_mod

    run = telemetry.TelemetryRun(str(tmp_path / "r.jsonl"), run="r",
                                 track_compiles=False)
    real_time = time_mod.time
    # Simulate the wall clock stepping back 1000s mid-run.
    monkeypatch.setattr(time_mod, "time", lambda: real_time() - 1000.0)
    run.finish()
    (end,) = [r for r in telemetry.read_records(run.path)
              if r["kind"] == "run_end"]
    assert 0 <= end["wall_s"] < 10


# ---------------------------------------------------------------------------
# Collectives accounting (trace-time, tagged by mesh axis)
# ---------------------------------------------------------------------------

def test_psum_mean_records_comm_volume(mesh8):
    from jax.sharding import PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collectives import psum_mean

    telemetry.registry().reset()
    x = jnp.arange(32, dtype=jnp.float32)

    f = jax.shard_map(lambda v: psum_mean(v, "data"), mesh=mesh8.mesh,
                      in_specs=P("data"), out_specs=P("data"))
    jax.jit(f)(x).block_until_ready()

    snap = telemetry.registry().snapshot()["counters"]
    key = "collective_wire_bytes_est{axis=data,kind=psum}"
    # Per-shard payload is 4 floats = 16 bytes; ring allreduce moves
    # 2*(8-1)/8 of it. Counted at least once (trace time).
    assert snap[key] >= 2 * 7 / 8 * 16
    assert snap["collective_traces{axis=data,kind=psum}"] >= 1


# ---------------------------------------------------------------------------
# Trainer integration + report CLI smoke (tiny CPU runs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_stream(tmp_path_factory):
    from distributed_model_parallel_tpu.train.trainer import Trainer

    tmp_path = tmp_path_factory.mktemp("telemetry_run")
    cfg = tiny_train_config(tmp_path, epochs=1, log_every_n_steps=1)
    t = Trainer(cfg)
    t.fit(1)
    return t.logger.jsonl_path


def test_trainer_writes_telemetry_stream(trained_stream):
    records = telemetry.read_records(trained_stream)
    by_kind = {}
    for r in records:
        by_kind.setdefault(r["kind"], []).append(r)
    assert by_kind["run_start"][0]["meta"]["workload"] == "cnn"
    assert by_kind["run_start"][0]["device"]["platform"] == "cpu"
    # Step records carry timing + throughput keys (ISSUE 1 acceptance).
    steps = by_kind["step"]
    assert steps, "no step records in the stream"
    for rec in steps:
        assert isinstance(rec["step_time_s"], float)
        assert isinstance(rec["data_time_s"], float)
        assert isinstance(rec["samples_per_s"], float)
    assert by_kind["epoch"][-1]["loss_train"] is not None
    # run_end preceded by the registry snapshot; compile tracking counted
    # the jitted step compilations.
    assert by_kind["metrics"][-1]["counters"].get("jax_compiles", 0) >= 1
    assert by_kind["run_end"][-1]["epochs_run"] == 1


def test_dmp_report_renders_cpu_run(trained_stream):
    dmp_report = _load_dmp_report()
    records = telemetry.read_records(trained_stream)
    text = dmp_report.build_report(records)
    assert "p50" in text and "p99" in text
    assert "samples/s" in text
    # On CPU the peak tables have no entry: the report must say MFU is
    # unavailable, not fabricate a number.
    assert "MFU unavailable" in text
    assert "run wall time" in text


def test_dmp_report_cli_main(trained_stream, capsys):
    dmp_report = _load_dmp_report()
    dmp_report.main([trained_stream])
    out = capsys.readouterr().out
    assert "== steps" in out and "MFU unavailable" in out


def test_dmp_report_computes_mfu_when_peak_known():
    dmp_report = _load_dmp_report()
    records = [
        {"ts": 0, "kind": "run_start", "run": "lm",
         "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                    "n_devices": 1},
         "meta": {"model_flops_per_step": 1.97e12}},
        {"ts": 1, "kind": "step", "step": 0, "step_time_s": 0.1,
         "tokens_per_s": 1000.0},
    ]
    text = dmp_report.build_report(records)
    # 1.97e12 flops / 0.1 s / 197e12 peak = 0.100
    assert "MFU 0.100" in text


def test_lm_trainer_stream_has_tokens_and_flops(tmp_path):
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    cfg = LMTrainConfig(
        model=tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                    n_layers=1, d_ff=64, max_seq_len=16),
        batch_size=4, seq_len=16, steps_per_epoch=2, epochs=1,
        n_tokens=2000, eval_batches=0,
        log_dir=str(tmp_path / "log"),
        checkpoint_dir=str(tmp_path / "ckpt"))
    t = LMTrainer(cfg)
    t.fit(1)
    records = telemetry.read_records(t.logger.jsonl_path)
    start = [r for r in records if r["kind"] == "run_start"][0]
    assert start["meta"]["model_flops_per_step"] > 0
    steps = [r for r in records if r["kind"] == "step"]
    assert len(steps) == 2
    for rec in steps:
        assert rec["tokens_per_s"] > 0 and rec["step_time_s"] > 0


# ---------------------------------------------------------------------------
# Live tail: StreamFollower / follow_records across rotations
# ---------------------------------------------------------------------------

def test_follower_tails_without_drop_or_dup(tmp_path):
    path = str(tmp_path / "tail.jsonl")
    run = telemetry.TelemetryRun(path, run="t", track_compiles=False,
                                 device={"platform": "cpu"})
    f = telemetry.StreamFollower(path)
    got = f.poll()
    assert [r["kind"] for r in got] == ["run_start"]
    for i in range(5):
        run.record("event", message=f"m{i}")
    got = f.poll()
    assert [r["message"] for r in got] == [f"m{i}" for i in range(5)]
    assert f.poll() == []                       # nothing new, nothing re-read


def test_follower_survives_rotation_mid_tail(tmp_path):
    """The rotation-during-tail contract: records written before, across
    and after a {stem}.N.jsonl rollover arrive exactly once, in order."""
    path = str(tmp_path / "rot.jsonl")
    run = telemetry.TelemetryRun(path, run="t", track_compiles=False,
                                 device={"platform": "cpu"},
                                 max_bytes=4096)
    f = telemetry.StreamFollower(path)
    seen = []
    for i in range(60):
        run.record("event", message="x" * 120 + f"-{i}")
        if i % 5 == 0:
            seen += f.poll()                    # poll WHILE it rotates
    seen += f.poll()
    nums = [int(r["message"].rsplit("-", 1)[1]) for r in seen
            if r["kind"] == "event"]
    assert nums == list(range(60))
    # The stream really did rotate (otherwise this test is vacuous).
    assert len(telemetry.stream_parts(path)) >= 2


def test_follower_buffers_partial_line_until_complete(tmp_path):
    path = str(tmp_path / "partial.jsonl")
    with open(path, "w") as fh:
        fh.write('{"kind": "event", "message": "whole"}\n')
        fh.write('{"kind": "event", "mess')        # torn mid-write
        fh.flush()
    f = telemetry.StreamFollower(path)
    got = f.poll()
    assert [r["message"] for r in got] == ["whole"]
    with open(path, "a") as fh:                    # the write completes
        fh.write('age": "late"}\n')
    got = f.poll()
    assert [r["message"] for r in got] == ["late"]


def test_follow_records_generator_stops_after_final_drain(tmp_path):
    path = str(tmp_path / "gen.jsonl")
    run = telemetry.TelemetryRun(path, run="t", track_compiles=False,
                                 device={"platform": "cpu"})
    run.record("event", message="a")
    stopped = {"v": False}
    gen = telemetry.follow_records(path, poll_s=0.01,
                                   stop=lambda: stopped["v"])
    first = next(gen)
    assert first["kind"] == "run_start"
    run.record("event", message="b")
    stopped["v"] = True
    rest = list(gen)
    assert [r.get("message") for r in rest if r["kind"] == "event"] \
        == ["a", "b"]


# ---------------------------------------------------------------------------
# Crash hygiene: failure/postmortem records survive a killed writer
# ---------------------------------------------------------------------------

def test_failure_record_survives_writer_killed_mid_record(tmp_path):
    """The fsync contract (satellite: crash hygiene): a process that
    dies IMMEDIATELY after recording a failure — os._exit(1), no
    interpreter shutdown, no buffer flush — must still leave the
    failure record intact on disk, followed by whatever tear the death
    produced."""
    path = str(tmp_path / "crash.jsonl")
    code = f"""
import os, sys
sys.path.insert(0, {REPO!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from distributed_model_parallel_tpu.utils.telemetry import TelemetryRun
run = TelemetryRun({path!r}, run="crash", track_compiles=False,
                   device={{"platform": "cpu"}})
run.record("step", step=1, step_time_s=0.01)
run.failure("simulated-fatal", detail="dying now")
# Tear the NEXT record mid-line, then die without any cleanup: the
# failure record above must already be fsync'd on disk.
with open({path!r}, "a") as f:
    f.write('{{"ts": 1.0, "kind": "event", "mess')
    os._exit(1)
"""
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    recs = telemetry.read_records(path)
    fails = [r for r in recs if r["kind"] == "failure"]
    assert len(fails) == 1 and fails[0]["error"] == "simulated-fatal"
    # The torn tail is skipped, not fatal (read_records contract).
    assert recs[-1]["kind"] == "failure"


def test_live_runs_tracks_unfinished_streams(tmp_path):
    run = telemetry.TelemetryRun(str(tmp_path / "live.jsonl"), run="t",
                                 track_compiles=False,
                                 device={"platform": "cpu"})
    assert run in telemetry.live_runs()
    run.finish()
    assert run not in telemetry.live_runs()
