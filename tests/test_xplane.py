"""utils/xplane.py: profiler-trace parsing against synthetic XSpace protos.

The real capture path needs a TPU; these tests pin the PARSING semantics — envelope exclusion,
zero-valued stat presence, fusion classification from HLO text — on
hand-built protos, so a regression fails fast on CPU. The proto-building
tests skip when tensorflow is absent (module-scoped ``tf_pb2`` fixture);
the graceful-degradation tests run REGARDLESS — they pin exactly the
no-tensorflow behavior.
"""

import pytest

from distributed_model_parallel_tpu.utils import xplane


@pytest.fixture(scope="module")
def tf_pb2():
    return pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")


def _plane(tf_pb2, events, stat_defs=None, line_name="XLA Ops"):
    """Build an XPlane with one line. ``events`` = list of
    (name, duration_ps, stats_dict); stats use int64 values."""
    plane = tf_pb2.XPlane()
    plane.name = "/device:TPU:0"
    stat_ids = {}
    for i, sname in enumerate(stat_defs or []):
        plane.stat_metadata[i].id = i
        plane.stat_metadata[i].name = sname
        stat_ids[sname] = i
    line = plane.lines.add()
    line.name = line_name
    for i, (name, dur, stats) in enumerate(events):
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = name
        ev = line.events.add()
        ev.metadata_id = i
        ev.duration_ps = dur
        # Nonzero host offset so a zero-valued device_offset_ps stat that
        # gets dropped by a truthiness regression is DETECTABLE (the
        # fallback would surface 999, not 0).
        ev.offset_ps = 999
        for k, v in stats.items():
            st = ev.stats.add()
            st.metadata_id = stat_ids[k]
            st.int64_value = v
    return plane


def test_op_breakdown_aggregates_and_sorts(tf_pb2):
    plane = _plane(tf_pb2, [
        ("%fusion.1 = f32[8] fusion(f32[8] %p), calls=%fused_computation.1",
         100, {}),
        ("%fusion.1 = f32[8] fusion(f32[8] %p), calls=%fused_computation.1",
         150, {}),
        ("%copy.2 = f32[8] copy(f32[8] %p)", 500, {}),
    ])
    rows = xplane.op_breakdown(plane)
    assert [r.name for r in rows] == ["%copy.2", "%fusion.1"]
    fusion = rows[1]
    assert fusion.count == 2 and fusion.total_ps == 250
    assert rows[0].category == "copy"


def test_exclude_envelopes_drops_while_and_conditional(tf_pb2):
    plane = _plane(tf_pb2, [
        ("%while.7 = (f32[8]) while((f32[8]) %t)", 1000, {}),
        ("%conditional.1 = f32[8] conditional(...)", 500, {}),
        ("%fusion.1 = f32[8] fusion(f32[8] %p)", 100, {}),
    ])
    rows = xplane.exclude_envelopes(xplane.op_breakdown(plane))
    assert [r.name for r in rows] == ["%fusion.1"]
    # category_totals over the filtered rows must not see the 1500ps
    totals = xplane.category_totals(rows)
    assert totals == {"fusion": pytest.approx(100 / 1e12)}


def test_stat_zero_value_is_not_dropped(tf_pb2):
    # device_offset_ps == 0 is legitimate (first event); a truthiness
    # chain would fall through to the host-timeline offset.
    plane = _plane(
        tf_pb2,
        [("jit_f(123)", 70, {"device_offset_ps": 0,
                             "device_duration_ps": 40})],
        stat_defs=["device_offset_ps", "device_duration_ps"],
        line_name="XLA Modules")
    (mod,) = xplane.module_events(plane)
    assert mod.start_ps == 0          # not the proto default offset_ps
    assert mod.duration_ps == 40      # device value, not ev.duration_ps


def test_module_events_fall_back_to_host_times(tf_pb2):
    plane = _plane(tf_pb2, [("jit_f(1)", 70, {})], line_name="XLA Modules")
    (mod,) = xplane.module_events(plane)
    assert mod.duration_ps == 70


def test_fusion_kinds_from_hlo():
    hlo = """
HloModule m

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  ROOT %c = f32[8,8] convolution(%p0, %p0), dim_labels=b01f_01io->b01f
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %a = f32[8] add(%p0, %p0)
}

ENTRY %main () -> f32[] {
  ROOT %r = f32[] constant(0)
}
"""
    kinds = xplane.fusion_kinds_from_hlo(hlo)
    assert kinds["fused_computation.1"] == "conv-fusion"
    assert kinds["fused_computation.2"] == "elementwise-fusion"


def test_op_breakdown_classifies_fusions_with_hlo(tf_pb2):
    hlo = """
%fused_computation.9 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  ROOT %c = f32[8,8] convolution(%p0, %p0)
}
"""
    plane = _plane(tf_pb2, [
        ("%fusion.9 = f32[8,8] fusion(f32[8,8] %p), "
         "calls=%fused_computation.9", 100, {}),
    ])
    (row,) = xplane.op_breakdown(plane, hlo)
    assert row.category == "conv-fusion"


def test_device_plane_raises_on_host_only_trace(tf_pb2):
    space = tf_pb2.XSpace()
    host = space.planes.add()
    host.name = "/host:CPU"
    with pytest.raises(ValueError, match="device events were not captured"):
        xplane.device_plane(space)


# ---------------------------------------------------------------------------
# Graceful degradation without the tensorflow proto bindings (no tf_pb2
# fixture — these must pass in a tensorflow-less environment too).
# ---------------------------------------------------------------------------

def _simulate_missing_protos(monkeypatch):
    """Make _pb2 behave as if tensorflow were absent."""
    monkeypatch.setattr(xplane, "_xplane_pb2", None)

    def boom():
        raise xplane.XplaneProtosUnavailable(xplane.PROTO_HINT)

    monkeypatch.setattr(xplane, "_pb2", boom)


def test_cli_prints_one_liner_without_protos(monkeypatch, tmp_path):
    _simulate_missing_protos(monkeypatch)
    with pytest.raises(SystemExit) as ei:
        xplane.main([str(tmp_path)])
    # SystemExit with a string message prints the message, no traceback.
    msg = str(ei.value)
    assert "xplane_pb2" in msg and "tensorflow" in msg
    assert "\n" not in msg.strip()      # an actionable ONE-liner


def test_load_xspace_raises_typed_import_error(monkeypatch, tmp_path):
    _simulate_missing_protos(monkeypatch)
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    with pytest.raises(xplane.XplaneProtosUnavailable):
        xplane.load_xspace(str(tmp_path))
    # Subclass of ImportError: pre-existing handlers keep working.
    assert issubclass(xplane.XplaneProtosUnavailable, ImportError)


def test_protos_available_reports_false_when_missing(monkeypatch):
    _simulate_missing_protos(monkeypatch)
    assert xplane.protos_available() is False


def test_report_cli_degrades_without_protos(monkeypatch, tmp_path):
    """scripts/dmp_report.py --trace prints the hint in the report body
    instead of dying on ImportError."""
    _simulate_missing_protos(monkeypatch)
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "dmp_report", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "dmp_report.py"))
    dmp_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dmp_report)
    records = [{"ts": 0.0, "kind": "run_start", "run": "t",
                "device": {"platform": "cpu", "device_kind": "cpu",
                           "n_devices": 1}, "meta": {}}]
    text = dmp_report.build_report(records, trace_dir=str(tmp_path))
    assert "trace analysis skipped" in text
    assert "tensorflow" in text


def test_interleave_roundtrip_and_mapping():
    # Not xplane, but the adjacent round-5 helper with pure-numpy
    # semantics worth pinning: storage row s*(V*Lc)+v*Lc+j must hold
    # canonical layer (v*S+s)*Lc+j, and deinterleave inverts exactly.
    import numpy as np

    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        deinterleave_block_rows,
        interleave_block_rows,
    )

    L, S, V = 12, 2, 3
    lc = L // (S * V)
    blocks = {"w": np.arange(L * 2).reshape(L, 2)}
    inter = interleave_block_rows(blocks, L, S, V)
    for s in range(S):
        for v in range(V):
            for j in range(lc):
                storage = s * V * lc + v * lc + j
                canonical = (v * S + s) * lc + j
                assert (inter["w"][storage] == blocks["w"][canonical]).all()
    back = deinterleave_block_rows(inter, L, S, V)
    assert (back["w"] == blocks["w"]).all()
