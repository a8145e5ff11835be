"""The trace reduction on small traces: one made by hand, one recorded
on the chip (``data/recorded_trace.json``, cut from a v5e run of the
training cell)."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

OPS = [("fusion.1", 0, 10), ("flash", 5, 10), ("copy", 30, 5),
       ("flash", 40, 10), ("all-reduce", 45, 15)]


def test_busy_union_and_gaps():
    assert tr.union_intervals(OPS) == [[0, 15], [30, 35], [40, 60]]
    assert tr.busy_ns(OPS) == 40
    assert tr.idle_gaps(OPS, 0, 70) == [(15, 30), (35, 40), (60, 70)]
    assert tr.idle_gaps(OPS, 10, 33) == [(15, 30)]
    assert tr.clip(OPS, 8, 32) == [("fusion.1", 8, 2), ("flash", 8, 7),
                                   ("copy", 30, 2)]


def test_kernel_sums_and_top():
    assert tr.sum_by_name(OPS)["flash"] == 20
    assert [e[0] for e in tr.select(OPS, ["flash", "copy"])] == \
        ["flash", "copy", "flash"]
    assert tr.top_ops(OPS, top=2) == [["flash", 20e-9],
                                      ["all-reduce", 15e-9]]


def test_gap_attribution_innermost_span():
    spans = [("fit", 0, 100), ("sample_batch", 14, 18), ("fetch", 58, 20)]
    got = dict(tr.attribute_gaps(tr.idle_gaps(OPS, 0, 70), spans))
    assert got == {"sample_batch": 15e-9, "fit": 5e-9, "fetch": 10e-9}
    assert tr.span_at(spans, 200) == "host"


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    trace = tr.Trace.from_json(rec["trace"])
    want = rec["expected"]
    (dev, lines), = trace.devices.items()
    ops, mods = lines["ops"], lines["modules"]
    t0, t1 = ops[0][1], max(s + d for _, s, d in ops)
    assert tr.busy_ns(ops) == want["busy_ns"]
    assert t1 - t0 == want["window_ns"]
    assert 0 < want["busy_ns"] <= want["window_ns"]
    assert len(mods) == want["n_modules"]
    for name, ns in want["kernel_ns"].items():
        assert sum(d for _, _, d in tr.select(ops, [name])) == ns
    gaps = tr.idle_gaps(ops, t0, t1)
    assert sum(b - a for a, b in gaps) == want["window_ns"] - want["busy_ns"]
    attributed = tr.attribute_gaps(gaps, trace.spans, top=100)
    assert sum(s for _, s in attributed) == pytest.approx(
        (want["window_ns"] - want["busy_ns"]) / 1e9)


def test_short_names_tell_the_unnamed_flash_kernels_apart():
    L = "{2,1,0:T(8,128)(2,1)S(1)}"
    t = 'custom_call_target="tpu_custom_call", frontend_attributes={x={}}'
    fwd = (f"%closed_call.18 = (bf16[24,8192,128]{L}, f32[24,8,8192]{L}) "
           f"custom-call(bf16[24,8192,128]{L} %a, bf16[24,8192,128]{L} %b, "
           f"bf16[24,8192,128]{L} %c), {t}")
    dq = (f"%checkpoint.18 = bf16[24,8192,128]{L} custom-call("
          f"bf16[24,8192,128]{L} %a, bf16[24,8192,128]{L} %b, "
          f"bf16[24,8192,128]{L} %c, bf16[24,8192,128]{L} %d, "
          f"f32[24,8,8192]{L} %e, f32[24,8,8192]{L} %f), {t}")
    wh = (f"%while.17 = (s32[]{{:T(128)}}, bf16[1,8192,3072]{L}) "
          f"while((s32[]{{:T(128)}}, bf16[1,8192,3072]{L}) %tuple), "
          f"condition=%c, body=%b")
    assert tr.short_name(fwd) == (
        "%closed_call.18 custom-call:tpu_custom_call "
        "out=(bf16[24,8192,128],f32[24,8,8192]) in=(bf16,bf16,bf16)")
    assert tr.short_name(dq).endswith(
        "out=(bf16[24,8192,128]) in=(bf16,bf16,bf16,bf16,f32,f32)")
    assert tr.is_container(tr.short_name(wh))
    assert not tr.is_container(tr.short_name(fwd))
    assert tr.short_name("jit_step(123)") == "jit_step(123)"
    import json as _json
    import os as _os
    bench = _os.path.dirname(HERE)
    ev = [(tr.short_name(fwd), 0, 5), (tr.short_name(dq), 5, 7),
          (tr.short_name(wh), 0, 12)]
    for metric, n in (("flash_fwd_roofline", 1), ("flash_bwd_roofline", 1)):
        with open(_os.path.join(bench, "layer_metrics",
                                f"{metric}.json")) as f:
            assert len(tr.select(ev, _json.load(f)["kernels"])) == n


def test_train_readers_on_the_recorded_step():
    """Every training reader finds its events in the recorded step and
    gives a number a v5e can give: a roofline share under 100%."""
    import harness
    import weights

    bench = os.path.dirname(HERE)
    root = os.path.dirname(bench)
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        trace = tr.Trace.from_json(json.load(f)["trace"])
    cell = harness.load_cell(root, "starcoder2-3b-train.seq8k")
    dims = weights.Dims.from_config(cell.config)
    (dev, lines), = trace.devices.items()
    step_s = lines["modules"][0][2] / 1e9
    import flops
    out = harness.RunOutput(
        metrics={}, attempted=1, failed=0, window_s=step_s,
        counters={"steps": 1, "seq_len": 8192, "sequences_per_chip": 1,
                  "heads_per_chip": 24,
                  "step_flops": flops.train_flops_per_step(dims, 1, 8192)},
        spans=harness.Spans(), dims=dims, trace=trace)
    ctx = harness.ReadContext(cell=cell, out=out,
                              peaks=harness.load_peaks("TPU v5 lite"),
                              n_chips=1)
    got = harness.read_layer_metrics(ctx)
    assert set(got) == {m["name"] for m in cell.per_layer()} - {
        "train_host_gap_ms"}          # one module: no gap between two
    assert 380 < got["train_step_dev_ms"]["value"] < 400
    assert 50 < got["train_mfu"]["value"] < 56
    assert 30 < got["flash_fwd_roofline"]["value"] < 60
    assert 30 < got["flash_bwd_roofline"]["value"] < 60
    assert 0 <= got["device_idle.train"]["value"] < 5
    busy, window = harness.device_busy(trace)
    assert 0 < busy <= window
    bd = harness.breakdown(trace)
    assert len(bd["device_ops"]) == 10 and not any(
        tr.is_container(n) for n, _ in bd["device_ops"])
    assert bd["device_ops"][0][0].startswith("%")
