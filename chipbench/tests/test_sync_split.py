"""``readers/sync_split`` on traces made by hand: the idle inside a
span cut into launch, holes and fetch; and the per-layer metrics that
read the engine's ``engine_step.meter`` and ``decode_round.sync`` spans
through it and through ``host_exposed``."""

import importlib
import os
import random
import types

import pytest

import harness
import trace_reduce as tr
from readers import host_exposed, sync_split

# the device's window is 10-300; it idles at 40-50, 70-75, 90-120,
# 160-200 and 230-280
OPS = [("%fusion.1 fusion", 10, 30), ("%fusion.2 fusion", 50, 20),
       ("%paged_decode_attention.1", 75, 15), ("%fusion.1 fusion", 120, 40),
       ("%fusion.2 fusion", 200, 30), ("%fusion.1 fusion", 280, 20)]
SYNC = "decode_round.sync"
SPANS = sorted([
    ("decode_round", 40, 60),
    # idle when it opens: launch 45-50, a hole 70-75, fetch 90-95
    (SYNC, 45, 50),
    # opens while an op runs: no launch; fetch 160-170
    (SYNC, 130, 40),
    # no op inside: the result was ready, all of it is fetch
    (SYNC, 240, 20),
    ("engine_step.meter", 100, 10),
], key=lambda e: e[1])
WANT = [(5, 5, 5), (0, 0, 10), (0, 0, 20)]   # ns, span by span


def ctx_of(spans=SPANS, ops=OPS, modules=(), devices=1):
    trace = tr.Trace(devices={f"/device:TPU:{i}": {"modules": list(modules),
                                                   "ops": list(ops)}
                              for i in range(devices)}, spans=list(spans))
    return types.SimpleNamespace(trace=trace)


def _split_each(ops, spans, name):
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    gaps = tr.idle_gaps(ops, t0, t1)
    ends = [b for _, b in gaps]
    mine = tr.clip([e for e in spans if e[0] == name], t0, t1)
    return gaps, mine, [sync_split.split(gaps, ends, s, s + d)
                        for _, s, d in mine]


def test_the_parts_sum_to_the_exposed_time_of_each_span():
    gaps, mine, parts = _split_each(OPS, SPANS, SYNC)
    assert parts == WANT
    for (_, s, d), p in zip(mine, parts):
        assert sum(p) == host_exposed.overlap_ns(gaps, [[s, s + d]])


def test_the_parts_sum_on_a_random_trace():
    rng = random.Random(2 ** 31 + 7)
    t, ops = 0, []
    for i in range(400):
        t += rng.choice((0, 0, 1, 3, 17))
        d = rng.randint(1, 40)
        ops.append((f"%op.{i}", t, d))
        t += rng.randint(0, d)           # ops may overlap
    spans, s = [], 0
    while s < t:
        s += rng.randint(1, 60)
        spans.append((SYNC, s, rng.randint(1, 90)))
        s += spans[-1][2]
    gaps, mine, parts = _split_each(ops, spans, SYNC)
    assert len(mine) > 20 and any(p[0] for p in parts)
    assert any(p[1] for p in parts) and any(p[2] for p in parts)
    for (_, s, d), p in zip(mine, parts):
        assert min(p) >= 0
        assert sum(p) == host_exposed.overlap_ns(gaps, [[s, s + d]])
    assert sum(map(sum, parts)) == host_exposed.overlap_ns(
        gaps, tr.union_intervals(mine))


@pytest.mark.parametrize("part,percentile,want_ns", [
    ("fetch", 50, 10), ("fetch", 100, 20),
    ("launch", 50, 0), ("launch", 100, 5),
    ("holes", 50, 0), ("holes", 100, 5),
])
def test_a_percentile_over_spans_in_ms_averaged_over_chips(part, percentile,
                                                          want_ns):
    spec = {"span": SYNC, "part": part, "percentile": percentile}
    for ctx in (ctx_of(), ctx_of(devices=4), ctx_of(ops=[], modules=OPS)):
        assert sync_split.read(ctx, spec) == pytest.approx(want_ns / 1e6)


def test_a_span_with_no_op_inside_is_all_fetch():
    gaps, _, parts = _split_each(OPS, [(SYNC, 232, 40)], SYNC)
    assert parts == [(0, 0, 40)]
    # one op inside and the device idle on both sides: launch and fetch
    gaps, _, parts = _split_each(OPS, [(SYNC, 195, 40)], SYNC)
    assert parts == [(5, 0, 5)]


def test_a_span_that_opens_while_an_op_runs_has_no_launch():
    _, _, parts = _split_each(OPS, [(SYNC, 20, 60)], SYNC)
    # 20-40 under an op, a hole 40-50, 70-75, and an op running at 80
    assert parts == [(0, 15, 0)]


def test_no_such_span_gives_nothing():
    spec = {"span": SYNC, "part": "fetch", "percentile": 50}
    old = ctx_of(spans=[("step_once", 0, 100), ("offer", 100, 2)])
    assert sync_split.read(old, spec) is None
    assert sync_split.read(types.SimpleNamespace(trace=None), spec) is None
    assert sync_split.read(ctx_of(ops=[], modules=[]), spec) is None
    # outside the device's window: clipped away
    assert sync_split.read(ctx_of(spans=[(SYNC, 300, 9)]), spec) is None


# -- the metrics that read the engine's spans ----------------------------------

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = [f"{m}.{c}" for m in ("engine_meter_exposed_ms",)
       for c in ("batch", "mixed", "hybrid")] + [
    f"{m}.{c}" for m in ("decode_fetch_p50_ms", "decode_launch_p50_ms")
    for c in ("batch", "steady", "hybrid")]


@pytest.mark.parametrize("name", NEW)
def test_engine_span_metrics_name_a_reader_and_cells_that_exist(name):
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                          f"{name}.json"))
    reader = importlib.import_module(f"readers.{spec['reader']}")
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    (cell,) = entry["workloads"]
    assert cell in cells
    assert entry["moves"] in {m["name"] for m in
                              harness.load_cell(ROOT, cell).end_to_end()}
    # read on the engine's spans; nothing where a program has none
    got = reader.read(ctx_of(spans=SPANS + [("engine_step.meter", 150, 20)]),
                      spec)
    assert got is not None and got >= 0
    assert reader.read(ctx_of(spans=[("step_once", 0, 100)]), spec) is None
