"""Span tracing (utils/tracing.py) + the Chrome-trace export
(scripts/dmp_trace.py): the span API's nesting/thread/tenant semantics,
the instrumented trainers' and serving engine's timelines end to end,
the exporter's event structure, the overhead contract (< 2% of the
CPU perf smoke's p50 step time), and the spans' second sink: the
profiler's timeline, read back the way the benchmark reads it
(chipbench/trace_reduce.py), with the names the kernels and the jitted
steps carry into a device trace."""

import json
import os
import sys
import tempfile
import threading
import time

import jax
import pytest

from distributed_model_parallel_tpu.utils import tracing
from distributed_model_parallel_tpu.utils.telemetry import (
    TelemetryRun,
    read_records,
    tenant_scope,
)
from distributed_model_parallel_tpu.utils.tracing import span
from scripts.dmp_trace import build_trace


@pytest.fixture(autouse=True)
def _clean_thread_sink():
    prev = tracing.installed()
    yield
    tracing.install(prev)


def _spans(path):
    return [r for r in read_records(path) if r["kind"] == "span"]


# ---------------------------------------------------------------------------
# span API semantics
# ---------------------------------------------------------------------------

def test_span_records_fields_and_monotonic_duration(tmp_path):
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="t",
                       track_compiles=False)
    tracing.install(run)
    with span("work", epoch=3):
        time.sleep(0.01)
    (s,) = _spans(run.path)
    assert s["name"] == "work" and s["epoch"] == 3
    assert s["dur_s"] >= 0.01
    assert s["parent"] is None and s["depth"] == 0
    assert isinstance(s["sid"], int) and s["thread"]
    # wall-clock start before wall-clock end stamp
    assert s["t0"] <= s["ts"]


def test_spans_nest_with_parent_ids(tmp_path):
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="t",
                       track_compiles=False)
    tracing.install(run)
    with span("outer"):
        with span("inner"):
            pass
    inner, outer = _spans(run.path)          # inner exits (writes) first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == outer["sid"] and inner["depth"] == 1


def test_no_sink_and_disabled_are_noops(tmp_path):
    tracing.uninstall()
    with span("dropped"):                     # no sink: must not raise
        pass
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="t",
                       track_compiles=False)
    tracing.install(run)
    tracing.set_enabled(False)
    try:
        with span("also-dropped"):
            pass
    finally:
        tracing.set_enabled(True)
    assert _spans(run.path) == []


def test_sink_scope_binds_and_restores(tmp_path):
    a = TelemetryRun(str(tmp_path / "a.jsonl"), run="a",
                     track_compiles=False)
    b = TelemetryRun(str(tmp_path / "b.jsonl"), run="b",
                     track_compiles=False)
    tracing.install(a)
    with tracing.sink_scope(b):
        with span("scoped"):
            pass
    with span("after"):
        pass
    assert [s["name"] for s in _spans(b.path)] == ["scoped"]
    assert [s["name"] for s in _spans(a.path)] == ["after"]
    # None sink leaves the binding alone
    with tracing.sink_scope(None):
        assert tracing.installed() is a


def test_span_survives_exception_and_marks_it(tmp_path):
    run = TelemetryRun(str(tmp_path / "t.jsonl"), run="t",
                       track_compiles=False)
    tracing.install(run)
    with pytest.raises(ValueError):
        with span("doomed"):
            raise ValueError("boom")
    (s,) = _spans(run.path)
    assert s["name"] == "doomed" and s["error"] == "ValueError"
    # stack is clean afterwards: next span is top-level
    with span("next"):
        pass
    nxt = _spans(run.path)[-1]
    assert nxt["parent"] is None and nxt["depth"] == 0


def test_sinks_and_stacks_are_thread_local(tmp_path):
    paths = {}

    def work(name):
        with tenant_scope(name):
            run = TelemetryRun(str(tmp_path / f"{name}.jsonl"), run=name,
                               track_compiles=False)
            tracing.install(run)
            with span("epoch"):
                with span("drain"):
                    pass
            paths[name] = run.path

    threads = [threading.Thread(target=work, args=(f"t{i}",))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for name, path in paths.items():
        recs = _spans(path)
        assert [s["name"] for s in recs] == ["drain", "epoch"]
        # tenant tag arrives through the stream, not the span API
        assert all(s["tenant"] == name for s in recs)
        drain, epoch = recs
        assert drain["parent"] == epoch["sid"]


# ---------------------------------------------------------------------------
# end to end: instrumented trainer + engine -> Chrome trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_runs(tmp_path_factory):
    """One tiny traced trainer fit + one traced engine run, shared by the
    e2e/export/overhead tests below."""
    tmp = tmp_path_factory.mktemp("traced")
    from tests.conftest import tiny_train_config
    from distributed_model_parallel_tpu.train.trainer import Trainer

    with tenant_scope("trainer0"):
        t = Trainer(tiny_train_config(tmp, epochs=2, log_every_n_steps=1))
        t.fit()
    trainer_path = t.logger.jsonl_path

    import jax.numpy as jnp  # noqa: F401
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq_len=64,
                                pos_embedding="rope")
    params = tfm.init_params(jax.random.key(0), cfg)
    with tenant_scope("serve0"):
        run = TelemetryRun(str(tmp / "serve.jsonl"), run="serve",
                           track_compiles=False)
        eng = Engine(params, cfg,
                     ServeConfig(n_slots=2, page_size=8, n_pages=32,
                                 max_seq_len=64, prefill_chunk=8),
                     telemetry=run, slo_metrics=False)
        for i in range(3):
            eng.submit([1, 2, 3, 4, 5], 6, seed=i)
        eng.run()
        run.finish()
    return str(trainer_path), str(tmp / "serve.jsonl")


def test_trainer_stream_carries_nested_spans(traced_runs):
    trainer_path, _ = traced_runs
    spans = _spans(trainer_path)
    names = {s["name"] for s in spans}
    assert {"train_epoch", "drain", "evaluate"} <= names
    drains = [s for s in spans if s["name"] == "drain"]
    epochs = {s["sid"] for s in spans if s["name"] == "train_epoch"}
    assert any(d["parent"] in epochs for d in drains)
    assert all(s["tenant"] == "trainer0" for s in spans)


def test_engine_stream_carries_request_lifecycle(traced_runs):
    _, serve_path = traced_runs
    recs = read_records(serve_path)
    names = {r["name"] for r in recs if r["kind"] == "span"}
    assert {"admit", "prefill_chunk", "decode_round"} <= names
    # the iteration's own span and the phases inside the phases are
    # profiler-only (stream=False): the stream keeps what it had
    assert "engine_step" not in names
    assert not any("." in n for n in names)
    completed = [r for r in recs if r["kind"] == "serve"
                 and r.get("event") == "completed"]
    assert len(completed) == 3


def test_chrome_trace_export_is_valid_and_nested(traced_runs, tmp_path):
    from distributed_model_parallel_tpu.utils.telemetry import merge_streams
    from scripts import dmp_trace

    trainer_path, serve_path = traced_runs
    out = str(tmp_path / "trace.json")
    dmp_trace.main([trainer_path, serve_path, "-o", out])
    trace = json.loads(open(out).read())      # valid JSON by construction
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert {"ph", "name", "pid", "ts"} <= set(e)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    # tenant lanes: one Chrome process per tenant
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"trainer0", "serve0"} <= lanes
    # nesting: a drain bar inside a train_epoch bar on the same track
    te = [e for e in xs if e["name"] == "train_epoch"]
    dr = [e for e in xs if e["name"] == "drain"]
    assert any(d["pid"] == e["pid"] and d["tid"] == e["tid"]
               and e["ts"] <= d["ts"]
               and d["ts"] + d["dur"] <= e["ts"] + e["dur"] + 1
               for e in te for d in dr)
    # serve request lifecycle bars reconstructed from the SLO records
    segs = {e["name"] for e in xs if e.get("cat") == "serve-request"}
    assert "decode" in segs
    # build_trace on a merged record list matches main()'s output shape
    merged = build_trace(merge_streams([trainer_path, serve_path]))
    assert merged["traceEvents"]


def test_span_overhead_under_two_percent_of_step_time(traced_runs,
                                                      tmp_path):
    """The overhead contract: spans recorded per drain window (not per
    step) must cost < 2% of the perf smoke's p50 step time. Measured
    directly: per-span cost (enter + record write + exit on a real
    stream) x observed spans-per-step vs the traced run's p50 step
    time — deterministic, unlike an on/off wall-clock diff on a noisy
    CI host."""
    trainer_path, _ = traced_runs
    recs = read_records(trainer_path)
    steps = [r for r in recs if r["kind"] == "step"
             and isinstance(r.get("step_time_s"), (int, float))]
    spans = [r for r in recs if r["kind"] == "span"]
    n_train_steps = 6 * 2        # 96 samples / batch 32 = 3 steps x 2 epochs
    assert steps and spans
    p50 = sorted(r["step_time_s"] for r in steps)[len(steps) // 2]
    spans_per_step = len(spans) / n_train_steps

    run = TelemetryRun(str(tmp_path / "bench.jsonl"), run="b",
                       track_compiles=False)
    tracing.install(run)
    n = 300
    t0 = time.perf_counter()
    for i in range(n):
        with span("probe", i=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    tracing.uninstall()
    overhead_per_step = per_span * spans_per_step
    assert overhead_per_step < 0.02 * p50, (
        f"span overhead {overhead_per_step * 1e6:.1f}us/step vs p50 step "
        f"{p50 * 1e3:.2f}ms ({spans_per_step:.2f} spans/step at "
        f"{per_span * 1e6:.1f}us each)")


# ---------------------------------------------------------------------------
# the second sink: the profiler's timeline
# ---------------------------------------------------------------------------

def _trace_reduce():
    """The benchmark's trace loader (the reader the spans are for)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import trace_reduce
    return trace_reduce


def _toy_lm_trainer(tmp):
    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        OptimizerConfig,
    )
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    t = LMTrainer(LMTrainConfig(
        model=tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                    n_layers=2, d_ff=64, max_seq_len=32),
        mesh=MeshConfig(data=1), optimizer=OptimizerConfig(),
        batch_size=2, seq_len=16, steps_per_epoch=5, epochs=2,
        n_tokens=2_000, eval_fraction=0.0, eval_batches=0,
        log_dir=str(tmp / "log"), log_name="lm",
        checkpoint_dir=str(tmp / "ckpt")))
    t.logger.echo = False
    return t


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profile session, opened the way the benchmark opens its own
    (chipbench/harness.TraceWindow), over: plain nested spans with no
    sink bound, spans with tracing switched off, a toy engine run and a
    toy LM trainer's epoch. Returns what the benchmark's loader reads
    back, the stream of the switched-off stretch, and the trainer's own
    stream (bound while its first, unprofiled epoch ran)."""
    tmp = tmp_path_factory.mktemp("profiled")
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq_len=64,
                                pos_embedding="rope")
    eng = Engine(tfm.init_params(jax.random.key(0), cfg), cfg,
                 ServeConfig(n_slots=2, page_size=8, n_pages=32,
                             max_seq_len=64, prefill_chunk=4),
                 slo_metrics=False)
    eng.warmup()
    trainer = _toy_lm_trainer(tmp)
    trainer.fit(epochs=1)           # compiles; the profiled epoch is warm
    prev = tracing.installed()
    off = TelemetryRun(str(tmp / "off.jsonl"), run="off",
                       track_compiles=False)
    log_dir = tempfile.mkdtemp(dir=tmp)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        tracing.uninstall()
        with span("outer", n=3):
            with span("outer.inner") as sp:
                sp.annotate(found=1)
                time.sleep(0.002)
        tracing.install(off)
        tracing.set_enabled(False)
        try:
            with span("switched-off"):
                pass
        finally:
            tracing.set_enabled(True)
        tracing.uninstall()
        for i in range(3):
            eng.submit([1, 2, 3, 4, 5, 6], 8, seed=i)
        eng.run()
        trainer.fit()
    finally:
        jax.profiler.stop_trace()
        tracing.install(prev)
    tr = _trace_reduce()
    return (tr.load_xplane(tr.find_xplane(log_dir)).spans, off.path,
            str(trainer.logger.jsonl_path))


def _inside(child, parent):
    return (parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


def _named(spans, name):
    return [e for e in spans if e[0] == name]


def test_prefix_is_the_one_the_benchmark_loader_keeps():
    assert tracing.PROFILER_PREFIX == _trace_reduce().SPAN_PREFIX


def test_span_reaches_the_profiler_with_no_sink_bound(profiled):
    spans, _, _ = profiled
    (outer,), (inner,) = _named(spans, "outer"), _named(spans,
                                                        "outer.inner")
    assert _inside(inner, outer) and inner[2] >= 2_000_000
    assert not any(n.startswith(tracing.PROFILER_PREFIX)
                   for n, _, _ in spans)        # the loader strips it


def test_switched_off_emits_to_neither_sink(profiled):
    spans, off_path, _ = profiled
    assert not _named(spans, "switched-off")
    assert _spans(off_path) == []


def test_engine_phases_on_the_profiler_timeline(profiled):
    spans, _, _ = profiled
    steps = _named(spans, "engine_step")
    assert len(steps) >= 8                       # 8 tokens a request
    for phase in ("admit", "prefill_chunk", "decode_round"):
        got = _named(spans, phase)
        assert got and all(any(_inside(p, s) for s in steps) for p in got)
    assert len(_named(spans, "admit")) == len(steps)   # every iteration
    for parent in ("prefill_chunk", "decode_round"):
        parents = _named(spans, parent)
        for k in ("inputs", "dispatch", "sync", "commit"):
            got = _named(spans, f"{parent}.{k}")
            assert got, f"no {parent}.{k} span"
            assert all(any(_inside(c, p) for p in parents) for c in got)
    # a chunk that is not a prompt's last has nothing to fetch
    assert (len(_named(spans, "prefill_chunk.sync"))
            < len(_named(spans, "prefill_chunk.dispatch")))
    rounds = _named(spans, "decode_round")
    covered = sum(c[2] for c in spans if c[0].startswith("decode_round."))
    assert covered >= 0.9 * sum(r[2] for r in rounds), (
        covered, sum(r[2] for r in rounds))


def test_train_step_phases_on_the_profiler_timeline(profiled):
    spans, _, lm_path = profiled
    steps = _named(spans, "train_step")
    assert len(steps) == 5
    for k in ("batch", "dispatch", "sync"):
        got = _named(spans, f"train_step.{k}")
        assert len(got) == 5
        assert all(any(_inside(c, s) for s in steps) for c in got)
    epoch = _named(spans, "train_epoch")
    assert all(any(_inside(s, e) for e in epoch) for s in steps)
    # profiler-only (stream=False): the stream has its ``step`` records
    streamed = {r["name"] for r in _spans(lm_path)}
    assert "train_epoch" in streamed
    assert not any(n.startswith("train_step") for n in streamed)


def test_span_cost_with_no_sink_and_no_session():
    """The annotation is all a span costs where nothing listens: the
    best of five rounds stays under 5 us a span."""
    tracing.uninstall()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(20_000):
            with span("probe", i=i):
                pass
        best = min(best, (time.perf_counter() - t0) / 20_000)
    assert best < 5e-6, f"{best * 1e6:.2f} us a span"


# ---------------------------------------------------------------------------
# names a device trace is read by
# ---------------------------------------------------------------------------

def _pallas_call_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue                          # not into the kernel's body
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_call_names(inner, out)
    return out


def test_flash_kernels_carry_their_names():
    import jax.numpy as jnp
    from distributed_model_parallel_tpu.ops import pallas_attention as fa

    x = jnp.zeros((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    fwd = jax.make_jaxpr(loss)(x, x, x)
    assert _pallas_call_names(fwd.jaxpr, []) == ["flash_fwd"]
    both = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    assert sorted(_pallas_call_names(both.jaxpr, [])) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_jitted_steps_lower_to_named_modules():
    import jax.numpy as jnp
    import optax
    from distributed_model_parallel_tpu.config import MeshConfig
    from distributed_model_parallel_tpu.mesh import make_mesh
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        make_spmd_train_step,
    )
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig

    def module_name(lowered):
        return lowered.as_text().split("module @", 1)[1].split()[0]

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq_len=64,
                                pos_embedding="rope")
    params = tfm.init_params(jax.random.key(0), cfg)
    eng = Engine(params, cfg,
                 ServeConfig(n_slots=2, page_size=8, n_pages=32,
                             max_seq_len=64, prefill_chunk=4, spec_k=2),
                 slo_metrics=False)
    b, n = 2, eng.cache.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    pools = (eng.params, eng.cache.pools, None)
    idle = jnp.zeros((b,), bool)
    got = [
        module_name(eng._prefill.lower(
            *pools, i32(1, 4), jnp.int32(0), jnp.int32(0), (i32(n), None),
            jax.random.key(0))),
        module_name(eng._decode.lower(
            *pools, i32(b), i32(b), (i32(b, n), None), idle, None)),
    ] + [
        module_name(eng._verify[w].lower(
            *pools, i32(b, w), i32(b), i32(b) + 1, (i32(b, n), None), idle,
            None))
        for w in eng._verify_widths
    ]
    tx = optax.sgd(0.1)
    spec = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    for schedule in ("gpipe", "1f1b"):
        step = make_spmd_train_step(cfg, spec, tx, schedule=schedule)
        got.append(module_name(step.lower(params, tx.init(params),
                                          i32(2, 16), i32(2, 16))))
    assert got[:2] == ["jit_prefill_step", "jit_decode_step"]
    assert set(got[2:-2]) == {"jit_verify_step"}
    assert got[-2:] == ["jit_lm_train_step"] * 2


def test_state_layers_carry_their_scopes_and_gauges():
    """A model with gated-delta layers: both steps' device ops sit under
    ``linattn_proj``, ``linattn_conv``, ``linattn_rule`` and
    ``linattn_gate`` (the full layers keep ``attn_full``), the decode
    round's rule is the kernel named ``gated_delta_decode``, and the
    memory gauges count the slots that hold a state."""
    import jax.numpy as jnp
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.ops import gated_delta
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig
    from distributed_model_parallel_tpu.serve.paged_kv import memory_gauges

    lin, full = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, n_layers=4,
        d_ff=64, max_seq_len=64, pos_embedding="rope", norm="rmsnorm",
        ffn="swiglu", qk_norm_whole=True, norm_placement="post",
        layer_kinds=(lin, lin, lin, full), lin_key_heads=2,
        lin_value_heads=2, lin_key_dim=8, lin_value_dim=64)
    eng = Engine(tfm.init_params(jax.random.key(0), cfg), cfg,
                 ServeConfig(n_slots=2, page_size=8, n_pages=32,
                             max_seq_len=64, prefill_chunk=8,
                             attn_impl="pallas"), slo_metrics=False)
    scopes = ("linattn_proj", "linattn_conv", "linattn_rule",
              "linattn_gate", "attn_full")
    got = eng.op_scopes(scopes)
    assert sorted(got) == ["jit_decode_step", "jit_prefill_step"]
    for module in got.values():
        assert set(module.values()) == set(scopes)
    # the kernel carries its name into the op line, inside its scope
    x = jnp.zeros((2, 2, 8))
    jaxpr = jax.make_jaxpr(lambda pool: gated_delta.gated_delta_kernel(
        pool, jnp.int32(0), x, x, jnp.zeros((2, 2, 64)), jnp.ones((2, 2)),
        jnp.ones((2, 2))))(jnp.zeros((3, 2, 8, 128)))
    assert _pallas_call_names(jaxpr.jaxpr, []) == ["gated_delta_decode"]
    req = eng.submit([1, 2, 3], 5)
    eng.step_once(0.0, 0.0)
    g = memory_gauges(eng.cache)
    assert g["state_slots"] == 1 and req.slot is not None
    assert g["state_bytes"] == eng.cache.state_bytes_per_slot == 3 * (
        8 * 128 * 4 + 3 * (2 * 2 * 8 + 128) * 4)
    assert g["full_layer_pages"] == g["used_pages"] == 1


def test_build_trace_tolerates_minimal_and_foreign_records():
    # Empty-ish and schema-poor records must not KeyError the exporter.
    trace = build_trace([])
    assert trace["traceEvents"] == []
    trace = build_trace([
        {"kind": "run_start", "run": "x", "ts": 1.0},
        {"kind": "span", "name": "s"},                    # no t0/dur
        {"kind": "serve", "event": "completed", "ts": 2.0},  # no wall_s
        {"kind": "failure", "ts": 1.5},                   # no error field
        {"not-even-a-kind": True},
    ])
    assert all("ts" in e for e in trace["traceEvents"])



def test_the_looped_stack_carries_its_scopes_counters_and_gauges():
    """A stack run three times over shared weights: both steps' device
    ops sit under ``loop_stack`` (every pass of every layer, the norm
    that closes a pass) or ``exit_gate``; ``Engine.loop_counters()``
    counts tokens, the passes they took and the gate's mass a pass; the
    memory gauges say how deep the cache is and what a token costs."""
    import jax.numpy as jnp
    from distributed_model_parallel_tpu.models import transformer as tfm
    from distributed_model_parallel_tpu.serve import Engine, ServeConfig
    from distributed_model_parallel_tpu.serve.paged_kv import memory_gauges

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=64, pos_embedding="rope", norm="rmsnorm",
        ffn="swiglu", norm_placement="sandwich", n_passes=3,
        loop_final_norm=True, exit_gate=True)
    eng = Engine(tfm.init_params(jax.random.key(0), cfg), cfg,
                 ServeConfig(n_slots=2, page_size=8, n_pages=32,
                             max_seq_len=64, prefill_chunk=8,
                             attn_impl="xla"), slo_metrics=False)
    got = eng.op_scopes(("loop_stack", "exit_gate"))
    assert sorted(got) == ["jit_decode_step", "jit_prefill_step"]
    for module in got.values():
        assert set(module.values()) == {"loop_stack", "exit_gate"}
    assert eng.loop_counters() == {
        "passes": 3, "tokens": 0, "token_passes": 0,
        "exit_mass": [0.0, 0.0, 0.0]}
    req = eng.submit([1, 2, 3], 5)
    eng.step_once(0.0, 0.0)
    eng.step_once(0.0, 0.0)
    c = eng.loop_counters()
    # the prompt, and every generated token but the last (not yet fed)
    fed = 3 + len(req.generated) - 1
    assert len(req.generated) >= 2
    assert (c["tokens"], c["token_passes"]) == (fed, 3 * fed)
    assert sum(c["exit_mass"]) == pytest.approx(fed, rel=1e-5)
    g = memory_gauges(eng.cache)
    assert g["cache_layers"] == 6 and req.slot is not None
    assert g["kv_bytes_per_token"] == 6 * 2 * 2 * 16 * 4
    assert g["full_layer_pages"] == 6 * g["used_pages"] == 6
    status = eng._status()
    assert (status["passes"], status["cache_layers"]) == (3, 6)
