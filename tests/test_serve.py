"""Serving-engine invariants: scheduler correctness, per-request
determinism, SLO accounting, and the kill-mid-stream failure contract.

The load-bearing properties (docs/SERVING.md):

* the page pool never double-allocates and every page returns on
  eviction (checked EVERY iteration, not just at the end);
* admission beyond pool capacity queues — it never over-commits or OOMs;
* a request's tokens are a pure function of (prompt, seed): solo run,
  mid-batch join, and the static-policy baseline all decode identical
  tokens, and the engine matches ``transformer.generate`` greedy;
* continuous batching beats static batching on slot utilization on a
  mixed-length workload (a timing-free comparison of the two policies);
* a killed engine reports every in-flight/queued request as a typed
  failure — nothing is silently dropped (chaos tier).
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import (
    Engine,
    EngineKilled,
    PagePool,
    PagePoolError,
    ServeConfig,
)
from distributed_model_parallel_tpu.serve.scheduler import RequestState
from distributed_model_parallel_tpu.utils.telemetry import (
    TelemetryRun,
    read_records,
)

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=128,
                                pos_embedding="rope")
    return cfg, tfm.init_params(jax.random.key(0), cfg)


def _serve(**kw):
    base = dict(n_slots=2, page_size=8, n_pages=32, max_seq_len=64,
                prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16]]
GENS = [12, 18, 7]


# ---------------------------------------------------------------------------
# page-pool unit invariants
# ---------------------------------------------------------------------------

def test_pool_never_double_allocates():
    pool = PagePool(8)
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert len(set(a) | set(b)) == 7          # disjoint
    with pytest.raises(PagePoolError, match="exceeds"):
        pool.alloc(2)                         # only 1 free
    pool.free(a)
    c = pool.alloc(3)
    assert not set(c) & set(b)
    assert pool.free_pages + pool.used_pages == 8


def test_pool_rejects_double_free_and_foreign_pages():
    pool = PagePool(4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.free(pages)
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.free([99])


def test_pool_allocation_order_deterministic():
    orders = []
    for _ in range(2):
        pool = PagePool(6)
        a = pool.alloc(2)
        pool.free(a)
        orders.append(pool.alloc(4))
    assert orders[0] == orders[1]


# ---------------------------------------------------------------------------
# engine correctness + determinism
# ---------------------------------------------------------------------------

def test_engine_greedy_matches_generate(model):
    cfg, params = model
    refs = []
    for p, g in zip(PROMPTS, GENS):
        out = tfm.generate(params, cfg, jnp.asarray([p], jnp.int32), g)
        refs.append([int(t) for t in out[0][len(p):]])
    eng = Engine(params, cfg, _serve())
    reqs = [eng.submit(p, g) for p, g in zip(PROMPTS, GENS)]
    eng.run()
    for r, ref in zip(reqs, refs):
        assert r.state is RequestState.COMPLETED
        assert r.generated == ref


def test_mid_batch_join_matches_solo_run(model):
    """The continuous-batching determinism contract: a request joining a
    busy batch mid-flight decodes the same tokens a solo run through the
    same engine geometry produces — greedy and sampled."""
    cfg, params = model
    for serve_kw in ({}, {"temperature": 0.9, "top_k": 16}):
        busy = Engine(params, cfg, _serve(**serve_kw))
        reqs = [busy.submit(p, g, seed=i)
                for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
        busy.run()
        for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
            solo = Engine(params, cfg, _serve(**serve_kw))
            sr = solo.submit(p, g, seed=i)
            solo.run()
            assert sr.generated == reqs[i].generated, (
                f"request {i} tokens depend on batch composition "
                f"({serve_kw})")


def test_forced_pallas_impl_decodes_identical_tokens(model):
    """attn_impl='pallas' forces the paged kernels, decode rounds and
    prefill chunks alike (interpret mode on CPU) — the engine must
    complete and produce the auto path's tokens bitwise."""
    cfg, params = model
    ref = Engine(params, cfg, _serve())
    refs = [ref.submit(p, g) for p, g in zip(PROMPTS, GENS)]
    ref.run()
    eng = Engine(params, cfg, _serve(attn_impl="pallas"))
    reqs = [eng.submit(p, g) for p, g in zip(PROMPTS, GENS)]
    eng.run()
    for r, rr in zip(reqs, refs):
        assert r.state is RequestState.COMPLETED
        assert r.generated == rr.generated


def test_static_policy_decodes_identical_tokens(model):
    """Scheduling policy moves throughput, never tokens: the static
    baseline must produce bitwise the continuous schedule's output for
    every request (that is what makes a comparison of the two fair)."""
    cfg, params = model
    outs = []
    for policy in ("continuous", "static"):
        eng = Engine(params, cfg, _serve(policy=policy))
        reqs = [eng.submit(p, g) for p, g in zip(PROMPTS, GENS)]
        eng.run()
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1]


def test_every_iteration_page_accounting_exact(model):
    """Mid-run invariant: at every engine iteration, used pages ==
    exactly the sum of resident requests' reservations, and after the
    run every page is back (eviction returns everything)."""
    cfg, params = model
    eng = Engine(params, cfg, _serve())

    def hook(i):
        expect = sum(eng.cache.pages_needed(r.total_capacity)
                     for r in eng.sched.active())
        assert eng.cache.pool.used_pages == expect
        table_pages = [p for sid in eng.cache._tables
                       for p in eng.cache._tables[sid]]
        assert len(table_pages) == len(set(table_pages)), \
            "a page is mapped by two sequences"

    eng.step_hook = hook
    for p, g in zip(PROMPTS, GENS):
        eng.submit(p, g)
    eng.run()
    assert eng.cache.pool.free_pages == eng.cache.pool.n_pages
    assert eng.cache.pool.used_pages == 0


def test_admission_beyond_capacity_queues(model):
    """A pool holding exactly one request's worst case serializes the
    work instead of over-committing: never more than one resident, all
    complete."""
    cfg, params = model
    serve = _serve(n_slots=3, n_pages=3, max_seq_len=24)
    eng = Engine(params, cfg, serve)
    max_resident = 0

    def hook(i):
        nonlocal max_resident
        max_resident = max(max_resident, len(eng.sched.active()))

    eng.step_hook = hook
    reqs = [eng.submit([1 + i, 2, 3], 12) for i in range(3)]  # 15 toks
    eng.run()                                  # -> 2 pages each, pool 3
    assert all(r.state is RequestState.COMPLETED for r in reqs)
    assert max_resident == 1
    assert eng.cache.pool.free_pages == 3


def test_submit_rejects_impossible_requests(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve(n_pages=4, max_seq_len=64))
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit([1] * 40, 20)               # 60 tokens > 4 pages
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit([1] * 60, 30)
    with pytest.raises(ValueError, match="vocab"):
        eng.submit([9999], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1], 0)
    eng.submit([1, 2], 4, rid="dup")
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit([1, 2], 4, rid="dup")


def test_engine_rejects_unsupported_models(model):
    cfg, params = model
    moe = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, moe_experts=4,
                                moe_top_k=2)
    with pytest.raises(ValueError, match="MoE"):
        Engine(params, moe, _serve())
    tp = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                               n_layers=2, d_ff=64, tp_axis="model")
    with pytest.raises(ValueError, match="replicated"):
        Engine(params, tp, _serve())
    with pytest.raises(ValueError, match="max_seq_len"):
        Engine(params, cfg, _serve(max_seq_len=4096))


def test_continuous_beats_static_slot_utilization(model):
    """A timing-free comparison of the two policies: on a mixed-length
    burst, continuous batching completes the same tokens in fewer decode
    steps (higher slot utilization) than the static baseline."""
    cfg, params = model
    prompts = [[i + 1, 2, 3] for i in range(6)]
    gens = [4, 30, 6, 28, 5, 26]               # high length variance
    sums = {}
    for policy in ("continuous", "static"):
        eng = Engine(params, cfg, _serve(policy=policy, n_slots=3))
        for i, (p, g) in enumerate(zip(prompts, gens)):
            eng.submit(p, g, seed=i)
        sums[policy] = eng.run()
    assert (sums["continuous"]["tokens_generated"]
            == sums["static"]["tokens_generated"])
    assert (sums["continuous"]["decode_steps"]
            < sums["static"]["decode_steps"])
    assert (sums["continuous"]["slot_utilization"]
            > sums["static"]["slot_utilization"])


def test_summary_and_serve_records(model, tmp_path):
    """SLO accounting lands in the summary and as typed ``serve``
    records with the documented keys (docs/OBSERVABILITY.md)."""
    cfg, params = model
    stream = str(tmp_path / "serve.jsonl")
    tel = TelemetryRun(stream, run="serve-test")
    eng = Engine(params, cfg, _serve(), telemetry=tel)
    for p, g in zip(PROMPTS, GENS):
        eng.submit(p, g)
    summary = eng.run()
    tel.finish()
    assert summary["requests_completed"] == len(PROMPTS)
    assert summary["requests_failed"] == 0
    assert summary["tokens_generated"] == sum(GENS)
    assert summary["ttft_s"]["count"] == len(PROMPTS)
    assert summary["ttft_s"]["p99"] >= summary["ttft_s"]["p50"] >= 0
    assert 0 < summary["slot_utilization"] <= 1
    assert summary["page_occupancy"]["max"] <= 1
    recs = read_records(stream)
    done = [r for r in recs if r.get("kind") == "serve"
            and r.get("event") == "completed"]
    assert len(done) == len(PROMPTS)
    for r in done:
        for key in ("request", "policy", "prompt_tokens", "new_tokens",
                    "ttft_s", "queue_wait_s", "wall_s"):
            assert key in r, f"serve record missing {key}"
    assert [r for r in recs if r.get("kind") == "serve"
            and r.get("event") == "summary"]


def test_prompt_length_bucketing_single_compile(model):
    """Any prompt length runs the same two compiled programs (the CLI
    satellite): decoding three different prompt/gen shapes through one
    engine geometry must not add compilations beyond the first run's."""
    from distributed_model_parallel_tpu.utils.telemetry import registry

    cfg, params = model
    eng = Engine(params, cfg, _serve())
    eng.submit([3, 1, 4, 1, 5], 6)
    eng.run()
    compiles = registry().counter("jax_compiles").value
    eng2 = Engine(params, cfg, _serve())
    eng2.submit([2, 7], 9, rid="a")
    eng2.submit([8] * 11, 4, rid="b")
    eng2.run()
    assert registry().counter("jax_compiles").value == compiles, (
        "a new prompt length re-compiled the engine programs")


def test_report_renders_serving_section(model, tmp_path):
    """dmp_report.py turns the engine's serve records into the
    ``== serving ==`` section (TTFT percentiles + per-policy summary)."""
    import importlib.util
    import os
    import sys

    cfg, params = model
    stream = str(tmp_path / "serve.jsonl")
    tel = TelemetryRun(stream, run="serve-report")
    eng = Engine(params, cfg, _serve(), telemetry=tel)
    for p, g in zip(PROMPTS, GENS):
        eng.submit(p, g)
    eng.run()
    tel.finish()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dmp_report", os.path.join(repo, "scripts", "dmp_report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["dmp_report"] = mod
    spec.loader.exec_module(mod)
    text = mod.build_report(read_records(stream))
    assert "== serving (3 completed, 0 failed) ==" in text
    assert "TTFT" in text and "token latency" in text
    assert "engine[continuous]" in text


# ---------------------------------------------------------------------------
# chaos: kill mid-stream
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kill_mid_stream_reports_typed_failures(model, tmp_path):
    """Killing the engine mid-stream (step hook raises after a few
    iterations) must leave every submitted request terminal — completed
    or a typed engine-killed failure — with matching ``serve``/
    ``failure`` records. Silent drops are the bug this pins out."""
    cfg, params = model
    stream = str(tmp_path / "killed.jsonl")
    tel = TelemetryRun(stream, run="serve-kill")

    def bomb(iteration):
        if iteration == 6:
            raise RuntimeError("injected mid-stream death")

    eng = Engine(params, cfg, _serve(), telemetry=tel, step_hook=bomb)
    reqs = [eng.submit(p, g) for p, g in zip(PROMPTS, GENS)]
    # Keep one request queued behind the page pool so the kill catches
    # requests in every lifecycle state.
    reqs.append(eng.submit([5, 5, 5], 40, rid="tail"))
    with pytest.raises(EngineKilled):
        eng.run()
    tel.finish()
    assert all(r.done for r in reqs), "a request was left in flight"
    failed = [r for r in reqs if r.state is RequestState.FAILED]
    assert failed, "the kill happened mid-stream; something must fail"
    for r in failed:
        assert r.error and r.error.startswith("engine-killed")
    # Pages all returned even on the failure path.
    assert eng.cache.pool.free_pages == eng.cache.pool.n_pages
    recs = read_records(stream)
    assert [r for r in recs if r.get("kind") == "failure"
            and r.get("error") == "engine-killed"]
    failed_recs = [r for r in recs if r.get("kind") == "serve"
                   and r.get("event") == "failed"]
    assert {r["request"] for r in failed_recs} == {r.rid for r in failed}
