"""Self-healing serving fleet: KV export/import round-trips, router
determinism + prefix affinity, and the replica-kill chaos drill.

The load-bearing properties (docs/SERVING.md "Fleet serving"):

* ``PagedKVCache.export_request``/``import_request`` round-trip a live
  sequence between replicas **by value** — with and without shared
  prefix pages, the migrated request carries no refcounts into the
  source replica's pool or radix tree;
* the router is deterministic: same trace + seed ⇒ same assignment
  sequence; a prompt whose prefix lives in some replica's radix tree
  routes there (affinity beats power-of-two-choices);
* killing one of >= 2 replicas mid-stream under seeded open-loop
  traffic loses zero requests: every in-flight and queued request
  completes on a peer, migrated requests' token streams bitwise-match
  an unkilled run, every page of the dead replica is returned, and the
  quarantined replica grows back and takes traffic again (chaos tier).
"""

import jax
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import (
    Engine,
    ServeConfig,
    ServeFleet,
)
from distributed_model_parallel_tpu.serve.scheduler import RequestState
from distributed_model_parallel_tpu.utils.health import (
    DeviceHealthMonitor,
    HealthPolicy,
)
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


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16],
           [3, 3, 3]]
GENS = [12, 18, 7, 10]


def _solo_reference(cfg, params, serve_kw=None):
    """Per-request token references from a single unkilled engine."""
    eng = Engine(params, cfg, _serve(**(serve_kw or {})))
    reqs = [eng.submit(p, g, seed=i)
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    eng.run()
    return {r.rid: r.generated for r in reqs}


# ---------------------------------------------------------------------------
# export/import round-trips
# ---------------------------------------------------------------------------

def test_export_import_roundtrip_mid_decode(model):
    """Drain a busy engine mid-stream and finish every request on a
    fresh peer: migrated requests (mid-prefill AND mid-decode) must
    decode exactly what an uninterrupted run produces."""
    cfg, params = model
    refs = _solo_reference(cfg, params)
    src = Engine(params, cfg, _serve())
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        src.submit(p, g, seed=i, rid=f"req-{i}")
    src.run(max_iterations=5)          # mid-stream: mixed lifecycle states
    drained = src.drain()
    assert drained, "nothing was in flight to migrate"
    states = {d["state"] if (d := r.resume) else "queued" for r in drained}
    src.clear_cache()
    assert src.cache.pool.free_pages == src.cache.pool.n_pages
    dst = Engine(params, cfg, _serve())
    for req in drained:
        dst.enqueue(req)
    dst.run()
    for req in drained:
        assert req.state is RequestState.COMPLETED
        assert req.generated == refs[req.rid], (
            f"{req.rid} diverged after migration (drained as {states})")
        assert req.migrations == 1
    assert dst.cache.pool.free_pages == dst.cache.pool.n_pages


def test_export_import_roundtrip_with_shared_prefix_pages(model):
    """A migrated request whose table holds SHARED prefix pages must not
    carry refcounts to the source replica's tree: the payload is pure
    values, the destination allocates fresh pages, and completing there
    leaves the source pool untouched."""
    cfg, params = model
    serve = _serve(page_size=4, n_pages=64, prefix_cache=True)
    base = [5] * 16                    # page- and chunk-aligned prefix
    src = Engine(params, cfg, serve)
    warm = src.submit(base + [1, 2], 6, seed=0, rid="warm")
    src.run()                          # prefix now cached in src's tree
    assert warm.state is RequestState.COMPLETED
    sharer = src.submit(base + [9, 8], 10, seed=1, rid="sharer")
    src.run(max_iterations=src._iterations + 4)   # cap is cumulative
    assert sharer.cached_prompt_tokens > 0, "the sharer must hit the tree"
    assert not sharer.done
    tree_pages_before = len(src.cache.prefix)
    [req] = src.drain()
    assert req is sharer
    # The source's tree survives the drain intact; the payload holds no
    # page ids — only contents.
    assert len(src.cache.prefix) == tree_pages_before
    assert set(req.resume) == {"k", "v", "n_written", "state"}
    used_before = src.cache.pool.used_pages
    dst = Engine(params, cfg, serve)
    dst.enqueue(req)
    dst.run()
    assert req.state is RequestState.COMPLETED
    # Completing on the peer never touched the source pool.
    assert src.cache.pool.used_pages == used_before
    ref = Engine(params, cfg, _serve())
    rr = ref.submit(base + [9, 8], 10, seed=1)
    ref.run()
    assert req.generated == rr.generated
    assert src.clear_cache() == tree_pages_before
    assert src.cache.pool.free_pages == src.cache.pool.n_pages


def test_import_queues_when_pool_full(model):
    """A migrated-in request honors the destination's backpressure: it
    queues until pages free up, never over-commits."""
    cfg, params = model
    src = Engine(params, cfg, _serve())
    src.submit([1, 2, 3], 12, rid="mover", seed=0)
    src.run(max_iterations=4)
    [req] = src.drain()
    # Destination whose pool is exactly one worst-case request wide and
    # currently busy.
    dst = Engine(params, cfg, _serve(n_slots=2, n_pages=3, max_seq_len=24))
    blocker = dst.submit([9, 9, 9], 12, rid="blocker", seed=1)
    waited = {"n": 0}

    def hook(i):
        if not blocker.done and req.slot is None:
            waited["n"] += 1

    dst.step_hook = hook
    dst.enqueue(req)
    dst.run()
    assert waited["n"] > 0, "the import should have queued behind blocker"
    assert req.state is RequestState.COMPLETED
    ref = Engine(params, cfg, _serve())
    rr = ref.submit([1, 2, 3], 12, seed=0)
    ref.run()
    assert req.generated == rr.generated


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_assignment_sequence_deterministic(model, tmp_path):
    """Same trace + same seed ⇒ the identical (request, replica,
    reason) assignment sequence, twice over."""
    cfg, params = model

    def run(seed):
        stream = str(tmp_path / f"router-{seed}-{run.calls}.jsonl")
        run.calls += 1
        tel = TelemetryRun(stream, run="router")
        fleet = ServeFleet(params, cfg, _serve(), 2, telemetry=tel,
                           router_seed=seed)
        for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
            fleet.submit(p, g, seed=i)
        fleet.run()
        tel.finish()
        return [(r["request"], r["replica"], r["reason"])
                for r in read_records(stream) if r.get("kind") == "router"]

    run.calls = 0
    a, b = run(0), run(0)
    assert a == b
    assert len(a) == len(PROMPTS)
    assert {r for _, r, _ in a} <= {"r0", "r1"}


def test_router_prefix_affinity_routes_to_warm_replica(model, tmp_path):
    """A prompt whose prefix lives in one replica's radix tree routes to
    that replica with reason=affinity (the per-replica prefix cache is
    only worth anything if the router exploits it)."""
    cfg, params = model
    stream = str(tmp_path / "affinity.jsonl")
    tel = TelemetryRun(stream, run="affinity")
    base = [5] * 16
    fleet = ServeFleet(params, cfg,
                       _serve(page_size=4, n_pages=64, prefix_cache=True),
                       2, telemetry=tel, router_seed=0)
    first = fleet.submit(base + [1, 2], 6, seed=0, rid="first")
    fleet.run()
    assert first.state is RequestState.COMPLETED
    follow = fleet.submit(base + [9, 8], 6, seed=1, rid="follow")
    fleet.run()
    tel.finish()
    assert follow.state is RequestState.COMPLETED
    routed = {r["request"]: r for r in read_records(stream)
              if r.get("kind") == "router"}
    assert routed["follow"]["reason"] == "affinity"
    assert routed["follow"]["replica"] == routed["first"]["replica"]


def test_fleet_statusz_provider_and_summary(model):
    """The fleet registers per-replica providers plus the serve-fleet
    provider (replica table, router counts), and the summary rolls the
    fleet view up."""
    from distributed_model_parallel_tpu.utils import statusz

    cfg, params = model
    # port 0 = ephemeral exporter; without any configured port the
    # registry drops registrations (the no-op contract).
    fleet = ServeFleet(params, cfg, _serve(statusz_port=0), 2,
                       router_seed=0)
    try:
        assert {"serve-r0", "serve-r1", "serve-fleet"} <= set(
            statusz.registered())
        for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
            fleet.submit(p, g, seed=i)
        summary = fleet.run()
        status = fleet._status()
        assert status["workload"] == "serve-fleet"
        assert set(status["replicas"]) == {"r0", "r1"}
        assert sum(r["assignments"]
                   for r in status["replicas"].values()) == len(PROMPTS)
        assert summary["policy"] == "fleet"
        assert summary["requests_completed"] == len(PROMPTS)
        assert summary["requests_failed"] == 0
        assert summary["live_replicas"] == 2
        assert summary["migrations"] == 0
        assert sum(summary["router"]["assignments"].values()) == len(PROMPTS)
    finally:
        fleet.close()
    # close() tears the whole fleet presence down — a discarded fleet
    # must not feed stale state into /statusz or pin its engines.
    assert not {"serve-r0", "serve-r1", "serve-fleet"} & set(
        statusz.registered())


def test_fleet_writes_all_engine_gauges(model):
    """The fleet owns ALL the process-global engine gauges in fleet
    mode (replica engines skip their own writes): occupancy, shared
    pages, and the pooled hit/accept rates must move when prefix cache
    + spec decode run under a fleet — not just occupancy."""
    from distributed_model_parallel_tpu.utils.telemetry import registry

    cfg, params = model
    reg = registry()
    gauges = ("serve_page_occupancy", "serve_cache_hit_rate",
              "serve_shared_pages", "serve_draft_accept_rate")
    for g in gauges:             # un-set: the registry is process-wide
        reg.gauge(g).value = None
    fleet = ServeFleet(params, cfg,
                       _serve(prefix_cache=True, spec_k=2), 2,
                       router_seed=0)
    shared = [1, 2, 3, 4, 5, 6, 7, 8]
    for i in range(4):
        fleet.submit(shared + [20 + i], 16, seed=i)
    fleet.run()
    assert reg.gauge("serve_page_occupancy").value is not None
    assert reg.gauge("serve_cache_hit_rate").value is not None
    assert reg.gauge("serve_shared_pages").value is not None
    # Drafts only ride once shadow gating opens, which depends on the
    # model's token stream — assert the gauge exactly tracks that.
    proposed = any(r.engine._draft_proposed for r in fleet.replicas)
    assert (reg.gauge("serve_draft_accept_rate").value
            is not None) == proposed


def test_device_pool_assign_ids_exact_slice():
    """DevicePool.assign_ids (orchestrator/scheduler.py): the grow-back
    path re-grants a replica its EXACT pre-quarantine slice — specific
    free ids only, loud otherwise."""
    from distributed_model_parallel_tpu.orchestrator.scheduler import (
        DevicePool,
    )

    class D:
        def __init__(self, i):
            self.id = i

    pool = DevicePool([D(i) for i in range(6)])
    got = pool.assign_ids("serve-r0", [2, 3])
    assert tuple(d.id for d in got) == (2, 3)
    assert pool.assigned_ids("serve-r0") == (2, 3)
    with pytest.raises(RuntimeError, match="already holds"):
        pool.assign_ids("serve-r0", [4])
    with pytest.raises(RuntimeError, match="not free"):
        pool.assign_ids("serve-r1", [3, 4])
    with pytest.raises(KeyError, match="unknown"):
        pool.assign_ids("serve-r1", [99])
    # The quarantine/reinstate cycle the fleet drives: release leaves
    # quarantined ids out of service; reinstate frees them for the exact
    # re-grant.
    pool.quarantine([2, 3])
    pool.release("serve-r0")
    assert 2 not in pool.free_ids and 3 not in pool.free_ids
    with pytest.raises(RuntimeError, match="not free"):
        pool.assign_ids("serve-r0", [2, 3])
    pool.reinstate([2, 3])
    got = pool.assign_ids("serve-r0", [2, 3])
    assert tuple(d.id for d in got) == (2, 3)


def test_fleet_rejects_bad_geometry(model):
    cfg, params = model
    with pytest.raises(ValueError, match="continuous"):
        ServeFleet(params, cfg, _serve(policy="static"), 2)
    with pytest.raises(ValueError, match="n_replicas"):
        ServeFleet(params, cfg, _serve(), 0)
    with pytest.raises(ValueError, match="free device"):
        ServeFleet(params, cfg, _serve(), 2,
                   devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# chaos: the replica-kill drill
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_replica_kill_drill_migrates_and_grows_back(model, tmp_path):
    """Kill one of two replicas mid-stream under seeded open-loop
    traffic: zero requests lost, migrated streams bitwise-match the
    unkilled run, all of the dead replica's pages return, the replica
    grows back, and it takes fresh traffic afterwards."""
    cfg, params = model
    refs = _solo_reference(cfg, params)
    stream = str(tmp_path / "drill.jsonl")
    tel = TelemetryRun(stream, run="fleet-drill")
    fleet = ServeFleet(params, cfg, _serve(), 2, telemetry=tel,
                       router_seed=0, revive_after=3)
    migrated_at_kill = {}

    def hook(rnd):
        if rnd == 4:
            migrated_at_kill["n"] = fleet.kill_replica("r0")

    fleet.step_hook = hook
    reqs = [fleet.submit(p, g, seed=i, rid=f"req-{i}")
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    summary = fleet.run()
    assert migrated_at_kill["n"] > 0, "the kill must catch live requests"
    assert summary["requests_failed"] == 0
    assert summary["requests_completed"] == len(PROMPTS)
    assert summary["migrations"] == migrated_at_kill["n"]
    for r in reqs:
        assert r.state is RequestState.COMPLETED
        assert r.generated == refs[r.rid], (
            f"{r.rid} diverged after the replica kill")
    r0 = fleet.replicas[0]
    assert r0.state == "live", "the killed replica must grow back"
    assert r0.kills == 1
    for rep in fleet.replicas:
        assert rep.engine.cache.pool.free_pages == \
            rep.engine.cache.pool.n_pages
    assert fleet.pool.quarantined_ids == ()
    assert set(fleet.pool.assignments()) == {"serve-r0", "serve-r1"}
    # The revived replica takes traffic again.
    before = fleet.router.assignments.get("r0", 0)
    wave2 = [fleet.submit(p, g, seed=10 + i, rid=f"wave2-{i}")
             for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    fleet.run()
    tel.finish()
    assert all(r.state is RequestState.COMPLETED for r in wave2)
    assert fleet.router.assignments.get("r0", 0) > before, (
        "the grown-back replica never received a new assignment")
    recs = read_records(stream)
    migs = [r for r in recs if r.get("kind") == "migration"]
    assert len(migs) == migrated_at_kill["n"]
    for m in migs:
        assert m["from_replica"] == "r0" and m["to_replica"] == "r1"
        assert m["request"] in refs
    assert [r for r in recs if r.get("kind") == "router"]
    assert [r for r in recs if r.get("kind") == "serve"
            and r.get("event") == "summary" and r.get("policy") == "fleet"]


@pytest.mark.chaos
def test_health_sentinel_quarantines_degrading_replica(model):
    """The health-driven path: scripted serve-signal outliers on one
    replica's slice quarantine it, its requests migrate, and the
    sentinel's probation heals it back — no operator kill involved."""
    cfg, params = model
    refs = _solo_reference(cfg, params)
    mon = DeviceHealthMonitor(HealthPolicy(warmup=2,
                                           min_probation_ticks=2))
    fleet = ServeFleet(params, cfg, _serve(), 2, health=mon,
                       router_seed=0)
    victim = fleet.replicas[0]

    def hook(rnd):
        if rnd < 4:
            mon.observe("serve", victim.device_ids, 0.01)
        elif rnd < 8:
            mon.observe("serve", victim.device_ids, 5.0)  # degradation

    fleet.step_hook = hook
    reqs = [fleet.submit(p, g, seed=i, rid=f"req-{i}")
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    summary = fleet.run()
    assert summary["requests_failed"] == 0
    assert summary["replica_kills"] == 1, "the sentinel must quarantine"
    assert summary["migrations"] > 0
    for r in reqs:
        assert r.generated == refs[r.rid]
    assert victim.state == "live", "probation must heal the replica back"


@pytest.mark.chaos
def test_idle_rounds_never_feed_health_baseline(model):
    """Idle fleet rounds (open-loop lulls) must not feed their
    microsecond wall times to the health sentinel: a baseline seeded
    from idle rounds would make the first BUSY round an outlier and
    quarantine a healthy replica."""
    cfg, params = model
    mon = DeviceHealthMonitor(HealthPolicy(warmup=2))
    fleet = ServeFleet(params, cfg, _serve(), 2, health=mon,
                       router_seed=0)
    # A lull before the first arrival forces idle rounds up front.
    reqs = [fleet.submit(p, g, seed=i, arrival_s=0.3)
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    summary = fleet.run()
    assert summary["requests_failed"] == 0
    assert summary["replica_kills"] == 0, (
        "an idle-seeded baseline quarantined a healthy replica")
    assert all(r.state is RequestState.COMPLETED for r in reqs)


@pytest.mark.chaos
def test_operator_kill_on_health_wired_fleet_still_revives(model):
    """kill_replica on a fleet that ALSO has a health monitor: the
    monitor never saw the quarantine, so no reinstate event will come —
    revive_after must still grow the replica back."""
    cfg, params = model
    mon = DeviceHealthMonitor(HealthPolicy())
    fleet = ServeFleet(params, cfg, _serve(), 2, health=mon,
                       router_seed=0, revive_after=3)
    fleet.step_hook = (lambda rnd: fleet.kill_replica("r1")
                       if rnd == 3 else None)
    reqs = [fleet.submit(p, g, seed=i)
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    summary = fleet.run()
    assert summary["requests_failed"] == 0
    assert all(r.state is RequestState.COMPLETED for r in reqs)
    assert fleet.replicas[1].state == "live", (
        "operator-killed replica stayed quarantined forever on a "
        "health-wired fleet")


@pytest.mark.chaos
def test_kill_with_no_peer_fails_typed(model):
    """Quarantining the LAST live replica must fail its requests with a
    typed error — never drop them silently (the engine kill contract,
    fleet-shaped)."""
    cfg, params = model
    fleet = ServeFleet(params, cfg, _serve(), 2, router_seed=0)

    def hook(rnd):
        if rnd == 3:
            fleet.kill_replica("r0")
            fleet.kill_replica("r1")

    fleet.step_hook = hook
    reqs = [fleet.submit(p, g, seed=i)
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    fleet.run(max_rounds=10)
    live = [r for r in reqs if not r.done]
    failed = [r for r in reqs if r.state is RequestState.FAILED]
    assert not any(r.slot is not None for r in live)
    assert failed, "the double kill caught requests in flight"
    for r in failed:
        assert r.error and "no reachable live peer" in r.error


@pytest.mark.chaos
def test_all_quarantined_fails_pending_typed(model):
    """A request still in the FLEET-level queue (not yet arrived) when
    the last live replica dies — with no sentinel and no revive timer —
    fails typed and run() returns, instead of spinning forever on a
    request nothing can ever dispatch."""
    cfg, params = model
    fleet = ServeFleet(params, cfg, _serve(), 2, router_seed=0)

    def hook(rnd):
        if rnd == 2:
            fleet.kill_replica("r0")
            fleet.kill_replica("r1")

    fleet.step_hook = hook
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        fleet.submit(p, g, seed=i)
    late = fleet.submit([1, 2, 3], 4, seed=9, arrival_s=3600.0,
                        rid="late")
    summary = fleet.run()          # no max_rounds: must terminate
    assert late.state is RequestState.FAILED
    assert late.error and "no revive path" in late.error
    assert summary["requests_failed"] >= 1


# ---------------------------------------------------------------------------
# observability surfaces
# ---------------------------------------------------------------------------

def test_report_and_top_render_fleet_serving(model, tmp_path):
    """The drill's typed records drive the ``== fleet serving ==``
    report section and dmp_top's fold (assignment counts, migration
    lines, the fleet summary's replica table)."""
    import importlib.util
    import os
    import sys

    cfg, params = model
    stream = str(tmp_path / "render.jsonl")
    tel = TelemetryRun(stream, run="fleet-render")
    fleet = ServeFleet(params, cfg, _serve(), 2, telemetry=tel,
                       router_seed=0, revive_after=3)
    fleet.step_hook = (lambda rnd: fleet.kill_replica("r1")
                       if rnd == 4 else None)
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        fleet.submit(p, g, seed=i)
    fleet.run()
    tel.finish()
    recs = read_records(stream)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dmp_report", os.path.join(repo, "scripts", "dmp_report.py"))
    report = importlib.util.module_from_spec(spec)
    sys.modules["dmp_report"] = report
    spec.loader.exec_module(report)
    text = report.build_report(recs)
    assert "== fleet serving (" in text
    assert "router: r0=" in text
    assert "migrated " in text and "r1 -> r0" in text
    assert "replicas live" in text
    spec = importlib.util.spec_from_file_location(
        "dmp_top", os.path.join(repo, "scripts", "dmp_top.py"))
    top = importlib.util.module_from_spec(spec)
    sys.modules["dmp_top"] = top
    spec.loader.exec_module(top)
    state = top.FleetState()
    for r in recs:
        state.observe(r)
    frame = state.render()
    assert "fleet serving  migrations=" in frame
    assert "r0:" in frame
    n_migs = len([r for r in recs if r.get("kind") == "migration"])
    assert n_migs > 0 and f"migrations={n_migs}" in frame
