"""Overload protection: deadlines + typed shedding, bounded admission
with priority classes, the router circuit breaker, the brownout ladder,
and the seeded 2x-overload drill.

The load-bearing properties (docs/SERVING.md "Overload and graceful
degradation"):

* a queued request past its queue budget or total deadline sheds with a
  typed ``shed`` record, and an in-flight request past its deadline is
  aborted with every reserved page returned immediately — mid-prefill
  and mid-decode alike;
* deadline accounting survives live migration: a drained request keeps
  its arrival clock and budgets on the destination replica;
* the submission queue is bounded: arrived overflow sheds typed
  (``queue-full``), batch first, and an interactive arrival displaces
  the newest queued batch request instead of being turned away;
* the router-level circuit breaker opens on repeated admission failures
  (distinct from health quarantine), half-open probes close it, and the
  injected ``admission_fail`` chaos drives the full cycle;
* brownout degrades deterministically and NEVER changes tokens — a
  level-3-clamped request's stream is the bitwise prefix of its
  unclamped run;
* the 2x-overload drill (scripts/dmp_soak.py --scenario overload) holds
  goodput within the band, accounts for every non-completed request,
  keeps queues bounded, and cycles brownout + breaker.
"""

import time

import jax
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import (
    BrownoutController,
    CircuitBreaker,
    Engine,
    ServeConfig,
    ServeFleet,
)
from distributed_model_parallel_tpu.serve.scheduler import (
    Request,
    RequestState,
    expiry_reason,
)
from distributed_model_parallel_tpu.utils.telemetry import (
    TelemetryRun,
    read_records,
    registry,
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


def _drive(engine, clocks):
    """Run iterations at the given synthetic open-loop clocks — the
    deterministic way to place an expiry mid-prefill or mid-decode."""
    t0 = time.monotonic()
    for now in clocks:
        engine.step_once(now, t0)


# ---------------------------------------------------------------------------
# deadlines + shedding
# ---------------------------------------------------------------------------

def test_expiry_reason_precedence():
    req = Request(rid="r", prompt=[1], max_new_tokens=4,
                  deadline_s=2.0, queue_budget_s=1.0)
    assert expiry_reason(req, 0.5) is None
    assert expiry_reason(req, 1.5) == "queue-deadline"
    assert expiry_reason(req, 2.5) == "total-deadline"
    # Engine defaults apply only when the request has no override.
    bare = Request(rid="b", prompt=[1], max_new_tokens=4)
    assert expiry_reason(bare, 9.0) is None
    assert expiry_reason(bare, 9.0, queue_budget_s=1.0) == "queue-deadline"


def test_expiry_while_queued_sheds_typed(model, tmp_path):
    """A request queued behind a full pool past its queue budget sheds
    with a typed record; the resident request is untouched."""
    cfg, params = model
    stream = str(tmp_path / "shed.jsonl")
    tel = TelemetryRun(stream, run="shed")
    # Pool holds exactly one worst-case request: the second queues.
    eng = Engine(params, cfg, _serve(n_slots=2, n_pages=2, max_seq_len=16,
                                     queue_budget_s=1.0), telemetry=tel)
    hog = eng.submit([1, 2, 3], 10, rid="hog")
    starved = eng.submit([4, 5, 6], 8, rid="starved")
    _drive(eng, [0.0, 0.1, 5.0])
    tel.finish()
    assert starved.state is RequestState.FAILED
    assert starved.shed_reason == "queue-deadline"
    assert starved.error == "shed: queue-deadline"
    assert hog.state is not RequestState.FAILED
    recs = [r for r in read_records(stream) if r.get("kind") == "shed"]
    assert len(recs) == 1 and recs[0]["request"] == "starved"
    assert recs[0]["reason"] == "queue-deadline"
    assert recs[0]["state"] == "queued"
    assert recs[0]["waited_s"] >= 1.0


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_expiry_in_flight_aborts_and_returns_pages(model, phase):
    """An in-flight request past its total deadline is aborted —
    mid-prefill (chunk-aligned) or mid-decode — and every reserved page
    returns immediately, reusable by the queued successor."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(n_slots=1, n_pages=4, max_seq_len=32,
                                     deadline_s=2.0))
    # 16-token prompt at chunk 4: 4 prefill iterations; expire after 2
    # of them (mid-prefill) or after prefill + 3 decodes (mid-decode).
    victim = eng.submit(list(range(1, 17)), 12, rid="victim")
    heir = eng.submit([7, 8, 9], 4, rid="heir", deadline_s=100.0)
    warm = [0.0, 0.1] if phase == "prefill" else 7 * [0.1]
    _drive(eng, warm)
    expect_state = (RequestState.PREFILL if phase == "prefill"
                    else RequestState.DECODE)
    assert victim.state is expect_state
    _drive(eng, [9.0])
    assert victim.state is RequestState.FAILED
    assert victim.shed_reason == "total-deadline"
    assert victim.slot is None
    # The freed reservation admits the heir, who completes normally.
    _drive(eng, [9.0 + 0.01 * i for i in range(1, 30)])
    assert heir.state is RequestState.COMPLETED
    assert eng.cache.pool.free_pages == eng.cache.pool.n_pages
    summary = eng.summary(record=False)
    assert summary["requests_shed"] == 1
    assert summary["shed_by_reason"] == {"total-deadline": 1}
    assert summary["requests_failed"] == 0     # shed is not failure


def test_deadline_survives_migration(model):
    """A drained request carries its arrival clock and budgets to the
    destination: an ample deadline completes there with the solo run's
    bitwise tokens, an expired one sheds there — reason total-deadline,
    accounted on the destination's record."""
    cfg, params = model
    solo = Engine(params, cfg, _serve())
    ref = solo.submit([1, 2, 3, 4, 5], 12, rid="keep", seed=3)
    solo.run()

    src = Engine(params, cfg, _serve(), replica="a")
    keep = src.submit([1, 2, 3, 4, 5], 12, rid="keep", seed=3,
                      deadline_s=50.0)
    doomed = src.submit([9, 9, 9], 12, rid="doomed", deadline_s=5.0)
    _drive(src, [0.0, 0.1, 0.2, 0.3])          # both mid-flight
    assert keep.generated and doomed.generated
    moved = src.drain()
    assert {r.rid for r in moved} == {"keep", "doomed"}
    src.clear_cache()

    dst = Engine(params, cfg, _serve(), replica="b")
    for r in moved:
        dst.enqueue(r, force=True)
    # Clock 6.0 on the shared fleet clock: doomed (deadline 5) expires
    # while queued on the DESTINATION; keep resumes and finishes.
    _drive(dst, [6.0 + 0.01 * i for i in range(40)])
    assert doomed.state is RequestState.FAILED
    assert doomed.shed_reason == "total-deadline"
    assert keep.state is RequestState.COMPLETED
    assert keep.generated == ref.generated
    assert keep.migrations == 1
    assert dst.cache.pool.free_pages == dst.cache.pool.n_pages


# ---------------------------------------------------------------------------
# bounded admission + priority
# ---------------------------------------------------------------------------

def test_arrived_submission_rejected_when_queue_full(model):
    """The runaway-client case: already-arrived submissions beyond
    max_queue reject typed at submit; the counter moves."""
    cfg, params = model
    shed0 = registry().counter("serve_rejected_total").value
    eng = Engine(params, cfg, _serve(max_queue=2))
    reqs = [eng.submit([1 + i, 2], 4, rid=f"r{i}") for i in range(4)]
    rejected = [r for r in reqs if r.shed_reason == "queue-full"]
    assert len(rejected) == 2
    assert all(r.error == "rejected: queue-full" for r in rejected)
    assert registry().counter("serve_rejected_total").value == shed0 + 2
    eng.run()
    assert sum(1 for r in reqs
               if r.state is RequestState.COMPLETED) == 2


def test_overflow_trim_sheds_batch_newest_first(model):
    """Future-dated trace entries enqueue freely; once arrived, the
    per-iteration trim bounds the backlog — batch before interactive,
    newest first within a class."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(n_slots=1, max_queue=2))
    reqs = [eng.submit([1 + i, 2], 4, rid=f"r{i}", arrival_s=1.0,
                       priority="batch" if i >= 2 else "interactive")
            for i in range(5)]
    assert all(r.shed_reason is None for r in reqs)   # future: no reject
    _drive(eng, [2.0])
    shed = {r.rid: r.shed_reason for r in reqs if r.shed_reason}
    # 5 arrived, 1 admitted to the slot, bound 2 -> 2 shed: the two
    # NEWEST batch requests go first (r4, r3), interactive r0/r1 stay.
    assert shed == {"r4": "queue-full", "r3": "queue-full"}


def test_interactive_jumps_queued_batch_at_admission(model):
    """Two priority classes: an interactive request admits before
    earlier-queued batch ones (FIFO within a class)."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(n_slots=1))
    order = []
    b1 = eng.submit([1, 2], 3, rid="b1", priority="batch")
    b2 = eng.submit([2, 3], 3, rid="b2", priority="batch")
    i1 = eng.submit([3, 4], 3, rid="i1")
    t0 = time.monotonic()
    while not eng.sched.idle():
        for req in eng.sched.admit(0.0):
            order.append(req.rid)
        eng.step_once(0.0, t0)
        for r in (b1, b2, i1):
            if r.slot is not None and r.rid not in order:
                order.append(r.rid)
    assert order.index("i1") < order.index("b1") < order.index("b2")


def test_fleet_full_queue_interactive_displaces_newest_batch(model):
    """Fleet-level bound: a batch submission on a full arrived queue is
    rejected; an interactive one displaces the newest queued batch
    request (typed) and takes its place."""
    cfg, params = model
    fleet = ServeFleet(params, cfg, _serve(max_queue=1), 2)
    try:
        fleet._now = 1.0                       # running-fleet clock
        # Bound = max_queue x n_replicas = 2: fill it with batch.
        b = [fleet.submit([1 + i, 2], 3, rid=f"b{i}", arrival_s=0.5,
                          priority="batch") for i in range(2)]
        assert all(r.shed_reason is None for r in b)
        i1 = fleet.submit([7, 8], 3, rid="i1", arrival_s=0.5)
        assert i1.shed_reason is None             # displaced a batch req
        assert b[1].shed_reason == "queue-full"   # the NEWEST batch one
        assert b[0].shed_reason is None
        b9 = fleet.submit([9, 9], 3, rid="b9", arrival_s=0.5,
                          priority="batch")
        assert b9.shed_reason == "queue-full"     # batch never displaces
        i2 = fleet.submit([8, 8], 3, rid="i2", arrival_s=0.5)
        assert i2.shed_reason is None and b[0].shed_reason == "queue-full"
        i3 = fleet.submit([6, 6], 3, rid="i3", arrival_s=0.5)
        assert i3.shed_reason == "queue-full"     # no batch left to shed
    finally:
        fleet.close()


def test_migrated_request_exempt_from_queue_bound(model):
    """A force-enqueued migrated request must never be trimmed by the
    destination's queue bound (rescued load is not new demand): it
    neither sheds nor counts against the bound, and completes with the
    solo run's bitwise tokens."""
    cfg, params = model
    solo = Engine(params, cfg, _serve())
    ref = solo.submit([1, 2, 3, 4, 5], 10, rid="mig", seed=5)
    solo.run()

    src = Engine(params, cfg, _serve(), replica="a")
    mig = src.submit([1, 2, 3, 4, 5], 10, rid="mig", seed=5)
    _drive(src, [0.0, 0.1, 0.2])               # mid-flight
    src.drain()
    src.clear_cache()

    dst = Engine(params, cfg, _serve(n_slots=1, max_queue=1), replica="b")
    resident = dst.submit([9, 8, 7], 20, rid="res")
    # Future-dated (the open-loop trace path): fills the bound once
    # arrived without tripping the submit-time runaway-client check.
    local = dst.submit([6, 6], 4, rid="loc", arrival_s=0.05)
    dst.enqueue(mig, force=True)               # newest queue entry
    _drive(dst, [0.3 + 0.01 * i for i in range(80)])
    assert mig.shed_reason is None
    assert mig.state is RequestState.COMPLETED
    assert mig.generated == ref.generated
    assert resident.state is RequestState.COMPLETED
    assert local.state is RequestState.COMPLETED


def test_priority_validation(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve())
    with pytest.raises(ValueError, match="priority"):
        eng.submit([1, 2], 4, priority="urgent")
    with pytest.raises(ValueError, match="queue_budget_s"):
        eng.submit([1, 2], 4, queue_budget_s=0.0)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_circuit_breaker_cycle():
    brk = CircuitBreaker(threshold=3, cooldown_rounds=5)
    for rnd in range(2):
        brk.note("r1", False, rnd)
        assert brk.state("r1") == "closed"
    brk.note("r1", False, 2)
    assert brk.state("r1") == "open" and brk.opens == 1
    assert not brk.allows("r1", 3)            # cooling down
    assert brk.allows("r1", 7)                # cooldown passed
    assert brk.state("r1") == "half-open"
    brk.note("r1", False, 7)                  # probe fails -> reopen
    assert brk.state("r1") == "open" and brk.opens == 2
    assert brk.allows("r1", 12)
    brk.note("r1", True, 12)                  # probe lands -> closed
    assert brk.state("r1") == "closed"
    states = [t["state"] for t in brk.drain_transitions()]
    assert states == ["open", "half-open", "open", "half-open", "closed"]
    assert brk.drain_transitions() == []
    # A success resets the consecutive-failure count.
    brk.note("r1", False, 13)
    brk.note("r1", False, 14)
    brk.note("r1", True, 15)
    brk.note("r1", False, 16)
    assert brk.state("r1") == "closed"


def test_admission_fail_chaos_cycles_breaker(model, tmp_path):
    """The injected admission_fail burst opens the victim's breaker,
    traffic flows to the peer meanwhile, the half-open probe closes it
    once the burst expires, and every request completes with the clean
    run's bitwise tokens."""
    cfg, params = model
    prompts = [[1 + i, 2, 3] for i in range(8)]
    clean = ServeFleet(params, cfg, _serve(max_queue=4), 2)
    refs = {}
    for i, p in enumerate(prompts):
        refs[f"q{i}"] = clean.submit(p, 6, rid=f"q{i}", seed=i)
    clean.run(record_summary=False)
    clean.close()

    stream = str(tmp_path / "chaos.jsonl")
    tel = TelemetryRun(stream, run="admission-chaos")
    fleet = ServeFleet(params, cfg, _serve(max_queue=4), 2, telemetry=tel,
                       faults=("admission_fail@0:4",), fault_replica="r1")
    reqs = [fleet.submit(p, 6, rid=f"q{i}", seed=i)
            for i, p in enumerate(prompts)]
    fleet.run(record_summary=False)
    # More traffic after the burst expired: the half-open probe lands.
    wave = [fleet.submit(p, 6, rid=f"w{i}", seed=i)
            for i, p in enumerate(prompts)]
    summary = fleet.run()
    tel.finish()
    fleet.close()
    assert all(r.state is RequestState.COMPLETED for r in reqs + wave)
    for i, r in enumerate(reqs):
        assert r.generated == refs[f"q{i}"].generated
    brk = [r for r in read_records(stream) if r.get("kind") == "breaker"]
    assert any(r["replica"] == "r1" and r["state"] == "open" for r in brk)
    assert summary["breaker"]["states"]["r1"] == "closed"
    assert summary["breaker"]["opens"] >= 1
    assert summary["requests_failed"] == 0


def test_fleet_rejects_train_site_fault_plans(model):
    cfg, params = model
    with pytest.raises(ValueError, match="serve/admit"):
        ServeFleet(params, cfg, _serve(), 2, faults=("nan_loss@0",))


def test_slow_replica_served_at_serve_site():
    """The slow_replica degradation sleeps on every serve-site poll from
    its firing on — the latency the fleet's timed round feeds the
    health sentinel."""
    from distributed_model_parallel_tpu.utils.faults import FaultInjector

    inj = FaultInjector(("slow_replica@1:0.05",))
    t0 = time.monotonic()
    inj.poll("serve")                          # occurrence 0: not yet
    assert time.monotonic() - t0 < 0.04
    t0 = time.monotonic()
    inj.poll("serve")                          # fires + sleeps
    inj.poll("serve")                          # keeps sleeping
    assert time.monotonic() - t0 >= 0.1


# ---------------------------------------------------------------------------
# brownout
# ---------------------------------------------------------------------------

def test_brownout_ladder_walks_up_and_back():
    bo = BrownoutController(_serve(
        brownout=True, brownout_ttft_target_s=0.1, brownout_budget=0.25,
        brownout_window_s=1.0, brownout_hold_iters=1))
    for i in range(8):
        bo.observe_completed(1.0, 0.1 * i)     # every completion violates
    levels = []
    for i in range(5):
        t = bo.tick(0.8 + 0.01 * i)
        if t:
            levels.append((t["direction"], t["level"]))
    assert levels == [("degrade", 1), ("degrade", 2), ("degrade", 3)]
    assert not bo.spec_enabled and not bo.prefill_full_share
    assert bo.max_new_cap == 32
    for i in range(6):                          # windows drain -> healthy
        t = bo.tick(30.0 + i)
        if t:
            levels.append((t["direction"], t["level"]))
    assert levels[-3:] == [("recover", 2), ("recover", 1), ("recover", 0)]
    assert bo.level == 0 and bo.max_level_seen == 3


def test_brownout_clamp_is_bitwise_prefix(model, tmp_path):
    """Level-3 brownout clamps admissions' max_new — the clamped stream
    must be the bitwise PREFIX of the unclamped run's (degradation never
    changes tokens), the original ask is preserved, and the transition
    is a typed record."""
    cfg, params = model
    plain = Engine(params, cfg, _serve())
    refs = [plain.submit([1 + i, 2, 3], 12, rid=f"r{i}", seed=i)
            for i in range(4)]
    plain.run()

    stream = str(tmp_path / "brownout.jsonl")
    tel = TelemetryRun(stream, run="brownout")
    eng = Engine(params, cfg, _serve(
        brownout=True, brownout_ttft_target_s=1e-4,
        brownout_window_s=0.5, brownout_hold_iters=1,
        brownout_max_new=4), telemetry=tel)
    # Hold the ladder at level 3 for the whole run (the walk itself is
    # pinned above): every admission is clamped deterministically.
    eng.brownout.level = 3
    eng.brownout.max_level_seen = 3
    eng.brownout._last_move = 10 ** 9
    reqs = [eng.submit([1 + i, 2, 3], 12, rid=f"r{i}", seed=i)
            for i in range(4)]
    eng.run()
    tel.finish()
    for r, ref in zip(reqs, refs):
        assert r.state is RequestState.COMPLETED
        assert r.max_new_requested == 12
        assert len(r.generated) <= 4
        assert r.generated == ref.generated[:len(r.generated)]
    assert eng.summary(record=False)["brownout"]["max_level_seen"] == 3


def test_brownout_fires_on_engine_and_records(model, tmp_path):
    """End to end on a real engine: a saturating burst with an absurdly
    low TTFT target must fire the ladder (typed brownout records, spec
    disabled path still decodes the plain engine's tokens)."""
    cfg, params = model
    plain = Engine(params, cfg, _serve(n_slots=2))
    refs = [plain.submit([1 + i, 3], 10, rid=f"r{i}", seed=i)
            for i in range(8)]
    plain.run()
    stream = str(tmp_path / "bo.jsonl")
    tel = TelemetryRun(stream, run="bo")
    eng = Engine(params, cfg, _serve(
        n_slots=2, spec_k=4, brownout=True, brownout_ttft_target_s=1e-4,
        brownout_window_s=2.0, brownout_hold_iters=1), telemetry=tel)
    reqs = [eng.submit([1 + i, 3], 10, rid=f"r{i}", seed=i)
            for i in range(8)]
    eng.run()
    tel.finish()
    recs = [r for r in read_records(stream) if r.get("kind") == "brownout"]
    assert recs and max(r["level"] for r in recs) >= 1
    assert eng.brownout.max_level_seen >= 1
    for r, ref in zip(reqs, refs):
        assert r.generated == ref.generated    # spec off/on: same tokens


# ---------------------------------------------------------------------------
# surfaces: statusz provider, report, cockpit
# ---------------------------------------------------------------------------

def test_statusz_provider_carries_overload_fields(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve(max_queue=1, brownout=True))
    eng.submit([1, 2], 4, rid="a")
    eng.submit([2, 3], 4, rid="b")             # arrived, bound 1: rejected
    status = eng._status()
    assert status["requests_rejected"] == 1
    assert status["requests_shed"] == 1
    assert status["shed_by_reason"] == {"queue-full": 1}
    assert status["brownout_level"] == 0
    assert status["max_queue"] == 1


def test_report_renders_overload_lines():
    import importlib.util
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dmp_report", os.path.join(repo, "scripts", "dmp_report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["dmp_report"] = mod
    spec.loader.exec_module(mod)
    records = [
        {"kind": "run_start", "run": "ovl", "ts": 0.0},
        {"kind": "serve", "event": "completed", "request": "a",
         "policy": "continuous", "ttft_s": 0.1, "queue_wait_s": 0.05,
         "token_latency_s": 0.01, "ts": 1.0},
        {"kind": "shed", "request": "b", "reason": "queue-deadline",
         "priority": "batch", "state": "queued", "ts": 1.1},
        {"kind": "shed", "request": "c", "reason": "queue-full",
         "priority": "interactive", "state": "queued", "ts": 1.2},
        {"kind": "brownout", "level": 1, "previous": 0,
         "direction": "degrade", "applied": ["spec-off"], "ts": 1.3},
        {"kind": "brownout", "level": 0, "previous": 1,
         "direction": "recover", "applied": [], "ts": 1.4},
        {"kind": "breaker", "replica": "r1", "state": "open",
         "round": 3, "failures": 3, "ts": 1.5},
        {"kind": "breaker", "replica": "r1", "state": "closed",
         "round": 9, "failures": 0, "ts": 1.6},
    ]
    text = mod.build_report(records)
    assert "2 shed" in text
    assert "shed: queue-deadline 1, queue-full 1" in text
    assert "brownout: 2 transitions, max level 1, final level 0" in text
    assert "breaker: 1 opens   r1=closed" in text
    data = mod.build_report_data(records)
    assert len(data["serving"]["shed"]) == 2
    assert len(data["serving"]["brownout"]) == 2
    assert len(data["serving"]["breaker"]) == 2


def test_cockpit_folds_overload_records():
    import importlib.util
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dmp_top", os.path.join(repo, "scripts", "dmp_top.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["dmp_top"] = mod
    spec.loader.exec_module(mod)
    state = mod.FleetState()
    state.observe({"kind": "shed", "reason": "queue-deadline"})
    state.observe({"kind": "shed", "reason": "queue-deadline"})
    state.observe({"kind": "brownout", "level": 2})
    state.observe({"kind": "breaker", "replica": "r1", "state": "open"})
    out = state.render()
    assert "overload  shed=queue-deadline:2  brownout=2  breaker=r1:open" \
        in out


# ---------------------------------------------------------------------------
# the seeded overload drill (CPU-sized smoke, tier-1)
# ---------------------------------------------------------------------------

def test_overload_drill_smoke(tmp_path):
    """The ISSUE-15 acceptance drill, CPU-sized: 2x offered load on a
    2-replica fleet must account for every non-completed request with a
    typed shed record, keep every queue bounded, fire AND resolve
    brownout, cycle the breaker through the injected admission_fail
    burst, still complete a third of the offered requests, and decode
    bitwise the clean run's tokens. The goodput band is off here: it
    compares two wall-clock rates taken minutes apart, so on a host
    shared with other test workers it measures the neighbours (0.47 to
    0.92 over four runs of one tree under load). It stays the gate of the
    drill run alone (``dmp_soak.py --scenario overload``); the gates
    below are exact at any host speed."""
    from scripts.dmp_soak import parse_args, run_overload_campaign

    args = parse_args(["--scenario", "overload", "--seed", "0",
                       "--goodput-band", "0"])
    summary, ok = run_overload_campaign(args, str(tmp_path), 0)
    assert ok, summary
    assert summary["unaccounted"] == []
    assert summary["token_mismatches"] == []
    assert summary["queue_bounded"]
    assert summary["brownout_fired"]
    assert summary["brownout_final_levels"] == [0, 0]
    assert summary["breaker_cycled"]
    assert sum(summary["shed_by_reason"].values()) >= 1
    assert summary["requests_failed"] == 0
    assert summary["completed"] >= summary["requests"] // 3
