"""The serving engine's one-turn pipeline (serve/engine.py).

A plain turn dispatches its chunks and decode round before it fetches the
previous turn's tokens. What it must keep:

* the tokens: every family's toy configuration (dense; routed over
  sliding and full layers; gated-delta; looped) serves its oracle's;
* the schedule: with no stop token the programs, and the rows,
  positions and chunks in each, are the ones the scheduler's turn order
  gives a synchronous loop, and a slot freed by a length-finished row is
  admitted into the very next turn;
* stop tokens learned a turn late: the committed tokens are unchanged,
  the token past the stop is counted and dropped, and the freed slot and
  pages serve the next request as a fresh engine's would;
* determinism across ``drain`` (by page and by replay), and across a
  hard crash replayed from the journal, with a round in flight;
* speculation synchronous (``rounds_in_flight`` 0), its tokens the plain
  engine's; the prefix cache's hits and tokens;
* ``_status()``'s counters, against the fetches the engine makes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import Engine, ServeConfig
from distributed_model_parallel_tpu.serve.fleet import ServeFleet
from distributed_model_parallel_tpu.serve.journal import RequestJournal
from distributed_model_parallel_tpu.serve.scheduler import RequestState

pytestmark = pytest.mark.serve

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16],
           [3, 3, 3], [5, 9, 2, 6, 1, 4], [8]]
GENS = [12, 5, 7, 10, 3, 6]


@pytest.fixture(scope="module")
def dense():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=128,
                                pos_embedding="rope")
    return cfg, tfm.init_params(jax.random.key(0), cfg)


def _serve(**kw):
    base = dict(n_slots=2, page_size=8, n_pages=32, max_seq_len=64,
                prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


def _run(eng, prompts, gens):
    reqs = [eng.submit(p, g, seed=i, rid=f"r{i}")
            for i, (p, g) in enumerate(zip(prompts, gens))]
    eng.run()
    assert all(r.state is RequestState.COMPLETED for r in reqs)
    return [list(r.generated) for r in reqs]


# -- tokens: each family against its oracle ------------------------------------

def _dense_case(dense):
    cfg, params = dense
    eng = Engine(params, cfg, _serve(), slo_metrics=False)
    got = _run(eng, PROMPTS, GENS)
    for p, g, out in zip(PROMPTS, GENS, got):
        ref = tfm.generate(params, cfg, jnp.asarray([p], jnp.int32), g)
        assert out == [int(t) for t in ref[0][len(p):]]
    return eng


def _routed_case(_):
    from tests import test_gated_moe_serving as moe

    cfg = moe.config()
    params = moe.random_params(cfg)
    prompts = moe._prompts(cfg, [35, 18, 9, 22])
    with jax.default_matmul_precision("highest"):
        eng = Engine(params, cfg, moe.serve_config(n_slots=2),
                     slo_metrics=False)
        got = _run(eng, prompts, [20, 9, 14, 6])
        for prompt, out in zip(prompts, got):
            lg = moe.reference_logits(params, cfg,
                                      np.asarray(prompt + out[:-1]))
            served = lg[len(prompt) - 1:]
            gap = served.max(-1) - served[np.arange(len(out)), out]
            assert gap.max() < 1e-4
    return eng


def _gated_delta_case(_):
    from tests import test_gated_delta_serving as gd

    cfg = gd.config()
    params = gd.random_params(cfg)
    rng = np.random.default_rng(0)
    eng = Engine(params, cfg, gd.serve_config(n_slots=2), slo_metrics=False)
    reqs = [eng.submit(rng.integers(0, gd.VOCAB, size=n), m)
            for n, m in [(37, 9), (16, 5), (5, 12), (33, 3)]]
    eng.run()
    for r in reqs:
        assert len(r.generated) == r.max_new_tokens
        assert gd.served_gap(params, cfg, r) < gd.LOGIT_TOL
    return eng


def _looped_case(_):
    from tests import test_looped_serving as lp

    cfg, dims, params = lp.model()
    prompts = lp.prompts(cfg, [21, 5, 32])
    with jax.default_matmul_precision("highest"):
        eng = Engine(params, cfg, lp.serve_config(n_slots=2),
                     slo_metrics=False)
        got = _run(eng, prompts, [14, 20, 9])
    for prompt, out in zip(prompts, got):
        rows = lp.reference_logits(params, dims,
                                   np.asarray(prompt + out[:-1], np.int32))
        assert out == rows[len(prompt) - 1:].argmax(-1).tolist()
    return eng


@pytest.mark.parametrize("case", [_dense_case, _routed_case,
                                  _gated_delta_case, _looped_case],
                         ids=["dense", "routed", "gated-delta", "looped"])
def test_pipelined_tokens_are_each_familys_oracles(dense, case):
    eng = case(dense)
    status = eng._status()
    assert status["rounds_in_flight"] > 0        # the pipeline engaged
    assert status["tokens_discarded"] == 0       # no stop token
    assert eng.cache.pool.used_pages == 0 and eng.sched.idle()


# -- the schedule --------------------------------------------------------------

def _record_programs(eng):
    """Wrap the engine's two steps: each call appends (turn, kind, ...)
    to the returned list, chunks with their cursor and valid tokens,
    rounds with their (slot, position) rows."""
    log, turn = [], [0]
    prefill, decode = eng._prefill, eng._decode
    eng.step_hook = lambda it: turn.__setitem__(0, it)

    def chunk(params, pools, stats, tokens, pos0, n_valid, *rest):
        log.append((turn[0], "chunk", int(pos0), int(n_valid)))
        return prefill(params, pools, stats, tokens, pos0, n_valid, *rest)

    def round_(params, pools, stats, tokens, positions, tables, active,
               keys):
        act, pos = np.asarray(active), np.asarray(positions)
        log.append((turn[0], "round",
                    tuple((int(s), int(pos[s])) for s in np.flatnonzero(act))))
        return decode(params, pools, stats, tokens, positions, tables,
                      active, keys)

    eng._prefill, eng._decode = chunk, round_
    return log


def _implied_schedule(prompts, gens, n_slots, chunk):
    """The turn order of the scheduler, played by hand: FIFO admission
    into free slots, one chunk a turn for the first prefilling slot, a
    round over every decoding row, and a row that has all its tokens
    leaves at the end of its turn."""
    queue = list(range(len(prompts)))
    slots = [None] * n_slots
    cursor, made = {}, {}
    log, turn = [], 0
    while queue or any(i is not None for i in slots):
        for s in range(n_slots):
            if slots[s] is None and queue:
                slots[s] = i = queue.pop(0)
                cursor[i], made[i] = 0, 0
        for s, i in enumerate(slots):
            if i is not None and cursor[i] < len(prompts[i]):
                n = min(chunk, len(prompts[i]) - cursor[i])
                log.append((turn, "chunk", cursor[i], n))
                cursor[i] += n
                made[i] += cursor[i] == len(prompts[i])
                break
        rows = [(s, len(prompts[i]) + made[i] - 1)
                for s, i in enumerate(slots)
                if i is not None and cursor[i] == len(prompts[i])
                and made[i] < gens[i]]
        if rows:
            log.append((turn, "round", tuple(rows)))
            for s, _ in rows:
                made[slots[s]] += 1
        for s, i in enumerate(slots):
            if i is not None and made[i] >= gens[i]:
                slots[s] = None
        turn += 1
    return log


def test_the_dispatch_schedule_is_the_turn_orders(dense):
    cfg, params = dense
    gens = [6, 1, 2, 9, 3, 4]                # one answer of a single token
    eng = Engine(params, cfg, _serve(), slo_metrics=False)
    log = _record_programs(eng)
    _run(eng, PROMPTS, gens)
    want = _implied_schedule(PROMPTS, gens, 2, 4)
    assert log == want
    # a length-finished row's slot takes the next request the next turn:
    # r0's last round feeds position 9 in slot 0; the turn after, the next
    # in the queue (r4, six prompt tokens) has its first chunk there
    end_r0 = next(t for t, kind, *rest in log
                  if kind == "round" and (0, 9) in rest[0])
    assert (end_r0 + 1, "chunk", 0, 4) in log
    assert eng._status()["rounds_in_flight"] > 0


# -- stop tokens ---------------------------------------------------------------

def _stop_case(make_engine, prompts, gens):
    """Run with no stop token, pick one from the middle of the first
    answer, run again with it: every answer is the first run's cut after
    its first stop token, the freed slots serve the later requests."""
    plain = _run(make_engine(None), prompts, gens)
    eos = plain[0][len(plain[0]) // 2]
    eng = make_engine(eos)
    got = _run(eng, prompts, gens)
    want = [out[:out.index(eos) + 1] if eos in out else out for out in plain]
    assert got == want
    assert sum(len(a) < len(b) for a, b in zip(got, plain)) >= 1
    status = eng._status()
    assert status["tokens_discarded"] >= 1
    assert eng.cache.pool.used_pages == 0 and eng.sched.idle()
    return status


def test_a_stop_token_commits_the_same_tokens_and_frees_the_slot(dense):
    cfg, params = dense
    _stop_case(lambda eos: Engine(params, cfg, _serve(eos_id=eos),
                                  slo_metrics=False),
               PROMPTS, [20, 18, 16, 20, 12, 14])


def test_a_stop_token_on_a_gated_delta_stack(dense):
    """A stopped row's round went on a turn longer in its state slot: the
    next request admitted there starts from zeros all the same."""
    from tests import test_gated_delta_serving as gd

    cfg = gd.config()
    params = gd.random_params(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, gd.VOCAB, size=n).tolist()
               for n in (21, 9, 30, 14, 5)]
    _stop_case(lambda eos: Engine(params, cfg,
                                  gd.serve_config(n_slots=2, eos_id=eos),
                                  slo_metrics=False),
               prompts, [16, 12, 14, 10, 12])


# -- drain, kill, crash replay with a round in flight --------------------------

def _in_flight_then_drain(params, cfg, serve, prompts, gens, steps):
    src = Engine(params, cfg, serve, slo_metrics=False)
    reqs = [src.submit(p, g, seed=i, rid=f"r{i}")
            for i, (p, g) in enumerate(zip(prompts, gens))]
    for _ in range(steps):
        src.step_once(0.0, 0.0)
    assert src._flight is not None and src._flight.rows  # a round out
    moved = src.drain()
    assert src._flight is None
    src.clear_cache()                 # raises if a page is still held
    carried = [(r.resume is not None, r.replay) for r in moved]
    dst = Engine(params, cfg, serve, slo_metrics=False)
    for r in moved:
        dst.enqueue(r, force=True)
    dst.run()
    return [list(r.generated) for r in reqs], carried


def test_drain_by_page_with_a_round_in_flight(dense):
    cfg, params = dense
    want = _run(Engine(params, cfg, _serve(), slo_metrics=False),
                PROMPTS, GENS)
    got, carried = _in_flight_then_drain(params, cfg, _serve(), PROMPTS,
                                         GENS, 5)
    assert got == want
    assert any(pages for pages, _ in carried)


def test_drain_by_replay_with_a_round_in_flight(dense):
    from tests import test_gated_delta_serving as gd

    cfg = gd.config()
    params = gd.random_params(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, gd.VOCAB, size=n).tolist()
               for n in (9, 40, 21)]
    gens = [14, 10, 8]
    serve = gd.serve_config(n_slots=2)
    want = _run(Engine(params, cfg, serve, slo_metrics=False), prompts,
                gens)
    got, carried = _in_flight_then_drain(params, cfg, serve, prompts, gens,
                                         4)
    assert got == want
    assert any(replay for _, replay in carried)
    assert not any(pages for pages, _ in carried)


def test_kill_fetches_what_is_in_flight(dense):
    cfg, params = dense
    eng = Engine(params, cfg, _serve(), slo_metrics=False)
    reqs = [eng.submit(p, g, seed=i) for i, (p, g) in
            enumerate(zip(PROMPTS, GENS))]
    for _ in range(4):
        eng.step_once(0.0, 0.0)
    before = sum(len(r.generated) for r in reqs)
    rows = len(eng._flight.rows) + len(eng._flight.chunks)
    eng.kill()
    assert eng._flight is None
    assert sum(len(r.generated) for r in reqs) == before + rows


@pytest.mark.chaos
def test_crash_replay_with_a_round_in_flight_is_bitwise(dense, tmp_path):
    cfg, params = dense
    want = _run(Engine(params, cfg, _serve(), slo_metrics=False),
                PROMPTS, GENS)
    j = RequestJournal(str(tmp_path / "j.jsonl"))
    fleet = ServeFleet(params, cfg, _serve(), 2, router_seed=0,
                       revive_after=3, journal=j)
    caught = {}

    def hook(rnd):
        if rnd == 4:
            victim = fleet.replicas[0].engine
            caught["in_flight"] = victim._flight is not None
            caught["n"] = fleet.crash_replica("r0")

    fleet.step_hook = hook
    reqs = [fleet.submit(p, g, seed=i, rid=f"r{i}")
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
    summary = fleet.run()
    assert caught["in_flight"] and caught["n"] > 0
    assert summary["requests_failed"] == 0
    assert [list(r.generated) for r in reqs] == want
    assert all(o == "completed" for o in j.state().terminals.values())


# -- speculation, the prefix cache ---------------------------------------------

def test_speculation_stays_synchronous_and_serves_the_plain_tokens(dense):
    cfg, params = dense
    motif = [5, 9, 2, 6]
    prompts = [motif * 4, [7, 8, 9], motif * 3 + [1]]
    gens = [24, 10, 16]
    plain_eng = Engine(params, cfg, _serve(), slo_metrics=False)
    plain = _run(plain_eng, prompts, gens)
    spec_eng = Engine(params, cfg, _serve(spec_k=3), slo_metrics=False)
    assert _run(spec_eng, prompts, gens) == plain
    assert spec_eng._status()["rounds_in_flight"] == 0
    assert plain_eng._status()["rounds_in_flight"] > 0


def test_the_prefix_cache_hits_and_serves_the_same_tokens(dense):
    """Turns of one conversation re-send the earlier turns; short answers
    (one and two tokens) finish with their last tokens in flight."""
    cfg, params = dense
    base = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17]
    prompts = [base, base + [20, 21], base + [30], base + [20, 21, 22, 23]]
    gens = [1, 2, 6, 3]
    off = _run(Engine(params, cfg, _serve(), slo_metrics=False), prompts,
               gens)
    eng = Engine(params, cfg, _serve(prefix_cache=True), slo_metrics=False)
    assert _run(eng, prompts, gens) == off
    assert eng.cache_hit_rate and eng.cache_hit_rate > 0
    eng.clear_cache()


# -- the counters ----------------------------------------------------------------

def test_status_counters_add_up(dense, monkeypatch):
    """Rounds dispatched while an earlier round's tokens were still on
    the device are ``rounds_in_flight``; the others went out on fetched
    tokens. Every round is fetched once, after it went out."""
    cfg, params = dense
    eng = Engine(params, cfg, _serve(), slo_metrics=False)
    events = []
    decode, real_get = eng._decode, jax.device_get

    def round_(*a):
        events.append("round")
        return decode(*a)

    def get(x):
        events.append("fetch" if x.shape == (2,) else "chunk")
        return real_get(x)

    eng._decode = round_
    monkeypatch.setattr(jax, "device_get", get)
    got = _run(eng, PROMPTS, GENS)
    unfetched, overlapped = 0, 0
    for e in events:
        if e == "round":
            overlapped += unfetched > 0
            unfetched += 1
        elif e == "fetch":
            unfetched -= 1
            assert unfetched >= 0
    status, summary = eng._status(), eng.summary(record=False)
    assert unfetched == 0
    assert status["rounds_in_flight"] == overlapped > 0
    assert events.count("round") == summary["decode_steps"]
    assert events.count("chunk") == len(PROMPTS)
    assert status["pipeline_flushes"] >= 1
    assert status["tokens_discarded"] == 0
    assert summary["tokens_generated"] == sum(map(len, got)) == sum(GENS)
    assert summary["decode_steps"] * 2 * summary["slot_utilization"] == (
        pytest.approx(sum(GENS) - len(PROMPTS)))
