"""Speculative-decoding invariants: the n-gram proposer, the batched
verify step, and the engine's pinned determinism contract with drafting
on (docs/SERVING.md, "Speculative decoding").

The load-bearing properties:

* the proposer is a deterministic pure function of the committed stream
  (longest-order most-recent match, incremental index);
* spec-on and spec-off token streams are IDENTICAL — greedy and
  sampled, solo and mid-batch join, accepted and rejected drafts: the
  verify step only ever commits the model's own per-position choice;
* a rejected draft's garbage KV is never readable (every round rewrites
  its window before reading it) — pinned by running a deliberately
  adversarial proposer;
* accept-rate accounting counts real proposals only, and page
  accounting stays exact with spec on;
* spec composes with prefix caching — the chat-trace smoke runs both on
  end-to-end and asserts the determinism trio against the PR 9 engine
  (cache off, spec off).
"""

import jax
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import (
    Engine,
    NGramProposer,
    ServeConfig,
)
from distributed_model_parallel_tpu.serve.scheduler import RequestState

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=128,
                                pos_embedding="rope")
    return cfg, tfm.init_params(jax.random.key(0), cfg)


def _serve(**kw):
    base = dict(n_slots=2, page_size=8, n_pages=48, max_seq_len=96,
                prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16]]
GENS = [12, 18, 7]


# ---------------------------------------------------------------------------
# proposer unit behavior
# ---------------------------------------------------------------------------

def test_proposer_copies_most_recent_continuation():
    p = NGramProposer(k=3, max_order=2)
    p.extend([5, 6, 7, 8, 1, 2, 5, 6])
    # suffix bigram (5, 6) last occurred at positions 0-1 -> continue 7, 8, 1
    assert p.propose() == [7, 8, 1]
    p.extend([9])
    assert p.propose() == []                   # (6, 9) and 9 never seen
    p.extend([5, 6])
    # bigram (5, 6) now has TWO earlier occurrences; most recent wins
    assert p.propose() == [9, 5, 6]


def test_proposer_prefers_longest_order():
    p = NGramProposer(k=2, max_order=3)
    p.extend([1, 2, 3, 9, 2, 3, 7, 1, 2, 3])
    # trigram (1,2,3) matches position 0-2 -> [9, 2]; the bigram match
    # (2,3)@4-5 -> [7, 1] must lose to the longer order.
    assert p.propose() == [9, 2]


def test_proposer_deterministic_and_incremental():
    a = NGramProposer(k=4, max_order=3)
    b = NGramProposer(k=4, max_order=3)
    stream = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 1, 4, 1, 5]
    a.extend(stream)
    for t in stream:
        b.extend([t])                          # one token at a time
    assert a.propose() == b.propose() != []


def test_proposer_rejects_bad_config():
    with pytest.raises(ValueError, match="k must be"):
        NGramProposer(k=0)
    with pytest.raises(ValueError, match="max_order"):
        NGramProposer(k=2, max_order=0)


# ---------------------------------------------------------------------------
# engine parity: spec on == spec off, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                        # greedy
    {"temperature": 0.9, "top_k": 16},         # sampled
    {"temperature": 0.7, "top_p": 0.9},        # nucleus
])
def test_spec_on_off_identical_tokens(model, kw):
    cfg, params = model
    outs = []
    for spec_k in (0, 4):
        eng = Engine(params, cfg, _serve(spec_k=spec_k, **kw))
        reqs = [eng.submit(p, g, seed=i)
                for i, (p, g) in enumerate(zip(PROMPTS, GENS))]
        eng.run()
        assert all(r.state is RequestState.COMPLETED for r in reqs)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1], f"spec decode changed tokens ({kw})"


def test_spec_mid_batch_join_matches_solo(model):
    """A request joining a spec-decoding batch mid-flight commits its
    solo trajectory — per-row drafts and widths must not couple rows."""
    cfg, params = model
    busy = Engine(params, cfg, _serve(spec_k=3, n_slots=2))
    first = busy.submit([1, 2, 3, 4], 24, seed=0)
    busy.run(max_iterations=6)
    joiner = busy.submit([9, 8, 7], 16, seed=1, rid="join")
    busy.run()
    for req, (p, g, s) in ((first, ([1, 2, 3, 4], 24, 0)),
                           (joiner, ([9, 8, 7], 16, 1))):
        solo = Engine(params, cfg, _serve(spec_k=0))
        ref = solo.submit(p, g, seed=s)
        solo.run()
        assert req.generated == ref.generated


def test_rejected_drafts_never_corrupt_tokens(model):
    """Adversarial proposer: drafts chosen to be maximally WRONG (every
    proposal is token+1 mod vocab, so rejection happens constantly).
    The committed stream must still be the sequential one — a rejected
    draft's KV write is garbage the next round always overwrites."""
    cfg, params = model
    ref = Engine(params, cfg, _serve())
    r0 = ref.submit(PROMPTS[0], 16)
    ref.run()
    eng = Engine(params, cfg, _serve(spec_k=4))

    class Hostile:
        def __init__(self, inner):
            self.inner = inner

        def extend(self, toks):
            self.inner.extend(toks)

        def propose(self):
            last = self.inner.tokens[-1]
            return [(last + 1 + i) % cfg.vocab_size for i in range(4)]

        def predict_next(self):
            return self.propose()[0]

    r1 = eng.submit(PROMPTS[0], 16)
    # Swap in the hostile proposer at admission via the step hook, and
    # force it LIVE every round — the shadow gate would (correctly)
    # never promote a proposer this bad, but the property under test is
    # that riding hostile drafts cannot corrupt tokens.
    def hook(i):
        prop = eng._proposers.get(r1.rid)
        if prop is not None:
            if not isinstance(prop, Hostile):
                eng._proposers[r1.rid] = Hostile(prop)
            eng._spec_live[r1.rid] = True

    eng.step_hook = hook
    eng.run()
    assert r1.generated == r0.generated
    assert eng.draft_accept_rate is not None
    # hostile drafts CAN collide with the true token occasionally, but
    # most must be rejected
    assert eng.draft_accept_rate < 0.5


def test_spec_respects_max_new_tokens_and_eos(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve(spec_k=6))
    reqs = [eng.submit([1, 2, 3], 5, rid="short"),
            eng.submit([4, 5, 6], 1, rid="one")]
    eng.run()
    assert len(reqs[0].generated) == 5
    assert len(reqs[1].generated) == 1
    # EOS: pick the greedy run's 3rd token as the stop symbol, rerun
    ref = Engine(params, cfg, _serve())
    rr = ref.submit([1, 2, 3], 8)
    ref.run()
    eos = rr.generated[2]
    stop_ref = Engine(params, cfg, _serve(eos_id=eos))
    sr = stop_ref.submit([1, 2, 3], 8)
    stop_ref.run()
    stop_spec = Engine(params, cfg, _serve(spec_k=4, eos_id=eos))
    ss = stop_spec.submit([1, 2, 3], 8)
    stop_spec.run()
    assert ss.generated == sr.generated
    assert ss.generated[-1] == eos


def test_spec_page_accounting_exact(model):
    """Reservation==allocation survives spec decode: window writes past
    a row's budget are masked, so used pages stay exactly the resident
    reservations every iteration and the pool drains at the end."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(spec_k=4))

    def hook(i):
        expect = sum(eng.cache.pages_needed(r.total_capacity)
                     for r in eng.sched.active())
        assert eng.cache.pool.used_pages == expect

    eng.step_hook = hook
    for p, g in zip(PROMPTS, GENS):
        eng.submit(p, g)
    eng.run()
    assert eng.cache.pool.used_pages == 0


def test_spec_accept_accounting_and_summary(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve(spec_k=4))
    eng.submit([1, 2] * 8, 24)                 # repetitive: drafts land
    summary = eng.run()
    assert summary["spec_k"] == 4
    assert summary["draft_tokens_proposed"] > 0
    assert 0 <= summary["draft_accept_rate"] <= 1
    assert (summary["draft_tokens_accepted"]
            <= summary["draft_tokens_proposed"])
    # fewer decode rounds than tokens: the whole point
    assert summary["decode_steps"] < summary["tokens_generated"]
    status = eng._status()
    assert status["spec_k"] == 4
    assert status["draft_accept_rate"] == eng.draft_accept_rate


@pytest.mark.parametrize("spec_k", [0, 4], ids=["plain", "spec"])
def test_every_token_carries_a_commit_stamp(model, spec_k):
    """``Request.t_tokens``: one engine-clock stamp a generated token,
    non-decreasing, beginning on ``t_first_token`` and ending on
    ``t_done``; the tokens of one speculative round share theirs."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(spec_k=spec_k))
    reqs = [eng.submit([1, 2] * 8, 24), eng.submit([5, 6, 7], 9, seed=1),
            eng.submit([3, 4, 5, 6], 1, seed=2)]   # done on its first token
    summary = eng.run()
    for r in reqs:
        assert r.state is RequestState.COMPLETED
        assert len(r.t_tokens) == len(r.generated) == r.max_new_tokens
        assert r.t_tokens == sorted(r.t_tokens)
        assert r.t_tokens[0] == r.t_first_token
        assert r.t_tokens[-1] == r.t_done
        assert r.t_admitted <= r.t_first_token <= r.t_done
    shared = len(reqs[0].t_tokens) - len(set(reqs[0].t_tokens))
    if spec_k:
        assert summary["draft_tokens_accepted"] > 0 and shared > 0
    else:
        assert shared == 0      # one round, one token, one clock read


def test_spec_config_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="spec_k"):
        Engine(params, cfg, _serve(spec_k=-1))
    with pytest.raises(ValueError, match="spec_ngram"):
        Engine(params, cfg, _serve(spec_k=2, spec_ngram=0))


# ---------------------------------------------------------------------------
# the chat-trace smoke: cache + spec end-to-end vs the PR 9 engine
# ---------------------------------------------------------------------------

def test_chat_trace_smoke_determinism_trio(model):
    """Fast CPU end-to-end over a multi-turn chat shape with BOTH levers
    on: every turn's tokens must be bitwise the PR 9 engine's (prefix
    cache off, spec off) — the determinism trio (cache-hit admission,
    accepted/rejected drafts, mid-batch joins) in one campaign — while
    the cache actually hits and drafting actually accepts."""
    cfg, params = model

    def run_campaign(serve_cfg):
        eng = Engine(params, cfg, serve_cfg)
        system = [11, 12, 13, 14, 15, 16, 17, 18]
        histories = [system + [20 + c, 21 + c] for c in range(3)]
        turns = []
        for t in range(3):
            wave = [eng.submit(histories[c], 6, seed=c, rid=f"c{c}t{t}")
                    for c in range(3)]
            eng.run()
            for c, req in enumerate(wave):
                assert req.state is RequestState.COMPLETED
                histories[c] = (histories[c] + req.generated
                                + [40 + 3 * t + c])
            turns.append([r.generated for r in wave])
        return turns, eng.summary()

    base = dict(n_slots=2, page_size=8, n_pages=64, max_seq_len=96,
                prefill_chunk=8)
    on, on_sum = run_campaign(ServeConfig(prefix_cache=True, spec_k=4,
                                          **base))
    off, off_sum = run_campaign(ServeConfig(**base))
    assert on == off, "cache+spec changed a token somewhere in the chat"
    assert on_sum["cache_hit_rate"] > 0.3
    assert on_sum["prefill_tokens_saved"] > 0
    assert on_sum["draft_tokens_proposed"] > 0
    assert on_sum["decode_steps"] <= off_sum["decode_steps"]
