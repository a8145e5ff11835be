"""Prefix-cache reuse invariants: refcounted copy-on-write pages, the
radix tree over token prefixes, post-sharing admission billing, and the
bitwise determinism contract for cache-hit requests.

The load-bearing properties (docs/SERVING.md, "Prefix-cache reuse"):

* PagePool refcount lifecycle — a shared page returns to the free list
  only when its LAST reference drops; double free still raises;
* radix-tree insert/match/evict are deterministic (logical clock +
  insertion-order tie-breaks, LRU-leaf-first eviction, a pinned
  descendant pins its ancestors);
* copy-on-write fork — two sequences sharing a prefix write their
  divergent suffixes into disjoint fresh pages, and releasing either
  leaves the other's view intact;
* admission bills only the uncached suffix — a cache-hit request admits
  where a cold one queues (the over-reservation fix);
* a cache-hit request decodes BITWISE the cold run's tokens, greedy and
  sampled, through the XLA path and the interpreter-mode Pallas kernel.
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import (
    Engine,
    PagedKVCache,
    PagePool,
    PagePoolError,
    PrefixCache,
    ServeConfig,
)
from distributed_model_parallel_tpu.serve.paged_kv import (
    share_granularity_for,
)
from distributed_model_parallel_tpu.serve.scheduler import (
    Request,
    RequestState,
    Scheduler,
)

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=128,
                                pos_embedding="rope")
    return cfg, tfm.init_params(jax.random.key(0), cfg)


def _serve(**kw):
    base = dict(n_slots=2, page_size=8, n_pages=48, max_seq_len=96,
                prefill_chunk=4, prefix_cache=True)
    base.update(kw)
    return ServeConfig(**base)


PROMPT = list(range(1, 19))                    # 18 tokens = 2 full pages


# ---------------------------------------------------------------------------
# PagePool refcount lifecycle
# ---------------------------------------------------------------------------

def test_refcount_shared_page_freed_only_at_zero():
    pool = PagePool(8)
    pages = pool.alloc(3)
    pool.retain(pages)                         # second holder
    assert pool.shared_pages == 3
    pool.free(pages)                           # first holder lets go
    assert pool.used_pages == 3                # still resident
    assert pool.free_pages == 5
    assert pool.shared_pages == 0
    pool.free(pages)                           # last holder
    assert pool.used_pages == 0
    assert pool.free_pages == 8


def test_refcount_double_free_still_raises():
    pool = PagePool(4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.free(pages)
    with pytest.raises(PagePoolError, match="not allocated"):
        pool.retain([pages[0]])                # retain needs a live page


def test_refcount_alloc_never_hands_out_shared_pages():
    pool = PagePool(4)
    a = pool.alloc(2)
    pool.retain(a)
    pool.free(a)                               # refcount back to 1
    b = pool.alloc(2)
    assert not set(a) & set(b)


# ---------------------------------------------------------------------------
# radix tree determinism
# ---------------------------------------------------------------------------

def _tree(n_pages=16, page=4):
    pool = PagePool(n_pages)
    return pool, PrefixCache(pool, page)


def test_radix_insert_match_page_granular():
    pool, tree = _tree()
    toks = list(range(10))                     # 2 full pages + tail of 2
    pages = pool.alloc(3)
    assert tree.insert(toks, pages) == 2       # the tail page never enters
    assert tree.match(toks) == pages[:2]
    assert tree.match(toks[:7]) == pages[:1]   # partial second page: 1 hit
    assert tree.match([99] + toks[1:]) == []   # first page diverges: miss
    assert pool.refcount(pages[0]) == 2        # owner + tree
    assert pool.refcount(pages[2]) == 1        # tail page not adopted


def test_radix_existing_nodes_win_on_duplicate_insert():
    pool, tree = _tree()
    toks = list(range(8))
    first = pool.alloc(2)
    tree.insert(toks, first)
    second = pool.alloc(2)
    assert tree.insert(toks, second) == 0      # existing nodes keep theirs
    assert tree.match(toks) == first
    assert pool.refcount(second[0]) == 1       # ours never adopted


def test_radix_eviction_lru_leaf_first_deterministic():
    orders = []
    for _ in range(2):
        pool, tree = _tree()
        a = pool.alloc(2)                      # chain A: 2 pages
        tree.insert(list(range(8)), a)
        b = pool.alloc(2)                      # chain B shares page 0 path?
        tree.insert([50, 51, 52, 53, 60, 61, 62, 63], b)
        pool.free(a)
        pool.free(b)                           # tree is now sole holder
        tree.match(list(range(8)))             # bump chain A's recency
        freed = tree.evict(3)
        orders.append(freed)
        # LRU: chain B's leaf then root go first, then A's leaf.
        assert freed[0] == b[1] and freed[1] == b[0]
    assert orders[0] == orders[1]


def test_radix_pinned_descendant_pins_ancestors():
    pool, tree = _tree()
    pages = pool.alloc(3)
    tree.insert(list(range(12)), pages)        # chain of 3
    pool.free([pages[0], pages[1]])            # tree-only
    # pages[2] still held by its "sequence": the whole chain is pinned.
    assert tree.evictable_pages() == 0
    assert tree.evict(3) == []
    pool.free([pages[2]])
    assert tree.evictable_pages() == 3
    assert tree.evict(3) == [pages[2], pages[1], pages[0]]  # leaf-first
    assert pool.free_pages == 16


def test_radix_exclude_protects_matched_path():
    pool, tree = _tree()
    pages = pool.alloc(2)
    tree.insert(list(range(8)), pages)
    pool.free(pages)
    assert tree.evictable_pages() == 2
    assert tree.evictable_pages(exclude={pages[0]}) == 1  # leaf still free


# ---------------------------------------------------------------------------
# cache-level copy-on-write + admission billing
# ---------------------------------------------------------------------------

def _cache(n_pages=12, page=4, max_seq=32):
    cfg = type("C", (), {"n_layers": 1, "n_passes": 1, "kv_heads": 1,
                         "head_dim": 4, "dtype": jnp.float32})
    return PagedKVCache(cfg, n_pages=n_pages, page_size=page,
                        max_seq_len=max_seq, prefix_cache=True)


def test_cow_fork_divergent_suffix_gets_fresh_pages():
    cache = _cache()
    toks = list(range(12))                     # 3 full pages
    cache.open("a")
    cache.ensure("a", 16)                      # 4-page reservation
    cache.insert_prefix("a", toks)
    a_pages = list(cache._tables["a"])
    # b shares the 2-page usable prefix (cap at len-1 -> 11 -> 8 tokens)
    got = cache.admit_with_prefix("b", toks, 16)
    assert got == 8
    b_pages = list(cache._tables["b"])
    assert b_pages[:2] == a_pages[:2]          # shared prefix
    assert not set(b_pages[2:]) & set(a_pages)  # divergent suffix: fresh
    assert cache.pool.refcount(a_pages[0]) == 3  # a + tree + b
    cache.release("a")
    assert cache.pool.refcount(b_pages[0]) == 2  # b + tree: view intact
    cache.release("b")
    assert cache.pool.refcount(b_pages[0]) == 1  # tree keeps the prefix
    assert cache.pool.used_pages == len(cache.prefix)


def test_admission_bills_only_uncached_suffix():
    """The over-reservation fix: a cache-hit request's admission bill is
    its uncached suffix, so it admits where a byte-for-byte-equal cold
    request queues. The warm writer stays RESIDENT (its pages refcount 2
    — unevictable), which is exactly the case the old prompt+max_new
    bill got wrong: the pool "looks" full but the hit only needs its
    suffix."""
    toks = list(range(16))                     # 4 full pages
    cold_toks = [90 + t for t in toks]

    def warm_pool():
        # 8 pages: warm resident holds 5, tree pins 4 of them, 3 free.
        cache = _cache(n_pages=8, page=4, max_seq=24)
        cache.admit_with_prefix("warm", toks, 20)
        cache.insert_prefix("warm", toks)
        return cache

    # Cold twin: needs 5 fresh pages; free 3, evictable 0 -> queues.
    sched = Scheduler(warm_pool(), 2)
    cold = Request(rid="cold", prompt=cold_toks, max_new_tokens=4)
    sched.submit(cold)
    assert sched.admit(0.0) == []
    assert cold.state is RequestState.QUEUED
    # Cache hit: 12 of 16 prompt tokens cached (cap at len-1, floor to
    # the 4-token share quantum) -> bills 5 - 3 = 2 fresh pages -> admits
    # into the same pool state the cold twin queued against.
    sched = Scheduler(warm_pool(), 2)
    hit = Request(rid="hit", prompt=toks, max_new_tokens=4)
    sched.submit(hit)
    assert [r.rid for r in sched.admit(0.0)] == ["hit"]
    assert hit.cached_prompt_tokens == 12
    assert hit.state is RequestState.PREFILL
    assert sched.cache.pool.free_pages == 1   # only the suffix was billed


def test_admission_evicts_lru_tree_pages_when_needed():
    cache = _cache(n_pages=6, page=4, max_seq=24)
    toks = list(range(16))
    cache.open("w")
    cache.ensure("w", 20)                      # all 5... 16+4=20 -> 5 pages
    cache.insert_prefix("w", toks)
    cache.release("w")                         # tree: 4 pages, free: 2
    cold = [70 + t for t in toks]
    got = cache.admit_with_prefix("c", cold, 20)
    assert got == 0
    assert cache.pool.used_pages >= 5
    assert len(cache.prefix) <= 1              # tree drained for the cold
    cache.release("c")


def test_share_granularity_quantizes_to_chunk_boundary():
    assert share_granularity_for(8, 4) == 8
    assert share_granularity_for(8, 32) == 32
    assert share_granularity_for(16, 12) == 48
    cache = PagedKVCache(
        type("C", (), {"n_layers": 1, "n_passes": 1, "kv_heads": 1,
                       "head_dim": 4, "dtype": jnp.float32}),
        n_pages=16, page_size=4, max_seq_len=64, prefix_cache=True,
        share_granularity=8)
    toks = list(range(13))                     # 3 full pages
    cache.open("a")
    cache.ensure("a", 16)
    cache.insert_prefix("a", toks)
    # raw match = 3 pages = 12 tokens; cap len-1 = 12; floor to g=8.
    cached, fresh, _ = cache.peek_admission(toks, 16)
    assert cached == 8
    assert fresh == 4 - 2


# ---------------------------------------------------------------------------
# engine-level bitwise determinism: cold vs cached admission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                        # greedy, auto impl
    {"temperature": 0.9, "top_k": 16},         # sampled
    {"attn_impl": "pallas"},                   # interpreter-mode kernel
])
def test_cached_prefix_decodes_bitwise_cold_tokens(model, kw):
    cfg, params = model
    cold = Engine(params, cfg, _serve(prefix_cache=False, **kw))
    ref = cold.submit(PROMPT, 12, seed=5)
    cold.run()
    eng = Engine(params, cfg, _serve(**kw))
    warm1 = eng.submit(PROMPT, 12, seed=5)
    eng.run()
    warm2 = eng.submit(PROMPT, 12, seed=5, rid="again")
    eng.run()
    assert warm1.generated == ref.generated
    assert warm2.cached_prompt_tokens > 0, "second pass must hit the tree"
    assert warm2.generated == ref.generated, (
        f"cache-hit tokens diverged from the cold run ({kw})")


def test_multi_turn_followup_reuses_generated_history(model):
    """The multi-turn shape: turn 2's prompt embeds turn 1's prompt AND
    its generated reply — decode-written pages must serve the follow-up
    bitwise (they were verified-written; the trimmed final token is
    re-prefilled)."""
    cfg, params = model
    eng = Engine(params, cfg, _serve())
    t1 = eng.submit(PROMPT, 10)
    eng.run()
    follow = PROMPT + t1.generated + [30, 31, 32]
    t2 = eng.submit(follow, 8, rid="turn2")
    eng.run()
    assert t2.cached_prompt_tokens >= 16, "history should be cached"
    cold = Engine(params, cfg, _serve(prefix_cache=False))
    ref = cold.submit(follow, 8)
    cold.run()
    assert t2.generated == ref.generated


def test_mid_batch_join_with_shared_prefix(model):
    """A cache-hit request joining a busy batch mid-flight still decodes
    its solo tokens — sharing must not couple co-resident rows."""
    cfg, params = model
    eng = Engine(params, cfg, _serve(n_slots=3))
    first = eng.submit(PROMPT, 20, seed=1)
    eng.run(max_iterations=8)                  # first mid-decode
    joiners = [eng.submit(PROMPT, 10, seed=2, rid="j1"),
               eng.submit(list(PROMPT) + [40, 41], 10, seed=3, rid="j2")]
    eng.run()
    solo_out = []
    for i, (p, g, seed) in enumerate([(PROMPT, 20, 1), (PROMPT, 10, 2),
                                      (list(PROMPT) + [40, 41], 10, 3)]):
        solo = Engine(params, cfg, _serve(prefix_cache=False))
        r = solo.submit(p, g, seed=seed)
        solo.run()
        solo_out.append(r.generated)
    assert first.generated == solo_out[0]
    assert joiners[0].generated == solo_out[1]
    assert joiners[1].generated == solo_out[2]


def test_page_accounting_with_sharing_exact(model):
    """Every iteration: total pool references == the sum of resident
    tables' lengths + the tree's holdings; after the run the pool holds
    exactly the tree."""
    cfg, params = model
    eng = Engine(params, cfg, _serve())

    def hook(i):
        refs = sum(eng.cache.pool._refs.values())
        tables = sum(len(t) for t in eng.cache._tables.values())
        assert refs == tables + len(eng.cache.prefix)

    eng.step_hook = hook
    for i in range(3):
        eng.submit(PROMPT, 8 + i, rid=f"r{i}")
    eng.run()
    assert eng.cache.pool.used_pages == len(eng.cache.prefix)
    assert eng.cache.pool.shared_pages == 0


def test_summary_and_status_carry_cache_fields(model):
    cfg, params = model
    eng = Engine(params, cfg, _serve())
    eng.submit(PROMPT, 8)
    eng.run()
    eng.submit(PROMPT, 8, rid="again")
    summary = eng.run()
    assert summary["prefix_cache"] is True
    assert summary["cache_hit_rate"] > 0
    assert summary["prefill_tokens_saved"] >= 16
    assert summary["cached_prefix_pages"] == len(eng.cache.prefix)
    status = eng._status()
    assert status["cache_hit_rate"] == eng.cache_hit_rate
    assert status["shared_pages"] == eng.cache.shared_pages
