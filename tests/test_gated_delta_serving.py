"""The hybrid block (gated-delta linear-attention layers 3:1 with full
attention, norms on the sublayers' outputs, whole-vector QK-norm) through
the serving path, against its plain reference
(models/gated_delta_reference.py: the recurrence token by token), at a
small size that keeps every kind of thing: two periods of linear, linear,
linear, full; 2 key heads serving 4 value heads of 8 x 64 (a pair of
heads is 128 lanes: the decode kernel's grouping); a convolution of 4;
chunks of 16 against sub-chunks of 64; pages of 4.

Tolerances. The rule's forms agree to float32 rounding (1e-5 on values
of order 1). The model's logits do not: at these random weights one ulp
on the embedding moves the reference's logits by 1e-4 (a test below reads
it), so program and reference, which round in different places, are held
to 2e-3 on logits of order 4, and every comparison is paired with a
control that a real fault (no carried tail, beta in (0, 1), a stale
state) moves them by a tenth or more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import gated_delta_reference as ref
from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.ops import gated_delta as gd
from distributed_model_parallel_tpu.serve import Engine, ServeConfig
from distributed_model_parallel_tpu.serve import paged_kv
from distributed_model_parallel_tpu.serve.paged_kv import memory_gauges

CHUNK, PAGE, MAX_SEQ, VOCAB = 16, 4, 160, 96
L = tfm.LayerKind(mixer="gated_delta")
F = tfm.LayerKind()
LOGIT_TOL = 2e-3


def config(kinds=(L, L, L, F) * 2, **kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=4, d_head=16,
        n_layers=len(kinds), d_ff=64, max_seq_len=MAX_SEQ,
        pos_embedding="rope", norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        qk_norm_whole=True, norm_placement="post", layer_kinds=kinds,
        lin_key_heads=2, lin_value_heads=4, lin_key_dim=8, lin_value_dim=64,
        lin_conv=4, lin_neg_eigval=True)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def random_params(cfg, seed=0):
    """init_params with every norm scale random and a unit embedding."""
    params = tfm.init_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = str(path[-1])
        noise = jax.random.normal(k, leaf.shape, jnp.float32)
        if "scale" in name or "_norm" in name:
            leaf = (1.0 + 0.2 * noise).astype(leaf.dtype)
        elif "embed" in name:
            leaf = noise.astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def layers_of(params, cfg):
    n_lead, period, n_periods = cfg.layer_plan
    blocks = params["blocks"]
    blocks = (blocks,) if isinstance(blocks, dict) else blocks
    out = list(params.get("lead", ()))
    for rep in range(n_periods):
        out += [jax.tree.map(lambda a: a[rep], blocks[i])
                for i in range(period)]
    return out


_reference = jax.jit(ref.sequence_logits,
                     static_argnames=("eps", "key_heads", "neg_eigval"))


def reference_logits(params, cfg, tokens, **wrong):
    """The reference over ``tokens``, padded to MAX_SEQ so that one
    compiled program serves every length (causal: what follows a token
    does not reach it)."""
    kw = dict(eps=cfg.norm_eps, key_heads=cfg.lin_key_heads,
              neg_eigval=cfg.lin_neg_eigval)
    kw.update(wrong)
    padded = np.zeros((MAX_SEQ,), np.int32)
    padded[:len(tokens)] = np.asarray(tokens)
    return np.asarray(_reference(
        {k: params[k] for k in ("embed", "ln_f_scale", "head")},
        layers_of(params, cfg), jnp.asarray(padded), **kw))[:len(tokens)]


def serve_config(**kw):
    base = dict(n_slots=4, page_size=PAGE, n_pages=128, max_seq_len=MAX_SEQ,
                prefill_chunk=CHUNK, attn_impl="xla")
    base.update(kw)
    return ServeConfig(**base)


def served_gap(params, cfg, req, **wrong):
    """The widest gap by which a served token's logit lies under the
    reference's best, over one request's answer."""
    seq = np.asarray(req.prompt + req.generated)
    lg = reference_logits(params, cfg, seq[:-1], **wrong)[req.prompt_len - 1:]
    return float(np.max(lg.max(-1) - lg[np.arange(len(lg)), req.generated]))


@pytest.fixture(scope="module")
def model():
    cfg = config()
    return cfg, random_params(cfg)


# -- (1) the rule's forms -------------------------------------------------------

def rule_inputs(t, b=2, h=3, dk=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    # neighbouring keys alike, as behind a short convolution
    k = unit(jax.random.normal(ks[1], (b, t, h, dk))
             + 2 * jax.random.normal(ks[2], (b, 1, h, dk)))
    v = jax.random.normal(ks[3], (b, t, h, dv))
    log_alpha = -jax.random.uniform(ks[4], (b, t, h), minval=1e-3, maxval=1.6)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[5], (b, t, h)))
    state = jax.random.normal(ks[6], (b, h, dk, dv))
    return q, k, v, log_alpha, beta, state


def by_recurrence(q, k, v, log_alpha, beta, state, valid):
    def token(s, xs):
        q, k, v, la, b, ok = xs
        o, s = gd.gated_delta_step(
            q, k, v, jnp.where(ok[:, None], jnp.exp(la), 1.0),
            jnp.where(ok[:, None], b, 0.0), s)
        return s, o

    t_major = lambda x: jnp.moveaxis(x, 1, 0)
    s, o = jax.lax.scan(token, state, tuple(
        t_major(x) for x in (q, k, v, log_alpha, beta, valid)))
    return t_major(o), s


@pytest.mark.parametrize("t,lens", [(150, (150, 97)), (16, (16, 5)),
                                    (64, (64, 0))],
                         ids=["not-a-multiple", "one-chunk",
                              "a-row-of-padding"])
def test_chunked_form_is_the_recurrence(t, lens):
    """Sub-chunks of 64 from a state that is not zero, for lengths that
    are and are not multiples of 64; a token that is not valid moves
    nothing (the second row's state stops at its length)."""
    q, k, v, log_alpha, beta, state = rule_inputs(t)
    valid = jnp.arange(t)[None, :] < jnp.asarray(lens)[:, None]
    o, s1 = gd.gated_delta_chunk(q, k, v, log_alpha, beta, state, valid)
    o2, s2 = by_recurrence(q, k, v, log_alpha, beta, state, valid)
    m = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(o)[m], np.asarray(o2)[m],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s1, s2, atol=1e-5, rtol=1e-5)
    if lens[1] == 0:
        np.testing.assert_array_equal(s1[1], state[1])


def test_solve_is_by_substitution_where_the_series_would_cancel():
    """Equal keys and beta = 2: ``(I + A)^-1`` has entries of 2 while the
    series' terms reach 1e27; forward substitution stays exact."""
    c = gd.SUB
    a = jnp.tril(jnp.full((c, c), 2.0), -1)
    rhs = jnp.eye(c)
    x = gd._solve_unit_lower(a, rhs)
    np.testing.assert_allclose((jnp.eye(c) + a) @ x, rhs, atol=1e-4)
    assert float(jnp.max(jnp.abs(x))) == 2.0


@pytest.mark.parametrize("layer", [0, 2])
def test_decode_kernel_is_the_step_on_the_pool(layer):
    """The Pallas kernel (interpreted here) against the step on the
    layer's slab: the same outputs and states, an idle row (alpha 1, beta
    0) bit for bit as it was, every other layer untouched."""
    n_layers, n, h, dk, dv = 3, 5, 4, 8, 64
    ks = jax.random.split(jax.random.key(0), 6)
    pool = jax.random.normal(ks[0], (n_layers, n, dk, h * dv))
    q, k = (jax.random.normal(kk, (n, h, dk)) for kk in ks[1:3])
    v = jax.random.normal(ks[3], (n, h, dv))
    alpha = jax.random.uniform(ks[4], (n, h), minval=0.5).at[2].set(1.0)
    beta = jax.random.uniform(ks[5], (n, h), maxval=2.0).at[2].set(0.0)
    assert gd.decode_kernel_takes(h, dv) and not gd.decode_kernel_takes(3, dv)
    o1, p1 = gd.gated_delta_decode(pool, jnp.int32(layer), q, k, v, alpha,
                                   beta, impl="xla")
    o2, p2 = gd.gated_delta_decode(pool, jnp.int32(layer), q, k, v, alpha,
                                   beta, impl="pallas")
    np.testing.assert_allclose(o1, o2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(p1, p2, atol=1e-5, rtol=1e-5)
    for p in (p1, p2):
        np.testing.assert_array_equal(p[layer, 2], pool[layer, 2])
        others = [i for i in range(n_layers) if i != layer]
        np.testing.assert_array_equal(np.asarray(p)[others],
                                      np.asarray(pool)[others])
    # the pool's layout and back
    s = jax.random.normal(ks[0], (2, h, dk, dv))
    np.testing.assert_array_equal(gd.unpool_state(gd.pool_state(s), h), s)


# -- (2) the convolution across chunk borders ---------------------------------------

def test_causal_conv_across_chunk_borders_is_one_pass():
    t, ch, k = 50, 12, 4
    u = jax.random.normal(jax.random.key(0), (2, t, ch))
    w = jax.random.normal(jax.random.key(1), (k, ch))
    zeros = jnp.zeros((2, k - 1, ch))
    whole, tail_end = gd.causal_conv(u, w, zeros, jnp.full((2,), t))
    np.testing.assert_array_equal(tail_end, u[:, -(k - 1):])
    # the plain definition, token by token
    x = np.concatenate([np.zeros((2, k - 1, ch), np.float32), np.asarray(u)],
                       axis=1)
    want = sum(x[:, i:i + t] * np.asarray(w)[i] for i in range(k))
    np.testing.assert_allclose(whole, jax.nn.silu(want), atol=1e-6)
    # in chunks of 16, the last one padded to 16 with only 2 valid
    tail, parts = zeros, []
    for lo in range(0, t, 16):
        n = min(16, t - lo)
        chunk = jnp.zeros((2, 16, ch)).at[:, :n].set(u[:, lo:lo + n])
        c, tail = gd.causal_conv(chunk, w, tail, jnp.full((2,), n))
        parts.append(c[:, :n])
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(tail, tail_end)
    # a row with nothing valid keeps its tail
    _, kept = gd.causal_conv(u[:, :1], w, tail, jnp.asarray([0, 1]))
    np.testing.assert_array_equal(kept[0], tail[0])
    np.testing.assert_array_equal(kept[1, -1], u[1, 0])


# -- (3) engine = forward = reference ------------------------------------------------

def test_forward_is_the_reference(model):
    cfg, params = model
    toks = jax.random.randint(jax.random.key(3), (2, 150), 0, VOCAB)
    got = np.asarray(tfm.apply(params, toks, cfg))
    for b in range(2):
        want = reference_logits(params, cfg, toks[b])
        assert np.abs(got[b] - want).max() < LOGIT_TOL
    # the tolerance's reason: one ulp on the embedding moves the reference
    # by a twentieth of it or more; and what it still tells apart
    nudged = dict(params, embed=params["embed"] * (1 + 1.2e-7))
    want0 = reference_logits(params, cfg, toks[0])
    assert np.abs(reference_logits(nudged, cfg, toks[0]) - want0
                  ).max() > LOGIT_TOL / 200
    assert np.abs(got[0] - reference_logits(params, cfg, toks[0],
                                            neg_eigval=False)).max() > 0.1


def test_engine_logits_are_the_references(model):
    """Prefill in chunks of 16 (the last one padded) and then decode
    through the state cache, several requests of different lengths
    sharing the batch: every served token is the reference's choice to
    within the tolerance, and a reference with beta in (0, 1) disowns
    them."""
    cfg, params = model
    eng = Engine(params, cfg, serve_config())
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, VOCAB, size=n), m)
            for n, m in [(37, 9), (16, 5), (70, 12), (5, 20), (33, 3),
                         (64, 6)]]
    eng.run()
    for r in reqs:
        assert len(r.generated) == r.max_new_tokens
        assert served_gap(params, cfg, r) < LOGIT_TOL
    assert max(served_gap(params, cfg, r, neg_eigval=False)
               for r in reqs) > 0.1
    assert eng.moe_counters() == {}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_steps_logits_match_the_full_forward(model, impl):
    """The two steps themselves, logits and not tokens: a prompt of 40 in
    chunks of 16 and five decode rounds in slot 2 of 4, beside the full
    forward over the same tokens (the kernels, interpreted, are slow: 12
    and one)."""
    from distributed_model_parallel_tpu.serve import model as smodel

    cfg, params = model
    serve = serve_config(attn_impl=impl)
    lay = paged_kv.CacheLayout.of(cfg, page_size=PAGE, max_seq_len=MAX_SEQ,
                                  span=CHUNK)
    cache = paged_kv.PagedKVCache(cfg, n_pages=64, page_size=PAGE,
                                  max_seq_len=MAX_SEQ, layout=lay, n_seqs=4)
    cache.open("s")
    cache.ensure("s", 48)
    n_prompt, n_all = (40, 45) if impl == "xla" else (12, 13)
    toks = np.asarray(jax.random.randint(jax.random.key(9), (n_all,), 0,
                                         VOCAB))
    want = np.asarray(tfm.apply(params, jnp.asarray(toks)[None], cfg))[0]
    kw = dict(page_size=PAGE, impl=impl, layout=lay)
    prefill = smodel.make_prefill_step(cfg, chunk=CHUNK, **kw)
    pools, slot = cache.pools, 2
    table = jnp.asarray(cache.table_array("s"))
    for lo in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - lo)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :n] = toks[lo:lo + n]
        pools, _, tok = prefill(params, pools, None, jnp.asarray(chunk),
                                jnp.int32(lo), jnp.int32(n),
                                (table, None, jnp.int32(slot)),
                                jax.random.key(0))
    assert int(tok[0]) == int(np.argmax(want[n_prompt - 1]))
    tables = (jnp.zeros((4, cache.pages_per_seq), jnp.int32).at[slot].set(
        table), None)
    active = jnp.zeros((4,), bool).at[slot].set(True)
    for pos in range(n_prompt, n_all):
        before = pools
        pools, _, lg = smodel.decode_logits(
            params, pools, None, jnp.zeros((4,), jnp.int32).at[slot].set(
                int(toks[pos])), jnp.zeros((4,), jnp.int32).at[slot].set(pos),
            tables, active, cfg, **kw)
        assert np.abs(np.asarray(lg[slot]) - want[pos]).max() < LOGIT_TOL
        # (6) idle rows change no state
        for was, now in zip(before[4:], pools[4:]):
            idle = [i for i in range(4) if i != slot]
            np.testing.assert_array_equal(np.asarray(was)[:, idle],
                                          np.asarray(now)[:, idle])
            assert not np.array_equal(np.asarray(was)[:, slot],
                                      np.asarray(now)[:, slot])


# -- (4), (5), (6) whose state is whose -----------------------------------------------

def test_a_request_decodes_the_same_alone_and_joining_mid_batch(model):
    cfg, params = model
    prompt = np.random.default_rng(1).integers(0, VOCAB, size=29)
    solo = Engine(params, cfg, serve_config())
    alone = solo.submit(prompt, 14)
    solo.run()
    eng = Engine(params, cfg, serve_config())
    rng = np.random.default_rng(2)
    others = [eng.submit(rng.integers(0, VOCAB, size=n), m)
              for n, m in [(50, 30), (9, 25), (21, 28)]]
    for _ in range(6):                     # the others are under way
        eng.step_once(0.0, 0.0)
    late = eng.submit(prompt, 14)
    eng.run()
    assert late.generated == alone.generated
    assert all(len(r.generated) == r.max_new_tokens for r in others)


def test_a_reused_slot_starts_from_zeros(model):
    """One slot, two requests one after the other: the second gets what
    a fresh engine gives, though nothing cleared the slot (the pools still
    hold the first one's state when the second is admitted)."""
    cfg, params = model
    rng = np.random.default_rng(3)
    first, second = rng.integers(0, VOCAB, size=40), rng.integers(
        0, VOCAB, size=23)
    eng = Engine(params, cfg, serve_config(n_slots=1))
    a = eng.submit(first, 8)
    eng.run()
    held = np.asarray(eng.cache.state)
    assert np.abs(held).max() > 0          # not cleared on release
    b = eng.submit(second, 10)
    eng.run()
    fresh = Engine(params, cfg, serve_config(n_slots=1))
    c = fresh.submit(second, 10)
    fresh.run()
    assert b.generated == c.generated and len(a.generated) == 8
    assert served_gap(params, cfg, b) < LOGIT_TOL


def test_padding_and_idle_rows_change_no_state(model):
    """A last chunk of 3 valid tokens and 13 of padding leaves the state
    and tail the 3 tokens give (= a chunk step told the same 3 tokens
    with other padding), and the warm-up's inert calls change nothing."""
    cfg, params = model
    eng = Engine(params, cfg, serve_config())
    eng.warmup()
    for pool in (eng.cache.state, eng.cache.tail):
        assert not np.asarray(pool).any()
    prompt = np.random.default_rng(4).integers(0, VOCAB, size=19)
    req = eng.submit(prompt, 4)
    eng.step_once(0.0, 0.0)                # chunk 1: 16 tokens
    eng.step_once(0.0, 0.0)                # chunk 2: 3 tokens and padding
    state, tail = np.asarray(eng.cache.state), np.asarray(eng.cache.tail)
    others = [i for i in range(4) if i != req.slot]
    assert not state[:, others].any() and not tail[:, others].any()
    # the tail is the last three inputs of the prompt, not of the padding:
    # a run whose prompt is the same 19 tokens in chunks of 19 + padding
    wide = Engine(params, cfg, serve_config(prefill_chunk=32))
    same = wide.submit(prompt, 4)
    wide.step_once(0.0, 0.0)
    # (to rounding through eight layers; a tail that had taken padding in
    # would be off by its own size, order 1)
    np.testing.assert_allclose(np.asarray(wide.cache.tail)[:, same.slot],
                               tail[:, req.slot], atol=5e-4)
    np.testing.assert_allclose(np.asarray(wide.cache.state)[:, same.slot],
                               state[:, req.slot], atol=5e-4)
    assert np.abs(tail[:, req.slot]).max() > 1


# -- (7) admission ----------------------------------------------------------------------

def test_admission_counts_full_layer_pages_and_a_slot(model):
    cfg, params = model
    lay = paged_kv.CacheLayout.of(cfg, page_size=PAGE, max_seq_len=MAX_SEQ,
                                  span=CHUNK)
    assert (lay.n_full, lay.n_ring, lay.n_state, lay.ring_pages) == (
        2, 0, 6, 0)
    assert lay.bodies == ((None, 0, 3), (None, 1, 3), (None, 2, 3),
                          (False, 0, 1))
    eng = Engine(params, cfg, serve_config(n_pages=40))
    assert eng.cache.ck.shape == (2, 40, PAGE, 4, 16)     # full layers only
    assert eng.cache.state.shape == (6, 4, 8, 4 * 64)
    assert eng.cache.state.dtype == jnp.float32
    assert eng.cache.tail.shape == (6, 4, 3, 2 * 2 * 8 + 4 * 64)
    per_slot = 6 * (8 * 256 * 4 + 3 * 288 * 4)
    assert eng.cache.state_bytes_per_slot == per_slot
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(0, VOCAB, size=30), 10) for _ in range(6)]
    eng.step_once(0.0, 0.0)
    # 40 tokens a request = 10 pages of the one shared pool: the pool
    # admits four whatever the six state layers hold
    assert sum(r.slot is not None for r in reqs) == 4
    g = memory_gauges(eng.cache)
    assert (g["used_pages"], g["full_layer_pages"]) == (40, 80)
    assert (g["state_slots"], g["state_bytes"]) == (4, 4 * per_slot)
    eng.run()
    g = memory_gauges(eng.cache)
    assert (g["state_slots"], g["state_bytes"], g["used_pages"]) == (0, 0, 0)
    assert all(len(r.generated) == 10 for r in reqs)
    status = eng._status()
    assert status["layers_by_cache_kind"] == {"full": 2, "ring": 0,
                                              "state": 6}
    assert "spec_k" in status["refused_for_state_layers"]


def test_a_model_without_state_layers_has_no_state_pools():
    cfg = tfm.TransformerConfig(n_layers=2, max_seq_len=MAX_SEQ)
    eng = Engine(tfm.init_params(jax.random.key(0), cfg), cfg, serve_config())
    assert eng.cache.pools[4:] == (None, None)
    g = memory_gauges(eng.cache)
    assert (g["state_slots"], g["state_bytes"]) == (0, 0)
    assert eng._status()["refused_for_state_layers"] == []
    assert len(eng._slot_tables(0)) == 2          # the steps' old pytree


# -- (8) what a state is refused ----------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(spec_k=2), "rolls back"),
], ids=["prefix_cache", "spec_k"])
def test_prefix_cache_and_speculation_are_refused(model, kw, what):
    cfg, params = model
    with pytest.raises(paged_kv.CacheKindError, match=what):
        Engine(params, cfg, serve_config(**kw))


def test_export_is_refused_and_drain_replays(model):
    cfg, params = model
    src = Engine(params, cfg, serve_config())
    rng = np.random.default_rng(6)
    reqs = [src.submit(rng.integers(0, VOCAB, size=n), 12)
            for n in (20, 35, 7)]
    for _ in range(5):
        src.step_once(0.0, 0.0)
    assert any(r.generated for r in reqs)
    with pytest.raises(paged_kv.CacheKindError, match="by value"):
        src.cache.export_request(reqs[0].rid, 4)
    with pytest.raises(paged_kv.CacheKindError, match="by value"):
        src.cache.import_request("x", None, None, 8)
    moved = src.drain()
    assert all(r.resume is None and r.prefill_cursor == 0 for r in moved)
    assert memory_gauges(src.cache)["state_slots"] == 0
    dst = Engine(params, cfg, serve_config())
    for r in moved:
        dst.enqueue(r, force=True)
    dst.run()                    # the replay asserts the re-sampled token
    solo = Engine(params, cfg, serve_config())
    again = [solo.submit(r.prompt, 12) for r in reqs]
    solo.run()
    assert [r.generated for r in reqs] == [r.generated for r in again]


# -- (9) the plan ---------------------------------------------------------------------------

@pytest.mark.parametrize("kinds,plan", [
    ((L, L, L, F) * 4, (0, 4, 4)),
    ((L, L, L, F), (0, 4, 1)),
    ((L,) * 3, (0, 1, 3)),
], ids=["one-stage", "one-period", "linear-only"])
def test_layer_plan_of_the_hybrid_stack(kinds, plan):
    cfg = config(kinds)
    assert cfg.layer_plan == plan
    params = tfm.init_params(jax.random.key(0), cfg)
    blocks = params["blocks"]
    first = blocks[0] if isinstance(blocks, tuple) else blocks
    assert "lin_wqkv" in first and "wq" not in first
    assert first["lin_A_log"].dtype == jnp.float32
    if isinstance(blocks, tuple):
        assert "wq" in blocks[3] and blocks[3]["q_norm"].shape[1:] == (4, 16)


def test_drawn_decays_lie_where_the_rule_forgets_slowly():
    """``A`` uniform in (0, 16), ``dt`` log-uniform in (0.001, 0.1): at a
    zero input the decay of most heads lies in 0.9-0.999."""
    cfg = config((L,) * 4, lin_value_heads=64, lin_key_heads=64)
    bp = tfm.init_params(jax.random.key(0), cfg)["blocks"]
    alpha = np.exp(-np.exp(np.asarray(bp["lin_A_log"]))
                   * np.asarray(jax.nn.softplus(bp["lin_dt_bias"])))
    assert 0.0 < alpha.min() and alpha.max() < 1.0
    assert np.mean((alpha > 0.9) & (alpha < 0.9999)) > 0.5


def test_config_refuses_linear_layers_without_widths():
    with pytest.raises(ValueError, match="lin_key_heads"):
        config(lin_key_heads=0)
    with pytest.raises(ValueError, match="norm_placement"):
        config(norm_placement="sideways")
    with pytest.raises(NotImplementedError, match="default block"):
        tfm.generate(random_params(config()), config(),
                     jnp.zeros((1, 4), jnp.int32), 2)


def test_stored_kv_heads_pads_above_sixteen(model):
    """30 KV heads lie on the chip as 32; the pool stores 32 and the
    block pads what it writes and asks: the same logits as unpadded."""
    assert [paged_kv.stored_kv_heads(n) for n in (1, 2, 8, 16, 30, 32, 40)
            ] == [1, 2, 8, 16, 32, 32, 48]
    cfg = config((F,), n_heads=18, n_kv_heads=18, d_head=8, d_model=36)
    params = random_params(cfg)
    eng = Engine(params, cfg, serve_config())
    assert eng.cache.ck.shape[3] == 32
    req = eng.submit(np.random.default_rng(7).integers(0, VOCAB, size=21), 6)
    eng.run()
    assert served_gap(params, cfg, req) < LOGIT_TOL
