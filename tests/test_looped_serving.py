"""The looped stack (layers run several times over shared weights) through
the serving path, against the benchmark's plain reference
(chipbench/model_types/ouro.py) and against ``layers_forward``, at a toy
size that keeps every part: 3 layers run 3 times (9 cache layers for 3 of
weights), sandwich norms (four RMSNorms a layer), the final norm closing
every pass, the exit gate; hidden 64, 2 heads of 32, no grouping; pages of
4, chunks of 16, float32."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import Engine, ServeConfig
from distributed_model_parallel_tpu.serve import model as smodel
from distributed_model_parallel_tpu.serve import paged_kv

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from model_types import ouro  # noqa: E402

LAYERS, PASSES, CHUNK, PAGE, MAX_SEQ = 3, 3, 16, 4, 64


def file_config(**kw):
    """A configuration file of the model type, toy sizes."""
    return dict({
        "model_type": "ouro", "torch_dtype": "float32", "hidden_size": 64,
        "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 128, "num_hidden_layers": LAYERS,
        "vocab_size": 96, "max_position_embeddings": MAX_SEQ,
        "rms_norm_eps": 1e-6, "hidden_act": "silu", "rope_theta": 1e6,
        "rope_scaling": None, "layer_types": ["full_attention"] * LAYERS,
        "sliding_window": None, "use_sliding_window": False,
        "tie_word_embeddings": False, "total_ut_steps": PASSES,
        "early_exit_threshold": 1}, **kw)


def model(seed=0, **kw):
    """(config, dims, params): the program's configuration through the
    model type's own mapping, weights from the benchmark's seed."""
    dims = ouro.Dims.from_config(file_config(**kw))
    cfg, dtype = ouro.transformer_config(file_config(**kw), dims)
    return cfg, dims, ouro.make_params(seed, dims, dtype)


def serve_config(**kw):
    base = dict(n_slots=4, page_size=PAGE, n_pages=64, max_seq_len=MAX_SEQ,
                prefill_chunk=CHUNK, attn_impl="xla")
    base.update(kw)
    return ServeConfig(**base)


def reference_logits(params, dims, tokens, quant=None):
    toks = np.zeros(MAX_SEQ, np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(ouro.sequence_logits(
            params, jnp.asarray(toks), jnp.arange(len(tokens)), dims=dims,
            quant=quant, q_block=MAX_SEQ))


def prompts(cfg, lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def run_requests(params, cfg, serve, prompts, max_new):
    eng = Engine(params, cfg, serve, slo_metrics=False)
    reqs = [eng.submit(p, n, rid=f"r{i}")
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    with jax.default_matmul_precision("highest"):
        eng.run()
    return eng, [list(r.generated) for r in reqs]


# -- the tree and the cache ----------------------------------------------------

def test_the_tree_holds_L_layers_and_the_pools_T_times_L():
    cfg, dims, params = model()
    assert (cfg.n_layers, cfg.n_passes, cfg.looped) == (LAYERS, PASSES, True)
    want = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(params)))
    blocks = params["blocks"]
    assert all(leaf.shape[0] == LAYERS for leaf in jax.tree.leaves(blocks))
    # four RMSNorms a layer, the gate's pair at the top
    assert {"ln1_scale", "ln1_out_scale", "ln2_scale",
            "ln2_out_scale"} <= set(blocks)
    assert params["gate_w"].shape == (64,) and params["gate_b"].shape == ()
    assert dims.n_params() == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    lay = paged_kv.CacheLayout.of(cfg, page_size=PAGE, max_seq_len=MAX_SEQ,
                                  span=CHUNK)
    assert lay == paged_kv.CacheLayout.all_full(cfg)
    assert (lay.n_full, lay.passes, lay.bodies) == (9, 3, ((False, 0, 1),))
    # pass t of layer l: cache layer t * L + l
    assert [lay.cache_layer(0, rep, t)[1]
            for t in range(PASSES) for rep in range(LAYERS)] == list(range(9))
    eng = Engine(params, cfg, serve_config(), slo_metrics=False)
    assert eng.cache.ck.shape == eng.cache.cv.shape == (9, 64, PAGE, 2, 32)
    gauges = paged_kv.memory_gauges(eng.cache)
    assert gauges["cache_layers"] == 9 == dims.cache_layers
    assert gauges["kv_bytes_per_token"] == 9 * 2 * 2 * 32 * 4   # float32
    status = eng._status()
    assert (status["passes"], status["cache_layers"]) == (3, 9)
    assert status["layers_by_cache_kind"]["full"] == 9


# -- against the reference and the full forward -------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [(21, 14), (32, 9), (5, 20)])
def test_engine_agrees_with_the_reference_and_the_full_forward(
        n_prompt, n_new):
    """Prefill in chunks of 16, then decode through the paged cache:
    every served token is the reference's first choice, and the logits of
    ``layers_forward`` (no cache) are the reference's. Float32 both
    sides, different order of sums, nine sandwich-normed layer passes:
    1e-4 (a bfloat16 or int8 forward lies 1e-2 and more away)."""
    cfg, dims, params = model()
    prompt = prompts(cfg, [n_prompt], seed=n_prompt)[0]
    _, [gen] = run_requests(params, cfg, serve_config(), [prompt], [n_new])
    seq = np.asarray(prompt + gen[:-1], np.int32)
    want = reference_logits(params, dims, seq)
    rows = want[n_prompt - 1:]
    assert gen == rows.argmax(-1).tolist()
    with jax.default_matmul_precision("highest"):
        full = np.asarray(tfm.apply(params, jnp.asarray(seq)[None], cfg)[0])
    np.testing.assert_allclose(full, want, atol=1e-4, rtol=1e-4)


def test_decode_logits_through_the_cache_are_the_references():
    """The paged steps' own logits (not only their argmax) against the
    reference: chunked prefill, then every decode position."""
    cfg, dims, params = model(seed=3)
    tokens = np.asarray(prompts(cfg, [40], seed=2)[0], np.int32)
    n_prompt, serve = 21, serve_config()
    lay = paged_kv.CacheLayout.of(cfg, page_size=PAGE, max_seq_len=MAX_SEQ,
                                  span=CHUNK)
    cache = paged_kv.PagedKVCache(cfg, n_pages=64, page_size=PAGE,
                                  max_seq_len=MAX_SEQ, layout=lay)
    cache.try_admit("other", [0] * 9, 9)
    assert cache.try_admit("s", tokens[:n_prompt].tolist(), len(tokens)) == 0
    kw = dict(page_size=PAGE, impl="xla", layout=lay)
    prefill = smodel.make_prefill_step(cfg, chunk=CHUNK, **kw)
    tables = (jnp.asarray(cache.table_array("s")), None)
    pools, stats = cache.pools, smodel.init_stats(cfg)
    decode = jax.jit(lambda p, pools, st, tok, pos: smodel.decode_logits(
        p, pools, st, tok, pos, (tables[0][None], None),
        jnp.ones((1,), bool), cfg, **kw))
    got = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n_prompt, CHUNK):
            n_valid = min(CHUNK, n_prompt - lo)
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :n_valid] = tokens[lo:lo + n_valid]
            pools, stats, _ = prefill(
                params, pools, stats, jnp.asarray(toks), jnp.int32(lo),
                jnp.int32(n_valid), tables, jax.random.key(0))
        for pos in range(n_prompt, len(tokens)):
            pools, stats, lg = decode(params, pools, stats,
                                      jnp.asarray(tokens[pos:pos + 1]),
                                      jnp.asarray([pos], jnp.int32))
            got.append(np.asarray(lg[0]))
    want = reference_logits(params, dims, tokens)[n_prompt:]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4, rtol=1e-4)
    loop = jax.device_get(stats["loop"])
    assert int(loop["tokens"]) == len(tokens)
    assert int(loop["token_passes"]) == PASSES * len(tokens)
    # the gate's mass a pass: the reference's p_exit summed over the tokens
    toks = np.zeros(MAX_SEQ, np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        p_exit = np.asarray(ouro.exit_probabilities(
            params, jnp.asarray(toks), dims=dims))[:, :len(tokens)]
    np.testing.assert_allclose(loop["exit_mass"], p_exit.sum(axis=1),
                               rtol=1e-4)


@pytest.mark.parametrize("quant", ["int8", "int8_fwd"])
def test_a_lower_precision_forward_is_told_apart(quant):
    """The controls the benchmark's ``correct`` has to fail: the
    reference with every projection's operands rounded to int8 lies a
    hundred times further from the float32 reference than the program."""
    cfg, dims, params = model()
    tokens = np.asarray(prompts(cfg, [48], seed=7)[0], np.int32)
    want = reference_logits(params, dims, tokens)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(tfm.apply(params, jnp.asarray(tokens)[None],
                                    cfg)[0])
    control = reference_logits(params, dims, tokens, quant=quant)
    assert np.abs(control - want).mean() > 100 * np.abs(full - want).mean()


# -- shared weights, separate cache entries ------------------------------------

def test_weights_are_shared_across_passes():
    """One layer's leaf changed changes that layer in EVERY pass: the
    looped forward is the one-pass forward applied three times, with the
    same leaves (a tree with a copy a pass would leave two passes as they
    were)."""
    cfg, _, params = model()
    once = dataclasses.replace(cfg, n_passes=1)
    x = jax.random.normal(jax.random.key(1), (1, 12, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = x
        for _ in range(PASSES):
            want, _ = tfm.layers_forward(params, want, once)
        got, _ = tfm.layers_forward(params, x, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_overwriting_a_cache_entry_changes_only_what_reads_it():
    """Cache layer (t, l) is read by pass t of layer l and by nothing
    before it: garbage written over (pass 1, layer 1) of the context
    leaves the decode step's stream through pass 0 and through layer 0 of
    pass 1 as it was, and changes the logits; the same garbage in an
    entry of ANOTHER sequence's pages changes nothing."""
    cfg, _, params = model()
    tokens = np.asarray(prompts(cfg, [24], seed=4)[0], np.int32)
    lay = paged_kv.CacheLayout.all_full(cfg)
    cache = paged_kv.PagedKVCache(cfg, n_pages=64, page_size=PAGE,
                                  max_seq_len=MAX_SEQ, layout=lay)
    cache.try_admit("s", tokens[:-1].tolist(), len(tokens))
    cache.try_admit("other", [0] * 8, 8)
    kw = dict(page_size=PAGE, impl="xla", layout=lay)
    prefill = smodel.make_prefill_step(cfg, chunk=CHUNK, **kw)
    tables = (jnp.asarray(cache.table_array("s")), None)
    pools = cache.pools
    with jax.default_matmul_precision("highest"):
        for lo in range(0, 23, CHUNK):
            n_valid = min(CHUNK, 23 - lo)
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :n_valid] = tokens[lo:lo + n_valid]
            pools, _, _ = prefill(params, pools, None, jnp.asarray(toks),
                                  jnp.int32(lo), jnp.int32(n_valid), tables,
                                  jax.random.key(0))

    def decode(pools):
        # the step donates nothing here: decode_logits is not jitted
        with jax.default_matmul_precision("highest"):
            return np.asarray(smodel.decode_logits(
                params, pools, None, jnp.asarray(tokens[23:]),
                jnp.asarray([23], jnp.int32), (tables[0][None], None),
                jnp.ones((1,), bool), cfg, **kw)[2][0])

    def spoiled(layer, pages):
        ck = pools[0].at[layer, jnp.asarray(pages)].set(7.0)
        return (ck,) + pools[1:]

    mine = cache.table_array("s")[:6].tolist()
    theirs = cache.table_array("other")[:2].tolist()
    plain = decode(pools)
    entry = lay.cache_layer(0, 1, 1)[1]                 # (pass 1, layer 1)
    assert entry == 4
    assert np.abs(decode(spoiled(entry, mine)) - plain).max() > 1e-3
    np.testing.assert_array_equal(decode(spoiled(entry, theirs)), plain)
    # what pass 1 wrote is not what pass 0 wrote: the entries differ
    assert np.abs(np.asarray(pools[0][4, mine[0]])
                  - np.asarray(pools[0][1, mine[0]])).max() > 1e-2
    # a model cut after pass 0 never reads entry 4: run one pass over the
    # same pools (cache layers 0..2) and the spoiled entry does not show
    once = dataclasses.replace(cfg, n_passes=1, exit_gate=False)
    lay1 = paged_kv.CacheLayout.all_full(once)
    one_pass = lambda pl: np.asarray(smodel.decode_logits(   # noqa: E731
        params, pl, None, jnp.asarray(tokens[23:]),
        jnp.asarray([23], jnp.int32), (tables[0][None], None),
        jnp.ones((1,), bool), once, page_size=PAGE, impl="xla",
        layout=lay1)[2][0])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(one_pass(spoiled(entry, mine)),
                                      one_pass(pools))


# -- the engine ------------------------------------------------------------------

def test_a_requests_tokens_do_not_depend_on_its_batch():
    cfg, _, params = model()
    ps = prompts(cfg, [9, 30, 17, 5])
    new = [12, 8, 10, 16]
    _, alone = run_requests(params, cfg, serve_config(n_slots=1), ps, new)
    _, together = run_requests(params, cfg, serve_config(n_slots=4), ps, new)
    assert together == alone


def test_loop_counters_count_tokens_passes_and_the_gates_mass():
    cfg, _, params = model()
    ps = prompts(cfg, [21, 9])
    eng, gens = run_requests(params, cfg, serve_config(), ps, [12, 8])
    got = eng.loop_counters()
    # every prompt token, and every generated token but a request's last
    tokens = 21 + 9 + 11 + 7
    assert got["passes"] == PASSES and got["tokens"] == tokens
    assert got["token_passes"] == PASSES * tokens
    assert len(got["exit_mass"]) == PASSES
    assert sum(got["exit_mass"]) == pytest.approx(tokens, rel=1e-5)
    assert min(got["exit_mass"]) > 0          # a gate that does something
    # without a gate every token leaves after the last pass
    nogate = dataclasses.replace(cfg, exit_gate=False)
    eng, _ = run_requests(params, nogate, serve_config(), ps, [12, 8])
    assert eng.loop_counters()["exit_mass"] == [0.0, 0.0, float(tokens)]
    # a stack run once counts nothing
    plain = tfm.TransformerConfig(vocab_size=96, d_model=64, n_heads=2,
                                  n_layers=2, d_ff=128, max_seq_len=MAX_SEQ)
    eng = Engine(tfm.init_params(jax.random.key(0), plain), plain,
                 serve_config(), slo_metrics=False)
    assert eng.loop_counters() == {} and eng._status()["passes"] == 1


def test_prefix_sharing_gives_the_tokens_it_gives_switched_off():
    """A page id spans all nine cache layers, so a shared prefix's pages
    hold every pass's K/V of it."""
    cfg, _, params = model()
    head = prompts(cfg, [2 * CHUNK], seed=5)[0]
    ps = [head + tail for tail in prompts(cfg, [7, 12, 3])]
    _, cold = run_requests(params, cfg, serve_config(n_slots=1), ps,
                           [6, 6, 6])
    eng, warm = run_requests(params, cfg,
                             serve_config(n_slots=1, prefix_cache=True), ps,
                             [6, 6, 6])
    assert warm == cold
    assert eng._cached_tokens == 2 * 2 * CHUNK


def test_speculation_gives_the_tokens_it_gives_switched_off():
    cfg, _, params = model()
    motif = prompts(cfg, [6], seed=2)[0]
    ps = [motif * 5, prompts(cfg, [22])[0]]
    _, plain = run_requests(params, cfg, serve_config(), ps, [24, 16])
    eng, spec = run_requests(params, cfg, serve_config(spec_k=3), ps,
                             [24, 16])
    assert spec == plain
    assert eng._draft_proposed > 0


def test_a_drained_request_carries_every_passes_pages():
    """Migration by page: the payload is [9 cache layers, pages, ...], and
    the tokens on the second engine are those of an undisturbed run."""
    cfg, _, params = model()
    ps, new = prompts(cfg, [9, 40, 21]), [14, 10, 8]
    _, want = run_requests(params, cfg, serve_config(n_slots=2), ps, new)
    src = Engine(params, cfg, serve_config(n_slots=2), slo_metrics=False)
    reqs = [src.submit(p, n, rid=f"r{i}")
            for i, (p, n) in enumerate(zip(ps, new))]
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            src.step_once(0.0, 0.0)
        moved = src.drain()
        assert [r.resume is not None for r in moved] == [True, True, False]
        assert moved[0].resume["k"].shape[0] == LAYERS * PASSES
        dst = Engine(params, cfg, serve_config(n_slots=2), slo_metrics=False)
        for r in moved:
            dst.enqueue(r, force=True)
        dst.run()
    assert [list(r.generated) for r in reqs] == want


def test_the_engine_maps_device_ops_to_the_loops_scopes():
    cfg, _, params = model()
    eng = Engine(params, cfg, serve_config(), slo_metrics=False)
    got = eng.op_scopes(("loop_stack", "exit_gate"))
    assert sorted(got) == ["jit_decode_step", "jit_prefill_step"]
    for module in got.values():
        assert set(module.values()) == {"loop_stack", "exit_gate"}
    # the attention's ops lie inside the stack's scope: asked for both,
    # the first that matches names them (what the cell's traffic file
    # relies on: its list starts with loop_stack)
    inner = eng.op_scopes(("attn_full",))
    both = eng.op_scopes(("loop_stack", "attn_full"))
    for name, module in inner.items():
        assert set(module.values()) == {"attn_full"}
        stack = [op for op, s in both[name].items() if s == "loop_stack"]
        assert len(set(module) & set(stack)) > len(module) // 2


# -- what is refused, by name ---------------------------------------------------

def test_what_is_not_run_is_refused_by_name():
    cfg, _, params = model()
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        dataclasses.replace(cfg, early_exit_threshold=0.5)
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ouro.Dims.from_config(file_config(early_exit_threshold=0.5))
    with pytest.raises(NotImplementedError, match="looped stack"):
        tfm.generate(params, cfg, jnp.zeros((1, 4), jnp.int32), 4)
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )
    with pytest.raises(NotImplementedError, match="looped stack"):
        LMTrainer(LMTrainConfig(model=cfg, seq_len=16, batch_size=8))
    with pytest.raises(ValueError, match="n_passes"):
        dataclasses.replace(cfg, n_passes=0)


# -- a stack run once is what it was -------------------------------------------

def _plain_passes(params, x, rest, fn, cfg):
    """``run_passes`` as the parent commit walked the layers: once, with
    no norm, no gate and no outer loop."""
    import functools

    (x, rest), outs = tfm.run_layers(params, (x, rest),
                                     functools.partial(fn, 0), cfg)
    return x, rest, outs, None


def _default_configs():
    """The four kinds of block the benchmark's configurations are, toy
    sizes: the default block (served, and trained: its ``lm_loss``
    gradient), the gated routed mixed one, the gated-delta hybrid."""
    kw = dict(vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, d_head=16,
              d_ff=64, max_seq_len=MAX_SEQ, pos_embedding="rope")
    s = tfm.LayerKind(window=8, rope=True, ffn="moe")
    f = tfm.LayerKind(window=None, rope=False, ffn="moe")
    lin, full = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
    return {
        "default-block": tfm.TransformerConfig(n_layers=3, **kw),
        "routed-mixed": tfm.TransformerConfig(
            n_layers=5, norm="rmsnorm", ffn="swiglu", qk_norm=True,
            layer_kinds=(dataclasses.replace(s, ffn="dense"), s, s, f, s),
            moe_experts=8, moe_top_k=2, moe_dropless=True,
            moe_scoring="sigmoid", moe_d_ff=32, moe_shared_experts=1,
            moe_experts_held=(2, 4), **kw),
        "gated-delta-hybrid": tfm.TransformerConfig(
            n_layers=4, norm="rmsnorm", ffn="swiglu", qk_norm_whole=True,
            norm_placement="post", layer_kinds=(lin, lin, lin, full),
            lin_key_heads=2, lin_value_heads=4, lin_key_dim=8,
            lin_value_dim=16, **kw),
    }


@pytest.mark.parametrize("name", list(_default_configs()))
def test_at_the_defaults_tree_and_steps_are_the_parents(name, monkeypatch):
    """``n_passes = 1`` with the other defaults: the parameter tree has
    no leaf of the loop's, and the prefill, decode and training programs
    are, equation for equation, those of a walk that knows no passes (the
    parent commit's ``run_layers`` call, kept here as ``_plain_passes``)."""
    cfg = _default_configs()[name]
    assert not cfg.looped and cfg.n_passes == 1
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    names = {str(p[-1]) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert not any("gate_" in n or "_out_" in n for n in names)
    serve = serve_config()
    lay = paged_kv.CacheLayout.of(cfg, page_size=PAGE, max_seq_len=MAX_SEQ,
                                  span=CHUNK)
    assert lay.passes == 1
    assert lay.n_full + lay.n_ring + lay.n_state == cfg.n_layers

    def programs():
        for make in (smodel.make_prefill_step, smodel.make_decode_step):
            make.cache_clear()
        eng = Engine(shapes, cfg, serve, slo_metrics=False)
        out = [str(jax.make_jaxpr(step)(shapes, eng.cache.pools, eng._stats,
                                        *inputs))
               for step, inputs in eng._inert_calls()]
        if cfg.homogeneous and not cfg.moe_dropless:
            toks = jnp.zeros((2, 16), jnp.int32)
            out.append(str(jax.make_jaxpr(jax.grad(
                lambda p: tfm.lm_loss(p, toks, toks, cfg)))(shapes)))
        return out

    # Engine takes arrays: zeros of the tree's shapes
    shapes = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    ours = programs()
    monkeypatch.setattr(tfm, "run_passes", _plain_passes)
    monkeypatch.setattr(smodel, "run_passes", _plain_passes)
    theirs = programs()
    for make in (smodel.make_prefill_step, smodel.make_decode_step):
        make.cache_clear()
    assert len(ours) == len(theirs) >= 2
    assert ours == theirs
