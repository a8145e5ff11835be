"""The engine's layout of the attention in-projections.

``serve/model.in_proj_d_last`` turns ``wq [L, d, H, Dh]``, ``wkv`` and
``wqkv`` of layers stacked under a scan into ``*_t [L, H, X, d]`` once,
when an ``Engine`` is built, and ``transformer._qkv_proj`` reads
whichever leaf a layer holds. Here, on the CPU: the product is the same
product, the conversion touches nothing
else and is idempotent, an engine built from another engine's tree
serves the same tokens, and nothing that is not an engine changes (a
training gradient's jaxpr is the one of the plain einsum). What the
layout is *for* (no layer's weights cut out of the stack and transposed
in the compiled steps) is read off the chip's compiler in
tests/test_chip_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.serve import Engine, ServeConfig
from distributed_model_parallel_tpu.serve import model as smodel
from distributed_model_parallel_tpu.serve.scheduler import RequestState

pytestmark = pytest.mark.serve

KW = dict(vocab_size=64, d_ff=64, max_seq_len=128, pos_embedding="rope")
LIN, FULL = tfm.LayerKind(mixer="gated_delta"), tfm.LayerKind()
SLIDING = tfm.LayerKind(window=16, rope=True, ffn="dense")

PROJECTIONS = {
    # StarCoder2's grouping (24 query heads over 2) at toy widths
    "grouped-24-over-2": dict(d_model=48, n_heads=24, n_kv_heads=2,
                              d_head=8, n_layers=2),
    "no-grouping": dict(d_model=32, n_heads=4, n_kv_heads=4, n_layers=2),
    "qk-norm-whole": dict(d_model=32, n_heads=4, n_kv_heads=4, n_layers=2,
                          norm="rmsnorm", qk_norm_whole=True),
    "fused-wqkv": dict(d_model=32, n_heads=4, n_layers=2),
}

TREES = {
    "equal-layers": dict(d_model=32, n_heads=4, n_kv_heads=2, n_layers=3),
    "fused-wqkv": dict(d_model=32, n_heads=4, n_layers=3),
    # one leading layer and a period of one: (1, 1, 3)
    "lead-and-period": dict(d_model=32, n_heads=4, n_kv_heads=2, n_layers=4,
                            layer_kinds=(SLIDING, FULL, FULL, FULL)),
    # one period that does not repeat, every index static: (0, 3, 1)
    "static-indices": dict(d_model=32, n_heads=4, n_kv_heads=2, n_layers=3,
                           layer_kinds=(SLIDING, FULL, SLIDING)),
    # a tuple of groups, three of them without attention leaves
    "hybrid-period": dict(
        d_model=32, n_heads=4, n_kv_heads=4, n_layers=8, norm="rmsnorm",
        ffn="swiglu", qk_norm_whole=True, norm_placement="post",
        layer_kinds=(LIN, LIN, LIN, FULL) * 2, lin_key_heads=2,
        lin_value_heads=4, lin_key_dim=8, lin_value_dim=16),
}


def _one_layer(blocks: dict) -> dict:
    return jax.tree.map(lambda a: a[0], blocks)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(PROJECTIONS))
def test_qkv_proj_of_converted_leaves_is_qkv_proj_of_stored(name, dtype):
    """Same operands, same contraction, same accumulation type: float32
    agrees to the last bit, bfloat16 to one rounding of the result (the
    CPU's two operand orders may sum in another order before it)."""
    cfg = tfm.TransformerConfig(dtype=dtype, **KW, **PROJECTIONS[name])
    params = tfm.init_params(jax.random.key(1), cfg)
    if cfg.qk_norm_whole:        # norm scales that are not all one
        params["blocks"].update({
            k: 1 + 0.1 * jax.random.normal(
                jax.random.key(i), params["blocks"][k].shape, dtype)
            for i, k in enumerate(("q_norm", "k_norm"))})
    held = smodel.in_proj_d_last(params)
    want = {"wqkv_t"} if name == "fused-wqkv" else {"wq_t", "wkv_t"}
    assert {k for k in held["blocks"] if k.startswith("wq")
            or k.startswith("wk")} == want
    h = jax.random.normal(jax.random.key(2), (3, 5, cfg.d_model), dtype)
    stored = tfm._qkv_proj(_one_layer(params["blocks"]), h, cfg)
    got = tfm._qkv_proj(_one_layer(held["blocks"]), h, cfg)
    for a, b in zip(stored, got):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        if dtype == jnp.float32:
            np.testing.assert_array_equal(a, b)
        else:
            # one bfloat16 step at the value's size (8 bits of mantissa)
            assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.maximum(
                np.abs(a), np.abs(b)))


@pytest.mark.parametrize("name", list(TREES))
def test_conversion_moves_the_stacked_in_projections_and_nothing_else(name):
    """Converted: the in-projections of a group stacked over more than one
    layer (its layers are cut out of the stack under the scan). Left as
    stored: those of a layer whose index is static (a leading layer, a
    period that does not repeat), and every other leaf."""
    cfg = tfm.TransformerConfig(**KW, **TREES[name])
    params = tfm.init_params(jax.random.key(0), cfg)
    before = jax.tree_util.tree_flatten_with_path(params)[0]
    held = smodel.in_proj_d_last(params)
    # the caller's tree is as it was, leaf for leaf
    after = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [(p, id(a)) for p, a in before] == [(p, id(a)) for p, a in after]
    mine = dict(jax.tree_util.tree_flatten_with_path(held)[0])
    n = 0
    for path, leaf in before:
        key = path[-1].key
        if (key in smodel.IN_PROJECTIONS and leaf.ndim == 4
                and leaf.shape[0] > 1):
            moved = mine[path[:-1] + (jax.tree_util.DictKey(key + "_t"),)]
            assert path not in mine
            np.testing.assert_array_equal(np.moveaxis(leaf, 1, -1), moved)
            n += 1
        else:
            assert mine[path] is leaf            # passed through, no copy
    relaid = smodel.in_proj_relaid(held)
    assert n == len(relaid) == len(mine) - (len(before) - n)
    assert smodel.in_proj_relaid(params) == []
    if name == "static-indices":
        assert cfg.layer_plan == (0, 3, 1)
        assert n == 0 and held is params
    else:
        assert n > 0
        stacked = {"lead-and-period": (1, 1, 3), "hybrid-period": (0, 4, 2)}
        assert cfg.layer_plan == stacked.get(name, (0, 1, 3))
    # idempotent: nothing left to convert, the very tree comes back
    assert smodel.in_proj_d_last(held) is held


def test_one_program_converts_every_leaf_of_a_tree():
    """Set-up gains one small program an engine, not one a leaf shape."""
    cfg = tfm.TransformerConfig(**KW, **TREES["lead-and-period"])
    params = tfm.init_params(jax.random.key(0), cfg)
    smodel._d_last.clear_cache()
    smodel.in_proj_d_last(params)
    assert smodel._d_last._cache_size() == 1
    smodel.in_proj_d_last(tfm.init_params(jax.random.key(1), cfg))
    assert smodel._d_last._cache_size() == 1


def _serve(**kw):
    base = dict(n_slots=2, page_size=8, n_pages=32, max_seq_len=64,
                prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16]]
GENS = [12, 18, 7]


@pytest.mark.parametrize("name", ["equal-layers", "lead-and-period",
                                  "static-indices", "hybrid-period"])
def test_engine_built_from_an_engines_params_serves_the_same_tokens(name):
    """``serve/fleet.py`` builds a fresh engine from a dead one's
    ``params``: the converted tree goes in and comes out as it is."""
    cfg = tfm.TransformerConfig(**KW, **TREES[name])
    params = tfm.init_params(jax.random.key(0), cfg)

    def served(tree):
        eng = Engine(tree, cfg, _serve(), slo_metrics=False)
        reqs = [eng.submit(p, g) for p, g in zip(PROMPTS, GENS)]
        eng.run()
        assert all(r.state is RequestState.COMPLETED for r in reqs)
        return eng, [r.generated for r in reqs]

    first, tokens = served(params)
    assert "wq" in jax.tree.leaves(
        params["blocks"], is_leaf=lambda x: isinstance(x, dict))[-1]
    second, again = served(first.params)
    assert second.params is first.params
    assert again == tokens
    if cfg.homogeneous:
        for p, g, got in zip(PROMPTS, GENS, tokens):
            out = tfm.generate(params, cfg, jnp.asarray([p], jnp.int32), g)
            assert got == [int(t) for t in out[0][len(p):]]


def test_status_counts_the_leaves_the_engine_holds_d_last():
    cfg = tfm.TransformerConfig(**KW, **TREES["lead-and-period"])
    params = tfm.init_params(jax.random.key(0), cfg)
    status = Engine(params, cfg, _serve(), slo_metrics=False)._status()
    # wq and wkv of the group stacked over three layers; the leading
    # layer's stay as stored
    assert cfg.layer_plan == (1, 1, 3)
    assert status["weights_relaid"] == 2
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    assert status["weights_relaid_bytes"] == 3 * d * (h + 2 * hkv) * dh * 4


@pytest.mark.parametrize("name", ["equal-layers", "fused-wqkv"])
def test_a_training_gradient_is_the_plain_einsums(name, monkeypatch):
    """Nothing but an engine makes ``*_t`` leaves, so the training step
    takes today's branch: its jaxpr is, equation for equation, the one of
    ``_qkv_proj`` as the parent commit had it (one einsum on the stored
    leaf, no lookup)."""
    cfg = tfm.TransformerConfig(**KW, **TREES[name])
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((2, 16), jnp.int32)

    def gradient():
        return str(jax.make_jaxpr(jax.grad(
            lambda p: tfm.lm_loss(p, toks, toks, cfg)))(shapes))

    ours = gradient()
    monkeypatch.setattr(
        tfm, "_in_proj",
        lambda bp, name, h: jnp.einsum("btd,dhx->bthx", h, bp[name]))
    assert gradient() == ours
