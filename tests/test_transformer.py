"""Transformer + ring attention + Ulysses + TP + SPMD pipeline.

Every parallel path is checked for *numerical parity* against the plain
single-device forward — the framework's core test invariant (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.config import MeshConfig, OptimizerConfig
from distributed_model_parallel_tpu.mesh import make_mesh
from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.ops.ring_attention import (
    full_attention,
    ring_attention,
    ulysses_attention,
)
from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
    make_pipeline_apply,
    make_spmd_train_step,
    shard_params,
)
from distributed_model_parallel_tpu.train.optim import make_optimizer

CFG = tfm.TransformerConfig(vocab_size=97, d_model=32, n_heads=4, n_layers=4,
                            d_ff=64, max_seq_len=64)


@pytest.fixture(scope="module")
def toks():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 32)))


@pytest.fixture()
def params():
    # function-scoped: donated train steps may alias (zero-copy device_put)
    # and delete buffers of whatever tree they were fed
    return tfm.init_params(jax.random.key(0), CFG)


# ---------------------------------------------------------------------------
# attention parity
# ---------------------------------------------------------------------------

def _qkv(seed=0, b=2, t=32, h=4, dh=8):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, dh)) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    spec = make_mesh(MeshConfig(data=1, seq=8))
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal),
        mesh=spec.mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_full():
    spec = make_mesh(MeshConfig(data=1, seq=4))
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=True)
    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq", causal=True),
        mesh=spec.mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_impl_forcing(monkeypatch):
    """impl='flash' forces the pallas kernel inside Ulysses (the escape
    hatch for dtypes the dispatch table excludes from auto); the kernel
    must actually run, and its results must match impl='xla'."""
    from distributed_model_parallel_tpu.ops import pallas_attention as pa

    spec = make_mesh(MeshConfig(data=1, seq=4))
    q, k, v = _qkv()
    calls = []
    real_flash = pa.flash_attention
    monkeypatch.setattr(
        pa, "flash_attention",
        lambda *a, **kw: (calls.append(1), real_flash(*a, **kw))[1])

    def run(impl):
        f = jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "seq", causal=True,
                                              impl=impl),
            mesh=spec.mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
            check_vma=False)
        return np.asarray(f(q, k, v))

    xla_out = run("xla")
    assert not calls                     # "xla" never touches the kernel
    flash_out = run("flash")
    assert calls                         # "flash" really forced it
    np.testing.assert_allclose(flash_out, xla_out, rtol=2e-2, atol=2e-2)


def test_ring_attention_grads_match_full():
    spec = make_mesh(MeshConfig(data=1, seq=4))
    q, k, v = _qkv(seed=1)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
        mesh=spec.mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# transformer forward / loss
# ---------------------------------------------------------------------------

def test_forward_shapes_and_loss(params, toks):
    logits = tfm.apply(params, toks, CFG)
    assert logits.shape == (4, 32, CFG.vocab_size)
    loss = tfm.lm_loss(params, toks[:, :-1], toks[:, 1:], CFG)
    assert np.isfinite(float(loss))
    # ~uniform at init
    assert float(loss) == pytest.approx(np.log(CFG.vocab_size), rel=0.2)


def test_remat_matches_no_remat(params, toks):
    """jax.checkpoint per block: same values/grads, recomputed backward."""
    cfg_r = tfm.TransformerConfig(**{**CFG.__dict__, "remat": True})
    l0, g0 = jax.value_and_grad(tfm.lm_loss)(params, toks[:, :-1],
                                             toks[:, 1:], CFG)
    l1, g1 = jax.value_and_grad(tfm.lm_loss)(params, toks[:, :-1],
                                             toks[:, 1:], cfg_r)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_loss_matches_dense(params):
    """loss_chunk (chunked cross-entropy head, logits never materialized)
    == the dense head: same loss, same grads (head remat only reorders
    the same math)."""
    rng = np.random.default_rng(3)
    t_in = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 32)))
    t_out = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 32)))
    cfg_c = tfm.TransformerConfig(**{**CFG.__dict__, "loss_chunk": 8})
    l0, g0 = jax.value_and_grad(tfm.lm_loss)(params, t_in, t_out, CFG)
    l1, g1 = jax.value_and_grad(tfm.lm_loss)(params, t_in, t_out, cfg_c)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_loss_rejects_nondivisible(params):
    cfg_c = tfm.TransformerConfig(**{**CFG.__dict__, "loss_chunk": 7})
    t = jnp.zeros((2, 32), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        tfm.lm_loss(params, t, t, cfg_c)


def test_spmd_step_with_chunked_loss(params, toks):
    """The SPMD train step takes the chunked-head path (loss_chunk set)
    and produces the same first-step loss as the dense head."""
    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        OptimizerConfig,
    )
    from distributed_model_parallel_tpu.mesh import make_mesh

    spec = make_mesh(MeshConfig(data=2))
    tx = make_optimizer(OptimizerConfig(learning_rate=0.1, warmup_steps=0),
                        1, 1)
    t_in, t_out = toks[:, :-1], toks[:, 1:]
    from jax.sharding import NamedSharding, PartitionSpec as P

    losses = {}
    for chunk in (0, 31):   # 31 = one chunk of the full (odd) length
        cfg = tfm.TransformerConfig(**{**CFG.__dict__, "loss_chunk": chunk})
        step = make_spmd_train_step(cfg, spec, tx)
        p = shard_params(tfm.init_params(jax.random.key(0), cfg), cfg, spec)
        opt = jax.device_put(tx.init(p), NamedSharding(spec.mesh, P()))
        _, _, m = step(p, opt, t_in, t_out)
        losses[chunk] = float(m["loss"])
    assert losses[0] == pytest.approx(losses[31], rel=1e-6)


def test_training_reduces_loss(params, toks):
    tx = make_optimizer(OptimizerConfig(name="sgd", learning_rate=0.5,
                                        momentum=0.9, weight_decay=0.0,
                                        warmup_steps=0), 10, 10)
    opt_state = tx.init(params)
    p = params

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(tfm.lm_loss)(p, toks[:, :-1],
                                                  toks[:, 1:], CFG)
        u, o = tx.update(g, o, p)
        return jax.tree.map(lambda a, b: a + b, p, u), o, loss

    losses = []
    for _ in range(10):
        p, opt_state, loss = step(p, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8


# ---------------------------------------------------------------------------
# tensor parallel / SPMD pipeline parity
# ---------------------------------------------------------------------------

def _ref_logits(params, toks):
    return np.asarray(tfm.apply(params, toks, CFG))


def test_tp_sharded_forward_matches(params, toks):
    spec = make_mesh(MeshConfig(data=2, model=4))
    cfg_tp = tfm.TransformerConfig(**{**CFG.__dict__, "tp_axis": "model"})
    pipeline = make_pipeline_apply(cfg_tp, spec, num_microbatches=1)

    def fwd(p, t):
        x = tfm.embed(p, t, cfg_tp)
        x, _ = pipeline(p["blocks"], x)
        return tfm.unembed(p, x)

    sp = shard_params(params, cfg_tp, spec)
    out = jax.jit(fwd)(sp, toks)
    np.testing.assert_allclose(np.asarray(out), _ref_logits(params, toks),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("microbatches", [1, 2])
def test_spmd_pipeline_forward_matches(params, toks, microbatches):
    spec = make_mesh(MeshConfig(data=2, stage=4))
    pipeline = make_pipeline_apply(CFG, spec, num_microbatches=microbatches)

    def fwd(p, t):
        x = tfm.embed(p, t, CFG)
        x, _ = pipeline(p["blocks"], x)
        return tfm.unembed(p, x)

    sp = shard_params(params, CFG, spec)
    out = jax.jit(fwd)(sp, toks)
    np.testing.assert_allclose(np.asarray(out), _ref_logits(params, toks),
                               rtol=2e-4, atol=2e-4)


def test_spmd_train_step_runs_and_learns(params, toks):
    spec = make_mesh(MeshConfig(data=2, stage=2, model=2))
    cfg = tfm.TransformerConfig(**{**CFG.__dict__, "tp_axis": "model"})
    tx = make_optimizer(OptimizerConfig(learning_rate=0.5, momentum=0.9,
                                        weight_decay=0.0, warmup_steps=0),
                        10, 10)
    step = make_spmd_train_step(cfg, spec, tx, num_microbatches=2)
    p = shard_params(params, cfg, spec)
    o = jax.device_put(tx.init(params),
                       NamedSharding(spec.mesh, P()))
    losses = []
    for _ in range(6):
        p, o, m = step(p, o, toks[:, :-1], toks[:, 1:])
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_spmd_pipeline_with_ring_attention(params, toks):
    """dp x pp x sp in one program: the long-context configuration."""
    spec = make_mesh(MeshConfig(data=2, stage=2, seq=2))
    cfg = tfm.TransformerConfig(**{**CFG.__dict__, "sp_axis": "seq"})
    pipeline = make_pipeline_apply(cfg, spec, num_microbatches=2)

    def fwd(p, t):
        x = tfm.embed(p, t, cfg)
        x, _ = pipeline(p["blocks"], x)
        return tfm.unembed(p, x)

    sp = shard_params(params, cfg, spec)
    out = jax.jit(fwd)(sp, toks)
    np.testing.assert_allclose(np.asarray(out), _ref_logits(params, toks),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# mixture-of-experts transformer
# ---------------------------------------------------------------------------

MOE_CFG = tfm.TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                                n_layers=4, d_ff=64, max_seq_len=64,
                                moe_experts=4, moe_top_k=2,
                                moe_capacity_factor=4.0)


@pytest.fixture()
def moe_params():
    return tfm.init_params(jax.random.key(0), MOE_CFG)


def test_moe_transformer_forward_and_aux(moe_params, toks):
    logits, aux = tfm.apply_with_aux(moe_params, toks, MOE_CFG)
    assert logits.shape == (*toks.shape, MOE_CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # aux = [balance, z, drop]: balanced routing gives balance ~1; any
    # routing gives balance >= 1 in expectation — just require sane values
    assert aux.shape == (tfm.AUX_STATS,)
    assert 0.0 < float(aux[0]) < 10.0
    assert float(aux[1]) > 0.0
    assert 0.0 <= float(aux[2]) <= 1.0


def test_moe_transformer_trains(moe_params, toks):
    import optax

    tx = make_optimizer(OptimizerConfig(learning_rate=0.5, momentum=0.9,
                                        weight_decay=0.0, warmup_steps=0),
                        10, 10)
    opt_state = tx.init(moe_params)
    p = moe_params

    @jax.jit
    def step(p, o):
        loss, grads = jax.value_and_grad(tfm.lm_loss)(
            p, toks[:, :-1], toks[:, 1:], MOE_CFG)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss, grads

    losses = []
    for _ in range(8):
        p, opt_state, loss, grads = step(p, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # router receives gradient (load-balance loss + gating both feed it)
    assert float(jnp.abs(grads["blocks"]["router"]).sum()) > 0


def test_moe_spmd_pipeline_forward_matches(moe_params, toks):
    """MoE blocks under the SPMD pipeline: logits == single-device forward
    (aux is dropped in the pipeline, logits must agree exactly)."""
    spec = make_mesh(MeshConfig(data=2, stage=4))
    pipeline = make_pipeline_apply(MOE_CFG, spec, num_microbatches=2)

    def fwd(p, t):
        x = tfm.embed(p, t, MOE_CFG)
        x, _ = pipeline(p["blocks"], x)
        return tfm.unembed(p, x)

    sp = shard_params(moe_params, MOE_CFG, spec)
    out = jax.jit(fwd)(sp, toks)
    ref = tfm.apply(moe_params, toks, MOE_CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_spmd_train_step_with_expert_axis(moe_params, toks):
    """Full SPMD train step on a mesh with a real expert axis: experts
    sharded over ``expert``, tokens exchanged via all_to_all."""
    spec = make_mesh(MeshConfig(data=2, stage=1, expert=2))
    cfg = tfm.TransformerConfig(**{**MOE_CFG.__dict__, "ep_axis": "expert"})
    tx = make_optimizer(OptimizerConfig(learning_rate=0.5, momentum=0.9,
                                        weight_decay=0.0, warmup_steps=0),
                        10, 10)
    step = make_spmd_train_step(cfg, spec, tx, num_microbatches=1)
    p = shard_params(moe_params, cfg, spec)
    o = jax.device_put(tx.init(moe_params), NamedSharding(spec.mesh, P()))
    losses = []
    for _ in range(6):
        p, o, m = step(p, o, toks[:, :-1], toks[:, 1:])
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# autoregressive generation (KV cache)
# ---------------------------------------------------------------------------

def test_generate_greedy_matches_teacher_forcing(params):
    """The cached decode must agree with the full (non-cached) forward:
    every generated token equals the argmax of the full model's logits at
    the preceding position of the generated sequence."""
    prompt = jnp.asarray(np.random.default_rng(3).integers(
        0, CFG.vocab_size, (2, 5)), jnp.int32)
    steps = 6
    out = tfm.generate(params, CFG, prompt, steps)
    assert out.shape == (2, 5 + steps)
    assert np.array_equal(np.asarray(out[:, :5]), np.asarray(prompt))
    logits = tfm.apply(params, out, CFG)
    pred = np.argmax(np.asarray(logits[:, :-1], np.float32), axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 5:]),
                                  pred[:, 4:4 + steps])


def test_generate_sampling_deterministic_and_jittable(params):
    prompt = jnp.zeros((1, 3), jnp.int32)
    gen = jax.jit(lambda p, r: tfm.generate(p, CFG, prompt, 4, rng=r,
                                            temperature=1.0),
                  static_argnums=())
    a = gen(params, jax.random.key(7))
    b = gen(params, jax.random.key(7))
    c = gen(params, jax.random.key(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (1, 7)
    # rng is threaded: different seeds sample different continuations
    # (near-uniform logits at init; coincidence odds ~vocab^-4)
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_generate_moe(moe_params):
    prompt = jnp.zeros((2, 4), jnp.int32)
    out = tfm.generate(moe_params, MOE_CFG, prompt, 3)
    assert out.shape == (2, 7)
    assert np.asarray(out).max() < MOE_CFG.vocab_size


def test_generate_rejects_overflow(params):
    with pytest.raises(ValueError):
        tfm.generate(params, CFG, jnp.zeros((1, 60), jnp.int32), 10)


def test_top_k_filter_masks_all_but_k():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0, 4.0]])
    out = np.asarray(tfm._filter_top_k(logits, 2))
    assert np.isfinite(out[0, [1, 4]]).all()       # top-2 kept
    assert np.isneginf(out[0, [0, 2, 3]]).all()    # rest masked


def test_top_p_filter_keeps_nucleus():
    # probs ~ [0.643, 0.237, 0.087, 0.032] -> p=0.7 keeps {0, 1}.
    logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
    out = np.asarray(tfm._filter_top_p(logits, 0.7))
    assert np.isfinite(out[0, [0, 1]]).all()
    assert np.isneginf(out[0, [2, 3]]).all()
    # p smaller than the top token's mass still keeps the top token.
    out = np.asarray(tfm._filter_top_p(logits, 0.01))
    assert np.isfinite(out[0, 0]) and np.isneginf(out[0, 1:]).all()


def test_top_p_filter_excludes_tied_logits_outside_nucleus():
    # probs ~ [0.464, 0.171, 0.171, 0.171, 0.023]: exclusive mass passes p
    # after two of the tied 3.0s (0 + 0.464 + 0.635 < 0.7 ≤ 0.806). A value
    # threshold would keep the third tied token too (4 survivors); the
    # scatter-through-argsort mask keeps exactly the minimal nucleus of 3.
    logits = jnp.asarray([[4.0, 3.0, 3.0, 3.0, 1.0]])
    out = np.asarray(tfm._filter_top_p(logits, 0.7))
    assert int(np.isfinite(out).sum()) == 3
    assert np.isfinite(out[0, 0])
    assert int(np.isfinite(out[0, 1:4]).sum()) == 2   # one tied token dropped
    assert np.isneginf(out[0, 4])


def test_generate_top_k_restricts_tokens(params):
    """With top_k=1, sampling at any temperature degenerates to greedy."""
    prompt = jnp.zeros((2, 3), jnp.int32)
    greedy = tfm.generate(params, CFG, prompt, 5)
    k1 = tfm.generate(params, CFG, prompt, 5, temperature=2.0, top_k=1,
                      rng=jax.random.key(11))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))


def test_generate_top_p_runs_and_differs_by_seed(params):
    prompt = jnp.zeros((1, 3), jnp.int32)
    a = tfm.generate(params, CFG, prompt, 5, temperature=1.0, top_p=0.9,
                     rng=jax.random.key(1))
    b = tfm.generate(params, CFG, prompt, 5, temperature=1.0, top_p=0.9,
                     rng=jax.random.key(2))
    assert a.shape == (1, 8)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_generate_sampler_arg_validation(params):
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="temperature"):
        tfm.generate(params, CFG, prompt, 2, top_k=5)
    with pytest.raises(ValueError, match="top_k"):
        tfm.generate(params, CFG, prompt, 2, temperature=1.0, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        tfm.generate(params, CFG, prompt, 2, temperature=1.0, top_p=1.5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_full(causal):
    """Kernel-in-ring composition: each hop through the pallas kernel
    (interpret mode on CPU), merged by logsumexp."""
    spec = make_mesh(MeshConfig(data=1, seq=4))
    q, k, v = _qkv(seed=2, t=64)
    ref = full_attention(q, k, v, causal=causal)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal,
                                       impl="flash"),
        mesh=spec.mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_full():
    """The ring backward (second ring pass over the FlashAttention-2
    kernels, dk/dv riding with their blocks) against plain autodiff."""
    spec = make_mesh(MeshConfig(data=1, seq=4))
    q, k, v = _qkv(seed=3, t=64)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True,
                                       impl="flash"),
        mesh=spec.mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-5)


def test_ring_bf16_accumulates_f32():
    """bf16 inputs must get f32 online-softmax accumulation in the ring —
    parity with the single-device path at f32-class tolerance, much tighter
    than bf16 accumulation drift."""
    spec = make_mesh(MeshConfig(data=1, seq=8))
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=4, t=64))
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    for impl in ("xla", "flash"):
        f = jax.shard_map(
            lambda q, k, v, impl=impl: ring_attention(
                q, k, v, "seq", causal=True, impl=impl),
            mesh=spec.mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
            check_vma=False)
        out = np.asarray(f(q, k, v)).astype(np.float32)
        # bf16 *inputs* bound the error (~1e-2); bf16 *accumulation* across
        # 8 hops would push beyond it.
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-2,
                                   atol=2e-2)
