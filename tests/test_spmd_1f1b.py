"""1F1B single-jit SPMD pipeline: gradient/loss parity with the GPipe path.

The GPipe step (whole-program autodiff through the shard_map pipeline) is
itself parity-anchored against the single-device ``tfm.lm_loss`` step
(tests/test_transformer.py, benchmarks/lm_parity.json), so agreement with it
across mesh factorizations proves the hand-scheduled 1F1B backward — chained
per-stage vjps, cotangent scaling, per-leaf psum completion
(parallel/spmd_pipeline.make_1f1b_loss_and_grad) — computes the same
mathematical gradient while interleaving the schedule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_model_parallel_tpu.config import MeshConfig
from distributed_model_parallel_tpu.mesh import make_mesh
from distributed_model_parallel_tpu.models import transformer as tfm
from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
    make_1f1b_loss_and_grad,
    make_spmd_train_step,
    shard_params,
)

B, T, V = 8, 32, 64


def _cfg(**kw):
    kw.setdefault("vocab_size", V)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 4)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_seq_len", T)
    return tfm.TransformerConfig(**kw)


def _data(seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    return toks, tgts


def _grads_close(ga, gb, tol):
    flat_a, tree_a = jax.tree.flatten(jax.device_get(ga))
    flat_b, tree_b = jax.tree.flatten(jax.device_get(gb))
    assert tree_a == tree_b
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=tol, atol=tol)


def _parity(mesh_kw, cfg_kw, M, tol=2e-5):
    cfg = _cfg(**cfg_kw)
    spec = make_mesh(MeshConfig(**mesh_kw))
    params = shard_params(tfm.init_params(jax.random.key(0), cfg), cfg, spec)
    toks, tgts = _data()

    gpipe_loss_and_grad = jax.jit(jax.value_and_grad(
        __import__(
            "distributed_model_parallel_tpu.parallel.spmd_pipeline",
            fromlist=["_make_loss_fn"])._make_loss_fn(cfg, spec, M),
        has_aux=True))
    (l_ref, aux_ref), g_ref = gpipe_loss_and_grad(params, toks, tgts)

    f1b = jax.jit(make_1f1b_loss_and_grad(cfg, spec, M))
    l_new, aux_new, g_new = f1b(params, toks, tgts)
    np.testing.assert_allclose(np.asarray(aux_new), np.asarray(aux_ref),
                               rtol=1e-4, atol=1e-6)

    np.testing.assert_allclose(float(l_new), float(l_ref), rtol=1e-5,
                               atol=1e-6)
    _grads_close(g_new, g_ref, tol)


def test_1f1b_pp_only():
    _parity(dict(data=1, stage=4), {}, M=4)


def test_1f1b_pp_dp():
    _parity(dict(data=2, stage=2), {}, M=2)


def test_1f1b_pp_tp():
    _parity(dict(data=1, stage=2, model=2), dict(tp_axis="model"), M=4)


def test_1f1b_pp_tp_dp():
    _parity(dict(data=2, stage=2, model=2), dict(tp_axis="model"), M=2)


def test_1f1b_pp_sp_ring():
    _parity(dict(data=1, stage=2, seq=2),
            dict(sp_axis="seq", pos_embedding="rope"), M=2)


def _parity_interleaved(mesh_kw, cfg_kw, M, V, tol=2e-5):
    """V>1 interleaved 1F1B vs the whole-program-AD GPipe reference:
    identical loss/aux and leaf-for-leaf grads after mapping the blocks
    back from interleaved storage order to canonical layer order."""
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        _make_loss_fn,
        deinterleave_block_rows,
        interleave_block_rows,
    )

    cfg = _cfg(**cfg_kw)
    spec = make_mesh(MeshConfig(**mesh_kw))
    S = spec.num_stages
    params = shard_params(tfm.init_params(jax.random.key(0), cfg), cfg, spec)
    toks, tgts = _data()

    gpipe = jax.jit(jax.value_and_grad(
        _make_loss_fn(cfg, spec, M), has_aux=True))
    (l_ref, aux_ref), g_ref = gpipe(params, toks, tgts)

    params_i = dict(params)
    params_i["blocks"] = interleave_block_rows(
        params["blocks"], cfg.n_layers, S, V)
    f1b = jax.jit(make_1f1b_loss_and_grad(cfg, spec, M, virtual_stages=V))
    l_new, aux_new, g_new = f1b(params_i, toks, tgts)
    g_new = dict(g_new)
    g_new["blocks"] = deinterleave_block_rows(
        g_new["blocks"], cfg.n_layers, S, V)

    np.testing.assert_allclose(np.asarray(aux_new), np.asarray(aux_ref),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(l_new), float(l_ref), rtol=1e-5,
                               atol=1e-6)
    _grads_close(g_new, g_ref, tol)


def test_1f1b_interleaved_v2():
    # 4 layers over S=2 x V=2 = 4 chunks; M=4 (M % S == 0). The last
    # single-controller-only capability: two-level
    # chunk scheduling with the wraparound (S-1)->0 hop riding the same
    # modular ppermute ring.
    _parity_interleaved(dict(data=1, stage=2), {}, M=4, V=2)


def test_1f1b_interleaved_v2_dp_tp():
    _parity_interleaved(dict(data=2, stage=2, model=2),
                        dict(tp_axis="model"), M=2, V=2)


def test_1f1b_interleaved_v2_steady_wrap():
    # M*V=16 steady fine ticks against a 2D-1=7-slot stash ring: the ring
    # wraps repeatedly, and M=8 > S exercises multiple microbatch groups.
    _parity_interleaved(dict(data=1, stage=2), {}, M=8, V=2)


def test_1f1b_interleaved_rejects_bad_m():
    from distributed_model_parallel_tpu.parallel.spmd_pipeline import (
        make_1f1b_loss_and_grad,
    )

    cfg = _cfg()
    spec = make_mesh(MeshConfig(data=1, stage=2))
    with pytest.raises(ValueError, match="divisible by the stage count"):
        make_1f1b_loss_and_grad(cfg, spec, 3, virtual_stages=2)


def test_1f1b_pp_sp_learned_pos():
    # Learned positions under sequence parallelism exercise _embed_local's
    # per-shard dynamic_slice of the pos table — and, in the backward, its
    # scatter-transposed gradient summed over the seq axis (ADVICE r4: the
    # rope case above never touches that path).
    _parity(dict(data=1, stage=2, seq=2),
            dict(sp_axis="seq", pos_embedding="learned"), M=2)


def test_1f1b_m_exceeds_stages():
    # More microbatches than stages: the steady-state 1F1B regime, where
    # the stash ring (2S-1 slots) actually wraps.
    _parity(dict(data=1, stage=2), {}, M=8)


def test_1f1b_single_stage():
    # Degenerate S=1: no ppermutes, schedule is fwd-then-bwd per microbatch.
    _parity(dict(data=2, stage=1), {}, M=2)


def test_1f1b_gqa_learned_pos():
    _parity(dict(data=1, stage=2, model=2),
            dict(tp_axis="model", n_kv_heads=2), M=2)


def test_1f1b_remat_chunked_head():
    _parity(dict(data=2, stage=2),
            dict(remat=True, remat_policy="dots", loss_chunk=8), M=2)


def test_1f1b_moe_ep():
    _parity(dict(data=1, stage=2, expert=2),
            dict(moe_experts=4, moe_top_k=2, ep_axis="expert"), M=2,
            tol=5e-5)


def test_1f1b_moe_ep_tp():
    _parity(dict(stage=2, model=2, expert=2),
            dict(moe_experts=4, moe_top_k=2, ep_axis="expert",
                 tp_axis="model"), M=2, tol=5e-5)


def test_1f1b_train_step_reduces_loss():
    """End-to-end: the jitted 1F1B train step optimizes, and tracks the
    GPipe step's loss trajectory step for step."""
    cfg = _cfg()
    spec = make_mesh(MeshConfig(data=2, stage=2))
    tx = optax.sgd(0.3)
    toks, tgts = _data()

    losses = {}
    for schedule in ("gpipe", "1f1b"):
        params = shard_params(tfm.init_params(jax.random.key(0), cfg), cfg,
                              spec)
        opt_state = tx.init(params)
        step = make_spmd_train_step(cfg, spec, tx, num_microbatches=2,
                                    schedule=schedule)
        ls = []
        for _ in range(6):
            params, opt_state, m = step(params, opt_state, toks, tgts)
            ls.append(float(m["loss"]))
        losses[schedule] = ls
    assert losses["1f1b"][-1] < losses["1f1b"][0]
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], rtol=2e-4)


def test_unknown_schedule_rejected():
    cfg = _cfg()
    spec = make_mesh(MeshConfig(stage=2))
    with pytest.raises(ValueError, match="unknown spmd pipeline schedule"):
        make_spmd_train_step(cfg, spec, optax.sgd(0.1), 2, schedule="pipedream")


def test_1f1b_interleaved_v2_moe_ep():
    # Interleaved chunks containing routed-MoE blocks with expert
    # parallelism: the chunk slice must carry the expert-sharded leaves
    # and the aux 1/V weighting must keep the balance/z stats in the
    # V=1 normalization.
    _parity_interleaved(dict(data=1, stage=2, expert=2),
                        dict(moe_experts=4, moe_top_k=2,
                             ep_axis="expert"), M=2, V=2, tol=5e-5)
