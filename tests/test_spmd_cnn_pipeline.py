"""Heterogeneous-stage SPMD CNN pipeline (shard_map + ppermute + switch).

The multi-host-capable path for the reference's centerpiece workload — the
staged MobileNetV2 pipeline (model_parallel.py:99-157). Parity targets:

* M=1 must reproduce the single-device step exactly (disjoint stage params,
  per-leaf SGD — same invariant test_pipeline.py pins for PipelineRunner).
* M>1 must match PipelineRunner's GPipe schedule leaf-for-leaf (same
  per-microbatch BN normalization, same pooled running-stat update).
* data x stage meshes must train (per-replica BN forward, pooled stats).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.config import (
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
)
from distributed_model_parallel_tpu.data.registry import (
    CIFAR10_MEAN,
    CIFAR10_STD,
    _synthetic,
)
from distributed_model_parallel_tpu.mesh import make_mesh
from distributed_model_parallel_tpu.models import get_model
from distributed_model_parallel_tpu.parallel.pipeline import PipelineRunner
from distributed_model_parallel_tpu.parallel.spmd_cnn_pipeline import (
    _pool_bn_over_axis,
    make_spmd_cnn_train_step,
)
from distributed_model_parallel_tpu.train.optim import make_optimizer
from distributed_model_parallel_tpu.train.trainer import (
    TrainState,
    make_train_step,
)


def _make(model_name="tinycnn", lr=0.1):
    model = get_model(ModelConfig(name=model_name))
    tx = make_optimizer(OptimizerConfig(learning_rate=lr, warmup_steps=0,
                                        momentum=0.9), 10, 10)
    params, state = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
    ts = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                    model_state=state, opt_state=tx.init(params))
    return model, tx, ts


def _spmd_step(model, tx, *, data=1, stage=4, microbatches=1,
               dispatch="switch", schedule="gpipe"):
    spec = make_mesh(MeshConfig(data=data, stage=stage))
    return jax.jit(make_spmd_cnn_train_step(
        model, spec, tx, sample_shape=(2, 32, 32, 3),
        mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=microbatches, augment=False,
        stage_dispatch=dispatch, schedule=schedule))


@pytest.fixture(scope="module")
def batch():
    ds = _synthetic(32, 32, 10, seed=3)
    return jnp.asarray(ds.images), jnp.asarray(ds.labels)


def _assert_tree_close(a, b, rtol=2e-4, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


def test_m1_matches_single_device(batch):
    """One batch in flight == the single-device step, params AND BN stats."""
    images, labels = batch
    model, tx, ts = _make()
    nts, m = _spmd_step(model, tx, stage=4)(ts, jax.random.key(9),
                                            images, labels)
    sstep = jax.jit(make_train_step(model, tx, mean=CIFAR10_MEAN,
                                    std=CIFAR10_STD, augment=False))
    _, _, ts2 = _make()
    sts, sm = sstep(ts2, jax.random.key(9), images, labels)
    assert float(m["loss"]) == pytest.approx(float(sm["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(nts.params), jax.device_get(sts.params))
    _assert_tree_close(jax.device_get(nts.model_state),
                       jax.device_get(sts.model_state))


def test_gpipe_matches_pipeline_runner(batch):
    """M=2 SPMD GPipe == the single-controller PipelineRunner GPipe: same
    per-microbatch BN forward, same pooled running stats, same update."""
    images, labels = batch
    model, tx, ts = _make()
    nts, m = _spmd_step(model, tx, stage=4, microbatches=2)(
        ts, jax.random.key(9), images, labels)
    runner = PipelineRunner(
        model, jax.devices()[:4], tx=tx, rng=jax.random.key(0),
        sample_shape=(2, 32, 32, 3), mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=2, augment=False, schedule="gpipe")
    rm = runner.train_step(jax.random.key(9), images, labels)
    assert float(m["loss"]) == pytest.approx(float(rm["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(nts.params), runner.merged_params())
    _assert_tree_close(jax.device_get(nts.model_state),
                       runner.merged_model_state())


def test_mobilenetv2_matches_pipeline_runner(batch):
    """The reference centerpiece: MobileNetV2's 19 heterogeneous units
    pipelined via shard_map+ppermute, loss- and param-parity against
    PipelineRunner's GPipe. Uses masked dispatch: the XLA CPU backend
    runs conditional bodies without intra-op threading, making MobileNet's
    depthwise-conv backward ~35x slower inside lax.switch — masked is
    numerically identical (test_masked_dispatch_matches_switch) and
    CPU-fast; the switch path is exercised by the tinycnn tests."""
    images, labels = batch
    model, tx, ts = _make(model_name="mobilenetv2")
    nts, m = _spmd_step(model, tx, stage=2, microbatches=2,
                        dispatch="masked")(
        ts, jax.random.key(9), images, labels)
    runner = PipelineRunner(
        model, jax.devices()[:2], tx=tx, rng=jax.random.key(0),
        sample_shape=(2, 32, 32, 3), mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=2, augment=False, schedule="gpipe")
    rm = runner.train_step(jax.random.key(9), images, labels)
    assert float(m["loss"]) == pytest.approx(float(rm["loss"]), rel=1e-4)
    _assert_tree_close(jax.device_get(nts.params), runner.merged_params(),
                       rtol=5e-4, atol=5e-5)


def test_masked_dispatch_matches_switch(batch):
    """stage_dispatch='masked' (compute-all + select_n) must equal
    'switch' (lax.switch) leaf-for-leaf — same program, different branch
    selection mechanics."""
    images, labels = batch
    model, tx, ts = _make()
    a, ma = _spmd_step(model, tx, stage=4, microbatches=2,
                       dispatch="switch")(ts, jax.random.key(9),
                                          images, labels)
    _, _, ts2 = _make()
    b, mb = _spmd_step(model, tx, stage=4, microbatches=2,
                       dispatch="masked")(ts2, jax.random.key(9),
                                          images, labels)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-6)
    _assert_tree_close(jax.device_get(a.params), jax.device_get(b.params),
                       rtol=1e-5, atol=1e-7)
    _assert_tree_close(jax.device_get(a.model_state),
                       jax.device_get(b.model_state), rtol=1e-5, atol=1e-7)


def test_dp_x_pp_trains(batch):
    """data=2 x stage=4 mesh: loss decreases over steps, stats stay finite
    (per-replica BN forward + cross-shard pooled running stats)."""
    images, labels = batch
    model, tx, ts = _make()
    step = _spmd_step(model, tx, data=2, stage=4, microbatches=2)
    losses = []
    for i in range(4):
        ts, m = step(ts, jax.random.key(9 + i), images, labels)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
    for leaf in jax.tree.leaves(jax.device_get(ts.model_state)):
        assert np.isfinite(leaf).all()


def test_dp_x_pp_matches_single_device(batch):
    """ADVICE r3: the data x stage path (per-replica BN forward + pooled
    running stats + mesh-wide grad psum) against the single-device step on
    the same global batch — params must match exactly; BN running stats
    through the pooled update.

    BN caveat that shapes the tolerance story: with data=2 each replica
    normalizes by ITS shard's batch moments, so activations (and thus
    gradients) differ from the big-batch forward — that is DataParallel
    semantics (reference Readme.md:17-143), not a bug. To anchor params
    exactly, this test freezes BN into eval-like behavior by training with
    momentum so running stats pool, and compares the data x stage step to
    a data-parallel-only (data=2, stage=1) step, which shares the
    per-replica BN forward. Stage splitting must then change nothing."""
    images, labels = batch
    model, tx, ts = _make()
    a, ma = _spmd_step(model, tx, data=2, stage=2, microbatches=1)(
        ts, jax.random.key(9), images, labels)
    _, _, ts2 = _make()
    b, mb = _spmd_step(model, tx, data=2, stage=1, microbatches=1)(
        ts2, jax.random.key(9), images, labels)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(a.params), jax.device_get(b.params))
    _assert_tree_close(jax.device_get(a.model_state),
                       jax.device_get(b.model_state))


def test_1f1b_matches_gpipe(batch):
    """The hand-scheduled 1F1B backward (make_cnn_1f1b_fwd_bwd) must equal
    the whole-program-AD GPipe step leaf-for-leaf — params, BN running
    stats, loss — across stage-only, data x stage, and M > S meshes."""
    images, labels = batch
    for kw in (dict(stage=4, microbatches=2),
               dict(data=2, stage=2, microbatches=2),
               dict(stage=2, microbatches=4)):
        model, tx, ts = _make()
        a, ma = _spmd_step(model, tx, schedule="gpipe", **kw)(
            ts, jax.random.key(9), images, labels)
        _, _, ts2 = _make()
        b, mb = _spmd_step(model, tx, schedule="1f1b", **kw)(
            ts2, jax.random.key(9), images, labels)
        assert float(ma["loss"]) == pytest.approx(float(mb["loss"]),
                                                  rel=1e-5), kw
        _assert_tree_close(jax.device_get(a.params), jax.device_get(b.params))
        _assert_tree_close(jax.device_get(a.model_state),
                           jax.device_get(b.model_state))


def test_trainer_accepts_1f1b(tmp_path):
    """The Trainer drives strategy='spmd_pipeline' with
    pipeline_schedule='1f1b' (the r3 GPipe-only rejection is lifted)."""
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from tests.conftest import tiny_train_config

    cfg = tiny_train_config(
        tmp_path, strategy="spmd_pipeline",
        mesh=MeshConfig(data=2, stage=4), num_microbatches=2, epochs=1,
        pipeline_schedule="1f1b")
    history = Trainer(cfg).fit()
    assert np.isfinite(history[-1]["loss_train"])


def test_trainer_spmd_pipeline_strategy(tmp_path):
    """strategy='spmd_pipeline' drives the full Trainer harness (epochs,
    eval, checkpointing) over a data x stage mesh and trains."""
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from tests.conftest import tiny_train_config

    cfg = tiny_train_config(
        tmp_path, strategy="spmd_pipeline",
        mesh=MeshConfig(data=2, stage=4), num_microbatches=2, epochs=2)
    history = Trainer(cfg).fit()
    assert len(history) == 2
    assert history[-1]["loss_train"] < history[0]["loss_train"] + 0.1
    assert np.isfinite(history[-1]["loss_train"])


def test_trainer_spmd_pipeline_rejects_bad_configs(tmp_path):
    from distributed_model_parallel_tpu.train.trainer import Trainer
    from tests.conftest import tiny_train_config

    with pytest.raises(ValueError, match="mesh.stage"):
        Trainer(tiny_train_config(tmp_path, strategy="spmd_pipeline",
                                  mesh=MeshConfig(data=8)))
    with pytest.raises(ValueError, match="device_resident_data"):
        Trainer(tiny_train_config(tmp_path, strategy="spmd_pipeline",
                                  mesh=MeshConfig(data=2, stage=4),
                                  device_resident_data=True))


def test_dp_bn_stat_pooling_matches_big_batch():
    """_pool_bn_over_axis reproduces the big-batch EMA update from
    per-shard EMA'd states (law of total variance across equal shards)."""
    from jax.sharding import Mesh

    rng = np.random.default_rng(0)
    mu, C = 0.9, 8
    o_mean = rng.normal(size=C)
    o_var = rng.uniform(0.5, 2.0, size=C)
    means = rng.normal(size=(2, C))       # per-shard batch moments
    varz = rng.uniform(0.1, 1.0, size=(2, C))
    shard_states = np.stack([
        np.stack([mu * o_mean + (1 - mu) * means[i],
                  mu * o_var + (1 - mu) * varz[i]]) for i in range(2)])

    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def f(x):
        st = {"bn": {"mean": x[0, 0], "var": x[0, 1]}}
        pooled = _pool_bn_over_axis(st, "d", mu)
        return jnp.stack([pooled["bn"]["mean"], pooled["bn"]["var"]])

    out = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=jax.sharding.PartitionSpec("d"),
        out_specs=jax.sharding.PartitionSpec()))(jnp.asarray(shard_states))

    big_mean = means.mean(0)
    big_var = varz.mean(0) + (means ** 2).mean(0) - big_mean ** 2
    np.testing.assert_allclose(out[0], mu * o_mean + (1 - mu) * big_mean,
                               rtol=1e-5)
    np.testing.assert_allclose(out[1], mu * o_var + (1 - mu) * big_var,
                               rtol=1e-5)


def test_1f1b_interleaved_matches_gpipe_and_runner(batch):
    """Interleaved virtual stages (V=2) in the SPMD CNN 1F1B engine:
    leaf-for-leaf parity against BOTH the SPMD
    GPipe step and the single-controller PipelineRunner's interleaved
    placement (virtual_stages=2, 1f1b dispatch order) — numerics are
    V-invariant, so all three must agree on params, BN stats, and loss."""
    images, labels = batch
    model, tx, ts = _make()
    a, ma = _spmd_step(model, tx, stage=2, microbatches=4,
                       schedule="gpipe")(
        ts, jax.random.key(9), images, labels)

    _, _, ts2 = _make()
    spec = make_mesh(MeshConfig(data=1, stage=2))
    step_v2 = jax.jit(make_spmd_cnn_train_step(
        model, spec, tx, sample_shape=(2, 32, 32, 3),
        mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=4, augment=False, stage_dispatch="switch",
        schedule="1f1b", virtual_stages=2))
    b, mb = step_v2(ts2, jax.random.key(9), images, labels)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(a.params), jax.device_get(b.params))
    _assert_tree_close(jax.device_get(a.model_state),
                       jax.device_get(b.model_state))

    runner = PipelineRunner(
        model, jax.devices()[:2], tx=tx, rng=jax.random.key(0),
        sample_shape=(2, 32, 32, 3), mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=4, augment=False, schedule="1f1b",
        virtual_stages=2)
    rm = runner.train_step(jax.random.key(9), images, labels)
    assert float(mb["loss"]) == pytest.approx(float(rm["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(b.params), runner.merged_params())
    _assert_tree_close(jax.device_get(b.model_state),
                       runner.merged_model_state())


def test_1f1b_interleaved_dp_x_pp(batch):
    images, labels = batch
    model, tx, ts = _make()
    a, ma = _spmd_step(model, tx, data=2, stage=2, microbatches=2,
                       schedule="gpipe")(
        ts, jax.random.key(9), images, labels)
    _, _, ts2 = _make()
    spec = make_mesh(MeshConfig(data=2, stage=2))
    step_v2 = jax.jit(make_spmd_cnn_train_step(
        model, spec, tx, sample_shape=(2, 32, 32, 3),
        mean=CIFAR10_MEAN, std=CIFAR10_STD,
        num_microbatches=2, augment=False, stage_dispatch="switch",
        schedule="1f1b", virtual_stages=2))
    b, mb = step_v2(ts2, jax.random.key(9), images, labels)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-5)
    _assert_tree_close(jax.device_get(a.params), jax.device_get(b.params))
    _assert_tree_close(jax.device_get(a.model_state),
                       jax.device_get(b.model_state))
