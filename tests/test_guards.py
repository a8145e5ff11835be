"""Guards: divergence, non-finite, stall detection."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.config import MeshConfig
from distributed_model_parallel_tpu.train.guards import (
    NonFiniteError,
    ReplicaDivergenceError,
    StallDetector,
    assert_replicated,
    check_finite,
)


def test_assert_replicated_ok(mesh8):
    tree = {"w": jax.device_put(jnp.ones((4, 4)), mesh8.replicated())}
    assert_replicated(tree)  # no raise


def test_assert_replicated_catches_divergence(mesh8):
    devs = list(mesh8.mesh.devices.ravel())
    shards = [jnp.full((2, 2), float(i)) for i in range(len(devs))]
    arr = jax.make_array_from_single_device_arrays(
        (2, 2),
        jax.sharding.NamedSharding(mesh8.mesh, jax.sharding.PartitionSpec()),
        [jax.device_put(s, d) for s, d in zip(shards, devs)])
    with pytest.raises(ReplicaDivergenceError):
        assert_replicated({"w": arr})


def test_assert_replicated_ignores_sharded(mesh8):
    x = jax.device_put(jnp.arange(16.0), mesh8.batch_sharded())
    assert_replicated({"x": x})  # sharded arrays are skipped, no raise


def _per_device_replicated(mesh8, shards):
    devs = list(mesh8.mesh.devices.ravel())
    return jax.make_array_from_single_device_arrays(
        shards[0].shape,
        jax.sharding.NamedSharding(mesh8.mesh, jax.sharding.PartitionSpec()),
        [jax.device_put(s, d) for s, d in zip(shards, devs)])


def test_assert_replicated_default_is_bitwise(mesh8):
    """atol=0 compares BIT PATTERNS (the sentinel's semantics): a
    sign-bit flip turning -0.0 into +0.0 diverges even though the values
    compare equal, while replicas all holding the same NaN bytes are
    identical — a non-finite incident, not a replication one."""
    n = len(mesh8.mesh.devices.ravel())
    zeros = [jnp.full((2,), -0.0)] * (n - 1) + [jnp.full((2,), 0.0)]
    with pytest.raises(ReplicaDivergenceError, match="bit patterns"):
        assert_replicated({"w": _per_device_replicated(mesh8, zeros)})
    nans = [jnp.full((2,), jnp.nan)] * n
    assert_replicated({"w": _per_device_replicated(mesh8, nans)})  # no raise
    # atol > 0 keeps the value comparison: -0.0 == +0.0 passes.
    assert_replicated({"w": _per_device_replicated(mesh8, zeros)},
                      atol=1e-9)


def test_check_finite():
    check_finite({"a": jnp.ones(3)})
    with pytest.raises(NonFiniteError):
        check_finite({"a": jnp.array([1.0, float("nan")])})
    with pytest.raises(NonFiniteError):
        check_finite({"a": jnp.array([float("inf")])})


def test_check_finite_single_device_get(monkeypatch):
    """The whole tree must come to host in ONE jax.device_get (one blocking
    round trip), not one per leaf — and the scan raises at the first bad
    leaf it meets."""
    from distributed_model_parallel_tpu.train import guards

    calls = []
    real_get = jax.device_get

    def counting_get(x):
        calls.append(x)
        return real_get(x)

    monkeypatch.setattr(guards.jax, "device_get", counting_get)
    tree = {f"leaf{i}": jnp.full((3,), float(i)) for i in range(10)}
    check_finite(tree)
    assert len(calls) == 1
    calls.clear()
    tree["leaf3"] = jnp.array([float("nan")])
    with pytest.raises(NonFiniteError, match="leaf3"):
        check_finite(tree)
    assert len(calls) == 1
    # Empty trees short-circuit without a fetch.
    calls.clear()
    check_finite({})
    assert calls == []


def test_stall_detector():
    s = StallDetector(budget_s=0.01)
    with s.step():
        pass
    assert not s.stalled
    with s.step():
        time.sleep(0.02)
    assert s.stalled
    assert s.worst_s >= 0.02


# ---------------------------------------------------------------------------
# integration: the trainers actually run the guards
# ---------------------------------------------------------------------------

def _poison(tree):
    """NaN every float leaf."""
    return jax.tree.map(
        lambda x: (jnp.full_like(x, jnp.nan)
                   if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                   else x), tree)


def test_trainer_check_finite_raises_on_nan(tmp_path):
    from tests.conftest import tiny_train_config
    from distributed_model_parallel_tpu.train.trainer import Trainer

    cfg = tiny_train_config(tmp_path, check_finite_every=1)
    t = Trainer(cfg)
    assert t.guards.enabled
    t.state = t.state.replace(params=_poison(t.state.params))
    with pytest.raises(NonFiniteError):
        t.train_epoch(0)


def test_trainer_guards_off_by_default(tmp_path):
    from tests.conftest import tiny_train_config
    from distributed_model_parallel_tpu.train.trainer import Trainer

    cfg = tiny_train_config(tmp_path)
    t = Trainer(cfg)
    assert not t.guards.enabled
    t.state = t.state.replace(params=_poison(t.state.params))
    t.train_epoch(0)  # silently NaNs, as configured — no raise


def test_trainer_stall_budget_logs(tmp_path):
    from tests.conftest import tiny_train_config
    from distributed_model_parallel_tpu.train.trainer import Trainer

    # An absurdly small budget: every drain overruns, the run completes,
    # and the log carries the guard line.
    cfg = tiny_train_config(tmp_path, epochs=1, stall_budget_s=1e-9)
    t = Trainer(cfg)
    t.train_epoch(0)
    assert t.guards.stall.stalled
    log_text = "".join(
        p.read_text() for p in (tmp_path / "log").glob("*.txt"))
    assert "stall budget" in log_text


def test_lm_trainer_check_finite_raises_on_nan(tmp_path):
    from distributed_model_parallel_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_model_parallel_tpu.train.lm_trainer import (
        LMTrainConfig,
        LMTrainer,
    )

    cfg = LMTrainConfig(
        model=TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq_len=32),
        batch_size=4, seq_len=16, steps_per_epoch=3, epochs=1,
        n_tokens=2000, check_finite_every=1,
        log_dir=str(tmp_path / "log"),
        checkpoint_dir=str(tmp_path / "ckpt"))
    t = LMTrainer(cfg)
    t.params = _poison(t.params)
    with pytest.raises(NonFiniteError):
        t.fit()


def test_pipeline_trainer_check_finite_raises_on_nan(tmp_path):
    from tests.conftest import tiny_train_config
    from distributed_model_parallel_tpu.train.pipeline_trainer import (
        PipelineTrainer,
    )

    cfg = tiny_train_config(tmp_path, mesh=MeshConfig(stage=2),
                            check_finite_every=1)
    t = PipelineTrainer(cfg)
    for stage in t.runner.stages:
        stage.params = _poison(stage.params)
    with pytest.raises(NonFiniteError):
        t.fit()
