"""Raw-speed pipeline smoke (pytest -m perf, tier-1-safe): the device
prefetcher really keeps batches in flight AND replays bitwise-identically
through a kill/resume; the donation assertion helper trips on an
intentionally undonated (and an intentionally unusable-donation) toy fn;
the bucketed-grad knob reaches the DDP step. docs/PERFORMANCE.md is the
map of what these properties protect."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.config import (
    DataConfig,
    RecoveryConfig,
)
from distributed_model_parallel_tpu.data.loader import (
    BatchLoader,
    DevicePrefetchLoader,
)
from distributed_model_parallel_tpu.data.registry import ArrayDataset
from distributed_model_parallel_tpu.train.trainer import Trainer
from distributed_model_parallel_tpu.utils.profiling import (
    DonationError,
    assert_donation,
)

from tests.conftest import tiny_train_config

pytestmark = pytest.mark.perf


def _dataset(n=96, hw=8, seed=0):
    rng = np.random.default_rng(seed)
    return ArrayDataset(
        images=rng.integers(0, 255, (n, hw, hw, 3), dtype=np.uint8),
        labels=rng.integers(0, 10, n, dtype=np.int32), num_classes=10,
        mean=np.zeros(3, np.float32), std=np.ones(3, np.float32))


# ---------------------------------------------------------------------------
# Device prefetcher: in-flight depth + consumer-driven cursor semantics
# ---------------------------------------------------------------------------

def test_device_prefetcher_keeps_depth_batches_in_flight():
    """At every yield, ``depth`` future batches are already uploaded
    (puts run ahead of consumption by exactly the configured depth)."""
    loader = BatchLoader(_dataset(), 16, shuffle=True, seed=1)
    puts = []

    def put(images, labels):
        puts.append(len(puts))
        return jnp.asarray(images), jnp.asarray(labels)

    dp = DevicePrefetchLoader(loader, put, depth=2)
    consumed = 0
    leads = []
    for images, labels in dp:
        consumed += 1
        leads.append(len(puts) - consumed)
    assert consumed == len(loader)
    # run-ahead held the full depth while batches remained
    assert max(leads) >= 2
    assert dp.last_stats["max_lead"] >= 2
    assert dp.last_stats["puts"] == len(loader)


def test_device_prefetcher_preserves_batch_stream_and_cursor():
    """Same batches, same order as the unwrapped loader — and the
    persistent cursor stays consumer-driven (run-ahead is never counted
    as consumed)."""
    ds = _dataset()
    plain = list(BatchLoader(ds, 16, shuffle=True, seed=5))
    loader = BatchLoader(ds, 16, shuffle=True, seed=5)
    dp = DevicePrefetchLoader(
        loader, lambda im, lb: (jnp.asarray(im), jnp.asarray(lb)), depth=2)
    it = iter(dp)
    for k, (ref_im, ref_lb) in enumerate(plain[:3]):
        im, lb = next(it)
        np.testing.assert_array_equal(np.asarray(im), ref_im)
        np.testing.assert_array_equal(np.asarray(lb), ref_lb)
        loader.position(0, k + 1)   # what the epoch drivers do
    # the prefetcher ran ahead, but the cursor reflects consumption only
    assert loader.state_dict() == {"epoch": 0, "batch_cursor": 3}
    it.close()


def _preempt_cfg(tmp_path, name, **kw):
    base = tiny_train_config(tmp_path / name, epochs=2, eval_every=100,
                             max_inflight_steps=1, log_every_n_steps=1000)
    data = dataclasses.replace(base.data, device_prefetch=2)
    return base.replace(data=data, **kw)


def test_kill_resume_bitwise_with_device_prefetch(tmp_path):
    """The headline safety property of the hot-path rewrite: with the
    device prefetcher running ahead, preempt mid-epoch, restart, and the
    final params are bitwise-identical to a never-interrupted run — the
    run-ahead uploads were never counted as consumed."""
    baseline = Trainer(_preempt_cfg(tmp_path, "base"))
    baseline.fit()

    killed = Trainer(_preempt_cfg(
        tmp_path, "kill",
        recovery=RecoveryConfig(faults=("preempt@4",))))
    killed.fit()
    assert killed._global_step == 5          # 3 steps/epoch, killed at 5
    assert killed.ckpt.exists("preempt")

    resumed = Trainer(_preempt_cfg(tmp_path, "kill", resume=True))
    assert resumed._global_step == 5
    resumed.fit()
    a = jax.tree.leaves(jax.device_get(baseline.state.params))
    b = jax.tree.leaves(jax.device_get(resumed.state.params))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_device_prefetch_matches_prefetch_off(tmp_path):
    """Switching the device prefetcher on changes performance, not math:
    bitwise-identical params after a fit with depth 0 vs depth 2."""
    def run(depth, sub):
        base = tiny_train_config(tmp_path / sub, epochs=1)
        cfg = base.replace(data=dataclasses.replace(
            base.data, device_prefetch=depth))
        t = Trainer(cfg)
        t.fit()
        return jax.tree.leaves(jax.device_get(t.state.params))

    for x, y in zip(run(0, "off"), run(2, "on")):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Donation audit helper
# ---------------------------------------------------------------------------

def test_assert_donation_trips_on_undonated_fn():
    """A jit with no donate_argnums compiles with zero input→output
    aliases — the helper must fail loudly, not shrug."""
    f = jax.jit(lambda s: s * 2.0)
    x = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(DonationError, match="donate_argnums"):
        assert_donation(f, x, min_aliased=1)


def test_assert_donation_trips_on_dropped_donation():
    """A donated buffer XLA cannot alias (no same-shaped output) is a
    DROPPED donation: allowed only when explicitly whitelisted."""
    f = jax.jit(lambda s, extra: (s * 2.0, extra.astype(jnp.float32).sum()),
                donate_argnums=(0, 1))
    s = jnp.zeros((8, 8), jnp.float32)
    extra = jnp.zeros((3, 3), jnp.uint8)
    with pytest.raises(DonationError, match="dropped"):
        assert_donation(f, s, extra, min_aliased=1)
    # whitelisting the batch-buffer dtypes passes (the trainer contract)
    f2 = jax.jit(lambda s, extra: (s * 2.0,
                                   extra.astype(jnp.float32).sum()),
                 donate_argnums=(0, 1))
    rep = assert_donation(f2, s, extra, min_aliased=1,
                          allow_dropped=("uint8",))
    assert rep["n_aliased"] == 1 and rep["dropped"]


def test_assert_donation_passes_on_clean_donation():
    f = jax.jit(lambda s: s + 1.0, donate_argnums=(0,))
    rep = assert_donation(f, jnp.zeros((16, 16), jnp.float32))
    assert rep["n_aliased"] == 1 and not rep["dropped"]


def test_trainer_step_donation_holds(tmp_path):
    """The live gspmd train step: state donation committed (params +
    opt_state alias in place), only the batch buffers dropped."""
    t = Trainer(tiny_train_config(tmp_path, epochs=1))
    images = t.train_ds.images[:32]
    labels = t.train_ds.labels[:32]
    rep = assert_donation(
        t._train_step, t.state, jax.random.key(0),
        *t._shard_batch(images, labels),
        min_aliased=len(jax.tree.leaves(t.state.params)),
        allow_dropped=("uint8", "int32"))
    assert all(d.startswith(("uint8", "int32")) for d in rep["dropped"])


# ---------------------------------------------------------------------------
# Bucketed grads knob
# ---------------------------------------------------------------------------

def test_grad_bucket_mb_trains_and_matches_unbucketed(tmp_path):
    """grad_bucket_mb reaches the DDP grad path (bucketed_psum) and does
    not change the math: identical loss to the per-leaf psum run."""
    def run(sub, **kw):
        cfg = tiny_train_config(tmp_path / sub, strategy="ddp", epochs=1,
                                eval_every=100, **kw)
        t = Trainer(cfg)
        hist = t.fit()
        return hist[0]["loss_train"], t

    loss_plain, _ = run("plain")
    loss_bucketed, t = run("bucketed", grad_bucket_mb=0.0625)
    assert np.isfinite(loss_bucketed)
    assert loss_bucketed == pytest.approx(loss_plain, rel=1e-5)


def test_grad_bucket_mb_rejected_on_gspmd(tmp_path):
    with pytest.raises(ValueError, match="grad_bucket_mb"):
        Trainer(tiny_train_config(tmp_path, grad_bucket_mb=1.0))


def test_grad_bucket_mb_rejected_on_hierarchical(tmp_path):
    """hierarchical_psum_tree has no bucket cap — a configured cap must
    reject, not silently do nothing."""
    with pytest.raises(ValueError, match="hierarchical"):
        Trainer(tiny_train_config(tmp_path, strategy="ddp",
                                  grad_bucket_mb=1.0,
                                  ddp_allreduce="hierarchical"))


def test_batch_donation_warning_suppressed(tmp_path):
    """The known-by-design uint8/int32 batch-buffer drop is filtered by
    the trainer module's shape-anchored filter; a real (float) dropped
    donation would not match it and stays loud."""
    import warnings

    from distributed_model_parallel_tpu.train import trainer as trainer_mod

    t = Trainer(tiny_train_config(tmp_path, epochs=1))
    images = t.train_ds.images[:32]
    labels = t.train_ds.labels[:32]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer_mod._filter_expected_batch_donation_warnings()
        # fresh jit instance → fresh lowering → the warning would fire
        # here if the filter didn't match the real message
        t._train_step(t.state, jax.random.key(0),
                      *t._shard_batch(images, labels))
    assert not [w for w in caught
                if "donated buffers" in str(w.message)]
    # and a float drop is NOT matched by the filter (stays loud); the
    # donated arg must be USED (an unused arg is pruned before lowering)
    f = jax.jit(lambda a, b: b * 2.0 + a.sum(), donate_argnums=(0,))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer_mod._filter_expected_batch_donation_warnings()
        f.lower(jnp.zeros((7, 3), jnp.float32),
                jnp.zeros((2, 2), jnp.float32)).compile()
    assert [w for w in caught if "donated buffers" in str(w.message)]
