"""CANARY: pinned JAX-internal semantics the 1F1B backward relies on.

``parallel/spmd_pipeline.make_1f1b_loss_and_grad`` hand-rolls ``jax.vjp``
INSIDE a ``shard_map(..., check_vma=False)`` body and corrects the result
with two empirically pinned facts about how psum transposes there (this
test NAMES the assumption instead of leaving it to the full parity suite):

1. transpose(psum) = psum — so a cotangent that is REPLICATED across the
   axis comes back inflated by exactly ``axis_size`` after one in-body
   vjp through ``psum``. The 1F1B engine compensates by pre-scaling the
   loss-side cotangent by ``1 / (n_model * n_expert)``
   (spmd_pipeline.py, "Gradient correctness under check_vma=False").
2. A DEVICE-VARYING cotangent transposes to the true cross-device sum —
   deeper chained psums need no extra correction.

If either assertion here starts failing after a JAX upgrade, the 1F1B
backward's ``1/(n_model*n_expert)`` rescale (and the final per-leaf psum
over missing axes) is computing WRONG GRADIENTS even though it may still
run without error. Fix site: spmd_pipeline.make_1f1b_loss_and_grad's
cotangent scaling; parity gate: tests/test_spmd_1f1b.py.

These probes are five-line shard_maps, deliberately free of pipeline
machinery, so a failure points at the moved JAX semantics and nothing
else.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_model_parallel_tpu.config import MeshConfig
from distributed_model_parallel_tpu.mesh import make_mesh

AXIS_SIZE = 4


def _mesh():
    return make_mesh(MeshConfig(data=AXIS_SIZE)).mesh


def test_psum_transpose_inflates_replicated_cotangent():
    mesh = _mesh()

    def body(x):
        y, vjp = jax.vjp(lambda v: jax.lax.psum(v, "data"), x)
        (gx,) = vjp(jnp.ones_like(y))          # replicated cotangent
        return gx

    x = jnp.ones((AXIS_SIZE, 2))
    gx = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)(x)
    np.testing.assert_allclose(
        np.asarray(gx), AXIS_SIZE * np.ones((AXIS_SIZE, 2)),
        err_msg=(
            "PINNED SEMANTICS MOVED: in-body jax.vjp through lax.psum "
            "under shard_map(check_vma=False) no longer inflates a "
            "replicated cotangent by axis_size (transpose(psum)=psum). "
            "The 1F1B backward's 1/(n_model*n_expert) cotangent rescale "
            "in parallel/spmd_pipeline.make_1f1b_loss_and_grad is built "
            "on this exact factor — its gradients are now WRONG. "
            "Re-derive the scaling there, then re-run the parity gate "
            "tests/test_spmd_1f1b.py."))


def test_all_gather_rows_follow_axis_index_order():
    """PINNED SEMANTICS the consistency sentinel relies on
    (train/consistency.py): ``lax.all_gather(x, axis, tiled=False)``
    inside ``shard_map(check_vma=False)`` stacks participants' values in
    AXIS-INDEX order. The sentinel's fingerprint rows are read as
    "row i = replica i" when it identifies the outlier to repair and the
    good replica to re-broadcast from (its ``good_idx`` dynamic index,
    and utils/faults._combined_replica_index's target) — if gather order
    ever decouples from axis_index, the sentinel would repair FROM a
    corrupted replica while reporting the wrong one. Fix site:
    ConsistencySentinel._fingerprint_fn/_repair_fn row indexing."""
    mesh = _mesh()

    def body(_):
        mine = jax.lax.axis_index("data").astype(jnp.float32)[None]
        return jax.lax.all_gather(mine, "data", axis=0, tiled=False)

    out = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P(), check_vma=False)(
        jnp.zeros((AXIS_SIZE,)))
    np.testing.assert_array_equal(
        np.asarray(out).ravel(), np.arange(AXIS_SIZE, dtype=np.float32),
        err_msg="PINNED SEMANTICS MOVED: all_gather row order != "
                "axis_index order; the consistency sentinel's replica "
                "identification and re-broadcast source are now wrong.")


def test_all_gather_rows_follow_combined_index_order_hierarchical():
    """Same pin as above for the dcn-factored DATA AXIS TUPLE: gathering
    over ("dcn", "data") must stack rows in the row-major combined index
    order ``axis_index(dcn) * |data| + axis_index(data)`` — the exact
    arithmetic of utils/faults._combined_replica_index and the sentinel's
    replica-row addressing. If multi-axis gather order ever decouples
    from it, the sentinel on a multi-host (dcn_data > 1) mesh convicts
    the wrong replica and re-broadcasts FROM the corrupted one. Fix
    site: ConsistencySentinel._fingerprint_fn/_repair_fn +
    _combined_replica_index."""
    from distributed_model_parallel_tpu.mesh import make_mesh as mk

    spec = mk(MeshConfig(data=4, dcn_data=2))
    axes = ("dcn", "data")

    def body(_):
        mine = (jax.lax.axis_index("dcn") * jax.lax.psum(1, "data")
                + jax.lax.axis_index("data")).astype(jnp.float32)[None]
        return jax.lax.all_gather(mine, axes, axis=0, tiled=False)

    out = jax.shard_map(body, mesh=spec.mesh, in_specs=P(axes),
                        out_specs=P(), check_vma=False)(jnp.zeros((4,)))
    np.testing.assert_array_equal(
        np.asarray(out).ravel(), np.arange(4, dtype=np.float32),
        err_msg="PINNED SEMANTICS MOVED: tuple-axis all_gather row order "
                "!= row-major combined axis_index order; the consistency "
                "sentinel's outlier identification and re-broadcast "
                "source are wrong on dcn-factored meshes.")


def test_claimed_replicated_output_keeps_divergent_shards():
    """PINNED SEMANTICS the corruption faults and the sentinel's whole
    detection premise rely on: a ``shard_map(..., out_specs=P(),
    check_vma=False)`` output whose per-device values DIFFER keeps each
    device's own buffer — no hidden re-broadcast or canonicalization
    "fixes" the divergence. This is what lets utils/faults.
    corrupt_one_replica materialize a lying replica for chaos tests, and
    what makes a real silently-corrupted buffer observable to the
    fingerprint at the next check instead of being silently papered over.
    If this fails after a JAX upgrade, the corruption faults inject
    nothing and every consistency test passes vacuously — fix site:
    utils/faults.corrupt_one_replica + train/consistency.py."""
    mesh = _mesh()

    def body(x):
        idx = jax.lax.axis_index("data")
        return jnp.where(idx == AXIS_SIZE - 1, x + 100.0, x)

    y = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False))(
        jnp.arange(4, dtype=jnp.float32))
    vals = {}
    for s in y.addressable_shards:
        vals[s.device.id] = np.asarray(s.data)[0]
    diverged = [d for d, v in vals.items() if v != 0.0]
    assert len(vals) == AXIS_SIZE and len(diverged) == 1, (
        "PINNED SEMANTICS MOVED: per-device divergence under a "
        "replicated out_spec no longer survives to the jax.Array "
        "shards — corrupt_one_replica can no longer simulate SDC and "
        "the sentinel's detection premise is void.")


def test_psum_transpose_sums_device_varying_cotangent():
    mesh = _mesh()

    def body(x, ct):
        y, vjp = jax.vjp(lambda v: jax.lax.psum(v, "data"), x)
        (gx,) = vjp(ct)                        # device-varying cotangent
        return gx

    x = jnp.ones((AXIS_SIZE, 2))
    # shard i carries cotangent value i -> every shard's grad must be the
    # cross-device sum 0+1+2+3.
    ct = jnp.repeat(jnp.arange(AXIS_SIZE, dtype=jnp.float32), 2
                    ).reshape(AXIS_SIZE, 2)
    gx = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=P("data"), check_vma=False)(x, ct)
    expect = np.full((AXIS_SIZE, 2), float(sum(range(AXIS_SIZE))))
    np.testing.assert_allclose(
        np.asarray(gx), expect,
        err_msg=(
            "PINNED SEMANTICS MOVED: in-body vjp through lax.psum under "
            "shard_map(check_vma=False) no longer turns a device-varying "
            "cotangent into the cross-device sum. Chained per-stage vjps "
            "in parallel/spmd_pipeline.make_1f1b_loss_and_grad assume "
            "this; its tp/sp gradient psums are now wrong. Re-derive, "
            "then re-run tests/test_spmd_1f1b.py."))
