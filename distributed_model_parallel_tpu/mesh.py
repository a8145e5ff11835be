"""Device discovery and mesh construction.

Replaces the reference's process bootstrap — ``mp.spawn`` +
``dist.init_process_group('nccl', 'tcp://127.0.0.1:1224')`` +
``torch.cuda.set_device(rank)`` (reference ``model_parallel.py:57-62,162``) —
with the TPU-native runtime: ``jax.distributed.initialize`` for multi-host
rendezvous and a ``jax.sharding.Mesh`` with named axes for everything else.
All parallelism in this framework is expressed as PartitionSpecs over these
axes; XLA inserts the collectives (psum/ppermute/all_gather) over ICI/DCN.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.config import MeshConfig

# Name of the cross-host (slow-network) sub-axis of data parallelism; it
# exists in the mesh only when MeshConfig.dcn_data > 1.
DCN_AXIS = "dcn"


def best_effort_distributed_init() -> bool:
    """Initialize the multi-host JAX runtime if the environment asks for it.

    The reference requires explicit ``--dist-url``/``--world-size`` flags and a
    TCP rendezvous even on one node (``model_parallel.py:19-24,57``). On TPU,
    single-host needs nothing, and multi-host pods are auto-detected by
    ``jax.distributed.initialize()`` from the cluster environment. Returns True
    if a multi-process runtime was initialized.
    """
    want = os.environ.get("DMP_TPU_DISTRIBUTED", "auto")
    if want == "0":
        return False
    if jax.distributed.is_initialized():
        return True
    if want == "1" or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        # Asked for: a failure here propagates — carrying on as one
        # process would train a different job than the one requested.
        jax.distributed.initialize()
        return True
    return False


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A constructed mesh plus canonical PartitionSpecs.

    Axis order is (data, stage, model, seq, expert) with size-1 axes kept in
    the mesh (they cost nothing and keep PartitionSpecs uniform).
    """

    mesh: Mesh
    config: MeshConfig

    # -- canonical axis names ------------------------------------------------
    @property
    def data_axis(self) -> str | tuple[str, str]:
        """Axis (or axes) replicas span. With ``dcn_data > 1`` the mesh has a
        real leading ``"dcn"`` axis and this returns ``("dcn", data_axis)`` —
        PartitionSpecs and collectives accept the tuple everywhere a single
        name is legal, so DP/DDP/FSDP code is hierarchy-agnostic, while
        two-level code can address ``dcn_axis``/``ici_data_axis`` separately.
        """
        if self.config.dcn_data > 1:
            return (DCN_AXIS, self.config.data_axis)
        return self.config.data_axis

    @property
    def data_axes(self) -> tuple[str, ...]:
        """``data_axis`` normalized to a tuple — the spelling collectives
        and shard_map axis lists want regardless of whether the data axis
        is flat or dcn-factored. ``num_data`` is the replica count over
        exactly these axes (the dcn factor included)."""
        da = self.data_axis
        return (da,) if isinstance(da, str) else tuple(da)

    @property
    def dcn_axis(self) -> str | None:
        """The cross-host sub-axis of data parallelism (None on one host)."""
        return DCN_AXIS if self.config.dcn_data > 1 else None

    @property
    def ici_data_axis(self) -> str:
        """The within-host sub-axis of data parallelism."""
        return self.config.data_axis

    @property
    def stage_axis(self) -> str:
        return self.config.stage_axis

    @property
    def model_axis(self) -> str:
        return self.config.model_axis

    @property
    def seq_axis(self) -> str:
        return self.config.seq_axis

    @property
    def expert_axis(self) -> str:
        return self.config.expert_axis

    # -- canonical shardings -------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharded(self) -> NamedSharding:
        """Batch-dim sharding: the TPU equivalent of DataParallel's ``scatter``
        (reference ``Readme.md:20,28-29``)."""
        return NamedSharding(self.mesh, P(self.data_axis))

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def num_data(self) -> int:
        return self.config.data

    @property
    def num_stages(self) -> int:
        return self.config.stage

    def stage_devices(self) -> list[jax.Device]:
        """One representative device per pipeline stage (data index 0).

        Used by the per-stage pipeline runtime (parallel/pipeline.py) for
        computation-follows-data placement.
        """
        devs = np.asarray(self.mesh.devices)
        axes = list(self.mesh.axis_names)
        idx = [slice(None) if a == self.stage_axis else 0 for a in axes]
        return list(np.atleast_1d(devs[tuple(idx)]).ravel())


def make_mesh(config: MeshConfig | None = None,
              devices: Sequence[jax.Device] | None = None) -> MeshSpec:
    """Build a named mesh from a MeshConfig.

    If ``config`` is None, all local devices go on the data axis — mirroring
    the reference's default of one DP replica per visible GPU
    (``data_parallel.py:77``, ``model_parallel.py:20``).
    """
    if devices is None:
        devices = jax.devices()
    if config is None:
        config = MeshConfig(data=len(devices))
    n = config.num_devices
    if n > len(devices):
        raise ValueError(
            f"mesh needs {n} devices ({config.axis_sizes()}), "
            f"only {len(devices)} available")
    shape = (config.data, config.stage, config.model, config.seq, config.expert)
    names = (config.data_axis, config.stage_axis, config.model_axis,
             config.seq_axis, config.expert_axis)
    if config.dcn_data < 1:
        raise ValueError(f"dcn_data must be >= 1, got {config.dcn_data}")
    if config.dcn_data > 1:
        # The data axis factors into a real leading "dcn" (cross-host) axis
        # and a within-host remainder, so shardings can span both
        # (MeshSpec.data_axis) and collectives can stage hierarchically.
        if config.data % config.dcn_data:
            raise ValueError(
                f"dcn_data={config.dcn_data} must divide data={config.data}")
        if DCN_AXIS in names:
            raise ValueError(f"axis name {DCN_AXIS!r} is reserved for dcn_data")
        shape = (config.dcn_data, config.data // config.dcn_data) + shape[1:]
        names = (DCN_AXIS,) + names
        if jax.process_count() > 1:
            # Real multi-host: let mesh_utils place the DCN granules along
            # process boundaries and optimize the ICI layout within each.
            from jax.experimental import mesh_utils

            grid = mesh_utils.create_hybrid_device_mesh(
                shape[1:], (config.dcn_data, 1, 1, 1, 1),
                devices=devices[:n], process_is_granule=True).reshape(shape)
            return MeshSpec(mesh=Mesh(grid, names), config=config)
    # Single process (or flat mesh): contiguous device-id blocks stand in
    # for hosts — the leading (dcn, data) reshape is host-major by
    # construction.
    grid = np.asarray(devices[:n]).reshape(shape)
    return MeshSpec(mesh=Mesh(grid, names), config=config)


def host_local_batch_to_global(batch, spec: MeshSpec,
                               sharding: NamedSharding | None = None):
    """Assemble a global sharded array from per-process local data.

    Multi-host form of the reference's rank-0-only data loading
    (``model_parallel.py:89-97`` loads on every rank and uses it on one):
    each host loads only its slice of the global batch and
    ``jax.make_array_from_process_local_data`` stitches the global
    ``jax.Array`` across hosts. On a single process this degenerates to a
    plain ``device_put``.
    """
    if sharding is None:
        sharding = spec.batch_sharded()
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        batch)


def local_batch_slice(global_batch: int, spec: MeshSpec) -> int:
    """Per-data-shard batch size; errors on uneven split (static shapes)."""
    d = spec.num_data
    if global_batch % d:
        raise ValueError(f"global batch {global_batch} not divisible by data={d}")
    return global_batch // d


class StragglerTimeoutError(RuntimeError):
    """A barrier/collective did not complete within its budget: one
    participant (host or device) is wedged or gone. Raised by
    :func:`barrier_with_timeout` so the caller reports a straggler event
    instead of hanging forever — the reference's failure mode
    (``dist.recv`` blocks eternally on a dead rank,
    ``distributed_layers.py:20``)."""


def barrier_with_timeout(fn, timeout_s: float, *, what: str = "barrier",
                         on_timeout=None):
    """Run the blocking rendezvous ``fn()`` with a wall-clock budget.

    ``fn`` (e.g. ``ops.collectives.mesh_barrier``) runs on a daemon worker
    thread; if it completes within ``timeout_s`` its result is returned
    (or its exception re-raised). On timeout, ``on_timeout(what,
    timeout_s)`` is invoked (telemetry hook) and
    :class:`StragglerTimeoutError` is raised. The wedged call itself
    cannot be cancelled — the worker thread is left blocked (daemonized,
    so it never holds up process exit); the point is that the *caller*
    gets control back to record the straggler and escalate, instead of
    inheriting the hang.
    """
    import threading

    box: dict = {}
    done = threading.Event()

    def _run():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised on the caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_run, daemon=True,
                         name=f"dmp-barrier-{what}")
    t.start()
    if not done.wait(timeout_s):
        if on_timeout is not None:
            on_timeout(what, timeout_s)
        raise StragglerTimeoutError(
            f"{what} did not complete within {timeout_s:.1f}s — a "
            f"participant is wedged or missing (straggler)")
    if "error" in box:
        raise box["error"]
    return box.get("result")
