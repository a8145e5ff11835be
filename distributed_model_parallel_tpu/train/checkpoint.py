"""Checkpoint / resume.

Capability parity with the reference's best-accuracy checkpointing —
save ``{net, acc, epoch}`` to ``./checkpoint/ckpt.pth`` when val accuracy
improves, restore on ``--resume`` (``data_parallel.py:80-87,143-155``) —
upgraded to the TPU-native form: orbax sharded pytree checkpoints that
save/restore distributed ``jax.Array``s directly (multi-host safe), covering
params, BN state, optimizer state, step and best-acc in one tree.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import time
import zlib
from typing import Any, Callable

import jax
import orbax.checkpoint as ocp

from distributed_model_parallel_tpu.utils.tracing import span
from distributed_model_parallel_tpu.utils.faults import (
    FaultInjector,
    InjectedFaultError,
    tear_checkpoint,
)

# Per-checkpoint integrity manifest, written into each version directory
# once its save has committed: relative path -> {size, crc32} for every
# file, plus an optional ``meta`` stamp (saving mesh shape/axis names,
# global step — the topology record elastic resume reads,
# train/elastic.py). A torn/truncated/partially-copied version fails
# verification and ``restore(..., allow_fallback=True)`` skips it. Absence
# of a manifest is "unverifiable" (legacy / foreign checkpoint), not "bad".
MANIFEST_FILENAME = "dmp_manifest.json"


class CheckpointIntegrityError(RuntimeError):
    """No committed checkpoint version survived verification/restore."""


class TopologyMismatchError(RuntimeError):
    """A checkpoint's *global* array shapes conflict with the restore
    target's — state that genuinely depends on the saving topology (e.g.
    the DDP engine's per-replica BatchNorm stats carry a leading
    ``num_replicas`` axis) cannot be resharded onto a mesh of a different
    degree. Carries both shapes per conflicting leaf; deliberately NOT a
    ``ValueError`` so the trainers' template-layout retry loops don't
    misread it as an EMA-layout mismatch."""

    def __init__(self, conflicts: list, *, saved_mesh=None,
                 current_mesh=None):
        self.conflicts = list(conflicts)
        self.saved_mesh = saved_mesh
        self.current_mesh = current_mesh
        detail = "; ".join(
            f"{path}: checkpoint {tuple(saved)} vs target {tuple(want)}"
            for path, saved, want in self.conflicts[:8])
        mesh = ""
        if saved_mesh or current_mesh:
            mesh = (f" (saved on mesh {saved_mesh}, restoring on "
                    f"{current_mesh})")
        super().__init__(
            f"checkpoint global shapes conflict with the restore target on "
            f"{len(self.conflicts)} leaves{mesh}: {detail}")


def _file_crc32(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                return crc
            crc = zlib.crc32(block, crc)


def write_manifest(path: str, meta: dict | None = None) -> str:
    """Write the integrity manifest for a committed checkpoint directory
    (atomic: temp file + rename). ``meta`` is the caller's stamp (mesh
    shape/axis names, global step); it is recorded verbatim and never
    participates in verification. Returns the manifest path."""
    entries: dict[str, dict] = {}
    for root, _dirs, files in os.walk(path):
        for fn in files:
            if fn == MANIFEST_FILENAME:
                continue
            p = os.path.join(root, fn)
            rel = os.path.relpath(p, path)
            entries[rel] = {"size": os.path.getsize(p),
                            "crc32": _file_crc32(p)}
    out = os.path.join(path, MANIFEST_FILENAME)
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"created": time.time(), "files": entries,
                   "meta": dict(meta or {})}, f)
    os.replace(tmp, out)
    return out


def read_manifest_meta(path: str) -> dict:
    """The ``meta`` stamp of one checkpoint version directory; ``{}`` when
    there is no manifest or no stamp (legacy/foreign checkpoint)."""
    try:
        with open(os.path.join(path, MANIFEST_FILENAME)) as f:
            return dict(json.load(f).get("meta") or {})
    except (OSError, json.JSONDecodeError, ValueError, TypeError):
        return {}


def _keystr(path) -> str:
    """Normalize a jax keypath so a flax-struct attribute, a dict key and a
    tuple index spell the same as orbax's metadata dict-tree paths."""
    parts = []
    for k in path:
        if hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:                    # pragma: no cover - future key types
            parts.append(str(k))
    return "/".join(parts)


def tree_shape_map(tree: Any) -> dict[str, tuple]:
    """``normalized path -> global shape`` for every leaf that has one."""
    import jax.tree_util as jtu

    out = {}
    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            out[_keystr(path)] = tuple(shape)
    return out


def verify_manifest(path: str) -> str | None:
    """Check a checkpoint directory against its manifest.

    Returns ``None`` when every recorded file matches (size + crc32),
    ``"missing"`` when there is no manifest to check (unverifiable, not
    necessarily bad), and a human-readable mismatch reason otherwise.
    """
    mpath = os.path.join(path, MANIFEST_FILENAME)
    if not os.path.exists(mpath):
        return "missing"
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        files = manifest["files"]
    except (json.JSONDecodeError, KeyError, OSError) as e:
        return f"unreadable manifest: {type(e).__name__}"
    for rel, want in files.items():
        p = os.path.join(path, rel)
        if not os.path.exists(p):
            return f"missing file {rel}"
        size = os.path.getsize(p)
        if size != want["size"]:
            return (f"size mismatch on {rel} "
                    f"({size} != {want['size']} bytes)")
        if _file_crc32(p) != want["crc32"]:
            return f"checksum mismatch on {rel}"
    return None


class Checkpointer:
    """Best-acc checkpoint + resume over an orbax StandardCheckpointer.

    Saves may be asynchronous (``wait=False``): orbax copies the arrays to
    host, then persists on a background thread while training continues —
    the step after a checkpoint no longer stalls behind filesystem writes.

    Crash safety: each save writes a fresh ``{name}-{v}`` directory (orbax
    commits it with an atomic rename); older versions are pruned only at the
    *next* save, after confirming the newer one committed, and the newest
    ``keep`` committed versions are retained per slot. So there is never a
    moment with zero committed checkpoints on disk, and a reader in another
    process sees whichever version last committed. ``restore`` / ``exists``
    resolve to the newest committed version (falling back to a bare legacy
    ``{name}`` directory).

    Integrity: once a save commits, an integrity manifest (file sizes +
    crc32 checksums) is written into the version directory.
    ``restore(..., allow_fallback=True)`` verifies each candidate version
    against its manifest (and survives a restore-time failure on
    manifest-less versions) and falls back to the previous committed
    version — the torn-newest-checkpoint recovery path
    (train/resilience.py).

    ``injector`` (utils/faults.py) is the chaos hook: ``save_fail`` /
    ``tear_save`` faults fire at their planned occurrence of the ``save``
    site. Disabled injectors cost one no-op poll per save.
    """

    def __init__(self, directory: str, *, keep: int = 2,
                 injector: FaultInjector | None = None,
                 meta_fn: Callable[[], dict] | None = None):
        self.directory = os.path.abspath(directory)
        self.keep = max(1, int(keep))
        os.makedirs(self.directory, exist_ok=True)
        self._ckpt = ocp.StandardCheckpointer()
        self._injector = injector
        # Stamp every committed version's manifest with this callable's
        # dict (mesh shape, global step — captured at save() call time,
        # not at async commit time): the topology record
        # restore_resharded / train/elastic.py read back.
        self.meta_fn = meta_fn
        # (path, meta) pairs whose manifest still needs writing once the
        # (possibly asynchronous) save commits.
        self._pending_manifest: list[tuple[str, dict]] = []
        # Version directory the last restore_resharded actually read —
        # may be an OLDER version than the slot's newest after a
        # torn-newest fallback, so provenance (read_manifest_meta) must
        # come from here, not from manifest_meta(name).
        self.last_restored_path: str | None = None

    def _path(self, name: str, version: int | None = None) -> str:
        leaf = name if version is None else f"{name}-{version}"
        return os.path.join(self.directory, leaf)

    def _versions(self, name: str) -> list[int]:
        """Committed version numbers for ``name``, ascending. Orbax tmp dirs
        carry a ``.orbax-checkpoint-tmp`` suffix and never match."""
        pat = re.compile(re.escape(name) + r"-(\d+)$")
        out = []
        for entry in os.listdir(self.directory):
            m = pat.match(entry)
            if m and os.path.isdir(os.path.join(self.directory, entry)):
                out.append(int(m.group(1)))
        return sorted(out)

    def _latest_path(self, name: str) -> str | None:
        versions = self._versions(name)
        if versions:
            return self._path(name, versions[-1])
        legacy = self._path(name)
        return legacy if os.path.exists(legacy) else None

    def _candidate_paths(self, name: str) -> list[str]:
        """Restore candidates, newest committed version first, legacy bare
        directory last."""
        out = [self._path(name, v)
               for v in sorted(self._versions(name), reverse=True)]
        legacy = self._path(name)
        if os.path.exists(legacy):
            out.append(legacy)
        return out

    def save(self, tree: Any, name: str = "ckpt", *, force: bool = True,
             wait: bool = True, keep: int | None = None,
             meta: dict | None = None) -> str:
        # Checkpoint I/O on the span timeline (utils/tracing.py): saves
        # sit on a trainer's critical path, so a slow disk shows up as a
        # wide checkpoint_save bar, not an anonymous step-time bump.
        with span("checkpoint_save", slot=name, wait=wait):
            return self._save(tree, name, wait=wait, keep=keep, meta=meta)

    def _save(self, tree: Any, name: str, *, wait: bool,
              keep: int | None, meta: dict | None) -> str:
        self.wait_until_finished()  # the previous save has committed...
        versions = self._versions(name)
        # Retention is strictly per-slot: the version scan matches
        # ``{name}-{v}`` exactly, so rotating one slot (the per-epoch
        # "ckpt"/"good" saves) can never garbage-collect another (the
        # emergency slot) — tests/test_elastic.py pins this. ``keep``
        # overrides the default for this slot's own rotation.
        keep_n = max(1, int(keep)) if keep is not None else self.keep
        for v in versions[:-keep_n]:      # ...keep the newest K, prune older
            shutil.rmtree(self._path(name, v), ignore_errors=True)
        if versions and os.path.exists(self._path(name)):
            # A versioned save has committed, so a bare legacy `{name}` dir
            # (pre-versioning format) is stale — prune it too.
            shutil.rmtree(self._path(name), ignore_errors=True)
        next_v = versions[-1] + 1 if versions else 0
        path = self._path(name, next_v)
        faults = (self._injector.poll("save")
                  if self._injector is not None else [])
        if any(s.kind == "save_fail" for s in faults):
            # Die "mid-write": a torn version directory appears committed
            # to the version scan but holds no restorable checkpoint.
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, "_DMP_TORN"), "w") as f:
                f.write("injected save failure\n")
            raise InjectedFaultError(f"injected save failure for {path}")
        tear = any(s.kind == "tear_save" for s in faults)
        self._ckpt.save(path, tree)
        stamp = dict(self.meta_fn() or {}) if self.meta_fn is not None else {}
        if meta:
            stamp.update(meta)
        self._pending_manifest.append((path, stamp))
        if wait or tear:
            self.wait_until_finished()
        if tear:
            tear_checkpoint(path)
        return path

    def wait_until_finished(self) -> None:
        """Block until any asynchronous save has fully committed, then
        write the integrity manifests for the newly committed versions."""
        self._ckpt.wait_until_finished()
        while self._pending_manifest:
            path, stamp = self._pending_manifest.pop()
            if os.path.isdir(path):
                write_manifest(path, meta=stamp)

    def manifest_meta(self, name: str = "ckpt") -> dict:
        """The newest committed version's manifest ``meta`` stamp (saving
        mesh, global step); ``{}`` when absent."""
        self.wait_until_finished()
        path = self._latest_path(name)
        return read_manifest_meta(path) if path is not None else {}

    def restore(self, target: Any, name: str = "ckpt", *,
                allow_fallback: bool = False,
                on_fallback: Callable[[str, str], None] | None = None) -> Any:
        """Restore the newest committed version into the structure/shardings
        of ``target`` (an abstract or concrete pytree). Raises
        FileNotFoundError if absent.

        With ``allow_fallback=True`` each candidate version (newest first)
        is verified against its integrity manifest before the restore is
        attempted, and a torn/corrupt/unrestorable version is skipped in
        favor of the previous committed one; ``on_fallback(path, reason)``
        observes every rejection (the supervisor turns it into
        failure/recovery telemetry). CheckpointIntegrityError when no
        version survives.
        """
        with span("checkpoint_restore", slot=name):
            return self._restore(target, name, allow_fallback=allow_fallback,
                                 on_fallback=on_fallback)

    def _restore(self, target: Any, name: str, *, allow_fallback: bool,
                 on_fallback: Callable[[str, str], None] | None) -> Any:
        self.wait_until_finished()
        candidates = self._candidate_paths(name)
        if not candidates:
            raise FileNotFoundError(self._path(name))
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target)
        if not allow_fallback:
            return self._ckpt.restore(candidates[0], abstract)
        rejected: list[tuple[str, str]] = []
        for path in candidates:
            reason = verify_manifest(path)
            if reason is None:
                # Verified intact: a restore error here is a template /
                # structure problem (e.g. resuming under a different
                # config), not corruption — an older version of the same
                # run can't fix that, so fail fast with orbax's error.
                return self._ckpt.restore(path, abstract)
            if reason != "missing":
                rejected.append((path, reason))
                if on_fallback is not None:
                    on_fallback(path, reason)
                continue
            # Unverifiable (no manifest — legacy or foreign checkpoint):
            # attempt the restore and treat failure as a torn version.
            try:
                return self._ckpt.restore(path, abstract)
            except Exception as e:  # noqa: BLE001 - fall back on any failure
                detail = f"restore failed: {type(e).__name__}: {e}"
                rejected.append((path, detail))
                if on_fallback is not None:
                    on_fallback(path, detail)
        raise CheckpointIntegrityError(
            f"no restorable version of {name!r} in {self.directory}: "
            + "; ".join(f"{os.path.basename(p)} ({r[:160]})"
                        for p, r in rejected))

    def _check_topology(self, path: str, target: Any) -> None:
        """Raise :class:`TopologyMismatchError` when the checkpoint's
        *global* leaf shapes conflict with ``target``'s. Global shapes are
        mesh-independent for replicated/DDP/FSDP leaves (sharding splits a
        fixed global array), so a conflict means the state itself encodes
        the saving topology and cannot be resharded. Structure differences
        (missing/extra leaves) are left for the restore itself to report —
        they are template-layout problems, not topology ones. A metadata
        read failure is ignored here: the restore attempt will surface it
        through the normal fallback machinery."""
        try:
            saved = tree_shape_map(ocp.PyTreeCheckpointer().metadata(
                path).item_metadata.tree)
        except Exception:  # noqa: BLE001 - torn version, fallback handles it
            return
        want = tree_shape_map(target)
        conflicts = [(k, saved[k], want[k]) for k in sorted(want)
                     if k in saved and tuple(saved[k]) != tuple(want[k])]
        if conflicts:
            raise TopologyMismatchError(
                conflicts, saved_mesh=read_manifest_meta(path).get("mesh"))

    def restore_resharded(self, target: Any, name: str = "ckpt", *,
                          allow_fallback: bool = True,
                          on_fallback: Callable[[str, str], None] | None = None,
                          verify_memo: dict | None = None) -> Any:
        """Topology-change-resilient restore: bring the newest committed
        version into the shardings of ``target`` — the *current* mesh's —
        regardless of the mesh it was saved under (a dp=8 checkpoint
        restores onto the degraded dp=4 slice a preempted TPU job got
        back). Mechanically: explicit per-leaf restore args carrying the
        target's shardings, so orbax never consults the sharding file
        written at save time (whose devices need not exist anymore).

        Global shapes must agree leaf-by-leaf; a genuine conflict (state
        that encodes the saving topology, e.g. DDP per-replica BN stats)
        raises :class:`TopologyMismatchError` with both shapes — and raises
        it *through* the fallback loop, because every version of the same
        run shares the conflict. Torn versions fall back exactly like
        :meth:`restore`.

        ``verify_memo`` caches per-path manifest verification (a full-file
        CRC sweep) across calls: elastic resume tries several template
        layouts against the same slot and must not re-read a multi-GB
        checkpoint directory once per layout (train/elastic.py).
        """
        with span("checkpoint_restore", slot=name, resharded=True):
            return self._restore_resharded(
                target, name, allow_fallback=allow_fallback,
                on_fallback=on_fallback, verify_memo=verify_memo)

    def _restore_resharded(self, target: Any, name: str, *,
                           allow_fallback: bool,
                           on_fallback: Callable[[str, str], None] | None,
                           verify_memo: dict | None) -> Any:
        self.wait_until_finished()
        candidates = self._candidate_paths(name)
        if not candidates:
            raise FileNotFoundError(self._path(name))
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target)
        restore_args = ocp.checkpoint_utils.construct_restore_args(target)

        def _verify(path):
            if verify_memo is None:
                return verify_manifest(path)
            if path not in verify_memo:
                verify_memo[path] = verify_manifest(path)
            return verify_memo[path]

        def _restore(path):
            out = ocp.PyTreeCheckpointer().restore(
                path, args=ocp.args.PyTreeRestore(item=abstract,
                                                  restore_args=restore_args))
            self.last_restored_path = path
            return out

        if not allow_fallback:
            self._check_topology(candidates[0], target)
            return _restore(candidates[0])
        rejected: list[tuple[str, str]] = []
        for path in candidates:
            reason = _verify(path)
            if reason is not None and reason != "missing":
                rejected.append((path, reason))
                if on_fallback is not None:
                    on_fallback(path, reason)
                continue
            self._check_topology(path, target)
            if reason is None:
                # Verified intact: a restore failure here is structural
                # (wrong config/template), not corruption — fail fast.
                return _restore(path)
            try:
                return _restore(path)
            except Exception as e:  # noqa: BLE001 - unverifiable version
                detail = f"restore failed: {type(e).__name__}: {e}"
                rejected.append((path, detail))
                if on_fallback is not None:
                    on_fallback(path, detail)
        raise CheckpointIntegrityError(
            f"no restorable version of {name!r} in {self.directory}: "
            + "; ".join(f"{os.path.basename(p)} ({r[:160]})"
                        for p, r in rejected))

    def restore_subtree(self, target: Any, name: str = "ckpt") -> Any:
        """Restore only the top-level keys present in ``target`` (a dict),
        e.g. just the params of a full train-state checkpoint for
        inference. Uses orbax partial restore: only the requested subtrees
        are read from storage — a params-only restore never materializes
        the (larger) optimizer state."""
        self.wait_until_finished()
        path = self._latest_path(name)
        if path is None:
            raise FileNotFoundError(self._path(name))
        tree = self._ckpt.metadata(path).item_metadata.tree
        missing = [k for k in target if k not in tree]
        if missing:
            raise KeyError(f"checkpoint {path} has no keys {missing}; "
                           f"available: {sorted(tree)}")
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, target)
        # Explicit per-leaf restore args carrying the TARGET's shardings:
        # without them PyTreeRestore falls back to the sharding file
        # written at save time, which breaks the moment the restoring
        # process has a different topology (e.g. a checkpoint trained on
        # an 8-device mesh restored for single-device inference —
        # scripts/generate.py's whole use case).
        restore_args = ocp.checkpoint_utils.construct_restore_args(target)
        return ocp.PyTreeCheckpointer().restore(
            path, args=ocp.args.PyTreeRestore(item=abstract,
                                              restore_args=restore_args,
                                              partial_restore=True))

    def exists(self, name: str = "ckpt") -> bool:
        self.wait_until_finished()
        return self._latest_path(name) is not None

    def names_by_recency(self, names: tuple[str, ...]) -> list[str]:
        """The subset of ``names`` with a committed version on disk,
        ordered newest-first by the latest version's mtime — the slot
        preference order elastic resume walks (train/elastic.py)."""
        self.wait_until_finished()
        stamped = []
        for name in names:
            path = self._latest_path(name)
            if path is not None:
                stamped.append((os.path.getmtime(path), name))
        return [name for _, name in sorted(stamped, reverse=True)]

    def newest_name(self, names: tuple[str, ...]) -> str | None:
        """The name whose latest committed version is most recent on disk
        (by mtime); None if none exist."""
        ordered = self.names_by_recency(names)
        return ordered[0] if ordered else None
