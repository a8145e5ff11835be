"""Host-side batching + on-device augmentation.

The reference pairs torchvision CPU transforms (random crop 32/pad 4, h-flip,
normalize; ``data_parallel.py:31-40``) with a multi-worker DataLoader
(``data_parallel.py:44-51``). The TPU-native design moves augmentation onto
the accelerator — `augment_batch` is pure jnp, fused by XLA into the train
step, leaving the host loop to shuffle indices and hand over uint8 batches
(cheap, bandwidth-friendly: normalization happens on-device so the wire
carries uint8, 4x less than float32).

Static shapes: the loader drops the last partial batch (`drop_last`
semantics), so every step compiles once.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.data.registry import ArrayDataset


class BatchLoader:
    """Epoch-shuffled uint8 batch iterator over an ArrayDataset.

    ``use_native=True`` assembles batches with the C++ row-gather
    (data/native.py); falls back to numpy fancy indexing transparently.

    Shuffle order is **stateless**: epoch ``e``'s permutation is derived
    from ``default_rng((seed, e))``, never from a consumed rng stream —
    epoch N's batch order is identical whether or not epochs 0..N-1 were
    ever iterated. That makes the loader's position a two-integer resume
    state (``state_dict``/``load_state_dict``: epoch + batch cursor), the
    property elastic resume (train/elastic.py) is built on: a run killed
    mid-epoch restarts at the exact next batch with nothing replayed or
    skipped.

    Position protocol: iteration itself never moves the persistent cursor
    (with a PrefetchLoader in front, the producer runs ahead of what the
    trainer actually consumed) except at clean exhaustion, which advances
    to the next epoch. The epoch drivers call :meth:`set_epoch` at epoch
    start and :meth:`position` after each *consumed* batch, so the cursor
    always reflects training progress, not prefetch progress.
    """

    def __init__(self, ds: ArrayDataset, batch_size: int, *,
                 shuffle: bool = True, seed: int = 0, drop_last: bool = True,
                 use_native: bool = False, num_workers: int = 4,
                 shard_by_process: bool = False):
        if batch_size > len(ds):
            raise ValueError(
                f"batch size {batch_size} exceeds dataset size {len(ds)}")
        self.ds = ds
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.use_native = use_native
        self.num_workers = num_workers
        self.seed = seed
        self._epoch = 0
        self._cursor = 0          # batches of self._epoch already consumed
        # Multi-process feeding: every process draws the *same* global batch
        # order (the rng seed is config-fixed, so permutations agree), but
        # materializes only its contiguous slice of each batch — the local
        # shard ``mesh.host_local_batch_to_global`` stitches into the global
        # array. Mirrors the per-rank DistributedSampler role in the
        # reference's multi-process runs (model_parallel.py:89-97).
        self.process_index = jax.process_index() if shard_by_process else 0
        self.process_count = jax.process_count() if shard_by_process else 1
        if batch_size % self.process_count:
            raise ValueError(
                f"batch size {batch_size} not divisible by process count "
                f"{self.process_count}")

    def __len__(self) -> int:
        n = len(self.ds)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    # -- resume position ----------------------------------------------------
    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def cursor(self) -> int:
        return self._cursor

    def set_epoch(self, epoch: int) -> None:
        """Position at the start of ``epoch`` — unless already positioned
        *inside* that epoch (a mid-epoch ``load_state_dict``), in which
        case the loaded cursor is preserved. Epoch drivers call this at
        the top of every training epoch."""
        if epoch != self._epoch:
            self._epoch, self._cursor = int(epoch), 0

    def position(self, epoch: int, batch_cursor: int) -> None:
        """Authoritative position update from the consumer: ``batch_cursor``
        batches of ``epoch`` have been consumed. Called by the epoch
        drivers after each dispatched step — the iterator cannot track this
        itself because a PrefetchLoader produces ahead of consumption."""
        self._epoch, self._cursor = int(epoch), int(batch_cursor)

    def state_dict(self) -> dict:
        """Resume state. A fully-consumed epoch is normalized to the start
        of the next one, so "end of epoch e" and "start of epoch e+1" are
        the same position."""
        ep, cur = self._epoch, self._cursor
        if cur >= len(self):
            ep, cur = ep + 1, 0
        return {"epoch": int(ep), "batch_cursor": int(cur)}

    def load_state_dict(self, state: Mapping) -> None:
        ep, cur = int(state["epoch"]), int(state["batch_cursor"])
        if ep < 0 or cur < 0 or cur > len(self):
            raise ValueError(
                f"invalid loader state epoch={ep} batch_cursor={cur} "
                f"(epoch has {len(self)} batches)")
        if cur >= len(self):
            ep, cur = ep + 1, 0
        self._epoch, self._cursor = ep, cur

    def epoch_indices(self, epoch: int | None = None) -> np.ndarray:
        """The (possibly shuffled) sample order for ``epoch`` (default: the
        current position's epoch). Shared by the materializing iterator
        below and the device-resident fast path (train/trainer.py), so both
        see identical batch composition. Stateless: derived from
        ``(seed, epoch)`` only."""
        n = len(self.ds)
        if not self.shuffle:
            return np.arange(n)
        e = self._epoch if epoch is None else int(epoch)
        return np.random.default_rng((self.seed, e)).permutation(n)

    def _local_slice(self, sel: np.ndarray) -> np.ndarray:
        """This process's contiguous rows of one global batch's indices."""
        if self.process_count == 1:
            return sel
        if len(sel) % self.process_count:
            # Only reachable on a drop_last=False final partial batch (the
            # constructor validates batch_size itself): silently flooring
            # would drop samples and break the "same global batch stream as
            # single-process" invariant.
            raise ValueError(
                f"partial batch of {len(sel)} rows not divisible by "
                f"process count {self.process_count}; use drop_last=True "
                f"or pad the dataset")
        local = len(sel) // self.process_count
        return sel[self.process_index * local:(self.process_index + 1) * local]

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        n = len(self.ds)
        epoch, start = self._epoch, self._cursor
        idx = self.epoch_indices(epoch)
        stop = (n // self.batch_size) * self.batch_size if self.drop_last else n
        # The native row-gather operates on materialized arrays; for a lazy
        # (file-backed) dataset, fancy indexing IS the batch decode
        # (LazyImageArray thread pool), so use_native does not apply.
        if self.use_native and not getattr(self.ds, "is_lazy", False):
            from distributed_model_parallel_tpu.data import native
            for lo in range(start * self.batch_size, stop, self.batch_size):
                sel = self._local_slice(idx[lo:lo + self.batch_size])
                yield (native.gather_rows(self.ds.images, sel,
                                          n_threads=self.num_workers),
                       self.ds.labels[sel])
        else:
            for lo in range(start * self.batch_size, stop, self.batch_size):
                sel = self._local_slice(idx[lo:lo + self.batch_size])
                yield self.ds.images[sel], self.ds.labels[sel]
        # Clean exhaustion: advance to the next epoch, so a plain
        # for-each-epoch consumer (benchmarks) reshuffles per epoch without
        # calling set_epoch. Abandoned iterations never reach this line —
        # the consumer's position() calls stay authoritative.
        if epoch == self._epoch and start == self._cursor:
            self._epoch, self._cursor = epoch + 1, 0


class PrefetchLoader:
    """Background-thread prefetch over any batch iterable — the capability of
    the reference's ``num_workers``/pinned-memory DataLoader settings
    (``data_parallel.py:44-51``) in single-controller form: batch k+1 is
    assembled on a host thread while the accelerator runs batch k.

    Shutdown/failure contract (the preemption path depends on it):

    * a consumer that **abandons** iteration mid-epoch (preemption break,
      exception in the train step) signals the worker immediately and waits
      only ``join_timeout_s`` for it — a worker wedged inside the underlying
      loader (slow disk, dead NFS) is left behind as a daemon instead of
      hanging the trainer's graceful checkpoint-and-exit;
    * a worker **exception** propagates to the consumer (after any batches
      already buffered), and a worker that dies without managing to enqueue
      its sentinel is detected by liveness-checking ``get`` — the consumer
      raises instead of blocking forever.
    """

    def __init__(self, loader: Iterable, depth: int = 2, *,
                 join_timeout_s: float = 5.0):
        self.loader = loader
        self.depth = depth
        self.join_timeout_s = join_timeout_s

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        sentinel = object()
        stop = threading.Event()
        err: list[BaseException] = []

        def put(item) -> bool:
            # Bounded-wait put so the worker can never be stranded if the
            # consumer abandons the loop mid-epoch (exception in the train
            # step, KeyboardInterrupt, ...).
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            it = iter(self.loader)
            try:
                for item in it:
                    if not put(item):
                        return
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                # Propagate the abandon to the SOURCE: a generator-backed
                # loader gets its close()/GeneratorExit now (releasing file
                # handles, decode pools), not at some later GC.
                close = getattr(it, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:   # noqa: BLE001 - already shutting down
                        pass
                put(sentinel)

        t = threading.Thread(target=worker, daemon=True,
                             name="dmp-prefetch")
        t.start()
        try:
            while True:
                try:
                    item = q.get(timeout=0.5)
                except queue.Empty:
                    # Liveness check: a worker that died without enqueueing
                    # its sentinel (killed thread, interpreter teardown)
                    # must not leave the consumer blocked forever. The
                    # worker may also have enqueued its final item/sentinel
                    # and exited BETWEEN our timeout and this check — drain
                    # before declaring it dead (TOCTOU).
                    if not t.is_alive():
                        try:
                            item = q.get_nowait()
                        except queue.Empty:
                            if err:
                                raise err[0]
                            raise RuntimeError(
                                "prefetch worker died without a result "
                                "or sentinel") from None
                        if item is sentinel:
                            break
                        yield item
                    continue
                if item is sentinel:
                    break
                yield item
        finally:
            stop.set()
            # Bounded join: the worker observes `stop` within one put poll
            # (~0.1s) unless it is wedged inside the underlying loader
            # itself — in that case it stays behind as a daemon thread
            # rather than blocking the consumer's exit path (the preemption
            # checkpoint must not wait on a dead disk).
            t.join(self.join_timeout_s)
            if err:
                raise err[0]


def maybe_prefetch(loader: Iterable, depth: int) -> Iterable:
    """Wrap ``loader`` in a PrefetchLoader when ``depth > 0`` (else as-is)."""
    return PrefetchLoader(loader, depth=depth) if depth > 0 else loader


class DevicePrefetchLoader:
    """Device-resident double-buffered input prefetch.

    Wraps a host batch iterable and eagerly issues ``put_fn`` (the sharded
    ``jax.device_put`` — e.g. ``Trainer._shard_batch``) for the next
    ``depth`` batches while the consumer's current step runs, so at every
    yield up to ``depth`` future batches are already in flight to (or
    resident on) the accelerators. ``jax.device_put`` enqueues the
    transfer asynchronously, so run-ahead here IS compute/H2D overlap —
    no extra thread needed on top of the host-side :class:`PrefetchLoader`
    (which overlaps batch *assembly*; this stage overlaps the *upload*).

    Resume semantics are untouched by design: the persistent loader cursor
    is consumer-driven (``BatchLoader.position`` called by the epoch
    drivers per *consumed* batch), so run-ahead uploads are never counted
    as consumed — a kill mid-epoch resumes at the exact next batch the
    trainer dispatched, bitwise-identically (tests/test_perf_pipeline.py).

    Abandoning iteration mid-epoch (preemption break, train-step
    exception) closes the underlying iterator, propagating the shutdown
    to a PrefetchLoader worker / generator source. Per-iteration transfer
    stats land in :attr:`last_stats` (``puts`` issued, ``max_lead`` =
    the largest number of uploaded-but-unconsumed batches observed) — the
    proof that batches really were in flight (tests/test_perf_pipeline.py).
    """

    def __init__(self, loader: Iterable, put_fn, depth: int = 2):
        if depth < 1:
            raise ValueError(f"device prefetch depth must be >= 1, "
                             f"got {depth}")
        self.loader = loader
        self.put_fn = put_fn
        self.depth = depth
        self.last_stats = {"puts": 0, "max_lead": 0}

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        stats = {"puts": 0, "max_lead": 0}
        self.last_stats = stats
        it = iter(self.loader)
        buf: list = []          # uploaded, not yet consumed (FIFO)
        exhausted = False
        try:
            while True:
                while not exhausted and len(buf) <= self.depth:
                    try:
                        batch = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    buf.append(self.put_fn(*batch))
                    stats["puts"] += 1
                if not buf:
                    return
                # Lead = batches in flight beyond the one about to be
                # consumed; the smoke test pins this at >= depth.
                stats["max_lead"] = max(stats["max_lead"], len(buf) - 1)
                yield buf.pop(0)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:   # noqa: BLE001 - already shutting down
                    pass


def maybe_device_prefetch(loader: Iterable, put_fn, depth: int) -> Iterable:
    """Wrap ``loader`` so it yields device-resident batches: a
    :class:`DevicePrefetchLoader` when ``depth > 0``, else a plain
    per-batch ``put_fn`` map (the historical per-step device_put)."""
    if depth > 0:
        return DevicePrefetchLoader(loader, put_fn, depth=depth)
    return (put_fn(*batch) for batch in loader)


def resolve_input_size(images_shape, image_size: int) -> tuple[int | None, int]:
    """(resize_to, input_hw) for the on-device resize input stage.

    ``resize_to`` is None when the configured ``image_size`` already matches
    the dataset's native resolution (no resize step compiled in). Shared by
    the DP and pipeline trainers so the squareness assumption is validated
    in exactly one place (ADVICE r2: comparing height alone would silently
    skip the resize for a non-square dataset whose height matches).
    """
    native_h, native_w = images_shape[1:3]
    if native_h != native_w:
        raise ValueError(
            f"the resize/input path assumes square images; dataset is "
            f"{native_h}x{native_w} — pre-crop it square")
    resize_to = image_size if image_size != native_h else None
    return resize_to, (resize_to or native_h)


def resize_batch(images_u8: jnp.ndarray, size: int) -> jnp.ndarray:
    """On-device bilinear resize NHWC uint8 -> (B, size, size, C) uint8.

    The input stage the reference's 224px finetune recipe needs
    (``Readme.md:186-196``: CIFAR images upsampled to the pretrained
    backbone's native resolution). Runs on the accelerator inside the train
    step — the wire still carries the small native-size uint8 batch, and
    XLA fuses the upsample with augmentation/normalization.
    """
    b, h, w, c = images_u8.shape
    if (h, w) == (size, size):
        return images_u8
    x = jax.image.resize(images_u8.astype(jnp.float32), (b, size, size, c),
                         method="bilinear")
    return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)


def normalize(images_u8: jnp.ndarray, mean: np.ndarray, std: np.ndarray,
              dtype=jnp.float32) -> jnp.ndarray:
    """uint8 NHWC -> normalized float (on device)."""
    x = images_u8.astype(dtype) / jnp.asarray(255.0, dtype)
    return (x - jnp.asarray(mean, dtype)) / jnp.asarray(std, dtype)


def augment_batch(rng: jax.Array, images_u8: jnp.ndarray, *, pad: int = 4,
                  flip: bool = True) -> jnp.ndarray:
    """Random crop (pad-and-crop) + horizontal flip, vectorized on device.

    Equivalent to the reference's ``RandomCrop(32, padding=4)`` +
    ``RandomHorizontalFlip`` (``data_parallel.py:33-35``). The crop is two
    batched ``take_along_axis`` gathers (rows then columns) rather than a
    vmapped ``dynamic_slice`` — the per-image dynamic-slice form lowers to
    a pathological scatter/gather on TPU (~20x slower, measured on v5e).
    uint8 in, uint8 out.
    """
    b, h, w, c = images_u8.shape
    rng_crop, rng_flip = jax.random.split(rng)
    padded = jnp.pad(images_u8, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     mode="constant")
    offs = jax.random.randint(rng_crop, (b, 2), 0, 2 * pad + 1)
    rows = offs[:, 0][:, None] + jnp.arange(h)[None, :]        # [B, H]
    cols = offs[:, 1][:, None] + jnp.arange(w)[None, :]        # [B, W]
    out = jnp.take_along_axis(padded, rows[:, :, None, None], axis=1)
    out = jnp.take_along_axis(out, cols[:, None, :, None], axis=2)
    if flip:
        do_flip = jax.random.bernoulli(rng_flip, 0.5, (b,))
        out = jnp.where(do_flip[:, None, None, None], out[:, :, ::-1, :], out)
    return out
