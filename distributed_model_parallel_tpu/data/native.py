"""ctypes bindings for the native host data path (native/dmp_native.cpp).

Builds the shared library with ``make`` on first use; every entry point has
a pure-numpy path so the framework works without a toolchain (one line on
stderr says so, and tests assert native == numpy when it is available).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdmp_native.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # Always ask make: it rebuilds when dmp_native.cpp is newer than
        # the library (which git ignores) and is a no-op otherwise.
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR, "-s"],
                           check=True, capture_output=True, timeout=120)
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, subprocess.SubprocessError) as e:
            print(f"[data/native] native library unavailable "
                  f"({type(e).__name__}: {e}); taking the numpy path",
                  file=sys.stderr, flush=True)
            return None
        lib.dmp_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        lib.dmp_augment_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int]
        lib.dmp_normalize_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
        lib.dmp_version.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def gather_rows(src: np.ndarray, idx: np.ndarray, *, n_threads: int = 4
                ) -> np.ndarray:
    """out[i] = src[idx[i]] over the leading axis (batch assembly)."""
    lib = _load()
    if lib is None:
        return src[idx]
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, np.int64)
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    item = int(np.prod(src.shape[1:], dtype=np.int64)) * src.itemsize
    lib.dmp_gather_rows(src.ctypes.data, idx.ctypes.data, out.ctypes.data,
                        len(idx), item, n_threads)
    return out


def augment_batch_host(images: np.ndarray, *, pad: int = 4, seed: int = 0,
                       n_threads: int = 4) -> np.ndarray:
    """Random pad-crop + h-flip on uint8 NHWC (numpy fallback is serial)."""
    assert images.dtype == np.uint8 and images.ndim == 4
    lib = _load()
    b, h, w, c = images.shape
    if lib is None:
        rng = np.random.default_rng(seed)
        padded = np.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        out = np.empty_like(images)
        for i in range(b):
            dy, dx = rng.integers(0, 2 * pad + 1, 2)
            img = padded[i, dy:dy + h, dx:dx + w]
            out[i] = img[:, ::-1] if rng.integers(2) else img
        return out
    images = np.ascontiguousarray(images)
    out = np.empty_like(images)
    lib.dmp_augment_batch(images.ctypes.data, out.ctypes.data, b, h, w, c,
                          pad, seed, n_threads)
    return out


def normalize_batch_host(images: np.ndarray, mean: np.ndarray,
                         std: np.ndarray, *, n_threads: int = 4) -> np.ndarray:
    """uint8 NHWC -> normalized float32 on the host."""
    assert images.dtype == np.uint8
    lib = _load()
    if lib is None:
        return ((images.astype(np.float32) / 255.0) - mean) / std
    images = np.ascontiguousarray(images)
    c = images.shape[-1]
    out = np.empty(images.shape, np.float32)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib.dmp_normalize_batch(images.ctypes.data, out.ctypes.data,
                            images.size // c, c,
                            mean.ctypes.data, std.ctypes.data, n_threads)
    return out
