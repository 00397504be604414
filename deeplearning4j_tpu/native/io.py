"""ctypes bindings for the native IO/ETL library (native/dl4jtpu_io.cpp)."""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdl4jtpu_io.so")

_lib = None
_lib_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # the binary is git-ignored, so it is always built from the source
        # git commits: make decides (a no-op while the .so is newer than the
        # .cpp), and a stale binary in one tree can never be what runs.
        # Fine to fail — no compiler, no make — (pure-python fallback).
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, timeout=120)
            lib = ctypes.CDLL(_SO_PATH)
        except (OSError, subprocess.SubprocessError):
            return None
        lib.dl4j_idx_info.argtypes = [ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_int64),
                                      ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_idx_info.restype = ctypes.c_int
        lib.dl4j_idx_read_f32.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_int]
        lib.dl4j_idx_read_f32.restype = ctypes.c_int
        lib.dl4j_cifar_read.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
        lib.dl4j_cifar_read.restype = ctypes.c_int64
        lib.dl4j_prefetcher_create.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int, ctypes.c_int]
        lib.dl4j_prefetcher_create.restype = ctypes.c_void_p
        lib.dl4j_prefetcher_next.argtypes = [ctypes.c_void_p,
                                             ctypes.POINTER(ctypes.c_float)]
        lib.dl4j_prefetcher_next.restype = ctypes.c_int64
        lib.dl4j_prefetcher_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def read_idx_native(path: str, normalize: bool = True) -> np.ndarray:
    """IDX file -> (n, item_size) float32 (pixels /255 when normalize)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native IO library unavailable")
    n = ctypes.c_int64()
    isz = ctypes.c_int64()
    rc = lib.dl4j_idx_info(path.encode(), ctypes.byref(n), ctypes.byref(isz))
    if rc != 0:
        raise IOError(f"dl4j_idx_info({path}) rc={rc}")
    out = np.empty((n.value, max(1, isz.value)), np.float32)
    rc = lib.dl4j_idx_read_f32(path.encode(), _fptr(out), out.size,
                               1 if normalize else 0)
    if rc != 0:
        raise IOError(f"dl4j_idx_read_f32({path}) rc={rc}")
    return out  # (n, item_size); 1-dim label files come back as (n, 1)


def read_cifar_native(path: str, max_records: int = 10000
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR binary batch -> ((n,3,32,32) float32, (n,) int32 labels)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native IO library unavailable")
    x = np.empty((max_records, 3072), np.float32)
    y = np.empty((max_records,), np.int32)
    n = lib.dl4j_cifar_read(path.encode(), _fptr(x),
                            y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                            max_records)
    if n < 0:
        raise IOError(f"dl4j_cifar_read({path}) rc={n}")
    return x[:n].reshape(n, 3, 32, 32), y[:n]


class NativeBatchPrefetcher:
    """Threaded shuffle+assemble pipeline over an in-memory (x, y) pool
    (the AsyncDataSetIterator decode stage, off the GIL)."""

    def __init__(self, x: np.ndarray, y: np.ndarray, batch: int,
                 seed: int = 12345, threads: int = 2, shuffle: bool = True):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        # keep C-contiguous float32 copies alive for the native side
        self._x = np.ascontiguousarray(x.reshape(x.shape[0], -1), np.float32)
        self._y = np.ascontiguousarray(y.reshape(y.shape[0], -1), np.float32)
        self.n = self._x.shape[0]
        self.feat = self._x.shape[1]
        self.lab = self._y.shape[1]
        self.batch = int(batch)
        self._buf = np.empty((self.batch * (self.feat + self.lab),), np.float32)
        self._handle = lib.dl4j_prefetcher_create(
            _fptr(self._x), _fptr(self._y), self.n, self.feat, self.lab,
            self.batch, seed, int(threads), 1 if shuffle else 0)

    def __iter__(self):
        while True:
            if self._handle is None:
                raise RuntimeError("prefetcher is closed")
            rows = self._lib.dl4j_prefetcher_next(self._handle,
                                                  _fptr(self._buf))
            if rows == 0:
                break
            xb = self._buf[:rows * self.feat].reshape(rows, self.feat).copy()
            yb = self._buf[rows * self.feat:
                           rows * (self.feat + self.lab)] \
                .reshape(rows, self.lab).copy()
            yield xb, yb

    def close(self):
        if self._handle:
            self._lib.dl4j_prefetcher_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
