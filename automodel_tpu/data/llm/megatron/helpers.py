"""ctypes loader for the C++ index builders (reference megatron/helpers.py:29, which
loads the pybind11 ``helpers_cpp``; here the extension is a plain shared library with
an extern "C" ABI, compiled on first use and cached beside the source).

Every function has a NumPy route with identical semantics, taken where the host has
no ``g++`` at all. Where there is a compiler, the library is built from the committed
source (the ``.so`` is ignored by git) and a build or load that fails is an error.
"""

from __future__ import annotations

import ctypes
import logging
import os
import shutil
import subprocess
import threading

import numpy as np

logger = logging.getLogger(__name__)

__all__ = [
    "build_sample_idx",
    "build_blending_indices",
    "build_exhaustive_blending_indices",
    "native_available",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "index_helpers.cpp")
_LIB = os.path.join(_HERE, "libindex_helpers.so")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def _load() -> ctypes.CDLL | None:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        if shutil.which("g++") is None:
            # no compiler on this host: the NumPy route is the one asked for
            logger.info("no g++ on PATH; megatron index helpers use the NumPy route")
            _build_failed = True
            return None
        # the library is built from what git commits (the .so is ignored): a
        # compiler that is there and fails is an error, not a reason to
        # switch to the slow route in silence
        if not os.path.exists(_LIB) or os.path.getmtime(_LIB) < os.path.getmtime(_SRC):
            try:
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", _LIB, _SRC],
                    check=True, capture_output=True, text=True, timeout=120,
                )
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"building {_LIB} failed (g++ rc={e.returncode}):\n{e.stderr[-2000:]}"
                ) from e
            logger.info("built %s", _LIB)
        try:
            lib = ctypes.CDLL(_LIB)
            lib.build_sample_idx.restype = ctypes.c_int64
            lib.build_sample_idx.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int64, ctypes.c_void_p,
            ]
            lib.build_blending_indices.restype = None
            lib.build_blending_indices.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_int64,
            ]
            lib.build_exhaustive_blending_indices.restype = None
            lib.build_exhaustive_blending_indices.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ]
            _lib = lib
        except OSError as e:
            raise RuntimeError(f"loading {_LIB} failed: {e}") from e
        return _lib


def native_available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(a.ctypes.data)


def build_sample_idx(
    sizes: np.ndarray,  # (n_docs,) int32 token counts
    doc_idx: np.ndarray,  # (doc_idx_len,) int64 shuffled document ids
    seq_length: int,
    num_samples: int,
) -> np.ndarray:
    """(num_samples+1, 2) int64 [doc_idx position, token offset] per sample start."""
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, dtype=np.int64)
    out = np.zeros((num_samples + 1, 2), dtype=np.int64)
    lib = _load()
    if lib is not None:
        rows = lib.build_sample_idx(
            _ptr(sizes), _ptr(doc_idx), len(doc_idx), seq_length, num_samples, _ptr(out)
        )
        return out[:rows]
    return _sample_idx_numpy(sizes, doc_idx, seq_length, num_samples)


def _sample_idx_numpy(sizes, doc_idx, seq_length, num_samples):
    out = [(0, 0)]
    doc_pos, doc_offset = 0, 0
    n = len(doc_idx)
    while len(out) <= num_samples and doc_pos < n:
        remaining = seq_length + 1
        while remaining > 0 and doc_pos < n:
            doc_len = int(sizes[doc_idx[doc_pos]]) - doc_offset
            if doc_len >= remaining:
                doc_offset += remaining - 1
                remaining = 0
            else:
                remaining -= doc_len
                doc_pos += 1
                doc_offset = 0
        if remaining > 0:
            break
        out.append((doc_pos, doc_offset))
    return np.asarray(out, dtype=np.int64)


def build_blending_indices(weights: np.ndarray, size: int) -> tuple[np.ndarray, np.ndarray]:
    """Proportional error-feedback interleave -> (dataset_index i16, sample_index i64)."""
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    dataset_index = np.zeros(size, dtype=np.int16)
    dataset_sample_index = np.zeros(size, dtype=np.int64)
    lib = _load()
    if lib is not None:
        lib.build_blending_indices(
            _ptr(dataset_index), _ptr(dataset_sample_index), _ptr(weights),
            len(weights), size,
        )
        return dataset_index, dataset_sample_index
    counts = np.zeros(len(weights), dtype=np.int64)
    for i in range(size):
        err = weights * max(i, 1) - counts
        d = int(np.argmax(err))
        dataset_index[i] = d
        dataset_sample_index[i] = counts[d]
        counts[d] += 1
    return dataset_index, dataset_sample_index


def build_exhaustive_blending_indices(sizes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Exact-count interleave: draw exactly sizes[d] samples from each dataset."""
    sizes = np.ascontiguousarray(sizes, dtype=np.int64)
    total = int(sizes.sum())
    dataset_index = np.zeros(total, dtype=np.int16)
    dataset_sample_index = np.zeros(total, dtype=np.int64)
    lib = _load()
    if lib is not None:
        lib.build_exhaustive_blending_indices(
            _ptr(dataset_index), _ptr(dataset_sample_index), _ptr(sizes), len(sizes)
        )
        return dataset_index, dataset_sample_index
    counts = np.zeros(len(sizes), dtype=np.int64)
    live = sizes > 0
    weights = sizes / max(total, 1)
    for i in range(total):
        err = np.where(live, weights * max(i, 1) - counts, -np.inf)
        d = int(np.argmax(err))
        dataset_index[i] = d
        dataset_sample_index[i] = counts[d]
        counts[d] += 1
        if counts[d] == sizes[d]:
            live[d] = False
    return dataset_index, dataset_sample_index
