"""Native (C++) host helpers, compiled on first use and loaded via
ctypes; every entry point has a pure-numpy/Python fallback so the
framework works without a toolchain.

Source: native/trivy_native.cpp at the repo root. The compiled object is
cached next to the source keyed by its content hash."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "trivy_native.cpp")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_SRC):
        return None
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        cache_dir = os.path.join(tempfile.gettempdir(), "trivy_tpu_native")
        os.makedirs(cache_dir, exist_ok=True)
        so_path = os.path.join(cache_dir, f"trivy_native_{digest}.so")
        if not os.path.exists(so_path):
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 _SRC, "-o", so_path + ".tmp"],
                check=True, capture_output=True)
            os.replace(so_path + ".tmp", so_path)
        lib = ctypes.CDLL(so_path)
        lib.fnv1a64_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p]
        lib.lower_pack_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p]
        lib.lower_pack_batch.restype = None
        lib.contains_lower.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64]
        lib.contains_lower.restype = ctypes.c_int32
        _lib = lib
    except (OSError, subprocess.CalledProcessError):
        _lib = None
    return _lib


def available() -> bool:
    return _build_and_load() is not None


def fnv1a64_batch(keys: list[bytes]) -> np.ndarray:
    """Hash a batch of byte strings → uint64[N]."""
    lib = _build_and_load()
    if lib is None or not keys:
        from ..ops.hashing import fnv1a64
        return np.asarray([fnv1a64(k) for k in keys], dtype=np.uint64)
    data = b"".join(keys)
    offsets = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    buf = np.frombuffer(data, dtype=np.uint8) if data else \
        np.zeros(1, np.uint8)
    out = np.empty(len(keys), dtype=np.uint64)
    lib.fnv1a64_batch(
        buf.ctypes.data, offsets.ctypes.data,
        ctypes.c_int64(len(keys)), out.ctypes.data)
    return out


def lower_pack_batch(files: list[bytes], counts: np.ndarray,
                     chunk_len: int, overlap: int, out: np.ndarray,
                     fp: Optional[np.ndarray] = None) -> bool:
    """Lowercase + chunk every file, row after row, into `out`
    (uint8[rows, chunk_len], C-contiguous): file i makes counts[i] rows
    (ops.ac.chunk_counts); rows past their sum are left as they are.
    When `fp` is given (uint64, one per row written), each row's
    fingerprint (see native/trivy_native.cpp). → False if the native
    library is unavailable (caller falls back)."""
    lib = _build_and_load()
    if lib is None:
        return False
    counts = np.ascontiguousarray(counts, np.int64)
    total = int(counts.sum())
    if counts.shape != (len(files),) or (counts < 0).any():
        raise ValueError(f"counts must be {len(files)} row counts ≥ 0")
    if out.dtype != np.uint8 or out.ndim != 2 or \
            out.shape[1] != chunk_len or not out.flags.c_contiguous or \
            out.shape[0] < total:
        raise ValueError(f"out must be C-contiguous uint8[≥{total}, "
                         f"{chunk_len}], got {out.dtype}{out.shape}")
    if fp is not None and (fp.dtype != np.uint64 or fp.ndim != 1 or
                           fp.shape[0] < total or chunk_len % 8):
        raise ValueError(f"fp must be uint64[≥{total}] and chunk_len a "
                         f"multiple of 8")
    # c_char_p points at each bytes object's own buffer (no copy);
    # `data` holds them across the call
    data = [f if type(f) is bytes else bytes(f) for f in files]
    ptrs = (ctypes.c_char_p * max(1, len(data)))(*data)
    lens = np.fromiter((len(f) for f in data), np.int64, len(data))
    lib.lower_pack_batch(
        ptrs, lens.ctypes.data, counts.ctypes.data,
        ctypes.c_int64(len(data)), ctypes.c_int32(chunk_len),
        ctypes.c_int32(overlap), out.ctypes.data,
        None if fp is None else fp.ctypes.data)
    return True
