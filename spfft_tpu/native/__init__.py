"""Loader for the native (C++) plan-time kernels.

The C++ sources in this directory are compiled on demand into a shared
library next to the sources (``g++ -O3 -fopenmp -shared -fPIC``) and loaded
with ctypes — this image has no pybind11, and a plain C ABI keeps the
boundary trivial. Everything here has a NumPy fallback in
:mod:`spfft_tpu.indexing`; the native path only accelerates plan
construction (the reference's plan-time index conversion,
src/compression/indices.hpp:120-186), never the jitted transform itself.

Set ``SPFFT_TPU_NO_NATIVE=1`` to force the NumPy fallback.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "planner.cpp")
_LIB = os.path.join(_DIR, f"_planner_{sys.implementation.cache_tag}.so")

logger = logging.getLogger("spfft_tpu")

_lock = threading.Lock()
#: guarded by _lock
_lib: Optional[ctypes.CDLL] = None
#: guarded by _lock
_load_failed = False


class WideCoverBlowup(Exception):
    """The wide-gather cover exceeded its chunk limit — the caller falls
    back exactly where the NumPy builder returns None. A dedicated type so
    unrelated ValueErrors are never misread as the fallback signal."""


def _compile() -> None:
    """Compile to a temp file and rename atomically: concurrent processes
    (multi-host plan construction, pytest-xdist) may race on first use, and
    a partially written .so must never be dlopen'd."""
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-fopenmp",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, _LIB)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.spfft_tpu_plan_indices.restype = ctypes.c_int64
    lib.spfft_tpu_plan_indices.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.spfft_tpu_inverse_map.restype = ctypes.c_int32
    lib.spfft_tpu_inverse_map.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int32]
    lib.spfft_tpu_wide_tables_plan.restype = ctypes.c_int32
    lib.spfft_tpu_wide_tables_plan.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.spfft_tpu_compression_inputs.restype = ctypes.c_int32
    lib.spfft_tpu_compression_inputs.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p]
    lib.spfft_tpu_wide_tables_fill.restype = ctypes.c_int32
    lib.spfft_tpu_wide_tables_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p]
    return lib


def _load() -> Optional[ctypes.CDLL]:
    """Compile (if stale) and load the native library; None if unavailable."""
    global _lib, _load_failed
    # lock: waived(double-checked fast path - _lib is write-once under _lock and a stale None just falls through to the locked slow path)
    if _lib is not None:
        return _lib  # lock: waived(same benign race - the handle is immutable once published)
    # lock: waived(racy pre-check - the locked block re-reads _load_failed before deciding)
    if _load_failed or os.environ.get("SPFFT_TPU_NO_NATIVE") == "1":
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            if (not os.path.exists(_LIB)
                    or os.path.getmtime(_LIB) < os.path.getmtime(_SRC)):
                _compile()
            try:
                _lib = _bind(ctypes.CDLL(_LIB))
            except (OSError, AttributeError):
                # A stale/foreign binary (restored with a fresh mtime by a
                # checkout, or built from an older source revision missing a
                # symbol) — rebuild once before giving up.
                _compile()
                _lib = _bind(ctypes.CDLL(_LIB))
        except (OSError, AttributeError, subprocess.CalledProcessError) as e:
            _load_failed = True
            logger.warning("spfft_tpu: native planner unavailable (%s) — "
                           "plan-time index work falls back to NumPy",
                           getattr(e, "stderr", None) or e)
    return _lib  # lock: waived(post-with read - either the handle this call published or another loader's, both final)


def available() -> bool:
    return _load() is not None


def plan_indices(hermitian: bool, dim_x: int, dim_y: int, dim_z: int,
                 triplets: np.ndarray):
    """Native ``convert_index_triplets`` core. Returns
    ``(value_indices, stick_keys, centered)`` or None if the native library
    is unavailable. Raises the same exception types as the NumPy path for
    invalid input (mapped from the C error codes)."""
    lib = _load()
    if lib is None:
        return None
    from ..errors import InvalidIndicesError, InvalidParameterError

    xyz = np.ascontiguousarray(triplets, dtype=np.int64)
    if xyz.ndim != 2 or xyz.shape[1] != 3:
        raise InvalidParameterError(
            f"expected (n, 3) index triplets, got shape {xyz.shape}")
    n = xyz.shape[0]
    value_indices = np.empty(n, np.int32)
    stick_keys = np.empty(max(n, 1), np.int32)
    centered = ctypes.c_int32(0)
    num_sticks = lib.spfft_tpu_plan_indices(
        ctypes.c_int32(1 if hermitian else 0), dim_x, dim_y, dim_z,
        xyz.ctypes.data, n, value_indices.ctypes.data,
        stick_keys.ctypes.data, ctypes.byref(centered))
    if num_sticks == -1:
        raise InvalidIndicesError(
            f"index triplet out of bounds for dims ({dim_x},{dim_y},{dim_z}),"
            f" hermitian={hermitian}")
    if num_sticks == -2:
        raise InvalidParameterError(
            "more frequency values than grid elements (indices.hpp:126-128)")
    if num_sticks == -3:
        # Grid too large for the dense-bitmap algorithm (allocation failed)
        # — let the NumPy path handle it.
        return None
    return value_indices, stick_keys[:num_sticks].copy(), bool(centered.value)


def compression_inputs(value_indices: np.ndarray, num_slots: int):
    """Native decompress-direction gather inputs (occupied mask +
    forward-filled position map; see
    ops/gather_kernel.compression_gather_inputs — the NumPy version is the
    specification). Returns (dec_idx int64[num_slots], occupied bool) or
    None if the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    vi = np.ascontiguousarray(value_indices, np.int64)
    dec_idx = np.empty(num_slots, np.int64)
    occupied = np.empty(num_slots, np.uint8)
    st = lib.spfft_tpu_compression_inputs(
        vi.ctypes.data, vi.shape[0], num_slots, dec_idx.ctypes.data,
        occupied.ctypes.data)
    if st != 0:
        raise IndexError(f"value index out of range [0, {num_slots})")
    return dec_idx, occupied.astype(bool)


def wide_gather_tables(idx: np.ndarray, valid: np.ndarray, *,
                       num_src: int, p_tiles: int, kp_rows: int,
                       k_rows: int):
    """Native wide-gather table build (the cover loop of
    ops/gather_kernel.build_wide_gather_tables — its NumPy version is the
    executable specification and the fallback).

    Returns ``(row0, sub, out_tile, first, packed, kp, K, max_row0)`` or
    None if the native library is unavailable or ``p_tiles`` is not a
    multiple of 4 in [4, 64]; raises
    :class:`WideCoverBlowup` on a chunk-count blowup exactly where the
    NumPy builder returns None — the caller maps that to its fallback."""
    lib = _load()
    if lib is None or p_tiles % 4 or not 4 <= p_tiles <= 64:
        return None
    idx64 = np.ascontiguousarray(idx, np.int64)
    val8 = np.ascontiguousarray(valid, np.uint8)
    L = idx64.shape[0]
    kp_o = ctypes.c_int32(0)
    k_o = ctypes.c_int32(0)
    c_o = ctypes.c_int64(0)
    st = lib.spfft_tpu_wide_tables_plan(
        idx64.ctypes.data, val8.ctypes.data, L, int(num_src), p_tiles,
        kp_rows, k_rows, ctypes.byref(kp_o), ctypes.byref(k_o),
        ctypes.byref(c_o))
    if st == -1:
        raise WideCoverBlowup()  # caller falls back
    if st != 0:
        return None
    C, kp, K = c_o.value, kp_o.value, k_o.value
    row0 = np.empty(C, np.int32)
    sub = np.empty((C, p_tiles // 4), np.int32)
    out_tile = np.empty(C, np.int32)
    first = np.empty(C, np.int32)
    packed = np.empty((C, p_tiles * 8, 128), np.int16)
    mx = ctypes.c_int32(0)
    st = lib.spfft_tpu_wide_tables_fill(
        idx64.ctypes.data, val8.ctypes.data, L, int(num_src), p_tiles, kp,
        K, C, row0.ctypes.data, sub.ctypes.data, out_tile.ctypes.data,
        first.ctypes.data, packed.ctypes.data, ctypes.byref(mx))
    if st != 0:  # pragma: no cover - phase disagreement would be a bug
        return None
    return row0, sub, out_tile, first, packed, kp, K, mx.value


def inverse_map(indices: np.ndarray, num_slots: int,
                sentinel: int) -> Optional[np.ndarray]:
    """Native inverse map (scatter of iota with last-wins duplicates), or
    None if the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    idx = np.ascontiguousarray(indices, dtype=np.int32).reshape(-1)
    out = np.empty(num_slots, np.int32)
    status = lib.spfft_tpu_inverse_map(idx.ctypes.data, idx.shape[0],
                                       out.ctypes.data, num_slots,
                                       ctypes.c_int32(sentinel))
    if status != 0:
        raise IndexError(
            f"inverse map index out of range [0, {num_slots})")
    return out
