"""Native host-tier: C++ hot paths behind ctypes, with pure-NumPy fallback.

The shared library is compiled on first use with the system ``g++`` (the
image ships no pybind11; the C ABI + ctypes needs nothing extra). If no
compiler is available the callers fall back to their NumPy implementations —
behavior is identical, only slower.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csr_builder.cpp")
_LIB_PATH = os.path.join(_HERE, "_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _compile() -> bool:
    """Build the .so next to the source; atomic rename so concurrent
    importers never load a half-written library."""
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        res = subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
            capture_output=True,
            timeout=120,
        )
        if res.returncode != 0:
            return False
        os.replace(tmp, _LIB_PATH)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, compiled on demand; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        stale = not os.path.exists(_LIB_PATH) or (
            os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)
        )
        if stale and not _compile():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _build_failed = True
            return None
        lib.parse_edge_list.restype = ctypes.c_int64
        lib.parse_edge_list.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.unique_sorted.restype = ctypes.c_int64
        lib.unique_sorted.argtypes = [
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.build_csr.restype = ctypes.c_int32
        lib.build_csr.argtypes = [
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
        return _lib


def _p64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _p32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def parse_edge_list_native(data: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Parse a SNAP-style edge-list buffer; None if the native lib is
    unavailable. Raises ValueError on malformed input (byte offset in the
    message), matching the Python loader's strictness."""
    lib = get_lib()
    if lib is None:
        return None
    max_edges = data.count(b"\n") + 1
    src = np.empty(max_edges, dtype=np.int64)
    dst = np.empty(max_edges, dtype=np.int64)
    n = lib.parse_edge_list(data, len(data), _p64(src), _p64(dst))
    if n < 0:
        off = -int(n) - 1
        line = data[:off].count(b"\n") + 1
        raise ValueError(f"line {line} (byte offset {off})")
    return src[:n].copy(), dst[:n].copy()


def build_csr_native(
    node_ids: np.ndarray, src: np.ndarray, dst: np.ndarray
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """(unique_ids, row_ptr, col_idx, src_idx) lexsorted by (src, dst), or
    None if the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    ids = np.ascontiguousarray(node_ids, dtype=np.int64)
    uniq = np.empty(len(ids), dtype=np.int64)
    n = lib.unique_sorted(_p64(ids), len(ids), _p64(uniq))
    uniq = uniq[:n].copy()
    s = np.ascontiguousarray(src, dtype=np.int64)
    d = np.ascontiguousarray(dst, dtype=np.int64)
    e = len(s)
    row_ptr = np.empty(n + 1, dtype=np.int32)
    col_idx = np.empty(e, dtype=np.int32)
    src_idx = np.empty(e, dtype=np.int32)
    rc = lib.build_csr(
        _p64(uniq), n, _p64(s), _p64(d), e, _p32(row_ptr), _p32(col_idx), _p32(src_idx)
    )
    if rc != 0:
        raise ValueError("Edge endpoint id not present in node_ids")
    return uniq, row_ptr, col_idx, src_idx
