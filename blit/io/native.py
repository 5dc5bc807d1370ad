"""Loader for blit's native (C++) acceleration libraries.

SURVEY.md §2.3: the reference's native surface lives in its dependencies —
the bitshuffle HDF5 filter (C/SSE2/AVX2) and Blio's block readers.  blit
provides C++ equivalents under ``blit/native/``; this module locates the
built artifacts.  When they are absent the GUPPI reader falls back to a
memmap copy — for CPU runs only: the device paths require the native
reader (:func:`blit.io.guppi.require_native_reader`) — and the bitshuffle
codec has no fallback at all.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native", "build")


def native_dir() -> str:
    return os.path.abspath(_NATIVE_DIR)


def lib_path(name: str) -> Optional[str]:
    p = os.path.join(native_dir(), name)
    return p if os.path.exists(p) else None


_guppi_lib = None


def guppi_lib() -> Optional[ctypes.CDLL]:
    """ctypes handle to the C++ GUPPI block reader, or None if not built."""
    global _guppi_lib
    if _guppi_lib is not None:
        return _guppi_lib
    p = lib_path("libblit_guppi.so")
    if p is None:
        return None
    lib = ctypes.CDLL(p)
    lib.blit_guppi_pread.restype = ctypes.c_int
    lib.blit_guppi_pread.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_void_p,
        ctypes.c_int,
    ]
    if not hasattr(lib, "blit_guppi_pread2"):
        return None  # stale build; rebuild with make -C blit/native
    lib.blit_guppi_pread2.restype = ctypes.c_int
    lib.blit_guppi_pread2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_void_p,
        ctypes.c_int,
    ]
    _guppi_lib = lib
    return _guppi_lib


def guppi_pread_strided(
    path: str,
    offset: int,
    nchan: int,
    chan_bytes: int,
    src_stride: int,
    dst,
    dst_stride: int,
    nthreads: int = 8,
) -> None:
    """Threaded strided read: channel ``c``'s bytes ``[offset +
    c*src_stride, +chan_bytes)`` land at ``dst + c*dst_stride`` — the
    zero-copy feed from a GUPPI block on disk into the streaming ring
    buffer (blit/native/guppi.cc).  ``dst``: a C-contiguous ndarray whose
    buffer the rows fit inside.  Raises ``OSError`` on failure;
    ``RuntimeError`` if the library is unbuilt."""
    lib = guppi_lib()
    if lib is None:
        raise RuntimeError("native GUPPI reader unbuilt: make -C blit/native")
    from numpy.lib.array_utils import byte_bounds

    low, high = byte_bounds(dst)
    base = dst.ctypes.data
    if base < low or base + dst_stride * (nchan - 1) + chan_bytes > high:
        raise ValueError("guppi_pread_strided: rows exceed dst buffer")
    rc = lib.blit_guppi_pread2(
        path.encode(), offset, nchan, chan_bytes, src_stride, dst_stride,
        base, nthreads,
    )
    if rc:
        import os as _os

        raise OSError(-rc, _os.strerror(-rc), path)


def guppi_pread(path: str, offset: int, size: int, nthreads: int = 8):
    """Threaded pread of ``[offset, offset+size)`` into a fresh uint8 array
    via the native reader (blit/native/guppi.cc).  Raises ``OSError`` on
    failure; ``RuntimeError`` if the library is unbuilt."""
    import numpy as np

    lib = guppi_lib()
    if lib is None:
        raise RuntimeError("native GUPPI reader unbuilt: make -C blit/native")
    out = np.empty(size, np.uint8)
    rc = lib.blit_guppi_pread(
        path.encode(), offset, size, out.ctypes.data, nthreads
    )
    if rc:
        import os as _os

        raise OSError(-rc, _os.strerror(-rc), path)
    return out
