"""ctypes binding to libweedtpu.so (native/weedtpu.cc) — the C++ runtime
kernels (CRC32C, AVX2 GF(2^8) baseline, the xorsched executor).

The library is generated output and is not in git. `build()` is the one
explicit build step (`make -C native`, which compiles to a temporary name
and renames, so no process ever maps a half-written file); server start-up,
`chip_smoke.py` and the tests' conftest call it once, before any thread or
child needs the library, and a failure there is an error that carries the
compiler's output. `load()` never builds: it maps what is there, and a
caller that finds nothing (`None`) is a tool or a library user running on
the pure-Python fallbacks by its own choice of not building.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libweedtpu.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


class NativeBuildError(RuntimeError):
    """`make -C native` failed, or what it built cannot be loaded."""


def build() -> str:
    """Build libweedtpu.so from native/weedtpu.cc if it is missing or older
    than its source (make's own rule), atomically, then load it. Returns
    the library's path; raises NativeBuildError with make's output."""
    global _load_failed
    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            capture_output=True,
            text=True,
            timeout=300,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"make -C {_NATIVE_DIR}: {e}") from e
    if proc.returncode != 0 or not os.path.exists(_LIB_PATH):
        raise NativeBuildError(
            f"make -C {_NATIVE_DIR} exited {proc.returncode}:\n"
            f"{proc.stdout}{proc.stderr}"
        )
    with _lock:
        _load_failed = False  # a load that failed before the build may retry
    if load() is None:
        raise NativeBuildError(f"{_LIB_PATH} was built but does not load")
    return _LIB_PATH


def load() -> Optional[ctypes.CDLL]:
    """The loaded library; None if it has not been built or cannot load."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            lib.weedtpu_crc32c.restype = ctypes.c_uint32
            lib.weedtpu_crc32c.argtypes = [
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_uint64,
            ]
            lib.weedtpu_has_avx2.restype = ctypes.c_int
            lib.weedtpu_gf_matrix_apply.restype = None
            _lib = lib
        except (OSError, AttributeError):
            # OSError: no such file / unloadable .so; AttributeError: a
            # binary missing expected symbols
            _load_failed = True
        return _lib


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------

_CRC_TABLE: Optional[list[int]] = None


def _py_table() -> list[int]:
    global _CRC_TABLE
    if _CRC_TABLE is None:
        tbl = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
            tbl.append(crc)
        _CRC_TABLE = tbl
    return _CRC_TABLE


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC32C (Castagnoli) — the needle checksum algorithm
    [VERIFY: weed/storage/needle/needle_read_write.go uses Castagnoli]."""
    lib = load()
    if lib is not None:
        return lib.weedtpu_crc32c(crc, bytes(data), len(data))
    tbl = _py_table()
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = (c >> 8) ^ tbl[(c ^ b) & 0xFF]
    return c ^ 0xFFFFFFFF


def gf_matrix_apply_native(matrix, inputs, length: int, threads: int = 1):
    """Native (AVX2 when available) GF matrix apply over byte slices.

    matrix: (R, C) uint8 numpy array; inputs: list of C bytes-like of `length`.
    threads: 1 = single core; 0 = all cores; N = exactly N workers (the
    multithreaded split mirrors the reference codec's WithAutoGoroutines).
    Returns list of R arrays, or None if the library is unavailable.
    """
    import numpy as np

    lib = load()
    if lib is None:
        return None
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    in_bufs = [np.ascontiguousarray(np.frombuffer(i, dtype=np.uint8)) for i in inputs]
    out_bufs = [np.zeros(length, dtype=np.uint8) for _ in range(rows)]
    InArr = ctypes.c_char_p * cols
    OutArr = ctypes.c_void_p * rows
    ins = InArr(*[i.ctypes.data_as(ctypes.c_char_p) for i in in_bufs])
    outs = OutArr(*[o.ctypes.data_as(ctypes.c_void_p) for o in out_bufs])
    mat_ptr = matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    if threads == 1 or not hasattr(lib, "weedtpu_gf_matrix_apply_mt"):
        lib.weedtpu_gf_matrix_apply(
            mat_ptr,
            ctypes.c_uint32(rows),
            ctypes.c_uint32(cols),
            ins,
            outs,
            ctypes.c_uint64(length),
        )
    else:
        lib.weedtpu_gf_matrix_apply_mt(
            mat_ptr,
            ctypes.c_uint32(rows),
            ctypes.c_uint32(cols),
            ins,
            outs,
            ctypes.c_uint64(length),
            ctypes.c_uint32(threads),
        )
    return out_bufs


def gf_matrix_apply_batch_native(matrix, shards, threads: int = 0):
    """Batched native apply: shards (B, C, N) uint8 -> (B, R, N), one
    library call (one worker pool over batch elements, zero repacking —
    the per-element slice pointers index straight into `shards`).
    Returns None when the library (or the batch symbol) is unavailable."""
    import numpy as np

    lib = load()
    if lib is None or not hasattr(lib, "weedtpu_gf_matrix_apply_batch"):
        return None
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    batch, c, n = shards.shape
    if c != cols:
        raise ValueError(f"matrix wants {cols} inputs, stack has {c}")
    out = np.zeros((batch, rows, n), dtype=np.uint8)
    InArr = ctypes.c_char_p * (batch * cols)
    OutArr = ctypes.c_void_p * (batch * rows)
    base_in = shards.ctypes.data
    base_out = out.ctypes.data
    ins = InArr(*[
        ctypes.c_char_p(base_in + (b * cols + ci) * n)
        for b in range(batch)
        for ci in range(cols)
    ])
    outs = OutArr(*[
        base_out + (b * rows + r) * n for b in range(batch) for r in range(rows)
    ])
    lib.weedtpu_gf_matrix_apply_batch(
        matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint32(rows),
        ctypes.c_uint32(cols),
        ins,
        outs,
        ctypes.c_uint64(n),
        ctypes.c_uint32(batch),
        ctypes.c_uint32(threads),
    )
    return out


def has_avx2() -> bool:
    lib = load()
    return bool(lib and lib.weedtpu_has_avx2())


def has_mt() -> bool:
    """True when the loaded library exports the multithreaded apply —
    a stale pre-MT binary would otherwise silently run single-threaded."""
    lib = load()
    return bool(lib and hasattr(lib, "weedtpu_gf_matrix_apply_mt"))
