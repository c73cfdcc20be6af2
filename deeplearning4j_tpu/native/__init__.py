"""Native (C++) runtime components.

The reference's runtime leans on external native code — libnd4j for ops,
DataVec/JavaCPP for ETL, Aeron's C media driver for transport (SURVEY.md §2
'Native / non-JVM components'). The TPU build's op path is XLA (C++ via
jit); this package holds the framework's OWN native pieces: the ETL record
readers + async batcher (recordreader.cpp).

Compilation happens lazily on first use with g++ (cached .so next to the
source, named by the source's content hash — a copy or a checkout does not
keep mtimes, and a stale library must never be trusted); every caller has a
pure-Python fallback, so a host without a toolchain still works (set
DL4J_TPU_NO_NATIVE=1 to force the fallback)."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from pathlib import Path
from typing import Optional

_DIR = Path(__file__).parent
_SRC = _DIR / "recordreader.cpp"

_lib = None
_tried = False


def _disabled() -> bool:
    return os.environ.get("DL4J_TPU_NO_NATIVE", "").lower() in (
        "1", "true", "yes", "on")


def _build() -> Optional[Path]:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    so = _DIR / f"_librecordreader-{digest}.so"
    if so.exists():
        return so
    # build under a private name, then rename: concurrent first users
    # (test workers) never load a half-written library
    tmp = _DIR / f".{so.name}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError):
        return None         # no toolchain: callers take the Python path
    finally:
        tmp.unlink(missing_ok=True)


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (build failure / disabled)."""
    global _lib, _tried
    if _disabled():
        return None
    if _lib is None and not _tried:
        _tried = True
        so = _build()
        if so is not None:
            lib = ctypes.CDLL(str(so))
            c = ctypes
            lib.idx_load.argtypes = [
                c.c_char_p, c.c_char_p, c.c_int,
                c.POINTER(c.c_int64), c.POINTER(c.c_int64),
                c.POINTER(c.c_float), c.POINTER(c.c_float)]
            lib.idx_load.restype = c.c_int
            lib.csv_dims.argtypes = [c.c_char_p, c.c_int, c.c_char,
                                     c.POINTER(c.c_int64),
                                     c.POINTER(c.c_int64)]
            lib.csv_dims.restype = c.c_int
            lib.csv_load.argtypes = [c.c_char_p, c.c_int, c.c_char,
                                     c.c_int64, c.c_int, c.c_int,
                                     c.POINTER(c.c_float),
                                     c.POINTER(c.c_float)]
            lib.csv_load.restype = c.c_int
            lib.batcher_create.argtypes = [
                c.POINTER(c.c_float), c.POINTER(c.c_float),
                c.c_int64, c.c_int64, c.c_int64, c.c_int64,
                c.c_int, c.c_uint64, c.c_int]
            lib.batcher_create.restype = c.c_void_p
            lib.batcher_next.argtypes = [c.c_void_p, c.POINTER(c.c_float),
                                         c.POINTER(c.c_float)]
            lib.batcher_next.restype = c.c_int64
            lib.batcher_reset.argtypes = [c.c_void_p]
            lib.batcher_destroy.argtypes = [c.c_void_p]
            _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None
