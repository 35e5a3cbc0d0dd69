"""Native (C++) runtime components, loaded via ctypes.

The reference's runtime is native C++/CUDA (host pipeline + launch wrappers,
``main.cu:124-207``); the TPU build keeps the host-side data plane native too.
The library is compiled on first use from the bundled source (g++ is part of
the toolchain; there is no pybind11 in the image, so the ABI is plain C via
ctypes).  Every native entry point has a pure-Python fallback — absence of a
compiler degrades performance, never correctness, and is logged as a
warning.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.runtime.logging import get_logger

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "chunker.cpp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

SEP_LUT = np.zeros(256, dtype=np.uint8)
for _b in constants.SEPARATOR_BYTES:
    SEP_LUT[_b] = 1


def lib_path() -> str:
    """Where the library built from the current ``chunker.cpp`` lives: the
    name carries a hash of the source, so a copied tree never loads a
    library built from another version of it."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_chunker-{digest}.so")


def _build(lib: str) -> str | None:
    """Compile ``lib``; returns the compiler's complaint on failure.

    Compiles to a private temp path and renames into place: an interrupted
    or concurrent build never leaves a partial library at the load path."""
    tmp = f"{lib}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            return proc.stderr.strip()[-2000:] or f"g++ rc={proc.returncode}"
        os.replace(tmp, lib)
        return None
    except (OSError, subprocess.TimeoutExpired) as e:
        return repr(e)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _unavailable(why: str) -> None:
    get_logger("mapreduce_tpu.native").warning(
        "native chunker unavailable, falling back to the slow Python "
        "chunker: %s", why)


def load() -> ctypes.CDLL | None:
    """The chunker library, building it on first call; None if unavailable
    (logged as a warning, once per process)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("MAPREDUCE_TPU_NO_NATIVE"):
            _unavailable("MAPREDUCE_TPU_NO_NATIVE is set")
            return None
        path = lib_path()
        if not os.path.exists(path):
            err = _build(path)
            if err is not None:
                _unavailable(f"build of {_SRC} failed: {err}")
                return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _unavailable(f"cannot load {path}: {e}")
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.mr_fill_batch.restype = ctypes.c_int64
        lib.mr_fill_batch.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, u8p, u8p, i64p, i64p]
        lib.mr_token_count.restype = ctypes.c_int64
        lib.mr_token_count.argtypes = [u8p, ctypes.c_int64, u8p]
        _lib = lib
        return _lib


def fill_batch(buf: np.ndarray, at_eof: bool, n_shards: int, chunk_bytes: int,
               max_token_bytes: int, out_data: np.ndarray,
               out_bases: np.ndarray, out_lengths: np.ndarray) -> int | None:
    """Native batch fill; returns consumed bytes, or None if lib unavailable."""
    lib = load()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf)
    return int(lib.mr_fill_batch(
        buf, buf.shape[0], int(at_eof), n_shards, chunk_bytes,
        max_token_bytes, SEP_LUT, out_data, out_bases, out_lengths))


def token_count(buf: np.ndarray) -> int | None:
    """Native exact token count, or None if lib unavailable."""
    lib = load()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf)
    return int(lib.mr_token_count(buf, buf.shape[0], SEP_LUT))
