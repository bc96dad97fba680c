"""Loader for the in-repo C++ helpers (csrc/).

Compiles ``csrc/datapack.cpp`` into a shared library on first use (g++)
and binds it via ctypes — no pybind11 dependency.  The library's file name
carries a hash of the SOURCE'S CONTENT, so a copy or checkout of the tree
(which preserves no mtimes and ships no binary — ``csrc/*.so`` is ignored
by git) rebuilds exactly when the source it holds differs from what the
cached library was built from.  Every native entry point has a pure-Python fallback
in its caller, so a missing/failed toolchain degrades gracefully
(AREAL_NATIVE=0 forces the fallbacks).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from areal_tpu.base import logging_

logger = logging_.getLogger("native")

_CSRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "csrc",
)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(src: str, out: str) -> bool:
    # build to a per-process temp path and os.replace into place: concurrent
    # workers on a fresh checkout must never dlopen a half-written library
    tmp = f"{out}.tmp-{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, out)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        logger.warning("native build failed (%s); using Python fallbacks", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The datapack shared library, building it if needed; None if
    unavailable."""
    global _lib, _tried
    if os.environ.get("AREAL_NATIVE", "1") == "0":
        return None
    with _lock:
        if _tried:
            return _lib
        _tried = True
        src = os.path.join(_CSRC, "datapack.cpp")
        if not os.path.isfile(src):
            return None
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        out = os.path.join(_CSRC, f"libdatapack-{digest}.so")
        if not os.path.isfile(out):
            if not _build(src, out):
                return None
            for stale in glob.glob(os.path.join(_CSRC, "libdatapack*.so")):
                if stale != out:
                    try:
                        os.unlink(stale)
                    except OSError:
                        pass
        try:
            lib = ctypes.CDLL(out)
        except OSError as e:
            logger.warning("native load failed (%s)", e)
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.ffd_pack.restype = ctypes.c_int64
        lib.ffd_pack.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p]
        lib.partition_balanced_dp.restype = ctypes.c_int64
        lib.partition_balanced_dp.argtypes = [
            i64p,
            ctypes.c_int64,
            ctypes.c_int64,
            i64p,
        ]
        _lib = lib
        logger.debug("native datapack loaded from %s", out)
        return _lib


def backend() -> str:
    """"native" when the C++ library is loaded, else "python" (the
    callers' pure-Python fallbacks are in use)."""
    return "native" if get_lib() is not None else "python"


def _as_i64(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, dtype=np.int64))


def ffd_pack(nums, capacity: int):
    """Native FFD; returns (bin_id per item [n], n_bins) or None."""
    lib = get_lib()
    if lib is None:
        return None
    arr = _as_i64(nums)
    out = np.empty(len(arr), np.int64)
    n_bins = lib.ffd_pack(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(arr),
        int(capacity),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out, int(n_bins)


def partition_balanced(nums, k: int):
    """Native balanced partition; returns cut boundaries [k+1] or None."""
    lib = get_lib()
    if lib is None:
        return None
    arr = _as_i64(nums)
    cuts = np.empty(k + 1, np.int64)
    rc = lib.partition_balanced_dp(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(arr),
        int(k),
        cuts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if rc != 0:
        return None
    return cuts
