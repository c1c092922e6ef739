"""Native (C) host-runtime kernels, loaded via ctypes.

The reference implements its regrid bookkeeping in C++ inside adapt()
(main.cpp:4717-4861); `amr_host.c` is this build's native equivalent.
No pybind11 exists in the image, so the shared object is compiled
lazily with the system compiler into a content-hashed file under the
in-checkout cache root (cache.CACHE_ROOT) and bound with ctypes; any
failure (no compiler, read-only checkout, exotic platform) degrades to
the pure-Python implementations in amr.py, which are semantically
identical (tests assert equality). The degrade is quiet for library
users; ``available()`` says which path is live, and chip_smoke.py
fails when it is not the native one.

Measured honestly: at 2.7k blocks the Python sweep already costs only
~7 ms, so the native path wins ~1.2x there (marshalling-bound); the
gap is asymptotic — at the 1e5-block scale of fully developed
canonical runs the Python dict sweeps are ~0.3 s/regrid vs ~20 ms
native.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

from ..cache import CACHE_ROOT

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "amr_host.c")

_lib = None
_poisoned = False


def available() -> bool:
    """True when the native library loads (compiling it on first use)."""
    return _load() is not None


def _load():
    global _lib, _poisoned
    if _lib is not None or _poisoned:
        return _lib
    try:
        with open(_SRC, "rb") as f:
            src = f.read()
        key = hashlib.sha256(src).hexdigest()[:16]
        cache = os.path.join(CACHE_ROOT, "native")
        os.makedirs(cache, exist_ok=True)
        so = os.path.join(cache, f"amr_host_{key}.so")
        if not os.path.exists(so):
            cc = os.environ.get("CC", "cc")
            tmp = so + f".tmp{os.getpid()}"
            subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", _SRC, "-o", tmp],
                check=True, capture_output=True)
            os.replace(tmp, so)   # atomic: concurrent builders race safely
        lib = ctypes.CDLL(so)
        lib.fix_states.restype = ctypes.c_int
        lib.fix_states.argtypes = [
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ]
        _lib = lib
    except Exception:
        _lib = None
        _poisoned = True   # don't retry the compile every call
    return _lib


def fix_states(lvl: np.ndarray, bi: np.ndarray, bj: np.ndarray,
               state: np.ndarray, level_max: int, bpdx: int,
               bpdy: int) -> bool:
    """In-place 2:1-balance state fixing; returns False if the native
    library is unavailable (caller falls back to Python)."""
    lib = _load()
    if lib is None:
        return False
    # pack() keys carry 29 bits per coordinate: degrade safely (not
    # silently-wrong) for configs beyond that
    if level_max >= 29 or (max(bpdx, bpdy) << level_max) >= (1 << 29):
        return False
    assert state.dtype == np.int8 and state.flags.c_contiguous, \
        "state must be a contiguous int8 array (mutated in place)"
    rc = lib.fix_states(
        len(lvl),
        np.ascontiguousarray(lvl, np.int32),
        np.ascontiguousarray(bi, np.int32),
        np.ascontiguousarray(bj, np.int32),
        state, level_max, bpdx, bpdy)
    return rc == 0
