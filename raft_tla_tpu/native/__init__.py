"""Native (C++) runtime components, bound via ctypes.

The checker's device pipeline is JAX/XLA; the host-side runtime pieces that
TLC implements natively (trace store; checkpoint IO helpers) are C++ here
too, built on first use with the ambient ``g++`` into a shared library next
to the sources.  The library is trusted only when the content hash stored
beside it equals the hash of the tracked sources, so a copied or restored
tree never runs a library built from something else.  On a host without a
compiler ``load()`` says why on stderr and returns None, and the engines
use the pure-Python store in ``engine/trace.py``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libraftnative.so")
_SRC = [os.path.join(_HERE, "trace_store.cpp")]
_LOCK = threading.Lock()
_LIB = None
_TRIED = False


def source_digest(src: List[str]) -> str:
    h = hashlib.sha256()
    for p in src:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build_if_stale(src: List[str], so: str) -> bool:
    """Build ``so`` from ``src`` unless ``so`` exists and the digest
    stored beside it (``<so>.sha256``) equals the sources' content hash.
    Returns whether it built; raises when the compiler fails."""
    stamp = so + ".sha256"
    digest = source_digest(src)
    if os.path.exists(so) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read().strip() == digest:
                return False
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                        "-o", tmp] + src,
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)     # atomic: concurrent loaders see old or new
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(stamp, "w") as f:
        f.write(digest + "\n")
    return True


def load() -> Optional[ctypes.CDLL]:
    """The shared library, (re)built if stale; None if unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            build_if_stale(_SRC, _SO)
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.SubprocessError) as e:
            err = getattr(e, "stderr", b"") or b""
            print(f"native: trace store unavailable ({type(e).__name__}: "
                  f"{e}) {err.decode(errors='replace')[-400:]}",
                  file=sys.stderr)
            return None
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.ts_create.restype = ctypes.c_void_p
        lib.ts_create.argtypes = [ctypes.c_uint64]
        lib.ts_destroy.argtypes = [ctypes.c_void_p]
        lib.ts_size.restype = ctypes.c_uint64
        lib.ts_size.argtypes = [ctypes.c_void_p]
        lib.ts_stats.argtypes = [ctypes.c_void_p, u64p]
        lib.ts_add_batch.argtypes = [ctypes.c_void_p, u64p, u64p, i32p,
                                     ctypes.c_uint64]
        lib.ts_get.restype = ctypes.c_int
        lib.ts_get.argtypes = [ctypes.c_void_p, ctypes.c_uint64, u64p, i32p]
        lib.ts_export.restype = ctypes.c_uint64
        lib.ts_export.argtypes = [ctypes.c_void_p, u64p, u64p, i32p,
                                  ctypes.c_uint64]
        _LIB = lib
        return _LIB
