"""Predecessor-trace stores: counterexample reconstruction (SURVEY §2.4 R5).

The engine appends one (fingerprint, parent fingerprint, action id) record
per newly discovered state; walking the records backwards from a violating
fingerprint and replaying the recorded action ids through the expand kernel
reproduces TLC's counterexample traces bit-exactly.

Two interchangeable implementations:

- ``NativeTraceStore`` — the C++ open-addressing map (native/trace_store.cpp)
  bound via ctypes; batch inserts take numpy arrays directly.
- ``PyTraceStore`` — dict fallback when no compiler is available.

``make_trace_store()`` picks the native one when it loads.  Action id -1
marks roots (initial states), whose full ``PyState`` is kept host-side in
``roots`` for replay starts.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.pystate import PyState
from .. import native


class PyTraceStore:
    """fp64 -> (parent fp64, action id); pure-Python fallback."""

    def __init__(self):
        self._d: Dict[int, Tuple[int, int]] = {}
        self.roots: Dict[int, PyState] = {}

    def __len__(self):
        return len(self._d)

    def add_batch(self, fps, parent_fps, actions):
        d = self._d
        for f, p, g in zip(fps.tolist(), parent_fps.tolist(),
                           actions.tolist()):
            if f not in d:
                d[f] = (p, g)

    def get(self, fp: int) -> Optional[Tuple[int, int]]:
        return self._d.get(fp)

    def stats(self) -> dict:
        """What growing has cost the store so far: ``rehashes`` and
        ``rehash_s``.  A dict grows out of sight: zeros."""
        return {"rehashes": 0, "rehash_s": 0.0}

    def export(self):
        n = len(self._d)
        fps = np.fromiter(self._d.keys(), np.uint64, n)
        parents = np.fromiter((p for p, _g in self._d.values()), np.uint64, n)
        actions = np.fromiter((g for _p, g in self._d.values()), np.int32, n)
        return fps, parents, actions

    def edges(self):
        """The recorded discovery edges as ``(fps, parents, actions)``
        numpy columns — ``export()`` under its graph name.  Root records
        carry action -1 (no incoming edge); one record per first
        discovery, so the edge set is TLC's BFS tree, which is what the
        full-graph export (engine/explain.py ``export_graph``) draws.
        Shared by both store implementations (NativeTraceStore overrides
        ``export`` only)."""
        return self.export()

    def chain(self, fp: int) -> List[Tuple[int, int]]:
        """Walk back to a root; returns [(fp, action_into_fp)] root-first."""
        out = []
        seen = set()
        while fp not in seen:
            rec = self.get(fp)
            if rec is None:
                break
            seen.add(fp)
            p, g = rec
            out.append((fp, g))
            if g < 0:
                break
            fp = p
        return list(reversed(out))


class NativeTraceStore(PyTraceStore):
    """C++-backed store; inherits the chain() walk (uses ``get``)."""

    def __init__(self, lib, initial_capacity: int = 1 << 16):
        self._lib = lib
        self._h = lib.ts_create(initial_capacity)
        self.roots: Dict[int, PyState] = {}

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.ts_destroy(h)

    def __len__(self):
        return int(self._lib.ts_size(self._h))

    def add_batch(self, fps, parent_fps, actions):
        fps = np.ascontiguousarray(fps, np.uint64)
        parents = np.ascontiguousarray(parent_fps, np.uint64)
        acts = np.ascontiguousarray(actions, np.int32)
        n = fps.shape[0]
        if n == 0:
            return
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        self._lib.ts_add_batch(
            self._h, fps.ctypes.data_as(u64p), parents.ctypes.data_as(u64p),
            acts.ctypes.data_as(i32p), n)

    def get(self, fp: int) -> Optional[Tuple[int, int]]:
        parent = ctypes.c_uint64()
        action = ctypes.c_int32()
        found = self._lib.ts_get(self._h, np.uint64(fp),
                                 ctypes.byref(parent), ctypes.byref(action))
        return (parent.value, action.value) if found else None

    def stats(self) -> dict:
        out = (ctypes.c_uint64 * 2)()
        self._lib.ts_stats(self._h, out)
        return {"rehashes": int(out[0]), "rehash_s": out[1] / 1e9}

    def export(self):
        n = len(self)
        fps = np.empty(n, np.uint64)
        parents = np.empty(n, np.uint64)
        actions = np.empty(n, np.int32)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        got = self._lib.ts_export(
            self._h, fps.ctypes.data_as(u64p), parents.ctypes.data_as(u64p),
            actions.ctypes.data_as(i32p), n)
        assert got == n
        return fps, parents, actions


def make_trace_store(initial_capacity: int = 1 << 16):
    lib = native.load()
    if lib is not None:
        return NativeTraceStore(lib, initial_capacity)
    return PyTraceStore()
