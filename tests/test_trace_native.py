"""Tests for the C++ trace store (native/trace_store.cpp via ctypes) and
its drop-in equivalence with the Python fallback."""

import numpy as np
import pytest

from raft_tla_tpu import native
from raft_tla_tpu.engine.trace import (NativeTraceStore, PyTraceStore,
                                       make_trace_store)


def _fill(store, n=5000, seed=3):
    rng = np.random.default_rng(seed)
    fps = rng.integers(1, 1 << 63, n, dtype=np.uint64)
    parents = rng.integers(1, 1 << 63, n, dtype=np.uint64)
    actions = rng.integers(0, 99, n, dtype=np.int32)
    store.add_batch(fps, parents, actions)
    return fps, parents, actions


def test_native_lib_builds():
    assert native.load() is not None, "g++ build of trace_store.cpp failed"


def test_native_rebuilds_when_source_content_changes(tmp_path):
    """Staleness is a content check, not an mtime one: an edited copy of
    the source rebuilds even when the old library is newer on disk."""
    import os
    import shutil
    src = str(tmp_path / "trace_store.cpp")
    so = str(tmp_path / "lib.so")
    shutil.copy(native._SRC[0], src)
    assert native.build_if_stale([src], so) is True
    assert native.build_if_stale([src], so) is False
    with open(src, "a") as f:
        f.write("\n// edited\n")
    future = os.path.getmtime(src) + 3600
    os.utime(so, (future, future))         # library "newer" than source
    assert native.build_if_stale([src], so) is True
    assert native.build_if_stale([src], so) is False


def test_native_matches_python_store():
    lib = native.load()
    assert lib is not None
    ns, ps = NativeTraceStore(lib, 1024), PyTraceStore()
    fps, parents, actions = _fill(ns)
    _fill(ps)
    # Duplicate batch: first insert must win in both.
    ns.add_batch(fps, parents[::-1].copy(), actions[::-1].copy())
    ps.add_batch(fps, parents[::-1].copy(), actions[::-1].copy())
    assert len(ns) == len(ps)
    rng = np.random.default_rng(9)
    for fp in rng.choice(fps, 200, replace=False):
        assert ns.get(int(fp)) == ps.get(int(fp))
    assert ns.get(12345) is None and ps.get(12345) is None


def test_native_growth_and_export():
    lib = native.load()
    assert lib is not None
    ns = NativeTraceStore(lib, 1024)       # forces several grows
    fps, parents, actions = _fill(ns, n=50000, seed=11)
    uniq = len(np.unique(fps))
    assert len(ns) == uniq
    efps, eparents, eactions = ns.export()
    assert len(efps) == uniq
    # Export round-trips through a fresh store.
    ns2 = NativeTraceStore(lib, 16)
    ns2.add_batch(efps, eparents, eactions)
    for fp in fps[:100]:
        assert ns2.get(int(fp)) == ns.get(int(fp))


def test_chain_walkback():
    store = make_trace_store()
    # Root 100 (action -1), chain 100 -> 200 -> 300.
    store.add_batch(np.array([100, 200, 300], np.uint64),
                    np.array([0, 100, 200], np.uint64),
                    np.array([-1, 5, 7], np.int32))
    assert store.chain(300) == [(100, -1), (200, 5), (300, 7)]
    assert store.chain(100) == [(100, -1)]
    assert store.chain(999) == []


def test_an_exported_store_goes_back_in_without_piling_up():
    """A snapshot's trace records are a store's export, in that store's
    slot order.  Fed to a new store that doubles on the way, the records
    wrap onto slots the batch's own head already filled, and where the
    exporting table was over half full they pile into one run that every
    later insert walks: quadratic (2.3 M records from a table at 55 %
    took 17 s here, 19.8 M at 59 % — a resume at level 12 of MCraft —
    over half an hour).  The batch's room is made first, and slot order
    is then the cheapest order of all: a tenth of a second.  Held to the
    same records in shuffled order into another fresh store, timed here
    on the same machine under the same load (the quadratic case was 170
    times that), not to a number of seconds."""
    import time
    if native.load() is None:
        pytest.skip("no compiler")
    src = make_trace_store()
    n = 2_300_000           # 55 % of the 2^22 slots it ends with
    rng = np.random.default_rng(29)
    fps = rng.integers(1, 1 << 63, n, dtype=np.uint64)
    src.add_batch(fps, fps ^ np.uint64(1), np.arange(n, dtype=np.int32))
    out = src.export()
    assert len(out[0]) == len(np.unique(fps))
    order = rng.permutation(len(out[0]))
    shuffled = [col[order] for col in out]
    ref = make_trace_store()
    t0 = time.perf_counter()
    ref.add_batch(*shuffled)
    t_shuffled = time.perf_counter() - t0
    dst = make_trace_store()
    t0 = time.perf_counter()
    dst.add_batch(*out)
    assert time.perf_counter() - t0 <= 3 * t_shuffled
    assert len(dst) == len(src) == len(ref)
    k = int(out[0][12345])
    assert dst.get(k) == src.get(k)
    # a second batch, and the flush-sized ones after it, still grow it
    _fill(dst, n=300_000, seed=5)
    assert len(dst) == len(src) + 300_000
