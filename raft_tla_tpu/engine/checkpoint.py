"""Checkpoint/resume — TLC's ``states/`` snapshot dir rebuilt (SURVEY §2.4 R8).

TLC periodically writes its FPSet + unexplored-queue to the ``states/``
directory so an interrupted run can resume (acknowledged by the reference's
``.gitignore:1``).  The TPU engine's equivalent is a *level-boundary*
snapshot: because the BFS is level-synchronous, the complete engine state
between levels is exactly

    (frontier rows, FPSet keys, counters, trace records, trace roots)

and all of it is host-materializable as flat numpy arrays.  One compressed
``.npz`` per snapshot, written atomically (tmp + rename) so a crash during
write never corrupts the latest good checkpoint.

Resume restores the FPSet by sentinel-padding the saved (already lex-sorted)
key arrays back to capacity — no re-hashing, no re-exploration: the run
continues from the exact level it stopped at, and counterexample replay
still reaches roots discovered before the interruption.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import pickle
import re
import zlib
from typing import Dict, Optional, Tuple

import numpy as np

from ..models.dims import RaftDims
from ..models.pystate import PyState
from ..resilience import faults

# v2: frontier rows are packed uint8 (v1 stored int32 rows with no value
# bounds; loading them into the packed engine could wrap silently, so v1
# files are rejected rather than converted).
# v3: the fingerprint function changed (ops/fingerprint.py hardening,
# 2026-07-31) — v2 snapshots' seen-keys and trace fingerprints are keyed
# by the old hash; resuming them would re-count explored states as new,
# so they are rejected rather than silently mis-resumed.
# v4: metadata carries the dims *class* and the packed row width.  v3
# restore rebuilt every checkpoint as base RaftDims, so a ReconfigDims
# snapshot could not round-trip (TypeError on its 'targets' key), and the
# variant's 2-byte value lanes changed state_width with no version signal
# — a stale variant snapshot would have died with an opaque shape error.
# v3 base-dims files still load; v3 *variant* files (written before the
# class was recorded) are rejected with a clear message rather than
# guessed at.
# v5: every array is stored as ``<name>__z`` (its bytes deflated in
# ``_CHUNK`` pieces by a pool of threads, concatenated) + ``<name>__zoff``
# (the pieces' offsets), its shape and dtype in the metadata
# (``deflated``); a small array is one piece.  One deflate stream per
# array made a level-12 MCraft snapshot (2.4 GB of rows, 158 MB of keys,
# 396 MB of trace records) a minute of one core; in pieces it is seconds,
# and the load inflates them side by side too.  v3 and v4 files (plain
# ``savez_compressed`` members) still load.
FORMAT_VERSION = 5
_CHUNK = 8 << 20

# Restorable dims classes.  An allowlist, not pickle: checkpoint metadata
# is JSON and the class name in it must map to a known, audited schema.
def _dims_registry():
    from ..models.reconfig import ReconfigDims
    return {"RaftDims": RaftDims, "ReconfigDims": ReconfigDims}


def check_dims_checkpointable(dims) -> None:
    """Raise at engine CONSTRUCTION time if ``dims`` could not be saved —
    otherwise the TypeError would first fire at the level-boundary
    snapshot write, after a full level of expansion work is already
    done and about to be lost."""
    name = type(dims).__name__
    if name not in _dims_registry():
        raise TypeError(
            f"dims class {name!r} is not checkpoint-restorable; add it "
            "to engine/checkpoint._dims_registry or run without "
            "checkpoint_dir")


@dataclasses.dataclass
class Checkpoint:
    """Host-side image of a BFS engine paused at a level boundary."""

    dims: RaftDims
    frontier: np.ndarray           # [cur_count, state_width] uint8 rows
    seen_hi: np.ndarray            # [size] uint32, lex-sorted with seen_lo
    seen_lo: np.ndarray            # [size] uint32
    distinct: int
    generated: int
    diameter: int
    levels: Tuple[int, ...]
    # Per-action-family generated counts (may be {} for snapshots written
    # before the field existed; the engines then under-report pre-resume
    # action stats but all other counters stay exact).
    action_counts: Dict[str, int]
    wall_seconds: float          # cumulative checking time before the snapshot
    trace_fps: np.ndarray          # [T] uint64
    trace_parents: np.ndarray      # [T] uint64
    trace_actions: np.ndarray      # [T] int32
    roots: Dict[int, PyState]


def level_of(path: str) -> Optional[int]:
    """BFS level encoded in a snapshot filename (single or piece), or
    None for non-snapshot paths — fault-plan params match on it, and a
    resumed run's ``run_start`` says it (``resume_level``)."""
    name = os.path.basename(path)
    m = _PIECE_RE.match(name)
    if m:
        return int(m.group(1)[len("level_"):])
    if name.startswith("level_") and name.endswith(".npz"):
        try:
            return int(name[len("level_"):-len(".npz")])
        except ValueError:
            return None
    return None


def _pool():
    from concurrent.futures import ThreadPoolExecutor
    return ThreadPoolExecutor(max(1, min(32, os.cpu_count() or 1)))


def _deflate(ex, arr: np.ndarray):
    """(bytes deflated piece by piece, the pieces' offsets) of one
    array; zlib releases the GIL, so the pieces go side by side."""
    buf = memoryview(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))
    parts = list(ex.map(lambda i: zlib.compress(buf[i:i + _CHUNK], 1),
                        range(0, len(buf), _CHUNK)))
    offs = np.zeros(len(parts) + 1, np.int64)
    np.cumsum([len(p) for p in parts], out=offs[1:])
    return np.frombuffer(b"".join(parts), np.uint8), offs


def _inflate(ex, z, name: str, spec: dict) -> np.ndarray:
    blob, offs = memoryview(z[name + "__z"]), z[name + "__zoff"]
    out = np.empty(spec["shape"], np.dtype(spec["dtype"]))
    flat = memoryview(out.reshape(-1).view(np.uint8))

    def piece(i):
        flat[i * _CHUNK:(i + 1) * _CHUNK] = zlib.decompress(
            blob[offs[i]:offs[i + 1]], bufsize=_CHUNK)
    list(ex.map(piece, range(len(offs) - 1)))
    return out


def save(path: str, ckpt: Checkpoint, metrics=None) -> Optional[dict]:
    """Atomically write ``ckpt`` to ``path`` (a ``.npz`` file).  Returns
    ``{"bytes_raw", "bytes_written"}``: the arrays' bytes as they lay in
    memory, and the file's (None where an injected fault skipped the
    write).  With ``metrics`` (the caller's ``MetricsRegistry``) its two
    parts are spans there, ``ckpt_deflate`` and ``ckpt_write`` (the file,
    its ``fsync``, the rename, the directory's ``fsync``), and the
    snapshot is counted: ``engine/checkpoints_written``,
    ``engine/checkpoint_bytes_raw``, ``engine/checkpoint_bytes_written``
    (``WORK_COUNTERS`` of engine/bfs.py, so ``run_end`` carries them)."""
    from ..models.schema import state_width
    part = (metrics.part_timer if metrics is not None
            else lambda _name: contextlib.nullcontext())
    if faults.ACTIVE:
        m = _PIECE_RE.match(os.path.basename(path))
        if faults.fire("ckpt_piece_missing", level=level_of(path),
                       piece=int(m.group(2)) if m else 0, path=path):
            # Injected: this controller died before its piece landed.
            return None
    arrays = dict(
        frontier=np.ascontiguousarray(ckpt.frontier).astype(
            np.uint8, casting="safe", copy=False),
        seen_hi=np.ascontiguousarray(ckpt.seen_hi, np.uint32),
        seen_lo=np.ascontiguousarray(ckpt.seen_lo, np.uint32),
        trace_fps=np.ascontiguousarray(ckpt.trace_fps, np.uint64),
        trace_parents=np.ascontiguousarray(ckpt.trace_parents, np.uint64),
        trace_actions=np.ascontiguousarray(ckpt.trace_actions, np.int32))
    check_dims_checkpointable(ckpt.dims)
    cls_name = type(ckpt.dims).__name__
    meta = {
        "version": FORMAT_VERSION,
        "dims_class": cls_name,
        "state_width": state_width(ckpt.dims),
        "dims": dataclasses.asdict(ckpt.dims),
        "distinct": ckpt.distinct,
        "generated": ckpt.generated,
        "diameter": ckpt.diameter,
        "levels": list(ckpt.levels),
        "action_counts": dict(ckpt.action_counts),
        "wall_seconds": ckpt.wall_seconds,
    }
    meta["deflated"] = {k: {"shape": list(a.shape), "dtype": a.dtype.str}
                        for k, a in arrays.items()}
    members = {}
    with part("ckpt_deflate"), _pool() as ex:
        for k, a in arrays.items():
            members[k + "__z"], members[k + "__zoff"] = _deflate(ex, a)
    with part("ckpt_write"):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f,
                     meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
                     roots=np.frombuffer(pickle.dumps(ckpt.roots), np.uint8),
                     **members)
            f.flush()
            os.fsync(f.fileno())  # the rename must never land a torn file
        if faults.ACTIVE:
            # The torn-write crash window: tmp is complete on disk, the
            # rename has not happened — exactly what a power cut here
            # leaves.
            faults.fire("ckpt_torn_write", level=level_of(path), path=path)
        os.replace(tmp, path)
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        size = {"bytes_raw": sum(a.nbytes for a in arrays.values()),
                "bytes_written": os.path.getsize(path)}
    if metrics is not None:
        metrics.counter("engine/checkpoints_written")
        for name, n in size.items():
            metrics.counter("engine/checkpoint_" + name, n)
    return size


# Multi-host runs write one PIECE per controller (its frontier slice +
# its seen-key shards; counters are psum-replicated so every piece
# carries identical metadata): level_00012.p0of2.npz, .p1of2.npz, ...
# load() on any piece merges the complete group, so a checkpoint written
# by M controllers resumes on 1 or N controllers and vice versa (the
# merged image is exactly the single-file format).  A shared filesystem
# across hosts is assumed, as with TLC's distributed states/ dir.
_PIECE_RE = re.compile(r"^(level_\d+)\.p(\d+)of(\d+)\.npz$")


def piece_path(checkpoint_dir: str, diameter: int, pid: int,
               nproc: int) -> str:
    return os.path.join(checkpoint_dir,
                        f"level_{diameter:05d}.p{pid}of{nproc}.npz")


def _merge(pieces) -> Checkpoint:
    base = pieces[0]
    for p in pieces[1:]:
        if p.dims != base.dims:
            raise ValueError("checkpoint pieces disagree on dims")
        # The counters are psum-replicated at write time, so every piece
        # of one generation carries identical metadata.  A mismatch means
        # the group mixes pieces from different run generations (a crash
        # between piece overwrites) — merging would silently produce a
        # frontier/seen-set belonging to neither run.
        if (p.distinct, p.generated, p.diameter, p.levels) != \
                (base.distinct, base.generated, base.diameter,
                 base.levels):
            raise ValueError(
                "checkpoint piece group mixes run generations "
                f"(counters disagree: {p.diameter}/{p.distinct} vs "
                f"{base.diameter}/{base.distinct}); delete the stale "
                "pieces or resume an older complete snapshot")
    hi = np.concatenate([p.seen_hi for p in pieces])
    lo = np.concatenate([p.seen_lo for p in pieces])
    order = np.lexsort((lo, hi))
    return dataclasses.replace(
        base,
        frontier=np.concatenate([p.frontier for p in pieces]),
        seen_hi=hi[order], seen_lo=lo[order],
        trace_fps=np.concatenate([p.trace_fps for p in pieces]),
        trace_parents=np.concatenate([p.trace_parents for p in pieces]),
        trace_actions=np.concatenate([p.trace_actions for p in pieces]),
        roots={k: v for p in pieces for k, v in p.roots.items()})


def load(path: str) -> Checkpoint:
    m = _PIECE_RE.match(os.path.basename(path))
    if m:
        base, nproc = m.group(1), int(m.group(3))
        d = os.path.dirname(os.path.abspath(path))
        paths = [os.path.join(d, f"{base}.p{i}of{nproc}.npz")
                 for i in range(nproc)]
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            raise FileNotFoundError(
                f"incomplete checkpoint piece group: missing {missing}")
        return _merge([_load_one(p) for p in paths])
    return _load_one(path)


def _load_one(path: str) -> Checkpoint:
    with np.load(path) as z:
        meta = json.loads(bytes(z["meta"]).decode())
        if meta["version"] not in (3, 4, FORMAT_VERSION):
            # Both loadable versions in the message: "!= v4" used to send
            # v3 holders hunting for a nonexistent problem (ADVICE r5).
            raise ValueError(
                f"checkpoint format v{meta['version']} not in "
                f"(v3, v4, v{FORMAT_VERSION})")
        # v3 snapshots predate dims_class; a v3 file carrying variant-only
        # keys (e.g. 'targets') cannot be restored to the right class with
        # confidence, so it is rejected rather than guessed at.
        cls_name = meta.get("dims_class")
        if cls_name is None:
            extra = set(meta["dims"]) - set(
                f.name for f in dataclasses.fields(RaftDims))
            if extra:
                # Only the UNEXPECTED keys: listing the full dims dict
                # buried the one key that mattered (ADVICE r5).
                raise ValueError(
                    "v3 checkpoint was written by a dims VARIANT "
                    f"(unexpected dims keys {sorted(extra)}); v3 metadata "
                    "does not record the class — re-run the variant from "
                    "scratch to produce a v4 snapshot")
            cls_name = "RaftDims"
        registry = _dims_registry()
        if cls_name not in registry:
            raise ValueError(
                f"checkpoint dims class {cls_name!r} is not in this "
                f"build's registry ({sorted(registry)}); it was written "
                "by a build with more dims variants")
        cls = registry[cls_name]
        dims = cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in meta["dims"].items()})
        if "state_width" in meta:
            from ..models.schema import state_width
            if state_width(dims) != meta["state_width"]:
                raise ValueError(
                    f"checkpoint row width {meta['state_width']} != "
                    f"{state_width(dims)} for {cls.__name__}: the packed "
                    "layout changed since this snapshot was written")
        deflated = meta.get("deflated", {})     # none before v5
        with _pool() as ex:
            got = {name: (_inflate(ex, z, name, deflated[name])
                          if name in deflated else z[name])
                   for name in ("frontier", "seen_hi", "seen_lo", "trace_fps",
                                "trace_parents", "trace_actions")}
        member = got.__getitem__
        return Checkpoint(
            dims=dims,
            frontier=member("frontier"),
            seen_hi=member("seen_hi"),
            seen_lo=member("seen_lo"),
            distinct=meta["distinct"],
            generated=meta["generated"],
            diameter=meta["diameter"],
            levels=tuple(meta["levels"]),
            action_counts=dict(meta.get("action_counts", {})),
            wall_seconds=float(meta.get("wall_seconds", 0.0)),
            trace_fps=member("trace_fps"),
            trace_parents=member("trace_parents"),
            trace_actions=member("trace_actions"),
            roots=pickle.loads(bytes(z["roots"])))


def _list_snapshots(checkpoint_dir: str):
    """``[(level, [names])]`` of single snapshots and COMPLETE piece
    groups in ``checkpoint_dir`` (no health check — callers decide)."""
    singles, groups = [], {}
    for name in os.listdir(checkpoint_dir):
        m = _PIECE_RE.match(name)
        if m:
            lvl = int(m.group(1)[len("level_"):])
            groups.setdefault((lvl, int(m.group(3))), []).append(name)
            continue
        if name.startswith("level_") and name.endswith(".npz"):
            try:
                singles.append((int(name[len("level_"):-len(".npz")]),
                                [name]))
            except ValueError:
                continue
    return singles + [(lvl, sorted(names))
                      for (lvl, nproc), names in groups.items()
                      if len(names) == nproc]


def _group_is_intact(checkpoint_dir: str, names) -> bool:
    """Every piece readable AND one run generation: pieces write their
    psum-replicated counters into the metadata, so disagreement means
    the group mixes pieces from different runs (a crash between piece
    overwrites) — load() would raise on it, which is exactly the crash
    pattern auto-resume exists for, so it must be skipped HERE."""
    counters = set()
    try:
        for name in names:
            with np.load(os.path.join(checkpoint_dir, name)) as z:
                meta = json.loads(bytes(z["meta"]).decode())
            counters.add((meta["distinct"], meta["generated"],
                          meta["diameter"], tuple(meta["levels"])))
    except Exception:
        return False
    return len(counters) == 1


def latest(checkpoint_dir: str) -> Optional[str]:
    """Path of the newest *resumable* checkpoint in ``checkpoint_dir`` —
    a single-file snapshot, or any piece of a COMPLETE multi-host piece
    group (load() resolves the siblings).  Unreadable/truncated files
    (e.g. a crash mid-write), incomplete groups, and groups whose pieces
    disagree on counters (mixed run generations — a crash between piece
    overwrites) are skipped, falling back to the next-newest intact
    snapshot."""
    if not os.path.isdir(checkpoint_dir):
        return None
    for _lvl, names in sorted(_list_snapshots(checkpoint_dir),
                              reverse=True):
        if _group_is_intact(checkpoint_dir, names):
            return os.path.join(checkpoint_dir, names[0])
    return None


# Any file retention may touch: single/piece snapshots and their .tmp
# leftovers.  Group 1 is the level — the only retention criterion.
_SNAP_FILE_RE = re.compile(r"^level_(\d+)(?:\.p\d+of\d+)?\.npz(?:\.tmp)?$")


def gc(checkpoint_dir: str, keep: Optional[int]) -> int:
    """Retention: once ``keep`` intact snapshots/piece groups exist,
    delete EVERY snapshot file strictly older than the oldest kept one —
    surplus good snapshots, incomplete piece groups, and orphaned
    ``.tmp`` leftovers of torn writes alike (crash debris is exactly
    what a long supervised run accumulates).  Called by the engines
    after each successful snapshot write (``EngineConfig.
    keep_checkpoints``; None/0/negative = keep all).  Torn or
    mixed-generation entries never count toward the ``keep`` quota —
    retention must not evict the last good snapshot because garbage
    outnumbers it — and nothing at or above the oldest kept level is
    ever touched (a sibling controller may still be renaming its piece
    of the newest group).  Returns the number of files removed."""
    if not keep or keep < 0 or not os.path.isdir(checkpoint_dir):
        return 0
    intact = [lvl for lvl, names in sorted(_list_snapshots(checkpoint_dir),
                                           reverse=True)
              if _group_is_intact(checkpoint_dir, names)]
    if len(intact) < keep:
        return 0             # quota not yet filled: nothing is surplus
    cutoff = intact[keep - 1]          # oldest kept level
    removed = 0
    for name in os.listdir(checkpoint_dir):
        m = _SNAP_FILE_RE.match(name)
        if m is None or int(m.group(1)) >= cutoff:
            continue
        try:
            os.unlink(os.path.join(checkpoint_dir, name))
            removed += 1
        except OSError:
            pass             # a sibling controller's gc got there first
    return removed
