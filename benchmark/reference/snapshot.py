"""A plain reader of the engine's level-boundary snapshot file, written
from the format's description (the engine's ``engine/checkpoint.py``,
format v5) with ``numpy``, ``zlib`` and ``json`` alone.  It imports nothing
of the program, so what it finds in a file is what the file holds, whoever
wrote it.

The file is an uncompressed ``.npz`` (a zip of ``.npy`` members):

  meta            uint8 bytes of a JSON object: ``version`` (5),
                  ``diameter`` (the level), ``distinct``, ``generated``,
                  ``levels`` (frontier rows per level so far),
                  ``wall_seconds``, ``state_width``, ``dims_class``,
                  ``dims``, ``action_counts``, and ``deflated``:
                  ``{array: {"shape": [...], "dtype": "<u4"}}``
  <array>__z      uint8: the array's bytes, cut into pieces of 8 MiB,
                  each piece deflated on its own, the pieces concatenated
  <array>__zoff   int64: where each piece starts in ``__z`` (one more
                  entry than pieces: the last is the total length)
  roots           a pickle of the trace roots (program objects: not read
                  here)

for the six arrays ``frontier`` ([rows, state_width] uint8), ``seen_hi``
and ``seen_lo`` (uint32: the two halves of the 64-bit keys, sorted by
(hi, lo)), ``trace_fps`` and ``trace_parents`` (uint64) and
``trace_actions`` (int32): one record a distinct state, its key, its
parent's key and the action instance that led to it.
"""

from __future__ import annotations

import json
import zlib

import numpy as np

ARRAYS = ("frontier", "seen_hi", "seen_lo", "trace_fps", "trace_parents",
          "trace_actions")


def read_meta(path: str) -> dict:
    """The metadata alone: one small member of the zip is read."""
    with np.load(path) as z:
        return json.loads(bytes(z["meta"]).decode())


def read(path: str) -> dict:
    """``{"meta": {...}, <array>: ndarray ...}`` of one snapshot file."""
    out = {}
    with np.load(path) as z:
        meta = out["meta"] = json.loads(bytes(z["meta"]).decode())
        if meta["version"] != 5:
            raise ValueError(f"{path}: format v{meta['version']}, this "
                             "reader was written for v5")
        for name in ARRAYS:
            spec = meta["deflated"][name]
            blob, offs = z[name + "__z"].tobytes(), z[name + "__zoff"]
            raw = b"".join(zlib.decompress(blob[offs[i]:offs[i + 1]])
                           for i in range(len(offs) - 1))
            out[name] = np.frombuffer(raw, np.dtype(spec["dtype"])).reshape(
                spec["shape"])
    return out


def keys64(snap: dict) -> np.ndarray:
    """The seen-set's keys as the file orders them, 64 bits each."""
    return ((snap["seen_hi"].astype(np.uint64) << np.uint64(32))
            | snap["seen_lo"].astype(np.uint64))


def strictly_ascending(keys: np.ndarray) -> bool:
    """Sorted and unique at once."""
    return bool(np.all(keys[1:] > keys[:-1]))


def missing_from(sorted_keys: np.ndarray, wanted: np.ndarray) -> int:
    """How many of ``wanted`` are not in ``sorted_keys``."""
    if not len(sorted_keys):
        return len(wanted)
    at = np.minimum(np.searchsorted(sorted_keys, wanted),
                    len(sorted_keys) - 1)
    return int(np.count_nonzero(sorted_keys[at] != wanted))


def records(snap: dict) -> np.ndarray:
    """The trace records as rows (key, parent's key, action), sorted by
    key: the order a store hands them out in is its own."""
    order = np.argsort(snap["trace_fps"], kind="stable")
    return np.column_stack([
        snap["trace_fps"][order], snap["trace_parents"][order],
        snap["trace_actions"][order].astype(np.int64).astype(np.uint64)])


def records_missing_from(newer: np.ndarray, older: np.ndarray) -> int:
    """How many records of ``older`` (``records`` rows) are not, all
    three fields, among ``newer``'s."""
    if not len(newer):
        return len(older)
    at = np.minimum(np.searchsorted(newer[:, 0], older[:, 0]),
                    len(newer) - 1)
    return int(np.count_nonzero(np.any(newer[at] != older, axis=1)))
