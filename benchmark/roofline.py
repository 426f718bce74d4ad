"""The least bytes one batch of the breadth-first search must move, and
the least time that takes: the yardstick behind ``chunk_roofline``.

The chunk program does no matrix work, so its roofline is the memory one.
Per batch of B parents the algorithm cannot avoid:
  - reading the B parent rows                        B * row_bytes
  - one 8-byte seen-set slot read per generated successor (a probe)
  - per new distinct state: the 8-byte key written to the seen-set, the
    row written to the next-level queue, and the 20-byte trace record
    (two 64-bit fingerprints and the action id)
Everything else the program moves (the [B, G] masks, the K compacted
candidate rows, extra probe rounds) is the program's choice, not the
algorithm's, and counts against it.
"""

from __future__ import annotations

KEY_BYTES = 8
TRACE_RECORD_BYTES = 20


def batch_bytes(batch: int, row_bytes: int, generated_per_parent: float,
                new_per_parent: float) -> float:
    parents = batch * row_bytes
    probes = batch * generated_per_parent * KEY_BYTES
    admitted = batch * new_per_parent * (KEY_BYTES + row_bytes
                                         + TRACE_RECORD_BYTES)
    return parents + probes + admitted


def least_batch_seconds(batch: int, row_bytes: int,
                        generated_per_parent: float, new_per_parent: float,
                        hbm_bytes_per_s: float) -> float:
    return batch_bytes(batch, row_bytes, generated_per_parent,
                       new_per_parent) / hbm_bytes_per_s


def peak_for(device_kind: str, peaks: dict) -> dict:
    """The table's row for this chip; a kind it does not list is an
    error, never a default."""
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (has {sorted(peaks)})")
    return peaks[device_kind]
