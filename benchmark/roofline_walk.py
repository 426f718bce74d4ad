"""The least bytes one lockstep step of the swarm's walk chunk must move,
and the least time that takes: the yardstick behind ``walk_roofline``.

The walk chunk does no matrix work, so its roofline is the memory one.
Per walker and step the algorithm cannot avoid:
  - reading the walker's packed row and writing its successor  2 * row_bytes
  - reading the ring of its last R accepted fingerprints       R * 8
  - writing the action it took into the trace's record         4
Everything else the program moves (the [lanes, G] guard masks, the
unpacked state, the observatory's filters and its lanes x lanes prior) is
the program's choice, not the algorithm's, and counts against it.
"""

from __future__ import annotations

KEY_BYTES = 8
ACTION_BYTES = 4


def walker_step_bytes(row_bytes: int, ring: int) -> int:
    return 2 * row_bytes + ring * KEY_BYTES + ACTION_BYTES


def least_step_seconds(walks: int, row_bytes: int, ring: int,
                       hbm_bytes_per_s: float) -> float:
    """One lockstep step of ``walks`` walkers."""
    return walks * walker_step_bytes(row_bytes, ring) / hbm_bytes_per_s
