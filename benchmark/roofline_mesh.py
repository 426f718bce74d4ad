"""The least bytes the mesh's owner-routed dedup must move between chips
in one pass, and the least time that takes: the yardstick behind
``exchange_roofline``.

Every candidate a chip generates is one query: its 64-bit fingerprint has
to reach the chip that owns it (``fp_hi mod chips``) and one novelty bit
has to come back.  A query whose owner is the chip that generated it
crosses nothing, and fingerprints are uniform, so of a chip's queries
``(chips - 1) / chips`` leave it.  Everything else the program ships (the
K-lane blocks padded with sentinels, a byte per answer where a bit would
do: ``shipped_bytes``) is the program's choice, and counts against it.
"""

from __future__ import annotations

KEY_BYTES = 8
ANSWER_BYTES = 1 / 8


def exchange_operations(queries_per_chip: float, chips: int) -> float:
    """Queries of one chip, in one pass, that cross the interconnect."""
    return queries_per_chip * (chips - 1) / chips


def least_exchange_bytes(queries_per_chip: float, chips: int) -> float:
    """Bytes one chip must send and receive for them: as many queries
    arrive as leave."""
    return exchange_operations(queries_per_chip, chips) * (
        KEY_BYTES + ANSWER_BYTES)


def shipped_bytes(lanes: int, chips: int) -> float:
    """Bytes one chip really sends in one pass: a block of ``lanes``
    32-bit halves, twice, to every other chip, and a byte per lane back,
    whatever part of a block holds queries (parallel/mesh.py
    ``route_insert``)."""
    return (chips - 1) * lanes * (4 + 4 + 1)


def least_exchange_seconds(queries_per_chip: float, chips: int,
                           ici_bytes_per_s: float) -> float:
    return least_exchange_bytes(queries_per_chip, chips) / ici_bytes_per_s


def peak_for(device_kind: str, peaks: dict) -> dict:
    """The table's row for this chip; a kind it does not list is an
    error, never a default."""
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks_ici.json (has {sorted(peaks)})")
    return peaks[device_kind]
