"""Reader ``suite``: the device time of the safety suite a pass, apart by
whether a predicate reads a log.

``readers/construct.py split`` already gives the self time of part
``invariants`` of stage ``construct`` by predicate (each runs under its cfg
name, ``engine/check.py resolve_invariants``); this reader sums it into
two: ``logs``, the seven predicates of raft.tla:1041-1180 that read a
log's entries (``LOGS``), whose arithmetic ran on empty logs in every cell
before ``leader-rich`` and ``reconfig3-safety``; and ``rest``: ``TypeOK``,
``MessagesInv``, ``LeaderVotesQuorum`` and the dispatch over the verdicts.
The two sum to ``construct_ms.invariants``.  By the FUSED operation's own
path, as ``construct`` reads its parts: a predicate XLA fused into a
neighbour is counted under the neighbour's name.

A program whose ``invariants`` names none of ``LOGS`` (a cfg that names
``TypeOK`` alone, or a program from before the predicates were named)
gives nothing.

Modes of ``read``:
  logs  self time of the operations under the seven, ms a pass
  rest  ``construct_ms.invariants`` less that
"""

from __future__ import annotations

import bench_lib as lib

LOGS = ("CandidateTermNotInLog", "ElectionSafety", "LogMatching",
        "VotesGrantedInv", "QuorumLogInv", "MoreUpToDateCorrect",
        "LeaderCompleteness")


def read(run: dict, mode: str):
    if mode not in ("logs", "rest"):
        raise ValueError(f"suite reader: unknown mode {mode!r}")
    tab = lib.load_module("readers", "construct").split(run)
    if tab is None or not any(p in tab["pred_ns"] for p in LOGS):
        return None
    logs = sum(tab["pred_ns"].get(p, 0) for p in LOGS)
    ns = logs if mode == "logs" else \
        tab["part_ns"].get("invariants", 0) - logs
    return ns / 1e6 / tab["passes"]
