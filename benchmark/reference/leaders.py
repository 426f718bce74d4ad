"""Roots past the first election for the base model (no JAX, no program
import): what a ``rooted_window`` cell of a configuration on upstream's
3-server model starts from, where ``reconfig.py`` gives the variant's.

From ``Init`` a breadth-first window a chip can walk to holds almost no
leader (levels 9-10: ``BecomeLeader`` 12 times in 1.42 M generated), so
the log-replication families (``ClientRequest``, ``AppendEntries``,
``AdvanceCommitIndex``) never fire there and every predicate that reads a
log reads an empty one.  ``leader_roots`` starts the search where they do
fire: for each server i the canonical election ``reconfig.canonical_roots``
opens with (``Timeout(i)``, ``RequestVote(i, j)`` ascending, every message
received in sorted order until the bag is empty, ``BecomeLeader(i)``; the
same ``_take``/``_deliver_all``, by import), then two reference levels
from the three elected states under TLC's constraint semantics (a state
outside ``BoundedSpace`` is generated, never expanded), keeping the states
that still hold a leader and lie inside the constraint.  At upstream's
constants (3 servers, 2 values, MaxTerm 3, MaxLogLen 2, MaxMsgCount 1)
the two levels hold 129 leader-holding states, 12 of them outside the
constraint (six hold a message twice, six a term of 4): 117 roots.

The module gives what ``traffic/rooted_window.py`` asks of a roots module:
``reference_dims``, ``reference_bounds``, ``FAMILY_NAMES``,
``path_is_legal`` and ``values_ok``.
"""

from __future__ import annotations

import dataclasses
from typing import List

from . import oracle
from .dims import (A_BECOMELEADER, A_REQUESTVOTE, A_TIMEOUT, LEADER, Bounds,
                   RaftDims, constraint_py)
from .pystate import PyState, init_state
from .reconfig import Root, _deliver_all, _take, path_is_legal  # noqa: F401

# The action families in grid order (raft.tla:421-430): the names the
# program's per-family statistics go by.
FAMILY_NAMES = ("Restart", "Timeout", "RequestVote", "BecomeLeader",
                "ClientRequest", "AdvanceCommitIndex", "AppendEntries",
                "Receive", "DuplicateMessage", "DropMessage")
# Families only a leader's existence enables.
LEADER_FAMILIES = ("BecomeLeader", "ClientRequest", "AdvanceCommitIndex",
                   "AppendEntries")

# Reference levels walked from the elected states.
LEVELS = 2


@dataclasses.dataclass(frozen=True)
class LeaderDims(RaftDims):
    """``RaftDims`` with the constraint's constants: which states are
    roots depends on them (a roots function is given the dims alone, as
    ``reconfig.ReconfigDims`` carries ``TargetConfigs``)."""

    bounds: Bounds = Bounds()


def reference_bounds(config: dict) -> Bounds:
    c = config["constants"]
    return Bounds(max_term=c.get("MaxTerm"), max_log_len=c.get("MaxLogLen"),
                  max_msg_count=c.get("MaxMsgCount"))


def reference_dims(config: dict) -> LeaderDims:
    """The dims a configuration file's numbers state."""
    c = config["constants"]
    return LeaderDims(n_servers=len(c["Server"]), n_values=len(c["Value"]),
                      n_msg_slots=config["n_msg_slots"],
                      bounds=reference_bounds(config))


def values_ok(s: PyState, dims: RaftDims) -> bool:
    """Every value a state carries (logs, ``mlog``, ``mentries``) is a
    client value 1..|Value|: ``TypeOK``'s domain in the base model."""
    entries = [e for log in s.log for e in log]
    for m, _count in s.messages:
        if m[0] == 1:       # RequestVoteResponse: mlog
            entries.extend(m[5])
        elif m[0] == 2:     # AppendEntriesRequest: mentries
            entries.extend(m[6])
    return all(1 <= v <= dims.n_values for _t, v in entries)


def elected(dims: RaftDims) -> List[list]:
    """For each server i, the path from ``Init`` to i elected by all."""
    paths = []
    for i in range(dims.n_servers):
        path = [(None, init_state(dims))]
        _take(path, dims, A_TIMEOUT, (i,))
        for j in range(dims.n_servers):
            if j != i:
                _take(path, dims, A_REQUESTVOTE, (i, j))
        _deliver_all(path, dims)
        _take(path, dims, A_BECOMELEADER, (i,))
        paths.append(path)
    return paths


def leader_states(dims: LeaderDims) -> List[Root]:
    """Every state within ``LEVELS`` reference levels of the three elected
    states that holds a leader, in the order the search meets them
    (servers ascending, ``oracle.successors``' own order a level), each
    with the path it was first reached by.  States outside the constraint
    are among them (generated, never expanded)."""
    constraint = constraint_py(dims.bounds)
    paths = {p[-1][1]: p for p in elected(dims)}      # insertion-ordered
    frontier = list(paths)
    for _level in range(LEVELS):
        nxt = []
        for s in frontier:
            for action, t in oracle.successors(s, dims):
                if t not in paths:
                    paths[t] = paths[s] + [(action, t)]
                    if constraint(t, dims):
                        nxt.append(t)
        frontier = nxt
    held = [(s, path) for s, path in paths.items() if LEADER in s.role]
    return [Root(f"L_{k}", s, path) for k, (s, path) in enumerate(held)]


def leader_roots(dims: LeaderDims) -> List[Root]:
    """``leader_states`` inside the constraint: the roots."""
    constraint = constraint_py(dims.bounds)
    return [r for r in leader_states(dims) if constraint(r.state, dims)]
