"""Constants and static sizes of the plain reference (no JAX, no program
import).  A trimmed copy of ``raft_tla_tpu/models/dims.py``: the codes the
interpreter in ``oracle.py`` needs and a ``RaftDims`` built from the numbers
a configuration file under ``benchmark/configs/`` states, never from an
object the program made.
"""

from __future__ import annotations

import dataclasses

# Role codes.
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
NIL = 0

# Message-type codes.
RVQ, RVR, AEQ, AER = 0, 1, 2, 3
MSG_TYPE_NAMES = ("RequestVoteRequest", "RequestVoteResponse",
                  "AppendEntriesRequest", "AppendEntriesResponse")

# Action-family codes; order mirrors the Next disjunction raft.tla:421-430.
A_RESTART = 0
A_TIMEOUT = 1
A_REQUESTVOTE = 2
A_BECOMELEADER = 3
A_CLIENTREQUEST = 4
A_ADVANCECOMMIT = 5
A_APPENDENTRIES = 6
A_RECEIVE = 7
A_DUPLICATE = 8
A_DROP = 9


@dataclasses.dataclass(frozen=True)
class RaftDims:
    """|Server|, |Value| and the fixed capacities of one model."""

    n_servers: int
    n_values: int
    max_log: int = 8
    n_msg_slots: int = 32

    def quorum_py(self, s, i: int, mask: int) -> bool:
        """Simple majority of Server (raft.tla:79-81)."""
        return 2 * bin(mask).count("1") > self.n_servers

    def extra_successors_py(self, s):
        """The base spec has no action family beyond raft.tla:421-430."""
        return ()


@dataclasses.dataclass(frozen=True)
class Bounds:
    """The BoundedSpace CONSTRAINT's constants (None = unbounded)."""

    max_term: int | None = None
    max_log_len: int | None = None
    max_msg_count: int | None = None


def constraint_py(bounds: Bounds):
    """TLC's CONSTRAINT: a state outside it is generated, counted and
    invariant-checked, but never expanded."""
    def constraint(s, dims) -> bool:
        ok = True
        if bounds.max_term is not None:
            ok &= max(s.current_term) <= bounds.max_term
        if bounds.max_log_len is not None:
            ok &= max(len(l) for l in s.log) <= bounds.max_log_len
        if bounds.max_msg_count is not None:
            ok &= all(c <= bounds.max_msg_count for _m, c in s.messages)
        return ok
    return constraint


def no_leader_py(s, dims) -> bool:
    """The NoLeaderElected canary: no server ever holds the Leader role."""
    return LEADER not in s.role
