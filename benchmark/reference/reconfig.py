"""Joint-consensus membership change for the plain reference (no JAX, no
program import): what ``configs/reconfig3.cfg`` adds to raft.tla, as a
``ReconfigDims`` over ``dims.RaftDims`` whose hooks ``oracle.py`` already
calls (``quorum_py`` in ``BecomeLeader`` and ``AdvanceCommitIndex``,
``extra_successors_py`` at the end of ``Next``), and the roots a
``rooted_window`` cell of it starts from.

Written from section 6 of "In Search of an Understandable Consensus
Algorithm" (Ongaro and Ousterhout, USENIX ATC 2014) and the rule list the
program's model documents (its ``models/reconfig.py`` docstring), not from
that module's code:

- configurations are log entries; a server uses the LATEST configuration
  entry in its own log, committed or not (paper 6: "a server always uses
  the latest configuration in its log"); with none, all of ``Server``;
- under C_old,new agreement (elections, commitment) takes a majority of
  C_old AND a majority of C_new, separately (paper 6); under a plain
  configuration, a majority of it.  A server counts towards a majority
  only where it is a member;
- ``InitiateReconfig(i, c)``: a leader whose latest configuration is plain
  (one change at a time) appends C_current,c for a target c other than its
  current one;
- ``FinalizeReconfig(i)``: a leader whose latest configuration is the joint
  C_old,new, once its commitIndex has reached that entry, appends C_new
  (paper 6: "once C_old,new has been committed ... the leader can create a
  log entry describing C_new").

Departures from the paper, all the model's own and kept because the
engine's counts are held to this reference's: a server outside every
current configuration still times out, campaigns and votes (its vote only
counts where it is a member); a leader that C_new leaves out does not step
down when C_new commits; new servers do not first catch up as non-voting
members; targets are the finite constant ``TargetConfigs``.

The value of a configuration entry is the one shared fact of the two
sides, as a message's layout is: ``CFG_BASE + (old << 8) + new`` with
``old == 0`` for a plain configuration; client values stay 1..|Value|.
Masks are bits over the server order of the cfg (r1 = bit 0).
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Tuple

from . import oracle
from .dims import (A_ADVANCECOMMIT, A_APPENDENTRIES, A_BECOMELEADER,
                   A_RECEIVE, A_REQUESTVOTE, A_TIMEOUT, LEADER, Bounds,
                   RaftDims)
from .pystate import PyState, init_state

CFG_BASE = 1 << 12
A_INITRECONFIG = 10
A_FINALIZE = 11

# The action families in grid order (raft.tla:421-430, then the two new
# ones): the names the program's per-family statistics go by.
FAMILY_NAMES = ("Restart", "Timeout", "RequestVote", "BecomeLeader",
                "ClientRequest", "AdvanceCommitIndex", "AppendEntries",
                "Receive", "DuplicateMessage", "DropMessage",
                "InitiateReconfig", "FinalizeReconfig")
# Families only a leader's existence enables, and the variant's own.
LEADER_FAMILIES = ("BecomeLeader", "ClientRequest", "AdvanceCommitIndex",
                   "AppendEntries", "InitiateReconfig", "FinalizeReconfig")
RECONFIG_FAMILIES = ("InitiateReconfig", "FinalizeReconfig")


def joint_value(old: int, new: int) -> int:
    return CFG_BASE + (old << 8) + new


def final_value(new: int) -> int:
    return CFG_BASE + new


def config_of(log, n: int) -> Tuple[int, int, int]:
    """(old, new, index) of the latest configuration entry of ``log``
    (1-based index; ``old == 0``: plain); all of Server at index 0 where
    the log holds none."""
    for index in range(len(log), 0, -1):
        value = log[index - 1][1]
        if value >= CFG_BASE:
            return ((value - CFG_BASE) >> 8) & 0xFF, \
                (value - CFG_BASE) & 0xFF, index
    return 0, (1 << n) - 1, 0


def _majority(members: int, config: int) -> bool:
    return 2 * bin(members & config).count("1") > bin(config).count("1")


@dataclasses.dataclass(frozen=True)
class ReconfigDims(RaftDims):
    """``RaftDims`` with ``TargetConfigs``."""

    targets: Tuple[int, ...] = ()

    @property
    def family_sizes(self) -> Tuple[int, ...]:
        """Instances of each family of ``FAMILY_NAMES`` in the program's
        action grid (the three network families one a message slot)."""
        n, v, m = self.n_servers, self.n_values, self.n_msg_slots
        return (n, n, n * n, n, n * v, n, n * n, m, m, m,
                n * len(self.targets), n)

    def quorum_py(self, s, i: int, mask: int) -> bool:
        old, new, _index = config_of(s.log[i], self.n_servers)
        if old:
            return _majority(mask, old) and _majority(mask, new)
        return _majority(mask, new)

    def extra_successors_py(self, s):
        out = []
        for i in range(self.n_servers):
            if s.role[i] != LEADER:
                continue
            old, new, index = config_of(s.log[i], self.n_servers)
            if not old:
                for c in self.targets:
                    if c != new:
                        out.append(((A_INITRECONFIG, (i, c)), _append(
                            s, i, joint_value(new, c))))
            elif s.commit_index[i] >= index:
                out.append(((A_FINALIZE, (i,)),
                            _append(s, i, final_value(new))))
        return out

    def value_ok_py(self, value: int) -> bool:
        """``TypeOK``'s domain of a log entry's value: a client value, or
        a configuration entry whose masks are sets of servers, the new
        one not empty."""
        if 1 <= value <= self.n_values:
            return True
        full = (1 << self.n_servers) - 1
        enc = value - CFG_BASE
        return (value >= CFG_BASE and enc >> 16 == 0
                and 1 <= (enc & 0xFF) <= full and (enc >> 8) <= full)


def _append(s: PyState, i: int, value: int) -> PyState:
    log = s.log[i] + ((s.current_term[i], value),)
    return s.replace(log=s.log[:i] + (log,) + s.log[i + 1:])


def values_ok(s: PyState, dims: ReconfigDims) -> bool:
    """Every value a state carries (logs, ``mlog`` of a vote response,
    ``mentries`` of an append request) is in ``value_ok_py``'s domain:
    what ``TypeOK`` asks of the variant beyond ``safety.type_ok``."""
    entries = [e for log in s.log for e in log]
    for m, _count in s.messages:
        if m[0] == 1:       # RequestVoteResponse: mlog
            entries.extend(m[5])
        elif m[0] == 2:     # AppendEntriesRequest: mentries
            entries.extend(m[6])
    return all(dims.value_ok_py(v) for _t, v in entries)


def reference_dims(config: dict) -> ReconfigDims:
    """The dims a configuration file's numbers state."""
    c = config["constants"]
    return ReconfigDims(n_servers=len(c["Server"]), n_values=len(c["Value"]),
                        n_msg_slots=config["n_msg_slots"],
                        targets=tuple(sorted(c["TargetConfigs"])))


def reference_bounds(config: dict) -> Bounds:
    c = config["constants"]
    return Bounds(max_term=c.get("MaxTerm"), max_log_len=c.get("MaxLogLen"),
                  max_msg_count=c.get("MaxMsgCount"))


# -- the roots ----------------------------------------------------------------

class Root(NamedTuple):
    name: str
    state: PyState
    path: list      # [(action or None, state)] from ``Init``, root last


def _take(path: list, dims, family: int, params: tuple) -> None:
    """Extend ``path`` by the named action instance, taken from the
    reference's own ``successors`` of the path's last state."""
    found = [t for a, t in oracle.successors(path[-1][1], dims)
             if a == (family, params)]
    if len(found) != 1:
        raise AssertionError(
            f"step {len(path)}: {FAMILY_NAMES[family]}{params} is enabled "
            f"{len(found)} times")
    path.append(((family, params), found[0]))


def _deliver_all(path: list, dims) -> None:
    """``Receive`` the least message of the bag (tuple order) until the
    bag is empty.  A receive may leave its message (``UpdateTerm``, the
    append that a second receive acknowledges); every loop is finite."""
    for _ in range(64):
        bag = sorted(m for m, _c in path[-1][1].messages)
        if not bag:
            return
        _take(path, dims, A_RECEIVE, (bag[0],))
    raise AssertionError("the bag did not drain in 64 receives")


def canonical_roots(dims: ReconfigDims) -> List[Root]:
    """Three roots a server, nine at three servers, each with its path
    from ``Init``: **E_i** server i elected by all; **J_i** its joint
    entry (to the one target other than the current configuration)
    replicated to both followers and committed; **F_i** the final entry
    appended.  For the last server, which C_new = {r1, r2} leaves out,
    committing the joint entry takes both followers: its own match does
    not count in C_new."""
    roots = []
    n = dims.n_servers
    for i in range(n):
        others = [j for j in range(n) if j != i]
        path = [(None, init_state(dims))]
        _take(path, dims, A_TIMEOUT, (i,))
        for j in others:
            _take(path, dims, A_REQUESTVOTE, (i, j))
        _deliver_all(path, dims)
        _take(path, dims, A_BECOMELEADER, (i,))
        roots.append(Root(f"E_{i}", path[-1][1], list(path)))
        _old, current, _index = config_of(path[-1][1].log[i], n)
        (target,) = [c for c in dims.targets if c != current]
        _take(path, dims, A_INITRECONFIG, (i, target))
        for j in others:
            _take(path, dims, A_APPENDENTRIES, (i, j))
            _deliver_all(path, dims)
        _take(path, dims, A_ADVANCECOMMIT, (i,))
        roots.append(Root(f"J_{i}", path[-1][1], list(path)))
        _take(path, dims, A_FINALIZE, (i,))
        roots.append(Root(f"F_{i}", path[-1][1], list(path)))
    return roots


def path_is_legal(root: Root, dims) -> bool:
    """The path starts at ``Init`` and every state is a member of the
    reference's ``successor_set`` of the one before it."""
    states = [s for _a, s in root.path]
    return (states[0] == init_state(dims) and states[-1] == root.state
            and all(t in oracle.successor_set(s, dims)
                    for s, t in zip(states, states[1:])))
