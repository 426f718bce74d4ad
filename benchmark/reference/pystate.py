"""Canonical pure-Python state representation (oracle side).

This module defines an immutable, hashable mirror of the reference spec's
state vector ``vars == <<messages, serverVars, candidateVars, leaderVars,
logVars>>`` (/root/reference/raft.tla:74), using the integer encodings from
``dims.py``.  It is the ground-truth representation for the differential
oracle and for decoding/pretty-printing device tensors.

Messages: the spec models the network as a *bag* (multiset) of records
(raft.tla:29-31).  Here a message is a flat tuple

    (mtype, msource, mdest, mterm, payload...)

with payload per type (schemas raft.tla:443-475):

    RVQ: (mlastLogTerm, mlastLogIndex)
    RVR: (mvoteGranted, mlog)          mlog = ((term, value), ...)
    AEQ: (mprevLogIndex, mprevLogTerm, mentries, mcommitIndex)
                                       mentries = () or ((term, value),)
    AER: (msuccess, mmatchIndex)

and the bag is a ``frozenset`` of ``(message, count)`` pairs — canonical and
hashable.  Servers here are 0-based ints; values are 1..V; roles/Nil per
``dims``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple, FrozenSet

from .dims import FOLLOWER, NIL, RVQ, RVR, AEQ, MSG_TYPE_NAMES, RaftDims

Entry = Tuple[int, int]                 # (term, value)
Log = Tuple[Entry, ...]
Message = Tuple                          # as documented above
Bag = FrozenSet[Tuple[Message, int]]


@dataclasses.dataclass(frozen=True)
class PyState:
    """One global state of the Raft spec (raft.tla:27-74)."""

    current_term: Tuple[int, ...]        # [N]  raft.tla:37
    role: Tuple[int, ...]                # [N]  raft.tla:39 ("state" in spec)
    voted_for: Tuple[int, ...]           # [N]  raft.tla:42; 0=Nil, j+1=server j
    log: Tuple[Log, ...]                 # [N]  raft.tla:48
    commit_index: Tuple[int, ...]        # [N]  raft.tla:50
    votes_responded: Tuple[int, ...]     # [N] bitmask  raft.tla:56
    votes_granted: Tuple[int, ...]       # [N] bitmask  raft.tla:59
    next_index: Tuple[Tuple[int, ...], ...]   # [N][N]  raft.tla:64
    match_index: Tuple[Tuple[int, ...], ...]  # [N][N]  raft.tla:67
    messages: Bag                        # raft.tla:31

    def bag_dict(self):
        return dict(self.messages)

    def replace(self, **kw) -> "PyState":
        return dataclasses.replace(self, **kw)


def init_state(dims: RaftDims) -> PyState:
    """The unique initial state — ``Init`` raft.tla:113-129."""
    n = dims.n_servers
    return PyState(
        current_term=(1,) * n,                       # raft.tla:113
        role=(FOLLOWER,) * n,                        # raft.tla:114
        voted_for=(NIL,) * n,                        # raft.tla:115
        log=((),) * n,                               # raft.tla:123
        commit_index=(0,) * n,                       # raft.tla:124
        votes_responded=(0,) * n,                    # raft.tla:116
        votes_granted=(0,) * n,                      # raft.tla:117
        next_index=tuple((1,) * n for _ in range(n)),   # raft.tla:121
        match_index=tuple((0,) * n for _ in range(n)),  # raft.tla:122
        messages=frozenset(),                        # raft.tla:125 (EmptyBag)
    )


# ---------------------------------------------------------------------------
# Bag helpers — WithMessage/WithoutMessage raft.tla:88-92.

def bag_add(bag: Bag, m: Message) -> Bag:
    d = dict(bag)
    d[m] = d.get(m, 0) + 1
    return frozenset(d.items())


def bag_remove(bag: Bag, m: Message) -> Bag:
    d = dict(bag)
    c = d.get(m, 0)
    if c <= 1:
        d.pop(m, None)
    else:
        d[m] = c - 1
    return frozenset(d.items())


def bag_reply(bag: Bag, response: Message, request: Message) -> Bag:
    """Reply == add response, remove request, atomically (raft.tla:102-103)."""
    return bag_remove(bag_add(bag, response), request)


# ---------------------------------------------------------------------------
# Pretty-printing (for counterexample traces; mirrors TLC's state dumps).
#
# ONE formatter: ``state_fields`` is the canonical decoded view of a state
# (JSON-able, per-server fields + the message bag), ``format_state`` and
# the counterexample explainer (engine/explain.py) both render FROM it,
# and ``diff_states`` computes changed-field deltas over the same keys —
# so the oracle/debug printouts and the explainer can never drift apart.

ROLE_LETTERS = {0: "F", 1: "C", 2: "L"}
ROLE_NAMES = {0: "Follower", 1: "Candidate", 2: "Leader"}


def format_message(m: Message, dims: RaftDims) -> str:
    t = m[0]
    head = f"{MSG_TYPE_NAMES[t]} r{m[1]+1}->r{m[2]+1} term={m[3]}"
    if t == RVQ:
        return head + f" lastLogTerm={m[4]} lastLogIndex={m[5]}"
    if t == RVR:
        return head + f" granted={bool(m[4])} mlog={list(m[5])}"
    if t == AEQ:
        return (head + f" prevLogIndex={m[4]} prevLogTerm={m[5]}"
                f" entries={list(m[6])} commitIndex={m[7]}")
    return head + f" success={bool(m[4])} matchIndex={m[5]}"


def state_fields(s: PyState, dims: RaftDims) -> dict:
    """Canonical decoded view of one state: ``{"r<i>.<field>": value}``
    per server plus the sorted message bag under ``"messages"`` —
    JSON-able, and the shared substrate for ``format_state``,
    ``diff_states``, and the counterexample explainer."""
    n = dims.n_servers
    out = {}
    for i in range(n):
        r = f"r{i+1}"
        out[f"{r}.term"] = s.current_term[i]
        out[f"{r}.role"] = ROLE_LETTERS.get(s.role[i], str(s.role[i]))
        out[f"{r}.votedFor"] = ("Nil" if s.voted_for[i] == NIL
                                else f"r{s.voted_for[i]}")
        out[f"{r}.log"] = [list(e) for e in s.log[i]]
        out[f"{r}.commitIndex"] = s.commit_index[i]
        out[f"{r}.votesResponded"] = f"{s.votes_responded[i]:0{n}b}"
        out[f"{r}.votesGranted"] = f"{s.votes_granted[i]:0{n}b}"
        out[f"{r}.nextIndex"] = list(s.next_index[i])
        out[f"{r}.matchIndex"] = list(s.match_index[i])
    out["messages"] = [{"count": c, "msg": format_message(m, dims)}
                       for m, c in sorted(s.messages)]
    return out


def diff_states(a: PyState, b: PyState, dims: RaftDims) -> dict:
    """Changed fields ``a -> b`` as ``{key: [old, new]}`` over the
    ``state_fields`` keys; the message bag diffs as added/removed
    rendered messages.  The explainer's per-step "what this action
    changed" column comes from exactly this."""
    fa, fb = state_fields(a, dims), state_fields(b, dims)
    out = {}
    for k in fa:
        if k == "messages":
            continue
        if fa[k] != fb[k]:
            out[k] = [fa[k], fb[k]]
    da = dict(a.messages)
    db = dict(b.messages)
    added = [f"{db[m] - da.get(m, 0)}x {format_message(m, dims)}"
             for m in sorted(db) if db[m] > da.get(m, 0)]
    removed = [f"{da[m] - db.get(m, 0)}x {format_message(m, dims)}"
               for m in sorted(da) if da[m] > db.get(m, 0)]
    if added:
        out["messages.added"] = added
    if removed:
        out["messages.removed"] = removed
    return out


def format_state(s: PyState, dims: RaftDims) -> str:
    n = dims.n_servers
    f = state_fields(s, dims)
    lines = []
    for i in range(n):
        r = f"r{i+1}"
        log = [tuple(e) for e in f[f"{r}.log"]]
        lines.append(
            f"  {r}: term={f[f'{r}.term']} role={f[f'{r}.role']}"
            f" votedFor={f[f'{r}.votedFor']} log={log}"
            f" commit={f[f'{r}.commitIndex']}"
            f" resp={f[f'{r}.votesResponded']} gran={f[f'{r}.votesGranted']}"
            f" nextIndex={f[f'{r}.nextIndex']}"
            f" matchIndex={f[f'{r}.matchIndex']}")
    msgs = f["messages"]
    lines.append(f"  messages ({len(msgs)} distinct):")
    for m in msgs:
        lines.append(f"    {m['count']}x {m['msg']}")
    return "\n".join(lines)
