"""``SmokeInit`` by the plain reference: the randomized initial states of
upstream's smoke test (lemmy/raft.tla ``Smokeraft.tla``), as membership
predicates, a product builder and a recogniser.  Imports nothing of the
program; ``oracle.py`` steps the states, unbounded (``Bounds()``).

Written from ``SURVEY.md`` section 3.2's account of ``Smokeraft.tla:4-76``
(no copy of the file is on this machine), in this reference's integer
encodings (``pystate.py``: servers 0-based, values 1..V, roles and Nil as
``dims.py`` has them, a vote set as a bitmask):

    :4-9    BoundedSeq(S, n) = sequences over S of length <= n;
            SmokeSeq(S) = BoundedSeq(S, 1), the sequences inside messages
    :11-12  SmokeNat = 0..2        :14-15  SmokeInt = -1..1
    :17-19  k = 2: the size of every RandomSubset, so k^9 initial states
    :24-62  SmokeMessageType: the union of four RandomSubset(k, .), one a
            message type, fields over SmokeNat but mprevLogIndex over
            SmokeInt, mentries and mlog over SmokeSeq(entries)
    :64-76  SmokeInit: each of the nine variables \\in RandomSubset(k, D)
            with D as ``DOMAINS`` below; ``messages`` one bag over
            SmokeMessageType with every multiplicity 1

Departures from upstream's text, each an assumption a letter-for-letter
copy of the file would settle:
- the bag is read as holding ALL of the four k-subsets (4k messages,
  exactly k of each type), shared by every initial state: TLC evaluates
  the one ``messages = ...`` conjunct once;
- ``nextIndex``'s domain is ``{n \\in SmokeNat : 1 <= n}`` = {1, 2}, since
  ``TypeOK`` (raft.tla:491) wants it positive;
- ``TypeOK`` is ``safety.py``'s with ``mprevLogIndex : Int`` (raft.tla:454,
  ``SURVEY.md`` section 2's table of message types), where that module
  reads ``Nat``: the two agree on every state reachable from ``Init``
  (``nextIndex >= 1``, so ``nextIndex - 1 >= 0``), and SmokeInt reaches -1;
- ``RandomSubset`` is TLC's own: which subsets it draws cannot be
  replayed, so a draw is DATA here (``product`` takes it, ``draw_of``
  reads it back) and the comparison with the program is over the shape
  (``is_smoke_init``) and, for a pinned draw, over the set.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence

from .dims import AEQ, AER, CANDIDATE, FOLLOWER, LEADER, NIL, RVQ, RVR, RaftDims
from .pystate import PyState
from . import safety

SMOKE_NAT = (0, 1, 2)           # Smokeraft.tla:11-12
SMOKE_INT = (-1, 0, 1)          # Smokeraft.tla:14-15
MAX_INIT_LOG = 3                # Smokeraft.tla:70
MAX_MESSAGE_SEQ = 1             # SmokeSeq, Smokeraft.tla:4-9

# The nine variables of SmokeInit, in PyState's order; ``messages`` is the
# tenth and is not a RandomSubset of its own.
VARIABLES = ("current_term", "role", "voted_for", "log", "commit_index",
             "votes_responded", "votes_granted", "next_index",
             "match_index")


def _entries(x, dims: RaftDims, longest: int) -> bool:
    """BoundedSeq([term : SmokeNat, value : Value], longest)."""
    return (isinstance(x, tuple) and len(x) <= longest and all(
        isinstance(e, tuple) and len(e) == 2 and e[0] in SMOKE_NAT
        and 1 <= e[1] <= dims.n_values for e in x))


def _per_server(x, n: int, member) -> bool:
    return isinstance(x, tuple) and len(x) == n and all(map(member, x))


def _square(x, n: int, cell) -> bool:
    return _per_server(x, n, lambda row: _per_server(row, n, cell))


# Smokeraft.tla:64-76: variable -> (value, dims) -> "is in its domain".
DOMAINS = {
    "current_term": lambda x, d: _per_server(
        x, d.n_servers, lambda t: t in SMOKE_NAT),
    "role": lambda x, d: _per_server(
        x, d.n_servers, lambda r: r in (FOLLOWER, CANDIDATE, LEADER)),
    "voted_for": lambda x, d: _per_server(
        x, d.n_servers, lambda v: v == NIL or 1 <= v <= d.n_servers),
    "log": lambda x, d: _per_server(
        x, d.n_servers, lambda l: _entries(l, d, MAX_INIT_LOG)),
    "commit_index": lambda x, d: _per_server(
        x, d.n_servers, lambda c: c in SMOKE_NAT),
    "votes_responded": lambda x, d: _per_server(
        x, d.n_servers, lambda m: 0 <= m < (1 << d.n_servers)),
    "votes_granted": lambda x, d: _per_server(
        x, d.n_servers, lambda m: 0 <= m < (1 << d.n_servers)),
    "next_index": lambda x, d: _square(
        x, d.n_servers, lambda i: i in SMOKE_NAT and i >= 1),
    "match_index": lambda x, d: _square(
        x, d.n_servers, lambda i: i in SMOKE_NAT),
}


def message_in_domain(m, dims: RaftDims) -> bool:
    """One element of SmokeMessageType (Smokeraft.tla:24-62), as the flat
    tuple ``pystate.py`` documents."""
    n = dims.n_servers
    if not (isinstance(m, tuple) and len(m) >= 4 and 0 <= m[1] < n
            and 0 <= m[2] < n and m[3] in SMOKE_NAT):
        return False
    if m[0] == RVQ:     # mlastLogTerm, mlastLogIndex
        return len(m) == 6 and m[4] in SMOKE_NAT and m[5] in SMOKE_NAT
    if m[0] == RVR:     # mvoteGranted, mlog
        return (len(m) == 6 and m[4] in (0, 1)
                and _entries(m[5], dims, MAX_MESSAGE_SEQ))
    if m[0] == AEQ:     # mprevLogIndex, mprevLogTerm, mentries, mcommitIndex
        return (len(m) == 8 and m[4] in SMOKE_INT and m[5] in SMOKE_NAT
                and _entries(m[6], dims, MAX_MESSAGE_SEQ)
                and m[7] in SMOKE_NAT)
    if m[0] == AER:     # msuccess, mmatchIndex
        return len(m) == 6 and m[4] in (0, 1) and m[5] in SMOKE_NAT
    return False


def type_ok(s: PyState, dims: RaftDims) -> bool:
    """TypeOK (raft.tla:482-492) as ``safety.py`` has it, but for
    ``mprevLogIndex``, which is an ``Int`` here as upstream has it."""
    def as_nat(m):
        if m[0] == AEQ and isinstance(m[4], int) \
                and not isinstance(m[4], bool):
            return m[:4] + (abs(m[4]),) + m[5:]
        return m
    return safety.type_ok(s.replace(messages=frozenset(
        (as_nat(m), c) for m, c in s.messages)), dims)


def product(draw: Dict[str, Sequence]) -> List[PyState]:
    """The initial states of one draw: ``draw[v]`` the k-subset of each of
    ``VARIABLES``, ``draw["messages"]`` the bag's messages.  k^9 states,
    every one holding the same bag at multiplicity 1."""
    bag = frozenset((m, 1) for m in draw["messages"])
    return [PyState(messages=bag, **dict(zip(VARIABLES, combo)))
            for combo in itertools.product(*(draw[v] for v in VARIABLES))]


def draw_of(states: Sequence[PyState]) -> Dict[str, list]:
    """The draw a set of states would be the product of: the values each
    variable takes, sorted, and the first state's bag (``is_smoke_init``
    says whether the states ARE that product)."""
    draw = {v: sorted({getattr(s, v) for s in states}) for v in VARIABLES}
    draw["messages"] = sorted(m for m, _c in states[0].messages)
    return draw


def is_smoke_init(states: Sequence[PyState], k: int, dims: RaftDims) -> list:
    """What keeps ``states`` from being a SmokeInit set of subset size k:
    a list of findings, empty when they are one.  k^9 distinct states;
    every variable taking exactly k values, each in its domain; all
    combinations present; one bag shared by all, every multiplicity 1,
    exactly k messages of each of the four types, each in its domain."""
    wrong = []
    if len(states) != k ** len(VARIABLES):
        wrong.append(f"{len(states)} states, not k^9 = {k ** len(VARIABLES)}")
    if len(set(states)) != len(states):
        wrong.append(f"only {len(set(states))} of {len(states)} distinct")
    if not states:
        return wrong
    draw = draw_of(states)
    for v in VARIABLES:
        if len(draw[v]) != k:
            wrong.append(f"{v} takes {len(draw[v])} values, not k = {k}")
        bad = [x for x in draw[v] if not DOMAINS[v](x, dims)]
        if bad:
            wrong.append(f"{v} outside its domain: {bad[:2]}")
    # k values a variable and k^9 distinct states over one bag: every
    # combination is there.  Said by building it, not by counting.
    if set(product(draw)) != set(states):
        wrong.append("not the product of the values the variables take")
    bags = {s.messages for s in states}
    if len(bags) != 1:
        wrong.append(f"{len(bags)} different bags, not one shared")
    for bag in list(bags)[:1]:
        if any(c != 1 for _m, c in bag):
            wrong.append("a multiplicity other than 1")
        bad = [m for m, _c in bag if not message_in_domain(m, dims)]
        if bad:
            wrong.append(f"messages outside SmokeMessageType: {bad[:2]}")
        by_type = [sum(m[0] == t for m, _c in bag)
                   for t in (RVQ, RVR, AEQ, AER)]
        if by_type != [k] * 4:
            wrong.append(f"messages by type {by_type}, not k = {k} of each")
    return wrong


def to_json(draw: Dict[str, Sequence]) -> dict:
    """A draw as JSON holds it (tuples become lists)."""
    return {v: _listed(x) for v, x in draw.items()}


def from_json(obj: dict) -> Dict[str, list]:
    """``to_json`` back: every value and message a (nested) tuple."""
    return {v: [_tupled(x) for x in xs] for v, xs in obj.items()}


def _listed(x):
    return [_listed(y) for y in x] if isinstance(x, (tuple, list)) else x


def _tupled(x):
    return tuple(_tupled(y) for y in x) if isinstance(x, list) else x
