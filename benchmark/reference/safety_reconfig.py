"""What joint-consensus reconfiguration adds to ``safety.py`` (no JAX, no
program import): the variant's ``TypeOK``, and witnesses whose logs hold
configuration entries.

``safety.py``'s nine safety predicates read ``PyState`` alone and are used
here as they stand.  Its ``type_ok`` is not the variant's: it holds an
entry's value to ``1..|Value|``, so it is false on every state whose logs
or messages hold a configuration entry, and its ``witness``,
``witness_parents`` and ``first_failing`` are written over it.  This
module writes ``TypeOK`` out again (raft.tla:443-493) with
``dims.value_ok_py`` (``reconfig.ReconfigDims``) as the domain of an
entry's value in ``log``, ``mlog`` and ``mentries``; on a state without
configuration entries the two agree.  ``INVARIANTS`` is that ``TypeOK`` and
the nine; ``first_failing``, ``witness`` and ``witness_parents`` are
``safety.py``'s over them.

The three ``Quorum`` predicates (``LeaderVotesQuorum``,
``CandidateTermNotInLog``, ``QuorumLogInv``) stay raft.tla:79-81's simple
majority over ``Server``, the text ``configs/reconfig3_safety.cfg`` names:
at three servers with ``TargetConfigs = {3, 7}`` every joint or plain
quorum has two members or more, so the joint rule implies them; it does
not equal them.

Witness makers (``MAKERS``: name -> (the invariant it is made for, the
maker)).  ``safety.py``'s nine by import, but ``LogMatching``'s: that one
needs two client values and ``reconfig3`` has one, so here the two records
differ in any two values of the variant's domain.  Two of the variant's
own: ``witness_log_matching_high_byte`` (two logs hold, at one index and
one term, ``joint_value(7, 3)`` = 5,891 and ``final_value(3)`` = 4,099:
records that differ in the value's HIGH byte alone, so a predicate that
compared one byte of a value would call the logs equal) and
``witness_leader_completeness_config`` (a follower has committed a
configuration entry that the leader's log lacks).

``witness_parents`` keeps at least half of a maker's witnesses from among
those that hold a configuration entry in some log, where the maker can
make such a witness.  Four cannot: ``VotesGrantedInv``, ``QuorumLogInv``,
``MoreUpToDateCorrect`` and ``LeaderCompleteness`` rewrite every server's
log with client entries and empty the bag (``safety.py``); the variant's
own ``witness_leader_completeness_config`` is ``LeaderCompleteness``'s
with a configuration entry.
"""

from __future__ import annotations

import random
from typing import Callable, Dict, Optional, Sequence, Tuple

from . import safety
from .dims import AEQ, AER, CANDIDATE, FOLLOWER, LEADER, NIL, RVQ, RVR
from .oracle import successors
from .pystate import PyState
from .reconfig import CFG_BASE, ReconfigDims, final_value, joint_value
from .safety import (_drop_votes_of, _elect, _nat, _pick, _quiet, _set,
                     _silent, _top_term)


# ---------------------------------------------------------------------------
# TypeOK — raft.tla:482-492 over the message schemas of :443-479, an
# entry's value in ``dims.value_ok_py``'s domain.

def _entries_ok(entries, dims: ReconfigDims) -> bool:
    """Seq([term : Nat, value : Value or a configuration entry])."""
    return isinstance(entries, tuple) and all(
        len(e) == 2 and _nat(e[0]) and dims.value_ok_py(e[1])
        for e in entries)


def _message_ok(m, dims: ReconfigDims) -> bool:
    n = dims.n_servers
    if not (len(m) >= 4 and m[0] in (RVQ, RVR, AEQ, AER)
            and 0 <= m[1] < n and 0 <= m[2] < n and _nat(m[3])):
        return False
    if m[0] == RVQ:         # :443-449 mlastLogTerm, mlastLogIndex
        return len(m) == 6 and _nat(m[4]) and _nat(m[5])
    if m[0] == RVR:         # :451-458 mvoteGranted, mlog
        return len(m) == 6 and m[4] in (0, 1) and _entries_ok(m[5], dims)
    if m[0] == AEQ:         # :460-470 prev index and term, entries, commit
        return (len(m) == 8 and _nat(m[4]) and _nat(m[5])
                and _entries_ok(m[6], dims) and len(m[6]) <= 1
                and _nat(m[7]))
    return len(m) == 6 and m[4] in (0, 1) and _nat(m[5])   # :472-479


def type_ok(s: PyState, dims: ReconfigDims) -> bool:
    n = dims.n_servers
    S = range(n)
    return (
        all(len(f) == n for f in (
            s.current_term, s.role, s.voted_for, s.log, s.commit_index,
            s.votes_responded, s.votes_granted, s.next_index,
            s.match_index))
        and all(_nat(s.current_term[i]) for i in S)
        and all(s.role[i] in (FOLLOWER, CANDIDATE, LEADER) for i in S)
        and all(s.voted_for[i] == NIL or 1 <= s.voted_for[i] <= n
                for i in S)
        and all(_entries_ok(s.log[i], dims) for i in S)
        and all(_nat(s.commit_index[i]) for i in S)
        and all(0 <= s.votes_responded[i] < (1 << n) for i in S)
        and all(0 <= s.votes_granted[i] < (1 << n) for i in S)
        and all(len(s.next_index[i]) == n
                and all(_nat(x) and x >= 1 for x in s.next_index[i])
                for i in S)                                     # :491
        and all(len(s.match_index[i]) == n
                and all(_nat(x) for x in s.match_index[i]) for i in S)
        and all(_nat(c) and c >= 1 and _message_ok(m, dims)
                for m, c in s.messages))


INVARIANTS: Dict[str, Callable[[PyState, ReconfigDims], bool]] = {
    **safety.INVARIANTS, "TypeOK": type_ok}


def first_failing(s: PyState, names: Sequence[str],
                  dims: ReconfigDims) -> Optional[str]:
    """``safety.first_failing`` over this module's ``INVARIANTS``."""
    for name in names:
        if not INVARIANTS[name](s, dims):
            return name
    return None


def holds_config(s: PyState) -> bool:
    """Some log holds a configuration entry."""
    return any(v >= CFG_BASE for log in s.log for _t, v in log)


# ---------------------------------------------------------------------------
# Witness makers (``safety.py``'s contract: a state, a ``random.Random``
# and the dims in, a mutant out; ``witness`` then CHECKS what the maker
# meant).

def _values(dims: ReconfigDims) -> list:
    """The variant's domain at these constants: the client values, and
    the joint and final entries over ``TargetConfigs`` and all of
    ``Server``."""
    masks = sorted({*dims.targets, (1 << dims.n_servers) - 1})
    return ([*range(1, dims.n_values + 1)]
            + [joint_value(a, b) for a in masks for b in masks if a != b]
            + [final_value(a) for a in masks])


def _two_logs(s: PyState, rng: random.Random, dims, v: int, w: int):
    """Two servers' logs rewritten to one record each, of term 1 (no
    server is in it any more: neither CandidateTermNotInLog nor
    ElectionSafety reads it) and values ``v`` and ``w``."""
    i, j = _pick(rng, dims.n_servers, 2)
    s = s.replace(
        log=_set(_set(s.log, i, ((1, v),)), j, ((1, w),)),
        commit_index=_set(_set(s.commit_index, i, 0), j, 0))
    return _quiet(s, {i, j})


def witness_log_matching(s, rng, dims):
    """``safety.witness_log_matching`` over the variant's values: two
    logs with the same term and another value at index 1."""
    v, w = rng.sample(_values(dims), 2)
    return _two_logs(s, rng, dims, v, w)


def witness_log_matching_high_byte(s, rng, dims):
    """The two records differ in the value's high byte alone:
    ``joint_value(7, 3)`` and ``final_value(3)`` both end in byte 3."""
    return _two_logs(s, rng, dims, *rng.sample(
        (joint_value(7, 3), final_value(3)), 2))


def witness_leader_completeness_config(s, rng, dims):
    """``safety.witness_leader_completeness`` with a configuration entry:
    an elected leader with an empty log beside a committed configuration
    entry the other two hold, of a term past the leader's."""
    n = dims.n_servers
    i, voter = _pick(rng, n, 2)
    j = rng.choice([x for x in range(n) if x != i])
    t = _top_term(s)
    entry = (t + 1, rng.choice([v for v in _values(dims) if v >= CFG_BASE]))
    s = _elect(s, i, voter, t)
    s = s.replace(
        log=tuple(() if x == i else (entry,) for x in range(n)),
        commit_index=tuple(int(x == j) for x in range(n)))
    return _silent(_drop_votes_of(s, j, but=j))


# name -> (the invariant the maker is for, the maker).
MAKERS: Dict[str, Tuple[str, Callable]] = {
    **{name: (name, fn) for name, fn in safety.WITNESS_MAKERS.items()},
    "LogMatching": ("LogMatching", witness_log_matching),
    "witness_log_matching_high_byte":
        ("LogMatching", witness_log_matching_high_byte),
    "witness_leader_completeness_config":
        ("LeaderCompleteness", witness_leader_completeness_config),
}
# What ``safety.py`` calls it: the makers that go by an invariant's name.
WITNESS_MAKERS = {name: fn for name, (inv, fn) in MAKERS.items()
                  if name == inv}


def made_for(maker: str) -> str:
    """The invariant ``maker``'s witnesses are made for."""
    return MAKERS[maker][0]


def witness(maker: str, s: PyState, rng: random.Random, dims: ReconfigDims,
            names: Sequence[str], constraint=None) -> Optional[PyState]:
    """``s`` mutated by ``maker``, if the mutant is one: TypeOK (with the
    variant's values) and the constraint hold, and the invariant the maker
    is for is the first of ``names`` to fail."""
    name, make = MAKERS[maker]
    w = make(s, rng, dims)
    if w is None or not type_ok(w, dims):
        return None
    if constraint is not None and not constraint(w, dims):
        return None
    return w if first_failing(w, names, dims) == name else None


def witness_parents(maker: str, pool: Sequence[PyState], count: int, seed,
                    dims: ReconfigDims, names: Sequence[str],
                    constraint=None, tries: int = 2000):
    """``safety.witness_parents`` over this module's ``witness``:
    ``count`` distinct witnesses fit to be expanded as PARENTS (every
    successor that fails anything fails the maker's invariant first, and
    at least one does), at least half of them holding a configuration
    entry where ``tries`` draws make as many.  Returns [(witness,
    {successors failing the invariant})]."""
    name = made_for(maker)
    rng = random.Random(f"{seed}:{maker}")
    want_config = (count + 1) // 2
    with_config, without, seen = [], [], set()
    for _ in range(tries):
        if len(with_config) >= want_config \
                and len(with_config) + len(without) >= count:
            break
        w = witness(maker, pool[rng.randrange(len(pool))], rng, dims, names,
                    constraint)
        if w is None or w in seen:
            continue
        if not holds_config(w) and len(without) >= count:
            continue                    # enough of those already
        verdicts = {t: first_failing(t, names, dims)
                    for _a, t in successors(w, dims)}
        failing = {t for t, v in verdicts.items() if v == name}
        if failing and all(v in (None, name) for v in verdicts.values()):
            seen.add(w)
            (with_config if holds_config(w) else without).append((w, failing))
    kept = with_config[:max(want_config, count - len(without))]
    return kept + without[:count - len(kept)]
