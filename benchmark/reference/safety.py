"""The correctness invariants of raft.tla (behind its module terminator,
raft.tla:896-1180) and ``TypeOK`` (:482-492), as plain quantifiers over the
reference's own ``PyState``; and, for each of the nine safety invariants, a
seeded maker of witnesses: states on which that invariant fails and none
before it in the cfg's order does.

No JAX, nothing of the program: written from the TLA+ text, one Python
``all``/``any`` a quantifier, servers 0-based, log positions 1-based as in
the text (``log[i][n]`` is ``s.log[i][n - 1]``).

Where this reading departs from the letter of the text:

- ``RequestVoteResponseInv`` (:903-910): the text's ``m.dest`` at :910 is a
  typo for ``m.mdest`` (TLC would report an unknown field); read as
  ``m.mdest``.
- ``ElectionSafety`` (:1124-1129) takes ``Max`` of a set that may be empty;
  ``Max({})`` is read as 0, so "no entry of that term" is index 0.
- ``AppendEntriesRequestInv`` (:924-930): ``log[m.msource][m.mprevLogIndex
  + 1]`` is not guarded; where the index lies outside ``DOMAIN
  log[m.msource]`` TLC stops with an error, and the invariant is read as
  violated.
- ``Committed(i)`` (:896) is ``SubSeq(log[i], 1, commitIndex[i])``, which
  TLC cannot evaluate for ``commitIndex[i] > Len(log[i])``; every
  ``IsPrefix(Committed(i), ..)`` is then read as false (all four uses have
  it as the consequent, so that is a violation).
- ``IsPrefix`` is the community ``SequencesExt`` module's: ``IsPrefix(s, t)
  == Len(s) <= Len(t) /\\ SubSeq(t, 1, Len(s)) = s``.
"""

from __future__ import annotations

import itertools
import random
from typing import Callable, Dict, Optional, Sequence

from .dims import (AEQ, AER, CANDIDATE, FOLLOWER, LEADER, NIL, RVQ, RVR,
                   RaftDims)
from .oracle import last_term, successors
from .pystate import PyState


def servers(s: PyState) -> range:
    return range(len(s.current_term))


# ---------------------------------------------------------------------------
# TypeOK — raft.tla:482-492, over the message schemas of :443-479.

def _nat(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= 0


def _entries_ok(entries, dims: RaftDims) -> bool:
    """Seq([term : Nat, value : Value])."""
    return isinstance(entries, tuple) and all(
        len(e) == 2 and _nat(e[0]) and 1 <= e[1] <= dims.n_values
        for e in entries)


def _message_ok(m, dims: RaftDims) -> bool:
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


def type_ok(s: PyState, dims: RaftDims) -> bool:
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


# ---------------------------------------------------------------------------
# Committed, IsPrefix — raft.tla:896 and SequencesExt.

def committed(s: PyState, i: int):
    """Committed(i), or None where TLC could not evaluate it."""
    if s.commit_index[i] > len(s.log[i]):
        return None
    return s.log[i][:s.commit_index[i]]


def committed_is_prefix(s: PyState, i: int, j: int) -> bool:
    """IsPrefix(Committed(i), log[j])."""
    c = committed(s, i)
    return (c is not None and len(c) <= len(s.log[j])
            and s.log[j][:len(c)] == c)


def more_up_to_date(s: PyState, i: int, j: int) -> bool:
    """log[i] is at least as up to date as log[j] (:1168-1170, and the
    comparison of :245-247 and :906-909)."""
    ti, tj = last_term(s.log[i]), last_term(s.log[j])
    return ti > tj or (ti == tj and len(s.log[i]) >= len(s.log[j]))


# ---------------------------------------------------------------------------
# The per-message invariants — raft.tla:903-935 — and MessagesInv :941-946.

def request_vote_response_inv(s: PyState, m) -> bool:
    """:903-910.  A vote granted in the term both ends are still in went
    to a candidate (mdest) whose log is at least as up to date as the
    voter's (msource)."""
    if m[0] != RVR:
        return True
    src, dst = m[1], m[2]
    if not (m[4] and s.current_term[src] == s.current_term[dst]
            and s.current_term[src] == m[3]):
        return True
    return more_up_to_date(s, dst, src)


def request_vote_request_inv(s: PyState, m) -> bool:
    """:915-920.  A candidate's request in its current term carries its
    log's last index and term."""
    if m[0] != RVQ:
        return True
    src = m[1]
    if not (s.role[src] == CANDIDATE and s.current_term[src] == m[3]):
        return True
    return m[5] == len(s.log[src]) and m[4] == last_term(s.log[src])


def append_entries_request_inv(s: PyState, m) -> bool:
    """:924-930.  A non-empty request in the sender's current term
    carries the sender's entry at prevLogIndex + 1 (unguarded: outside
    the log's domain is a violation) and, where prevLogIndex lies in the
    log, that entry's term."""
    if m[0] != AEQ:
        return True
    src, prev, prev_term, entries = m[1], m[4], m[5], m[6]
    if not (entries != () and m[3] == s.current_term[src]):
        return True
    log = s.log[src]
    if not 1 <= prev + 1 <= len(log):
        return False                    # log[src][prev + 1]: no such index
    if log[prev + 1 - 1] != entries[0]:
        return False
    if 0 < prev <= len(log):
        return log[prev - 1][0] == prev_term
    return True


def message_terms_lt_current_term(s: PyState, m) -> bool:
    """:934-935.  No message is ahead of its sender's term."""
    return m[3] <= s.current_term[m[1]]


def messages_inv(s: PyState, dims: RaftDims) -> bool:
    """:941-946.  Over DOMAIN messages: a bag's multiplicities play no
    part."""
    return all(request_vote_response_inv(s, m)
               and request_vote_request_inv(s, m)
               and append_entries_request_inv(s, m)
               and message_terms_lt_current_term(s, m)
               for m, _count in s.messages)


# ---------------------------------------------------------------------------
# The state invariants — raft.tla:1033-1180.

def quorums(n: int):
    """Quorum (:79-81): every subset of Server with a majority."""
    return [set(q) for k in range(n // 2 + 1, n + 1)
            for q in itertools.combinations(range(n), k)]


def leader_votes_quorum(s: PyState, dims: RaftDims) -> bool:
    """:1033-1037.  A leader's voters, or servers already past its term,
    are a quorum."""
    S = servers(s)
    return all(
        {j for j in S
         if s.current_term[j] > s.current_term[i]
         or (s.current_term[j] == s.current_term[i]
             and s.voted_for[j] == i + 1)} in quorums(len(S))
        for i in S if s.role[i] == LEADER)


def candidate_term_not_in_log(s: PyState, dims: RaftDims) -> bool:
    """:1041-1047.  Where a candidate could still win its term, no log
    holds an entry of that term."""
    S = servers(s)
    return all(
        all(s.log[j][n - 1][0] != s.current_term[i]
            for j in S for n in range(1, len(s.log[j]) + 1))
        for i in S
        if s.role[i] == CANDIDATE
        and {j for j in S
             if s.current_term[j] == s.current_term[i]
             and s.voted_for[j] in (i + 1, NIL)} in quorums(len(S)))


def _max_index_of_term(log, term: int) -> int:
    """Max({n \\in DOMAIN log : log[n].term = term}), 0 for the empty set."""
    return max((n for n in range(1, len(log) + 1)
                if log[n - 1][0] == term), default=0)


def election_safety(s: PyState, dims: RaftDims) -> bool:
    """:1124-1129.  No log reaches further in a leader's term than the
    leader's own."""
    S = servers(s)
    return all(
        _max_index_of_term(s.log[i], s.current_term[i])
        >= _max_index_of_term(s.log[j], s.current_term[i])
        for i in S if s.role[i] == LEADER for j in S)


def log_matching(s: PyState, dims: RaftDims) -> bool:
    """:1132-1136.  Two logs with the same term at an index are the same
    up to it (whole records: term and value)."""
    S = servers(s)
    return all(
        s.log[i][:n] == s.log[j][:n]
        for i in S for j in S
        for n in range(1, min(len(s.log[i]), len(s.log[j])) + 1)
        if s.log[i][n - 1][0] == s.log[j][n - 1][0])


def votes_granted_inv(s: PyState, dims: RaftDims) -> bool:
    """:1145-1153.  A vote i holds from j, both still in one term: what j
    had committed is in i's log."""
    S = servers(s)
    return all(
        committed_is_prefix(s, j, i)
        for i in S for j in S
        if (s.votes_granted[i] >> j) & 1
        and s.current_term[i] == s.current_term[j])


def quorum_log_inv(s: PyState, dims: RaftDims) -> bool:
    """:1157-1161.  Every quorum holds a server with all that i
    committed."""
    S = servers(s)
    return all(any(committed_is_prefix(s, i, j) for j in q)
               for i in S for q in quorums(len(S)))


def more_up_to_date_correct(s: PyState, dims: RaftDims) -> bool:
    """:1167-1172.  A log at least as up to date as j's holds all that j
    committed."""
    S = servers(s)
    return all(committed_is_prefix(s, j, i)
               for i in S for j in S if more_up_to_date(s, i, j))


def leader_completeness(s: PyState, dims: RaftDims) -> bool:
    """:1176-1180.  What anyone committed is in every leader's log."""
    S = servers(s)
    return all(committed_is_prefix(s, j, i)
               for i in S if s.role[i] == LEADER for j in S)


# TypeOK and the nine, in the order raft.tla defines them (the order of
# configs/MCraft_safety.cfg).
INVARIANTS: Dict[str, Callable[[PyState, RaftDims], bool]] = {
    "TypeOK": type_ok,
    "MessagesInv": messages_inv,
    "LeaderVotesQuorum": leader_votes_quorum,
    "CandidateTermNotInLog": candidate_term_not_in_log,
    "ElectionSafety": election_safety,
    "LogMatching": log_matching,
    "VotesGrantedInv": votes_granted_inv,
    "QuorumLogInv": quorum_log_inv,
    "MoreUpToDateCorrect": more_up_to_date_correct,
    "LeaderCompleteness": leader_completeness,
}


def first_failing(s: PyState, names: Sequence[str],
                  dims: RaftDims) -> Optional[str]:
    """The first of ``names``, in their order, that fails on ``s``; None
    where all hold (what TLC reports: the first violated INVARIANT of the
    cfg's list)."""
    for name in names:
        if not INVARIANTS[name](s, dims):
            return name
    return None


# ---------------------------------------------------------------------------
# Witness makers.  Each takes a state (a reachable one, for the variety of
# what it leaves alone), a ``random.Random`` and the dims, and returns a
# mutated state on which its invariant fails and, by construction, those
# before it in the order above hold.  "By construction" is then CHECKED,
# not trusted: ``witness`` keeps a mutant only if ``first_failing`` names
# the invariant it was made for and the constraint holds.  A maker changes
# a few servers and leaves the rest of the state as it found it.

def _set(tup, i, val):
    return tup[:i] + (val,) + tup[i + 1:]


def _quiet(s: PyState, touched) -> PyState:
    """``s`` less the messages whose per-message invariant reads a server
    in ``touched`` (its term, role or log changed under them): every
    message such a server sent, and every vote response sent to it."""
    keep = frozenset(
        (m, c) for m, c in s.messages
        if m[1] not in touched and not (m[0] == RVR and m[2] in touched))
    return s.replace(messages=keep)


def _silent(s: PyState) -> PyState:
    """``s`` with an empty bag: for a maker that rewrites every server."""
    return s.replace(messages=frozenset())


def _pick(rng: random.Random, n: int, k: int):
    return rng.sample(range(n), k)


def _top_term(s: PyState) -> int:
    return max(max(s.current_term), 2)


def _elect(s: PyState, i: int, voter: int, term: int) -> PyState:
    """``i`` a leader of ``term`` by its own and ``voter``'s vote, as
    LeaderVotesQuorum wants it at three servers."""
    for j in (i, voter):
        s = s.replace(current_term=_set(s.current_term, j, term),
                      voted_for=_set(s.voted_for, j, i + 1))
    return s.replace(role=_set(_set(s.role, voter, FOLLOWER), i, LEADER))


def witness_messages_inv(s, rng, dims):
    """One message that fails one of the four per-message invariants."""
    n = dims.n_servers
    i, j = _pick(rng, n, 2)
    kind = rng.randrange(4)
    log_i = s.log[i]
    if kind == 0:       # :934-935: a message ahead of its sender's term
        m = (RVQ, i, j, s.current_term[i] + 1, last_term(log_i), len(log_i))
    elif kind == 1:     # :915-920: a candidate advertising a longer log
        s = s.replace(role=_set(s.role, i, CANDIDATE))
        m = (RVQ, i, j, s.current_term[i], last_term(log_i),
             len(log_i) + 1)
    elif kind == 2:     # :903-910: a vote granted to a staler log
        t = max(s.current_term[i], s.current_term[j])
        s = s.replace(
            current_term=_set(_set(s.current_term, i, t), j, t),
            log=_set(_set(s.log, i, ((1, rng.randint(1, dims.n_values)),)),
                     j, ()),
            commit_index=_set(_set(s.commit_index, i, 0), j, 0))
        m = (RVR, i, j, t, 1, s.log[i])
    else:               # :924-930: an entry the sender's log does not hold
        entry = (s.current_term[i], rng.randint(1, dims.n_values))
        if log_i and rng.random() < 0.5:
            other = (log_i[-1][0], 3 - log_i[-1][1]) \
                if dims.n_values == 2 else entry
            prev = len(log_i) - 1       # in the domain, another record
            m = (AEQ, i, j, s.current_term[i], prev,
                 log_i[prev - 1][0] if prev else 0, (other,), 0)
        else:                           # outside the domain
            m = (AEQ, i, j, s.current_term[i], len(log_i),
                 last_term(log_i), (entry,), 0)
    bag = dict(s.messages)
    bag[m] = 1
    return s.replace(messages=frozenset(bag.items()))


def witness_leader_votes_quorum(s, rng, dims):
    """A leader of the highest term nobody voted for."""
    n = dims.n_servers
    i = rng.randrange(n)
    t = _top_term(s)
    s = s.replace(
        role=_set(s.role, i, LEADER),
        current_term=_set(s.current_term, i, t),
        voted_for=tuple(NIL if s.current_term[j] == t or j == i
                        else s.voted_for[j] for j in range(n)))
    return _quiet(s, {i})


def witness_candidate_term_not_in_log(s, rng, dims):
    """A candidate a quorum could still elect, its term already in a
    log."""
    n = dims.n_servers
    i, j = _pick(rng, n, 2)
    k = rng.randrange(n)
    t = s.current_term[i]
    s = s.replace(
        role=_set(s.role, i, CANDIDATE),
        current_term=_set(s.current_term, j, t),
        voted_for=_set(_set(s.voted_for, i, rng.choice((NIL, i + 1))),
                       j, rng.choice((NIL, i + 1))),
        log=_set(s.log, k, ((t, rng.randint(1, dims.n_values)),)),
        commit_index=_set(s.commit_index, k, 0))
    return _quiet(s, {i, j, k})


def witness_election_safety(s, rng, dims):
    """An elected leader with no entry of its term, beside a log that has
    one at index 2 (where the leader's next ClientRequest, which writes
    index 1, does not meet it: LogMatching comes first in the order)."""
    n = dims.n_servers
    i, voter = _pick(rng, n, 2)
    holder = rng.choice([j for j in range(n) if j != i])
    t = _top_term(s)
    first = _first_entry(s, rng, dims, but=(i, holder))
    s = _elect(s, i, voter, t)
    s = s.replace(
        log=_set(_set(s.log, i, ()), holder,
                 (first, (t, rng.randint(1, dims.n_values)))),
        commit_index=_set(_set(s.commit_index, i, 0), holder, 0))
    return _quiet(s, {i, voter, holder})


def witness_log_matching(s, rng, dims):
    """Two logs with the same term and another value at index 1."""
    if dims.n_values < 2:
        return None
    n = dims.n_servers
    i, j = _pick(rng, n, 2)
    v = rng.randint(1, dims.n_values)
    w = rng.choice([x for x in range(1, dims.n_values + 1) if x != v])
    s = s.replace(
        log=_set(_set(s.log, i, ((1, v),)), j, ((1, w),)),
        commit_index=_set(_set(s.commit_index, i, 0), j, 0))
    return _quiet(s, {i, j})


def _first_entry(s: PyState, rng, dims, but=()):
    """An entry of term 1 for index 1 that agrees with the logs left
    alone (so LogMatching holds)."""
    for k in servers(s):
        if k not in but and s.log[k] and s.log[k][0][0] == 1:
            return s.log[k][0]
    return (1, rng.randint(1, dims.n_values))


def witness_votes_granted_inv(s, rng, dims):
    """i holds j's vote in their common term and lacks what j committed;
    the third holds it, so every quorum does."""
    n = dims.n_servers
    i, j = _pick(rng, n, 2)
    t = max(s.current_term[i], s.current_term[j])
    entry = (1, rng.randint(1, dims.n_values))
    s = s.replace(
        current_term=_set(_set(s.current_term, i, t), j, t),
        votes_granted=_set(s.votes_granted, i,
                           s.votes_granted[i] | (1 << j)),
        votes_responded=_set(s.votes_responded, i,
                             s.votes_responded[i] | (1 << j)),
        log=tuple(() if x == i else (entry,) for x in range(n)),
        commit_index=tuple(int(x == j) for x in range(n)))
    return _silent(s)


def _drop_votes_of(s: PyState, j: int, but: int) -> PyState:
    """Nobody but ``but`` holds ``j``'s vote (VotesGrantedInv then says
    nothing of what j committed)."""
    return s.replace(votes_granted=tuple(
        g if x == but else g & ~(1 << j)
        for x, g in enumerate(s.votes_granted)))


def witness_quorum_log_inv(s, rng, dims):
    """i committed an entry no other log holds."""
    n = dims.n_servers
    i = rng.randrange(n)
    entry = (1, rng.randint(1, dims.n_values))
    s = s.replace(
        log=tuple((entry,) if x == i else () for x in range(n)),
        commit_index=tuple(int(x == i) for x in range(n)))
    return _silent(_drop_votes_of(s, i, but=i))


def witness_more_up_to_date_correct(s, rng, dims):
    """i's log ends in a later term than j's and lacks what j committed;
    the third holds it, so every quorum does."""
    n = dims.n_servers
    i, j = _pick(rng, n, 2)
    entry = (1, rng.randint(1, dims.n_values))
    later = (max(s.current_term) + 1, rng.randint(1, dims.n_values))
    s = s.replace(
        log=tuple((later,) if x == i else (entry,) for x in range(n)),
        commit_index=tuple(int(x == j) for x in range(n)))
    return _silent(_drop_votes_of(s, j, but=j))


def witness_leader_completeness(s, rng, dims):
    """An elected leader with an empty log beside a committed entry the
    other two hold, of a term past the leader's (so that no entry the
    leader writes makes its log the more up to date:
    MoreUpToDateCorrect comes first in the order)."""
    n = dims.n_servers
    i, voter = _pick(rng, n, 2)
    j = rng.choice([x for x in range(n) if x != i])
    t = _top_term(s)
    entry = (t + 1, rng.randint(1, dims.n_values))
    s = _elect(s, i, voter, t)
    s = s.replace(
        log=tuple(() if x == i else (entry,) for x in range(n)),
        commit_index=tuple(int(x == j) for x in range(n)))
    return _silent(_drop_votes_of(s, j, but=j))


WITNESS_MAKERS: Dict[str, Callable] = {
    "MessagesInv": witness_messages_inv,
    "LeaderVotesQuorum": witness_leader_votes_quorum,
    "CandidateTermNotInLog": witness_candidate_term_not_in_log,
    "ElectionSafety": witness_election_safety,
    "LogMatching": witness_log_matching,
    "VotesGrantedInv": witness_votes_granted_inv,
    "QuorumLogInv": witness_quorum_log_inv,
    "MoreUpToDateCorrect": witness_more_up_to_date_correct,
    "LeaderCompleteness": witness_leader_completeness,
}


def witness(name: str, s: PyState, rng: random.Random, dims: RaftDims,
            names: Sequence[str], constraint=None) -> Optional[PyState]:
    """``s`` mutated by ``name``'s maker, if the mutant is one: TypeOK and
    the constraint hold, and ``name`` is the first of ``names`` to fail."""
    w = WITNESS_MAKERS[name](s, rng, dims)
    if w is None or not type_ok(w, dims):
        return None
    if constraint is not None and not constraint(w, dims):
        return None
    return w if first_failing(w, names, dims) == name else None


def witness_parents(name: str, pool: Sequence[PyState], count: int, seed,
                    dims: RaftDims, names: Sequence[str], constraint=None,
                    tries: int = 2000):
    """``count`` distinct witnesses of ``name`` made from states of
    ``pool`` drawn by ``seed``, fit to be expanded as PARENTS: every
    successor that fails anything fails ``name`` first, and at least one
    does.  (A checker that evaluates invariants on generated states,
    never on the frontier it was handed, must then stop on a successor,
    under that name.)  Returns [(witness, {successors failing name})]."""
    rng = random.Random(f"{seed}:{name}")
    out, seen = [], set()
    for _ in range(tries):
        if len(out) == count:
            break
        w = witness(name, pool[rng.randrange(len(pool))], rng, dims, names,
                    constraint)
        if w is None or w in seen:
            continue
        verdicts = {t: first_failing(t, names, dims)
                    for _a, t in successors(w, dims)}
        failing = {t for t, v in verdicts.items() if v == name}
        if failing and all(v in (None, name) for v in verdicts.values()):
            seen.add(w)
            out.append((w, failing))
    return out
