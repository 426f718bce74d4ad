"""The spec's correctness-invariant suite as vectorized TPU kernels.

The reference keeps its safety properties *outside* the module terminator
(/root/reference/raft.tla:505) — dead text for TLC, live TLAPS proof goals
(SURVEY §2.3).  Here they are first-class, runtime-checkable invariants: each
is a branch-free predicate over one ``StateBatch`` (vmap'd over the frontier
by the engine, exactly like ``TypeOK``), with a pure-Python mirror for
differential testing.

Transcribed semantics, with reference citations:

- ``Committed(i) == SubSeq(log[i], 1, commitIndex[i])`` — raft.tla:896.
- ``RequestVoteResponseInv`` — raft.tla:903-910.  The reference's ``m.dest``
  at :910 is a typo for ``m.mdest`` (it would crash TLC if enabled naively;
  SURVEY §2.3); fixed here.
- ``RequestVoteRequestInv`` — raft.tla:915-920.
- ``AppendEntriesRequestInv`` — raft.tla:924-930.  Note the TLA+ operator
  precedence: the second conjunct is ``(prev > 0 /\\ prev <= Len) =>
  term-match``; the first (``log[src][prev+1] = mentries[1]``) is an
  *unguarded* access — out-of-domain evaluates to a TLC error, which this
  engine reports as a violation of the invariant.
- ``MessageTermsLtCurrentTerm`` — raft.tla:934-935.
- ``MessagesInv`` — raft.tla:941-946 (conjunction over all in-flight
  messages; multiplicities are irrelevant, only the support matters).
- ``LeaderVotesQuorum`` — raft.tla:1033-1037.
- ``CandidateTermNotInLog`` — raft.tla:1041-1047.
- ``ElectionSafety`` — raft.tla:1124-1129.  ``Max`` over a possibly-empty
  index set is taken as 0 (the natural total extension; both sides empty
  ⇒ trivially true, leader-side empty with follower-side occupied ⇒
  violation — the intended reading).
- ``LogMatching`` — raft.tla:1132-1136 (``SubSeq`` equality compares whole
  records: term *and* value).
- ``VotesGrantedInv`` — raft.tla:1145-1153 (needs ``IsPrefix`` from the
  community SequencesExt module [external]: ``IsPrefix(s, t) ==
  Len(s) <= Len(t) /\\ SubSeq(t, 1, Len(s)) = s``).
- ``QuorumLogInv`` — raft.tla:1157-1161.  Quantifying over all quorums
  compiles to a popcount: ``\\A S \\in Quorum : \\E j \\in S : ok(j)`` holds
  iff the NOT-ok set contains no majority, i.e. ``2*|bad| <= N``.
- ``MoreUpToDateCorrect`` — raft.tla:1167-1172.
- ``LeaderCompleteness`` — raft.tla:1176-1180.

Every kernel returns a scalar bool: True = invariant holds in this state.

No predicate indexes a table by a traced value (``st.term[src]``): under
``vmap`` that is a gather, 8-12 ns an element on the TPU and 62 % of a pass
of the benchmark's ``safety9`` until PR 36.  ``_pick`` compares the position
with the table's static axis, selects and sums (``actions2.py dvec``'s idiom).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp

from .dims import AEQ, CANDIDATE, LEADER, NIL, RVQ, RVR, RaftDims
from .pystate import PyState

# ---------------------------------------------------------------------------
# Shared tensor helpers (single state, no batch axis).


def _is_at(at, size):
    """One-hot rows of in-range positions: ``[len(at), size]`` bools."""
    return at[:, None] == jnp.arange(size)[None, :]


def _pick(is_, table):
    """``table`` at the one position each row of ``is_`` marks along axis 1
    (``table`` broadcasts against ``is_``): the suite's one way to read at
    a traced position (module docstring)."""
    return jnp.sum(jnp.where(is_, table, 0), axis=1)


def _last_terms(st, L):
    """LastTerm(log[i]) for all i — raft.tla:84.  [N]."""
    at = jnp.clip(st.log_len - 1, 0, L - 1)
    return jnp.where(st.log_len > 0, _pick(_is_at(at, L), st.log_term), 0)


def _entry_eq(st):
    """E[a,b,l] = log[a][l+1] and log[b][l+1] are the same record.  [N,N,L]."""
    te = st.log_term[:, None, :] == st.log_term[None, :, :]
    ve = st.log_val[:, None, :] == st.log_val[None, :, :]
    return te & ve


def _committed_prefix(st, L):
    """P[a,b] = IsPrefix(Committed(a), log[b]) — raft.tla:896 + SequencesExt.
    Committed(a) with commitIndex[a] > Len(log[a]) is undefined in the spec;
    reported as not-a-prefix (the TLC-error reading).  [N,N]."""
    lane = jnp.arange(L)[None, None, :]
    c = st.commit[:, None, None]
    within = lane < c
    match = jnp.all(~within | _entry_eq(st), axis=2)
    well_def = st.commit <= st.log_len
    return well_def[:, None] & (st.commit[:, None] <= st.log_len[None, :]) \
        & match


# ---------------------------------------------------------------------------
# Per-invariant kernel builders (signature matches build_type_ok).


def build_messages_inv(dims: RaftDims):
    """MessagesInv — raft.tla:941-946: the four per-message invariants
    conjoined over every in-flight message."""
    N, L = dims.n_servers, dims.max_log

    def messages_inv(st):
        occ = st.msg_cnt > 0                              # [M]
        mt = st.msg[:, 0] - 1
        src = jnp.clip(st.msg[:, 1] - 1, 0, N - 1)
        dst = jnp.clip(st.msg[:, 2] - 1, 0, N - 1)
        mterm = st.msg[:, 3]
        src_is, dst_is = _is_at(src, N), _is_at(dst, N)   # [M, N]
        lt = _last_terms(st, L)                           # [N]
        len_src, len_dst = _pick(src_is, st.log_len), \
            _pick(dst_is, st.log_len)
        lt_src, lt_dst = _pick(src_is, lt), _pick(dst_is, lt)
        t_src, t_dst = _pick(src_is, st.term), _pick(dst_is, st.term)
        # log[src], whole rows; a position is picked out of them below.
        log_term_src = _pick(src_is[:, :, None], st.log_term)   # [M, L]
        log_val_src = _pick(src_is[:, :, None], st.log_val)

        # MessageTermsLtCurrentTerm — raft.tla:934-935 (all message types).
        terms_ok = mterm <= t_src

        # RequestVoteResponseInv — raft.tla:903-910 (:910 typo fixed).
        rvr_ante = (mt == RVR) & (st.msg[:, 4] > 0) \
            & (t_src == t_dst) & (t_src == mterm)
        rvr_cons = (lt_dst > lt_src) \
            | ((lt_dst == lt_src) & (len_dst >= len_src))
        rvr_ok = ~rvr_ante | rvr_cons

        # RequestVoteRequestInv — raft.tla:915-920.
        rvq_ante = (mt == RVQ) & (_pick(src_is, st.role) == CANDIDATE) \
            & (t_src == mterm)
        rvq_cons = (st.msg[:, 5] == len_src) & (st.msg[:, 4] == lt_src)
        rvq_ok = ~rvq_ante | rvq_cons

        # AppendEntriesRequestInv — raft.tla:924-930.
        prev, pterm = st.msg[:, 4], st.msg[:, 5]
        n_ent, eterm, eval_ = st.msg[:, 6], st.msg[:, 7], st.msg[:, 8]
        aeq_ante = (mt == AEQ) & (n_ent > 0) & (mterm == t_src)
        at1 = _is_at(jnp.clip(prev, 0, L - 1), L)         # prev+1, 0-based
        entry1_ok = (prev + 1 >= 1) & (prev + 1 <= len_src) \
            & (_pick(at1, log_term_src) == eterm) \
            & (_pick(at1, log_val_src) == eval_)
        atp = _is_at(jnp.clip(prev - 1, 0, L - 1), L)
        prev_in = (prev > 0) & (prev <= len_src)
        pterm_ok = ~prev_in | (_pick(atp, log_term_src) == pterm)
        aeq_ok = ~aeq_ante | (entry1_ok & pterm_ok)

        return jnp.all(~occ | (terms_ok & rvr_ok & rvq_ok & aeq_ok))

    return messages_inv


def build_leader_votes_quorum(dims: RaftDims):
    """LeaderVotesQuorum — raft.tla:1033-1037."""
    N = dims.n_servers

    def leader_votes_quorum(st):
        # voters[i,j]: j counts toward i's leadership quorum.
        higher = st.term[None, :] > st.term[:, None]
        voted = (st.term[None, :] == st.term[:, None]) \
            & (st.voted_for[None, :] == jnp.arange(N)[:, None] + 1)
        cnt = jnp.sum(higher | voted, axis=1)
        return jnp.all((st.role != LEADER) | (2 * cnt > N))

    return leader_votes_quorum


def build_candidate_term_not_in_log(dims: RaftDims):
    """CandidateTermNotInLog — raft.tla:1041-1047."""
    N, L = dims.n_servers, dims.max_log

    def candidate_term_not_in_log(st):
        same_term = st.term[None, :] == st.term[:, None]
        votable = (st.voted_for[None, :] == jnp.arange(N)[:, None] + 1) \
            | (st.voted_for[None, :] == NIL)
        cnt = jnp.sum(same_term & votable, axis=1)
        electable = (st.role == CANDIDATE) & (2 * cnt > N)      # [N] over i
        lane = jnp.arange(L)[None, None, :]
        in_log = lane < st.log_len[None, :, None]               # [1,N,L]
        term_hit = st.log_term[None, :, :] == st.term[:, None, None]
        in_any_log = jnp.any(in_log & term_hit, axis=(1, 2))    # [N] over i
        return jnp.all(~electable | ~in_any_log)

    return candidate_term_not_in_log


def build_election_safety(dims: RaftDims):
    """ElectionSafety — raft.tla:1124-1129 (empty Max = 0)."""
    L = dims.max_log

    def election_safety(st):
        lane = jnp.arange(L)[None, None, :]
        in_log = lane < st.log_len[None, :, None]               # [1,N,L]
        hit = in_log & (st.log_term[None, :, :] == st.term[:, None, None])
        # A[i,j] = greatest index in log[j] whose term is currentTerm[i].
        A = jnp.max(jnp.where(hit, lane + 1, 0), axis=2)        # [N,N]
        own = jnp.diagonal(A)                                   # A[i,i]
        return jnp.all((st.role != LEADER)[:, None] | (own[:, None] >= A))

    return election_safety


def build_log_matching(dims: RaftDims):
    """LogMatching — raft.tla:1132-1136."""
    L = dims.max_log

    def log_matching(st):
        lane = jnp.arange(L)[None, None, :]
        eq = _entry_eq(st)                                      # [N,N,L]
        # prefix_eq[i,j,l]: SubSeq(log[i],1,l+1) = SubSeq(log[j],1,l+1).
        prefix_eq = jnp.cumprod(eq, axis=2).astype(bool)
        in_both = lane < jnp.minimum(st.log_len[:, None],
                                     st.log_len[None, :])[:, :, None]
        term_eq = st.log_term[:, None, :] == st.log_term[None, :, :]
        return jnp.all(~in_both | ~term_eq | prefix_eq)

    return log_matching


def build_votes_granted_inv(dims: RaftDims):
    """VotesGrantedInv — raft.tla:1145-1153."""
    N, L = dims.n_servers, dims.max_log

    def votes_granted_inv(st):
        granted = ((st.votes_gran[:, None] >> jnp.arange(N)[None, :])
                   & 1) > 0                                     # [N i, N j]
        same_term = st.term[:, None] == st.term[None, :]
        # IsPrefix(Committed(j), log[i]) — P[j,i] with P from the helper.
        pref = _committed_prefix(st, L).T                       # [i,j]
        return jnp.all(~granted | ~same_term | pref)

    return votes_granted_inv


def build_quorum_log_inv(dims: RaftDims):
    """QuorumLogInv — raft.tla:1157-1161 via the popcount reduction."""
    N, L = dims.n_servers, dims.max_log

    def quorum_log_inv(st):
        pref = _committed_prefix(st, L)                         # [i,j]
        bad = jnp.sum(~pref, axis=1)                            # per i
        return jnp.all(2 * bad <= N)

    return quorum_log_inv


def build_more_up_to_date_correct(dims: RaftDims):
    """MoreUpToDateCorrect — raft.tla:1167-1172."""
    L = dims.max_log

    def more_up_to_date_correct(st):
        lt = _last_terms(st, L)
        newer = (lt[:, None] > lt[None, :]) \
            | ((lt[:, None] == lt[None, :])
               & (st.log_len[:, None] >= st.log_len[None, :]))  # [i,j]
        pref = _committed_prefix(st, L).T                       # [i,j]
        return jnp.all(~newer | pref)

    return more_up_to_date_correct


def build_leader_completeness(dims: RaftDims):
    """LeaderCompleteness — raft.tla:1176-1180."""
    L = dims.max_log

    def leader_completeness(st):
        pref = _committed_prefix(st, L).T                       # [i,j]
        return jnp.all(~(st.role == LEADER)[:, None] | pref)

    return leader_completeness


# Registry fragment: name -> builder, in the reference's order of definition.
SAFETY_INVARIANTS: Dict[str, Callable] = {
    "MessagesInv": build_messages_inv,
    "LeaderVotesQuorum": build_leader_votes_quorum,
    "CandidateTermNotInLog": build_candidate_term_not_in_log,
    "ElectionSafety": build_election_safety,
    "LogMatching": build_log_matching,
    "VotesGrantedInv": build_votes_granted_inv,
    "QuorumLogInv": build_quorum_log_inv,
    "MoreUpToDateCorrect": build_more_up_to_date_correct,
    "LeaderCompleteness": build_leader_completeness,
}


# ---------------------------------------------------------------------------
# Pure-Python mirrors (oracle side, for differential tests).


def _py_last_term(log):
    return log[-1][0] if log else 0


def _py_committed(s: PyState, a: int):
    """Committed(a); None marks the undefined commitIndex > Len case."""
    if s.commit_index[a] > len(s.log[a]):
        return None
    return s.log[a][:s.commit_index[a]]


def _py_is_prefix_committed(s: PyState, a: int, b: int) -> bool:
    c = _py_committed(s, a)
    return c is not None and s.log[b][:len(c)] == c


def messages_inv_py(s: PyState, dims: RaftDims) -> bool:
    for (m, _cnt) in s.messages:
        mt, src, dst, mterm = m[0], m[1], m[2], m[3]
        if mterm > s.current_term[src]:                 # :934-935
            return False
        if mt == RVR and m[4] \
                and s.current_term[src] == s.current_term[dst] \
                and s.current_term[src] == mterm:       # :903-910
            lts, ltd = _py_last_term(s.log[src]), _py_last_term(s.log[dst])
            if not (ltd > lts or (ltd == lts
                                  and len(s.log[dst]) >= len(s.log[src]))):
                return False
        if mt == RVQ and s.role[src] == CANDIDATE \
                and s.current_term[src] == mterm:       # :915-920
            if m[5] != len(s.log[src]) or m[4] != _py_last_term(s.log[src]):
                return False
        if mt == AEQ and m[6] and mterm == s.current_term[src]:  # :924-930
            prev, pterm, entries = m[4], m[5], m[6]
            if not (1 <= prev + 1 <= len(s.log[src])
                    and s.log[src][prev] == entries[0]):
                return False
            if 0 < prev <= len(s.log[src]) \
                    and s.log[src][prev - 1][0] != pterm:
                return False
    return True


def leader_votes_quorum_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        if s.role[i] != LEADER:
            continue
        cnt = sum(
            1 for j in range(n)
            if s.current_term[j] > s.current_term[i]
            or (s.current_term[j] == s.current_term[i]
                and s.voted_for[j] == i + 1))
        if not 2 * cnt > n:
            return False
    return True


def candidate_term_not_in_log_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        if s.role[i] != CANDIDATE:
            continue
        cnt = sum(
            1 for j in range(n)
            if s.current_term[j] == s.current_term[i]
            and s.voted_for[j] in (i + 1, NIL))
        if 2 * cnt > n:
            for j in range(n):
                if any(t == s.current_term[i] for (t, _v) in s.log[j]):
                    return False
    return True


def election_safety_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers

    def max_idx(j, t):
        hits = [k + 1 for k, (et, _v) in enumerate(s.log[j]) if et == t]
        return max(hits) if hits else 0

    for i in range(n):
        if s.role[i] != LEADER:
            continue
        for j in range(n):
            if max_idx(i, s.current_term[i]) < max_idx(j, s.current_term[i]):
                return False
    return True


def log_matching_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        for j in range(n):
            for k in range(min(len(s.log[i]), len(s.log[j]))):
                if s.log[i][k][0] == s.log[j][k][0] \
                        and s.log[i][:k + 1] != s.log[j][:k + 1]:
                    return False
    return True


def votes_granted_inv_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        for j in range(n):
            if (s.votes_granted[i] >> j) & 1 \
                    and s.current_term[i] == s.current_term[j] \
                    and not _py_is_prefix_committed(s, j, i):
                return False
    return True


def quorum_log_inv_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        bad = sum(1 for j in range(n)
                  if not _py_is_prefix_committed(s, i, j))
        if 2 * bad > n:
            return False
    return True


def more_up_to_date_correct_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        for j in range(n):
            lti, ltj = _py_last_term(s.log[i]), _py_last_term(s.log[j])
            if (lti > ltj or (lti == ltj
                              and len(s.log[i]) >= len(s.log[j]))) \
                    and not _py_is_prefix_committed(s, j, i):
                return False
    return True


def leader_completeness_py(s: PyState, dims: RaftDims) -> bool:
    n = dims.n_servers
    for i in range(n):
        if s.role[i] == LEADER:
            for j in range(n):
                if not _py_is_prefix_committed(s, j, i):
                    return False
    return True


SAFETY_INVARIANTS_PY: Dict[str, Callable] = {
    "MessagesInv": messages_inv_py,
    "LeaderVotesQuorum": leader_votes_quorum_py,
    "CandidateTermNotInLog": candidate_term_not_in_log_py,
    "ElectionSafety": election_safety_py,
    "LogMatching": log_matching_py,
    "VotesGrantedInv": votes_granted_inv_py,
    "QuorumLogInv": quorum_log_inv_py,
    "MoreUpToDateCorrect": more_up_to_date_correct_py,
    "LeaderCompleteness": leader_completeness_py,
}
