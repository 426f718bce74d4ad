"""Differential tests of the correctness-invariant suite (models/safety.py).

Mirrors the reference's proof tier (raft.tla:896-1180; SURVEY §2.3): every
safety invariant is evaluated two independent ways — pure-Python mirror vs
vectorized JAX kernel — over (a) reachable states of a small bounded model
(where the whole suite must hold) and (b) unstructured random states (where
violations are common, exercising the False paths of both implementations).
Hand-crafted violating states then pin each invariant's failure mode.
"""

import dataclasses
import functools
import os
import sys

import jax
import numpy as np
import pytest

from raft_tla_tpu.analysis.lint import _walk_eqns
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models import smoke
from raft_tla_tpu.models.dims import (AEQ, CANDIDATE, LEADER, RVQ, RVR,
                                      RaftDims)
from raft_tla_tpu.models.invariants import Bounds, constraint_py
from raft_tla_tpu.models.pystate import PyState, init_state
from raft_tla_tpu.models.safety import (SAFETY_INVARIANTS,
                                        SAFETY_INVARIANTS_PY,
                                        _py_last_term as _last)
from raft_tla_tpu.models.schema import encode_state, stack_states

DIMS2 = RaftDims(n_servers=2, n_values=1, max_log=3, n_msg_slots=12)
DIMS3 = RaftDims(n_servers=3, n_values=2, max_log=3, n_msg_slots=12)


@functools.lru_cache(maxsize=None)
def _kernels(dims):
    return {name: jax.jit(jax.vmap(build(dims)))
            for name, build in SAFETY_INVARIANTS.items()}


def _eval_both(states, dims):
    """Evaluate every safety invariant via mirror and kernel; compare."""
    batch = stack_states([encode_state(s, dims) for s in states])
    results = {}
    for name, kern in _kernels(dims).items():
        got = np.asarray(kern(batch))
        want = np.array([SAFETY_INVARIANTS_PY[name](s, dims)
                         for s in states])
        mism = np.nonzero(got != want)[0]
        assert mism.size == 0, (
            f"{name}: kernel/oracle disagree on {mism.size} states, "
            f"first at index {mism[0] if mism.size else None}:\n"
            f"{states[int(mism[0])] if mism.size else None}")
        results[name] = want
    return results


def test_suite_holds_on_reachable_and_matches_kernel():
    """On reachable states of a bounded 2-server model the entire suite
    holds, and mirror == kernel state-for-state."""
    bounds = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
    res = orc.bfs([init_state(DIMS2)], DIMS2,
                  constraint=constraint_py(bounds), check_deadlock=False,
                  stop_predicate=lambda r: r.distinct_states >= 1200)
    states = list(res.parent.keys())
    assert len(states) >= 500
    results = _eval_both(states, DIMS2)
    for name, vals in results.items():
        assert vals.all(), f"{name} violated on a reachable state"


def test_kernel_matches_oracle_on_random_states():
    """Unstructured random states: many violate the suite; both sides must
    agree exactly (False paths included)."""
    states = smoke.random_states(DIMS2, 150, seed=7)
    results = _eval_both(states, DIMS2)
    # Sanity: the random set actually exercises violations somewhere.
    assert any((~vals).any() for vals in results.values())


def _base(dims, **kw):
    s = init_state(dims)
    return s.replace(**kw)


def _crafted_violations():
    """(invariant name, dims, violating state) for every suite member."""
    d2, d3 = DIMS2, DIMS3
    out = []
    # ElectionSafety raft.tla:1124-1129: leader 0 (term 2) lacks an entry
    # with its own term while server 1 has one.
    out.append(("ElectionSafety", d2, _base(
        d2, role=(LEADER, 0), current_term=(2, 2),
        log=((), ((2, 1),)))))
    # LogMatching raft.tla:1132-1136: same (index, term), different value.
    out.append(("LogMatching", d3, _base(
        d3, log=(((1, 1),), ((1, 2),), ()))))
    # LeaderVotesQuorum raft.tla:1033-1037: leader without any votes.
    out.append(("LeaderVotesQuorum", d2, _base(
        d2, role=(LEADER, 0), current_term=(2, 1))))
    # CandidateTermNotInLog raft.tla:1041-1047: electable candidate whose
    # term already appears in a log.
    out.append(("CandidateTermNotInLog", d2, _base(
        d2, role=(CANDIDATE, 0), current_term=(2, 2),
        log=((), ((2, 1),)))))
    # VotesGrantedInv raft.tla:1145-1153: 0 holds 1's vote at equal term but
    # misses 1's committed entry.
    out.append(("VotesGrantedInv", d2, _base(
        d2, votes_granted=(0b10, 0), log=((), ((1, 1),)),
        commit_index=(0, 1))))
    # QuorumLogInv raft.tla:1157-1161 (N=3): 0's committed entry is in no
    # other log -> a quorum {1, 2} exists with no holder.
    out.append(("QuorumLogInv", d3, _base(
        d3, log=(((1, 1),), (), ()), commit_index=(1, 0, 0))))
    # MoreUpToDateCorrect raft.tla:1167-1172: 0 is more up to date than 1
    # yet lacks 1's committed entry.
    out.append(("MoreUpToDateCorrect", d2, _base(
        d2, log=(((2, 1),), ((1, 1),)), commit_index=(0, 1))))
    # LeaderCompleteness raft.tla:1176-1180: leader misses a committed entry.
    out.append(("LeaderCompleteness", d2, _base(
        d2, role=(LEADER, 0), current_term=(2, 1),
        log=((), ((1, 1),)), commit_index=(0, 1))))
    # MessagesInv raft.tla:941-946 via RequestVoteRequestInv :915-920: a
    # candidate's vote request advertises a wrong lastLogIndex.
    out.append(("MessagesInv", d2, _base(
        d2, role=(CANDIDATE, 0), current_term=(2, 1),
        messages=frozenset({((0, 0, 1, 2, 0, 5), 1)}))))
    return [x for x in out if x is not None]


@pytest.mark.parametrize("name,dims,state",
                         _crafted_violations(),
                         ids=[x[0] for x in _crafted_violations()])
def test_crafted_violation_detected(name, dims, state):
    py = SAFETY_INVARIANTS_PY[name](state, dims)
    assert py is False, f"{name} mirror failed to flag the crafted state"
    kern = SAFETY_INVARIANTS[name](dims)
    got = bool(kern(encode_state(state, dims)))
    assert got is False, f"{name} kernel failed to flag the crafted state"


def test_registry_resolution(tmp_path):
    """A cfg naming the full suite resolves through the front-end registry."""
    from raft_tla_tpu.engine.check import resolve_invariants
    from raft_tla_tpu.utils.cfg import load_config
    cfg = tmp_path / "Safety2.cfg"
    cfg.write_text("""
CONSTANTS
    Server = {r1, r2}
    Value = {v1}
    MaxTerm = 2
    MaxLogLen = 1
    MaxMsgCount = 1
SPECIFICATION Spec
INVARIANTS TypeOK MessagesInv LeaderVotesQuorum CandidateTermNotInLog
           ElectionSafety LogMatching VotesGrantedInv QuorumLogInv
           MoreUpToDateCorrect LeaderCompleteness
CONSTRAINT BoundedSpace
""")
    setup = load_config(str(cfg))
    invs = resolve_invariants(setup)
    assert len(invs) == 10


# ---------------------------------------------------------------------------
# The reads at a traced position (safety.py ``_pick``): ``MessagesInv``'s
# ten of the sender's and receiver's term, role, log length, last term and
# log entries, and ``_last_terms``.  Reachable states of the small models
# above hold short logs and few messages in the sender's term; these sets
# are made to reach every read, in and out of the TLA+ text's domain.

READ_DIMS = [RaftDims(n_servers=2, n_values=2, max_log=2, n_msg_slots=8),
             RaftDims(n_servers=3, n_values=2, max_log=3, n_msg_slots=12),
             RaftDims(n_servers=5, n_values=2, max_log=4, n_msg_slots=16)]
READ_KINDS = ["AEQ", "RVQ", "RVR", "mixed", "logs"]


def _some_log(rng, dims, length):
    terms = sorted(int(t) for t in rng.integers(1, 4, size=length))
    return tuple((t, int(rng.integers(1, dims.n_values + 1)))
                 for t in terms)


def _aeq(rng, dims, logs, term, src, dst, prev):
    """An AppendEntriesRequest of ``src``'s own term at ``prev``: as
    ``src``'s log has it (one time in two, where it has it at all), or
    with the entry or prevLogTerm wrong."""
    log = logs[src]
    entry = log[prev] if 0 <= prev < len(log) else (
        int(rng.integers(1, 4)), int(rng.integers(1, dims.n_values + 1)))
    pterm = log[prev - 1][0] if 0 < prev <= len(log) \
        else int(rng.integers(0, 4))
    wrong = int(rng.integers(-2, 4))        # one time in two: none
    if wrong == 1:
        entry = (entry[0], entry[1] % dims.n_values + 1)
    elif wrong == 2:
        entry = (entry[0] + 1, entry[1])
    elif wrong == 3:
        pterm += 1
    return (AEQ, src, dst, term[src], prev, pterm, (entry,), 0)


def _rvq(rng, logs, term, src, dst):
    log, wrong = logs[src], int(rng.integers(0, 3))
    return (RVQ, src, dst, term[src], _last(log) + (wrong == 1),
            len(log) + (wrong == 2))


def _read_states(dims, kind, count=56):
    """Seeded states whose bag holds messages of ``kind`` in their
    sender's current term over non-empty logs; slots past the bag are
    empty (their ``src`` clips to server 0)."""
    rng = np.random.default_rng(
        [2147536001, dims.n_servers, READ_KINDS.index(kind)])
    n, L = dims.n_servers, dims.max_log
    out = []
    for k in range(count):
        logs = [_some_log(rng, dims, int(rng.integers(1, L + 1)))
                for _ in range(n)]
        src = int(rng.integers(n))
        dst = (src + 1 + int(rng.integers(n - 1))) % n
        third = (dst + 1) % n
        term = [_last(log) + int(rng.integers(0, 2)) for log in logs]
        role, commit, bag = [0] * n, [0] * n, {}
        ln = len(logs[src])
        # prev = 0, inside, Len, past Len, max_log, past max_log, negative.
        prev = [0, ln - 1, ln, ln + 1, L, L + 2, -1][k % 7]
        if kind in ("AEQ", "mixed"):
            bag[_aeq(rng, dims, logs, term, src, dst, prev)] = 1
        if kind in ("RVQ", "mixed"):
            cand = src if kind == "RVQ" else dst
            role[cand] = CANDIDATE
            bag[_rvq(rng, logs, term, cand, (cand + 1) % n)] = 2
        if kind in ("RVR", "mixed"):
            voter = src if kind == "RVR" else third
            term[voter] = term[dst] = max(term[voter], term[dst])
            bag[(RVR, voter, dst, term[voter], 1, ())] = 1
        if kind == "logs" and k % 3:
            commit = [int(rng.integers(0, len(log) + 1)) for log in logs]
            logs = [logs[0][:len(log)] if rng.integers(2) else log
                    for log in logs]
        out.append(dataclasses.replace(
            init_state(dims), current_term=tuple(term), role=tuple(role),
            log=tuple(logs), commit_index=tuple(commit),
            messages=frozenset(bag.items())))
    return out


def _reference_suite(states, dims):
    """``benchmark/reference/safety.py``'s predicates (it imports nothing
    of the program) on ``states``: ``{name: [bool]}``."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import bench_lib as lib
    from reference import dims as rd
    from reference import pystate as rp
    from reference import safety as rs
    rdims = rd.RaftDims(**{f.name: getattr(dims, f.name)
                           for f in dataclasses.fields(rd.RaftDims)})
    own = [lib.to_reference_state(s, rp) for s in states]
    return {name: [bool(rs.INVARIANTS[name](s, rdims)) for s in own]
            for name in SAFETY_INVARIANTS}


@pytest.mark.parametrize("kind", READ_KINDS)
@pytest.mark.parametrize("dims", READ_DIMS,
                         ids=[f"{d.n_servers}x{d.max_log}"
                              for d in READ_DIMS])
def test_kernel_matches_mirror_and_reference_where_the_reads_are(dims, kind):
    """Kernel == mirror == the benchmark's reference for the whole suite
    on states that reach every ``_pick``, and the predicate the set is
    made for takes both truth values in it."""
    states = _read_states(dims, kind)
    assert all(len(s.messages) < dims.n_msg_slots for s in states)
    results = _eval_both(states, dims)
    for name, want in _reference_suite(states, dims).items():
        assert results[name].tolist() == want, name
    vals = results["MoreUpToDateCorrect" if kind == "logs"
                   else "MessagesInv"]
    assert vals.any() and not vals.all(), vals


@pytest.mark.parametrize("name", ["MessagesInv", "MoreUpToDateCorrect"])
@pytest.mark.parametrize("dims", READ_DIMS[1:], ids=["3", "5"])
def test_no_predicate_indexes_a_table_by_a_traced_value(dims, name):
    """``table[at]`` under ``vmap`` is a ``gather`` (a traced-start slice
    a ``dynamic_slice``), which costs a CPU nothing and the TPU 8-12 ns
    an element: 100 ms a pass of the cell ``safety9`` until PR 36.  The
    reads are compare-and-select over the static axis (``_pick``)."""
    batch = stack_states([encode_state(init_state(dims), dims)] * 4)
    jaxpr = jax.make_jaxpr(jax.vmap(SAFETY_INVARIANTS[name](dims)))(batch)
    prims = {eqn.primitive.name for eqn in _walk_eqns(jaxpr)}
    assert "reduce_sum" in prims, prims     # else the walk saw nothing
    assert not prims & {"gather", "dynamic_slice"}, prims
