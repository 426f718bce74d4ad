"""Invariant and state-constraint kernels.

``build_type_ok`` is the tensor-side TypeOK (/root/reference/raft.tla:482-492).
In the fixed-width encoding most of TypeOK holds *by construction* (fields are
always int tensors of the right shape), so the kernel checks exactly the
residual content conditions that encoding does not force:

- roles in {Follower, Candidate, Leader}; votedFor in {Nil} ∪ Server;
- log entries (below log_len) have Nat terms and values in Value; tails zero;
- commitIndex ∈ Nat; nextIndex >= 1 (raft.tla:491); matchIndex ∈ Nat;
- vote bitmasks ⊆ Server; message rows well-typed per the :443-479 schemas
  with positive bag multiplicities.

``build_constraint`` builds the CONSTRAINT predicate for bounded exhaustive
runs (SURVEY §2.4 R9).  TLC semantics: a state violating the constraint is
still generated, invariant-checked and counted distinct, but not expanded —
the engine applies this predicate only when deciding what to enqueue.  The
reference's MCraft.cfg sets no constraint (the space is unbounded as
configured); bounds here (MaxTerm / MaxLogLen / per-message count cap) are
the BASELINE.json bounded configs.  The count cap also bounds
``DuplicateMessage`` (raft.tla:410), which is what keeps the bag finite.

The oracle mirrors (``*_py``) keep differential tests honest.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from .dims import RaftDims
from .pystate import PyState
from .schema import StateBatch


def _count(cells, axis=None):
    """How many of ``cells`` hold, as an int32 sum.  Every reduction of
    ``type_ok`` is one of these and none a ``jnp.all``: under ``vmap``
    over the K lanes a sum of integers over a field's axes makes the
    TPU's compiler lay the field out with the lanes minor-most, where
    an and-reduction of booleans states no preference and takes the
    layout of whatever produced the field (the parents' gather: lanes
    major, an axis of N or L padded to a 128-wide vector; 3.9 ms a pass
    until PR 38, PERF.md section 6)."""
    return jnp.sum(cells, axis=axis, dtype=jnp.int32)


def build_type_ok(dims: RaftDims):
    N, L = dims.n_servers, dims.max_log
    value_ok = dims.build_value_ok()     # entries-in-Value, variant-widened

    def type_ok(st: StateBatch):
        lane = jnp.arange(L)[None, :]
        in_log = lane < st.log_len[:, None]
        occ = st.msg_cnt > 0
        mt = st.msg[:, 0]
        src, dst = st.msg[:, 1], st.msg[:, 2]
        cells = [
            (st.role >= 0) & (st.role <= 2),
            (st.voted_for >= 0) & (st.voted_for <= N),
            jnp.where(in_log,
                      (st.log_term >= 0) & value_ok(st.log_val),
                      (st.log_term == 0) & (st.log_val == 0)),
            (st.log_len >= 0) & (st.log_len <= L),
            (st.term >= 0) & (st.commit >= 0),
            (st.votes_resp >= 0) & (st.votes_resp < (1 << N)),
            (st.votes_gran >= 0) & (st.votes_gran < (1 << N)),
            st.next_idx >= 1,                   # raft.tla:491
            st.match_idx >= 0,
            jnp.where(occ,
                      (mt >= 1) & (mt <= 4)
                      & (src >= 1) & (src <= N)
                      & (dst >= 1) & (dst <= N)
                      & (st.msg[:, 3] >= 0),
                      _count(st.msg != 0, axis=1) == 0),
            st.msg_cnt >= 0,
        ]
        return sum(_count(~ok) for ok in cells) == 0

    return type_ok


def type_ok_py(s: PyState, dims: RaftDims) -> bool:
    """Oracle-side TypeOK (subset mirroring build_type_ok's content checks)."""
    n = dims.n_servers
    ok = all(0 <= r <= 2 for r in s.role)
    ok &= all(0 <= vf <= n for vf in s.voted_for)
    ok &= all(t >= 0 and dims.value_ok_py(val)
              for log in s.log for (t, val) in log)
    ok &= all(t >= 0 for t in s.current_term)
    ok &= all(c >= 0 for c in s.commit_index)
    ok &= all(0 <= m < (1 << n)
              for m in s.votes_responded + s.votes_granted)
    ok &= all(x >= 1 for row in s.next_index for x in row)
    ok &= all(x >= 0 for row in s.match_index for x in row)
    ok &= all(c >= 1 for _m, c in s.messages)
    return ok


def build_no_leader(dims: RaftDims):
    """``NoLeaderElected`` — a DELIBERATELY FALSIFIABLE canary: asserts no
    server ever reaches the Leader role, which any live election run
    violates at the first ``BecomeLeader``.  It exists for the
    counterexample tooling (engine/explain.py, the CI violation smoke):
    checking it turns "model-check the spec" into "extract a minimal
    election trace", the standard TLC trick for demonstrating the error
    reporting path on a healthy model.  Never include it in a cfg that is
    supposed to pass."""
    from .dims import LEADER

    def no_leader(st: StateBatch):
        return jnp.all(st.role != LEADER)

    return no_leader


def no_leader_py(s: PyState, dims: RaftDims) -> bool:
    from .dims import LEADER
    return LEADER not in s.role


@dataclasses.dataclass(frozen=True)
class Bounds:
    """CONSTRAINT bounds for exhaustive runs (BASELINE.json configs)."""

    max_term: Optional[int] = None       # \A i : currentTerm[i] <= MaxTerm
    max_log_len: Optional[int] = None    # \A i : Len(log[i]) <= MaxLogLen
    max_msg_count: Optional[int] = None  # \A m : messages[m] <= MaxDup
    # Cardinality(DOMAIN messages) <= MaxInFlight: bounds the number of
    # DISTINCT in-flight messages.  Without it the bag domain is the
    # dominant growth axis (the MCraft_bounded space passes 63M states by
    # level 13, BASELINE.md §b); the standard TLC recipe bounds it with
    # exactly this kind of state constraint.
    max_in_flight: Optional[int] = None


def build_inv_id(inv_fns):
    """First-failing-invariant dispatch shared by the three engines:
    returns ``inv_id(state) -> int32`` yielding the index of the first
    violated invariant in ``inv_fns`` order, or -1 when all hold."""
    import jax.numpy as _jnp

    def inv_id(st: StateBatch):
        out = _jnp.int32(-1)
        for q in range(len(inv_fns) - 1, -1, -1):
            out = _jnp.where(inv_fns[q](st), out, _jnp.int32(q))
        return out

    return inv_id


def build_constraint(dims: RaftDims, bounds: Bounds):
    def constraint(st: StateBatch):
        ok = jnp.bool_(True)
        if bounds.max_term is not None:
            ok = ok & jnp.all(st.term <= bounds.max_term)
        if bounds.max_log_len is not None:
            ok = ok & jnp.all(st.log_len <= bounds.max_log_len)
        if bounds.max_msg_count is not None:
            ok = ok & jnp.all(st.msg_cnt <= bounds.max_msg_count)
        if bounds.max_in_flight is not None:
            ok = ok & (jnp.sum((st.msg_cnt > 0).astype(jnp.int32))
                       <= bounds.max_in_flight)
        return ok

    return constraint


#: Reserved predicate name for the cfg CONSTRAINT in read-set exports and
#: POR certificates (a cfg names its constraint operator, e.g.
#: ``BoundedSpace``, but the certificate cares about the *predicate the
#: engine actually evaluates*, so one canonical name covers it).
CONSTRAINT_PREDICATE = "CONSTRAINT"


def invariant_registry():
    """THE name -> builder registry of checkable invariants: TypeOK plus
    the models/safety.py suite.  Single source of truth — both
    ``engine/check.py``'s cfg resolution and the POR pass's visibility
    condition read this, so a new invariant registers once and is
    immediately nameable in cfgs AND part of the analyzer's conservative
    default predicate set.  (A function, not a constant: safety.py is
    imported lazily to keep this module import-light.)"""
    from .safety import SAFETY_INVARIANTS
    # NoLeaderElected is the deliberately falsifiable canary (see
    # build_no_leader): registered so a cfg can name it to exercise the
    # violation/counterexample path, and part of the analyzer's
    # conservative default predicate set like every other entry (its
    # reads only make certificates MORE conservative).
    return {"TypeOK": build_type_ok, "NoLeaderElected": build_no_leader,
            **SAFETY_INVARIANTS}


def checkable_predicates(dims: RaftDims, invariant_names=None,
                         bounds: Optional[Bounds] = None,
                         constraint=None):
    """Every state predicate a check run can evaluate, as
    ``[(name, kernel)]`` — the machine-readable export the POR pass's
    invariant-visibility condition traces read sets from (analysis/por.py).

    ``invariant_names=None`` returns the CONSERVATIVE default: TypeOK plus
    the full safety suite (models/safety.py) — a certificate proved
    against every registered predicate stays valid for any cfg that
    checks a subset of them.  Passing the cfg's INVARIANT list narrows
    the set (and therefore the visibility condition) to what that model
    actually checks.  The CONSTRAINT predicate is appended (under
    :data:`CONSTRAINT_PREDICATE`) when ``constraint`` is given or
    ``bounds`` carries any bound: constraint reads gate *expansion*, so
    POR must treat them exactly like invariant reads."""
    registry = invariant_registry()
    names = (list(registry) if invariant_names is None
             else list(invariant_names))
    out = []
    for name in names:
        if name not in registry:
            raise ValueError(f"unknown invariant {name!r}; registered: "
                             f"{sorted(registry)}")
        out.append((name, registry[name](dims)))
    if constraint is not None:
        out.append((CONSTRAINT_PREDICATE, constraint))
    elif bounds is not None and any(
            getattr(bounds, f.name) is not None
            for f in dataclasses.fields(bounds)):
        out.append((CONSTRAINT_PREDICATE, build_constraint(dims, bounds)))
    return out


def constraint_py(bounds: Bounds):
    def constraint(s: PyState, dims: RaftDims) -> bool:
        ok = True
        if bounds.max_term is not None:
            ok &= max(s.current_term) <= bounds.max_term
        if bounds.max_log_len is not None:
            ok &= max(len(l) for l in s.log) <= bounds.max_log_len
        if bounds.max_msg_count is not None:
            ok &= all(c <= bounds.max_msg_count for _m, c in s.messages)
        if bounds.max_in_flight is not None:
            ok &= len(s.messages) <= bounds.max_in_flight
        return ok

    return constraint
