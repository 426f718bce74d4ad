"""Static model dimensions and the action-instance grid.

The reference spec's abstract constants (``Server``, ``Value`` —
/root/reference/raft.tla:11-14) are bound to finite model-value sets by the
TLC harness (/root/reference/MCraft.tla:15-21: 3 servers, 2 values).  In the
TPU build those bindings become *static dimensions*: every tensor shape and
the complete action-instance grid are known at trace time, so XLA compiles
one fixed program per (N, V, L, M) tuple.

Encoding conventions (used by both the JAX kernels and the Python oracle):

- servers are ``0..N-1`` (model values ``r1..rN`` interned in order);
- values are ``1..V`` (``0`` is reserved for "empty log slot");
- roles: ``0=Follower, 1=Candidate, 2=Leader`` (any distinct codes are
  sound per ``ASSUME DistinctRoles`` raft.tla:494-496);
- ``votedFor``: ``0=Nil, 1..N`` = server id + 1 (``Nil`` distinct: raft.tla:20);
- message types: ``0=RequestVoteRequest, 1=RequestVoteResponse,
  2=AppendEntriesRequest, 3=AppendEntriesResponse`` (distinctness:
  raft.tla:498-503);
- vote sets (``votesResponded``/``votesGranted`` raft.tla:56-59) are N-bit
  bitmasks, bit ``j`` = server ``j``;
- logs (raft.tla:48) are fixed ``[L]`` term/value lanes plus a length; slots
  ``>= len`` MUST be zero (canonical form for fingerprinting).

Message slot layout (one in-flight distinct message = one ``[MSG_WIDTH]``
int32 row plus a count; the bag of messages raft.tla:31 is the multiset
{row: count}).  Field 0 stores ``mtype + 1`` so an all-zero row is an
unambiguous free slot.  Payload union (schemas raft.tla:443-475):

  common:  [0]=mtype+1  [1]=msource+1  [2]=mdest+1  [3]=mterm
  RVReq :  [4]=mlastLogTerm  [5]=mlastLogIndex
  RVResp:  [4]=mvoteGranted  [5]=Len(mlog)  [6:6+L]=mlog terms  [6+L:6+2L]=mlog values
  AEReq :  [4]=mprevLogIndex (SmokeInt can be -1: Smokeraft.tla:14-15, type Int
           raft.tla:454)  [5]=mprevLogTerm  [6]=Len(mentries) (<=1:
           raft.tla:181-183)  [7]=entry term  [8]=entry value  [9]=mcommitIndex
  AEResp:  [4]=msuccess  [5]=mmatchIndex

``mlog`` (the full log copy in RequestVoteResponse, raft.tla:259,465) forces
the payload width to ``2 + 2L``.

Lane widths: the static analyzer (``analysis/``) is the AUTHORITY on
whether every packed lane is wide enough for this model.  ``python -m
raft_tla_tpu analyze`` proves the declared domains (machine-readable in
``analysis/lane_map.py``) fit the uint8 row per action kernel by
interval abstract interpretation, naming the witness action otherwise;
``schema.audit_lane_widths`` (construction) and ``build_pack_guard``
(runtime) are the enforcement backstops, not the source of truth.  A
variant that widens a domain should run the analyzer before trusting
the audit's static table.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

# Role codes.
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
NIL = 0

# Message-type codes.
RVQ, RVR, AEQ, AER = 0, 1, 2, 3
MSG_TYPE_NAMES = ("RequestVoteRequest", "RequestVoteResponse",
                  "AppendEntriesRequest", "AppendEntriesResponse")

# Action-family codes; order mirrors the Next disjunction raft.tla:421-430.
A_RESTART = 0        # \E i : Restart(i)            raft.tla:421 -> :136
A_TIMEOUT = 1        # \E i : Timeout(i)            raft.tla:422 -> :146
A_REQUESTVOTE = 2    # \E i,j : RequestVote(i,j)    raft.tla:423 -> :157
A_BECOMELEADER = 3   # \E i : BecomeLeader(i)       raft.tla:424 -> :195
A_CLIENTREQUEST = 4  # \E i,v : ClientRequest(i,v)  raft.tla:425 -> :206
A_ADVANCECOMMIT = 5  # \E i : AdvanceCommitIndex(i) raft.tla:426 -> :219
A_APPENDENTRIES = 6  # \E i,j : AppendEntries(i,j)  raft.tla:427 -> :171
A_RECEIVE = 7        # \E m : Receive(m)            raft.tla:428 -> :388
A_DUPLICATE = 8      # \E m : DuplicateMessage(m)   raft.tla:429 -> :410
A_DROP = 9           # \E m : DropMessage(m)        raft.tla:430 -> :415

FAMILY_NAMES = ("Restart", "Timeout", "RequestVote", "BecomeLeader",
                "ClientRequest", "AdvanceCommitIndex", "AppendEntries",
                "Receive", "DuplicateMessage", "DropMessage")


@dataclasses.dataclass(frozen=True)
class LogAppend:
    """A ``build_extra_v2`` entry for a family whose whole footprint is ONE
    log append: instance ``(i, *rest)`` (the family's
    ``build_extra_kernels`` parameters: the appending server first, at
    most one more) writes ``(term[i], value_fn(state, i, *rest))`` at
    ``(i, Len(log[i]))``, ``ClientRequest``'s footprint (raft.tla:206-213)
    with another value.  ``lane_out`` then writes the lane through the
    append it already has; ``value_fn`` is all the family runs there."""

    value_fn: Callable


@dataclasses.dataclass(frozen=True)
class RaftDims:
    """Static shape parameters of one compiled checker instance."""

    n_servers: int           # |Server|   (MCraft.tla:20-21 -> 3)
    n_values: int            # |Value|    (MCraft.tla:15-17 -> 2)
    max_log: int = 8         # L: log tensor capacity (>= any reachable length)
    n_msg_slots: int = 32    # M: capacity for distinct in-flight messages

    def __post_init__(self):
        if not (1 <= self.n_servers <= 8):
            raise ValueError("n_servers must be in 1..8 (bitmask encoding)")
        if not (1 <= self.n_values <= 255):
            raise ValueError("n_values must be in 1..255 (uint8 row packing)")
        # Log indices (incl. mprevLogIndex, which can also be -1) must stay
        # in int8 range: the uint8 row packing sign-extends that column.
        if not (1 <= self.max_log <= 127):
            raise ValueError("max_log must be in 1..127 (uint8 row packing)")
        # Systematic lane-width audit (schema.audit_lane_widths): every
        # packed field whose maximum domain value is STATIC — value lanes
        # (incl. variant encodings like reconfig's CFG_BASE+masks), vote
        # bitmasks, index/count lanes, message header columns — must fit
        # its lane width, checked HERE at construction so the reconfig
        # value-wrap bug class (a domain silently exceeding its byte
        # width, invisible at shallow depths) can never recur in a new
        # variant.  Lazy import: schema imports this module at top level.
        from .schema import audit_lane_widths
        audit_lane_widths(self)

    # -- derived widths ----------------------------------------------------
    @property
    def max_log_value(self) -> int:
        """The largest value the spec can place in a log-entry VALUE lane
        (and hence in the message value columns — AEReq entry value,
        RVResp mlog values).  Base spec: client values are interned codes
        1..|Value|.  Variants with encoded values (reconfig's
        CFG_BASE + (old << 8) + new entries) override this; the
        construction-time lane audit (schema.audit_lane_widths) checks it
        against ``256**value_bytes - 1``, which is what makes a
        too-narrow value lane a BUILD error instead of a silent wrap at
        depth (the round-5 reconfig bug class)."""
        return self.n_values

    @property
    def value_bytes(self) -> int:
        """Bytes per log-entry VALUE in the packed uint8 row (schema.py).
        Base spec: 1 (values are interned client codes 1..V <= 255).
        Variants whose values exceed 255 — models/reconfig.py's
        configuration entries at CFG_BASE + masks — override this to 2;
        flatten/unflatten then carry high-byte planes for the log value
        lanes and the message columns that hold values (AEReq entry
        value, RVResp mlog values), appended at the END of the row so
        the base layout is unchanged."""
        return 1

    @property
    def payload_width(self) -> int:
        return max(6, 2 + 2 * self.max_log)

    @property
    def msg_width(self) -> int:
        return 4 + self.payload_width

    # -- action-instance grid ---------------------------------------------
    # Per-family instance counts; the expand kernel emits exactly one
    # candidate successor per instance with an enabled mask.  Receive yields
    # at most one successor per message because its disjuncts are pairwise
    # mutually exclusive (term comparisons partition on </=/>; see the
    # guards at raft.tla:282,296,335,361,374,383).
    @property
    def family_sizes(self) -> tuple:
        n, v, m = self.n_servers, self.n_values, self.n_msg_slots
        base = (n, n, n * n, n, n * v, n, n * n, m, m, m)
        return base + tuple(sz for _name, sz in self.extra_families)

    @property
    def family_names(self) -> tuple:
        return FAMILY_NAMES + tuple(nm for nm, _sz in self.extra_families)

    # -- model-variant hooks ----------------------------------------------
    # A spec variant (e.g. models/reconfig.py's joint-consensus extension)
    # subclasses RaftDims and overrides these; the JAX kernels
    # (models/actions.py), the Python oracle (models/oracle.py), and the
    # invariants (models/invariants.py) all dispatch through them, so every
    # engine (single-chip BFS, mesh BFS, simulation) picks up a variant
    # just by being handed its dims.

    @property
    def extra_families(self) -> tuple:
        """Extra action families beyond the raft.tla:421-430 alphabet:
        tuple of (name, instance_count)."""
        return ()

    def build_quorum(self):
        """JAX kernel ``quorum(state, i, member) -> bool`` deciding whether
        the [N]-bool ``member`` vector is a quorum from server i's point of
        view.  Base spec: simple majority of Server (raft.tla:79-81)."""
        import jax.numpy as jnp
        n = self.n_servers

        def quorum(st, i, member):
            return 2 * jnp.sum(member.astype(jnp.int32)) > n

        return quorum

    def quorum_py(self, s, i: int, mask: int) -> bool:
        """Oracle-side quorum on a membership bitmask (raft.tla:81)."""
        return 2 * bin(mask).count("1") > self.n_servers

    def build_extra_kernels(self):
        """JAX kernels for the extra families, in ``extra_families`` order:
        list of (param_arrays, kernel) with
        ``kernel(state, *params) -> (enabled, overflow, state')``."""
        return []

    def build_extra_v2(self, fp_helpers):
        """Delta-pipeline kernels for the extra families (models/
        actions2.py), in ``extra_families`` order, or ``None`` if the
        variant does not support the v2 pipeline (engines then fall back
        to v1).  An entry takes one of two forms:

        - a :class:`LogAppend` declaration, for a family that appends one
          entry to ``log[i]``.  It costs a lane nothing beyond its value:
          ``lane_out`` folds the family into ``ClientRequest``'s write
          (position, term, length, the three fingerprint deltas and the
          three successor fields are computed once for all of them), and
          reads the instance's parameters from its static decode tables,
          which it fills from ``build_extra_kernels``' arrays at build
          time;
        - a general ``lane_fn(state, *params) -> ((d_base0, d_base1),
          (d_msum0, d_msum1), successor)`` — the fingerprint-sum deltas
          plus the sparsely-constructed successor for ONE instance, for
          any other footprint.  It runs on every lane, its parameters
          read at the lane's traced position, and the successor is
          selected in over the whole state: what a declaration saves.

        The parameter arrays are NOT duplicated here: both forms get the
        ``build_extra_kernels`` arrays of the same family (single source
        of truth for the grid order).  ``fp_helpers`` is actions2's
        delta toolkit (dpos/dvec/dsum/offsets...), for the general form.
        Masks and the pack guard come from ``build_extra_masks_v2`` or,
        without it, from ``build_extra_kernels`` (actions2 evaluates the
        v1 kernel's guards and folds ``enabled & ~pack_ok(successor)``
        exactly as the v1 chunk does).  Base spec: no extras."""
        return []

    def build_extra_masks_v2(self):
        """OPTIONAL guards-only mask kernels for the extra families, in
        ``extra_families`` order, or ``None`` to have the v2 masks pass
        fall back to running the family's full v1 kernel (complete
        successor construction + whole-state pack guard) per lane.  Each
        entry is ``mask_fn(state, pack_ok_parent, *params) -> (enabled,
        overflow)`` and MUST be bit-identical to the v1 evaluation
        ``(en, ovf | (en & ~pack_ok(successor)))`` — actions2
        property-tests this.  ``pack_ok_parent`` is ``pack_ok(state)``
        evaluated ONCE per parent so footprints whose written values fit
        their lanes by construction can reuse it instead of re-checking
        the whole successor.  Base spec: no extras."""
        return None

    def extra_successors_py(self, s):
        """Oracle-side successors for the extra families: iterable of
        ((family_code, params), successor_state)."""
        return ()

    def build_value_ok(self):
        """JAX elementwise predicate: is a log-entry value lane well-typed
        (entries in Value — raft.tla:456/:465)?  Variants widen this."""
        import jax.numpy as jnp
        v = self.n_values

        def value_ok(vals):
            return (vals >= 1) & (vals <= v)

        return value_ok

    def value_ok_py(self, val: int) -> bool:
        return 1 <= val <= self.n_values

    @property
    def family_offsets(self) -> tuple:
        offs, acc = [], 0
        for s in self.family_sizes:
            offs.append(acc)
            acc += s
        return tuple(offs)

    @property
    def n_instances(self) -> int:
        return sum(self.family_sizes)

    def instance_info(self, g: int) -> tuple:
        """Decode grid index -> (family, params dict). Host-side helper for
        trace printing/replay."""
        n, v = self.n_servers, self.n_values
        for fam, (off, size) in enumerate(zip(self.family_offsets,
                                              self.family_sizes)):
            if off <= g < off + size:
                k = g - off
                if fam in (A_RESTART, A_TIMEOUT, A_BECOMELEADER,
                           A_ADVANCECOMMIT):
                    return fam, {"i": k}
                if fam in (A_REQUESTVOTE, A_APPENDENTRIES):
                    return fam, {"i": k // n, "j": k % n}
                if fam == A_CLIENTREQUEST:
                    return fam, {"i": k // v, "v": k % v + 1}
                return fam, {"slot": k}
        raise IndexError(g)

    def describe_instance(self, g: int) -> str:
        fam, p = self.instance_info(g)
        name = self.family_names[fam]
        return f"{name}({', '.join(f'{k}={v}' for k, v in p.items())})"
