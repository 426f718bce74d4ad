"""Struct-of-arrays state schema: the spec's variables as fixed-width tensors.

``vars == <<messages, serverVars, candidateVars, leaderVars, logVars>>``
(/root/reference/raft.tla:74) becomes ``StateBatch``, a NamedTuple pytree of
int32 tensors.  Used both per-state (no leading axis, inside kernels) and
batched (leading frontier axis, under vmap).  Encoding conventions are
documented in ``dims.py``; the invariants that keep states canonical for
fingerprinting are:

- log lanes at positions >= log_len are zero;
- free message slots (count == 0) are all-zero rows;
- votedFor uses 0 for Nil; bitmask bits beyond n_servers are zero.

``encode_state``/``decode_state`` convert to/from the oracle's ``PyState``
(host-side, numpy) for differential testing and trace pretty-printing.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

from .dims import AEQ, RVQ, RVR, RaftDims
from .pystate import PyState


class StateBatch(NamedTuple):
    """One Raft global state (or a batch: add leading axes uniformly)."""

    term: "np.ndarray"        # [N]    currentTerm  raft.tla:37
    role: "np.ndarray"        # [N]    state        raft.tla:39
    voted_for: "np.ndarray"   # [N]    votedFor     raft.tla:42 (0=Nil)
    log_term: "np.ndarray"    # [N,L]  log entry terms   raft.tla:48
    log_val: "np.ndarray"     # [N,L]  log entry values
    log_len: "np.ndarray"     # [N]    Len(log[i])
    commit: "np.ndarray"      # [N]    commitIndex  raft.tla:50
    votes_resp: "np.ndarray"  # [N]    votesResponded bitmask  raft.tla:56
    votes_gran: "np.ndarray"  # [N]    votesGranted bitmask    raft.tla:59
    next_idx: "np.ndarray"    # [N,N]  nextIndex    raft.tla:64
    match_idx: "np.ndarray"   # [N,N]  matchIndex   raft.tla:67
    msg: "np.ndarray"         # [M,W]  distinct in-flight messages raft.tla:31
    msg_cnt: "np.ndarray"     # [M]    bag multiplicities


def audit_lane_widths(dims: RaftDims) -> None:
    """Construction-time audit: every packed field whose maximum domain
    value is STATIC must fit its lane width.  Called from
    ``RaftDims.__post_init__`` — a too-narrow lane is a build error with
    the field named, never a silent mod-256 wrap at depth (the reconfig
    value-wrap bug class: ``CFG_BASE + (old << 8) + new`` aliased to the
    plain client value the moment a state was enqueued, and no test
    shallower than a leader's first config entry could see it).

    Runtime-growing fields — terms (and the message columns that carry
    term values: mterm at column 3, and column 4's term half), bag
    counts — are NOT in this audit; ``build_pack_guard`` bounds those
    per-state on device and the engines treat an overflow as a hard
    error.  Columns 5+ carrying terms (AEReq prevLogTerm, RVResp mlog
    entry terms) are bounded by the sender's mterm <= 255 which the
    pack guard checks.
    """
    n, L = dims.n_servers, dims.max_log
    vmax = 256 ** dims.value_bytes - 1
    checks = (
        # field, static max over the spec's domain, lane limit
        ("votes_resp/votes_gran bitmask", (1 << n) - 1, 255),
        ("voted_for (0=Nil, else server+1)", n, 255),
        ("role", 2, 255),
        ("log_len / commit / match_idx", L, 255),
        ("next_idx (<= Len(log)+1)", L + 1, 255),
        # One check covers BOTH the log value lanes and the message value
        # columns (AEReq entry value, RVResp mlog values): flatten_state
        # gives them identical widths (value_bytes), and their domain is
        # the same value alphabet.
        ("log_val / msg value columns (dims.max_log_value)",
         dims.max_log_value, vmax),
        ("msg column 0 (mtype+1)", 5, 255),
        ("msg columns 1-2 (src+1, dst+1)", n, 255),
        # Column 4 is sign-extended (mprevLogIndex reaches -1); its
        # INDEX uses must fit int8.  (Its term uses are runtime-guarded.)
        ("msg column 4 index uses (mprevLogIndex)", L, 127),
        ("msg index/count columns (mlog len, nentries, mcommit)",
         L + 1, 255),
    )
    for field, domain_max, limit in checks:
        if domain_max > limit:
            raise ValueError(
                f"packed lane too narrow for {type(dims).__name__}: "
                f"field {field!r} reaches {domain_max} but its lane "
                f"holds at most {limit}; widen the lane "
                "(dims.value_bytes for value lanes) or shrink the domain")


def encode_message(m: tuple, dims: RaftDims) -> np.ndarray:
    """Message tuple (pystate.py layout) -> [W] int32 row (dims.py layout)."""
    w = np.zeros(dims.msg_width, np.int32)
    mtype, src, dst, mterm = m[0], m[1], m[2], m[3]
    w[0], w[1], w[2], w[3] = mtype + 1, src + 1, dst + 1, mterm
    if mtype == RVQ:
        w[4], w[5] = m[4], m[5]
    elif mtype == RVR:
        granted, mlog = m[4], m[5]
        w[4], w[5] = granted, len(mlog)
        for k, (t, v) in enumerate(mlog):
            w[6 + k] = t
            w[6 + dims.max_log + k] = v
    elif mtype == AEQ:
        prev, pterm, entries, mcommit = m[4], m[5], m[6], m[7]
        w[4], w[5], w[6] = prev, pterm, len(entries)
        if entries:
            w[7], w[8] = entries[0]
        w[9] = mcommit
    else:  # AER
        w[4], w[5] = m[4], m[5]
    return w


def decode_message(w: np.ndarray, dims: RaftDims) -> tuple:
    mtype = int(w[0]) - 1
    src, dst, mterm = int(w[1]) - 1, int(w[2]) - 1, int(w[3])
    if mtype == RVQ:
        return (RVQ, src, dst, mterm, int(w[4]), int(w[5]))
    if mtype == RVR:
        ln = int(w[5])
        mlog = tuple((int(w[6 + k]), int(w[6 + dims.max_log + k]))
                     for k in range(ln))
        return (RVR, src, dst, mterm, int(w[4]), mlog)
    if mtype == AEQ:
        n_ent = int(w[6])
        entries = ((int(w[7]), int(w[8])),) if n_ent else ()
        return (AEQ, src, dst, mterm, int(w[4]), int(w[5]), entries, int(w[9]))
    return (3, src, dst, mterm, int(w[4]), int(w[5]))


def check_packable(st: "StateBatch", dims: "RaftDims") -> None:
    """Raise if any field value cannot round-trip the uint8 row packing.
    ``st`` is one state or a batch of roots (``stack_states``: one
    leading axis); of a batch the error names the first root at fault
    by its number, then what it names of a single state.

    Host-side, roots only; kernel-produced successors are guarded by
    ``build_pack_guard``.  Engines call this *after* the pre-pack root
    invariant check, so a root that an invariant would flag (e.g.
    matchIndex = -1 under TypeOK) is reported as the violation it is; this
    guard only rejects roots that would otherwise alias silently.  ``msg``
    column 4 — the one sign-extended field — admits [-128, 127]; value
    lanes (log values; msg value columns) admit [0, 65535] when
    ``dims.value_bytes == 2`` (reconfiguration entries); every other
    value is unsigned [0, 255]."""
    # The analyzer's lane map (analysis/lane_map.py) decodes the failing
    # lane for the error message: the field name plus, for message rows,
    # the semantic column meaning, plus the action families that write
    # the field — so the report points at the model code to look at, not
    # just a raw lane index.  Import-light by design (no jax, no cycle).
    from ..analysis import lane_map
    caps = lane_map.lane_capacities(dims)
    st = StateBatch(*(np.asarray(x) for x in st))
    # 'msg': per-column [W] bounds, broadcast over the slots.
    bad = StateBatch(*((a < caps[f][0]) | (a > caps[f][1])
                       for f, a in zip(StateBatch._fields, st)))
    if not any(b.any() for b in bad):
        return
    where = ""
    if st.term.ndim == 2:
        root = int(np.argmax(np.any(
            [b.reshape(len(b), -1).any(axis=1) for b in bad], axis=0)))
        where = f"root {root}: "
        st, bad = (StateBatch(*(x[root] for x in t)) for t in (st, bad))
    name, a, b = next(t for t in zip(StateBatch._fields, st, bad)
                      if t[2].any())
    lo_col, hi_col = caps[name]
    idx = tuple(int(i) for i in np.argwhere(b)[0])
    if name == "msg":
        lo_b, hi_b = int(lo_col[idx[-1]]), int(hi_col[idx[-1]])
    else:
        lo_b, hi_b = int(lo_col), int(hi_col)
    raise ValueError(
        f"{where}value {int(a[idx])} at "
        f"{lane_map.describe_lane(name, idx, dims)} "
        f"is outside the packable range [{lo_b}, {hi_b}] "
        f"(uint8 row packing would alias it silently; "
        f"{int(b.sum())} offending element(s) total)")


def encode_state(s: PyState, dims: RaftDims) -> StateBatch:
    """PyState -> single-state StateBatch (numpy int32, no leading axis)."""
    n, L, M = dims.n_servers, dims.max_log, dims.n_msg_slots
    log_term = np.zeros((n, L), np.int32)
    log_val = np.zeros((n, L), np.int32)
    log_len = np.zeros(n, np.int32)
    for i, log in enumerate(s.log):
        if len(log) > L:
            raise ValueError(f"log length {len(log)} exceeds capacity {L}")
        log_len[i] = len(log)
        for k, (t, v) in enumerate(log):
            log_term[i, k], log_val[i, k] = t, v
    bag = sorted(s.messages)
    if len(bag) > M:
        raise ValueError(f"{len(bag)} distinct messages exceed {M} slots")
    msg = np.zeros((M, dims.msg_width), np.int32)
    msg_cnt = np.zeros(M, np.int32)
    for slot, (m, c) in enumerate(bag):
        msg[slot] = encode_message(m, dims)
        msg_cnt[slot] = c
    return StateBatch(
        term=np.asarray(s.current_term, np.int32),
        role=np.asarray(s.role, np.int32),
        voted_for=np.asarray(s.voted_for, np.int32),
        log_term=log_term, log_val=log_val, log_len=log_len,
        commit=np.asarray(s.commit_index, np.int32),
        votes_resp=np.asarray(s.votes_responded, np.int32),
        votes_gran=np.asarray(s.votes_granted, np.int32),
        next_idx=np.asarray(s.next_index, np.int32),
        match_idx=np.asarray(s.match_index, np.int32),
        msg=msg, msg_cnt=msg_cnt)


def stack_states(states: List[StateBatch]) -> StateBatch:
    return StateBatch(*(np.stack(cols) for cols in zip(*states)))


def decode_state(st: StateBatch, dims: RaftDims) -> PyState:
    """Single-state StateBatch -> PyState (host-side)."""
    n = dims.n_servers
    a = StateBatch(*(np.asarray(x) for x in st))
    logs = tuple(
        tuple((int(a.log_term[i, k]), int(a.log_val[i, k]))
              for k in range(int(a.log_len[i])))
        for i in range(n))
    bag = frozenset(
        (decode_message(a.msg[s], dims), int(a.msg_cnt[s]))
        for s in range(dims.n_msg_slots) if a.msg_cnt[s] > 0)
    return PyState(
        current_term=tuple(int(x) for x in a.term),
        role=tuple(int(x) for x in a.role),
        voted_for=tuple(int(x) for x in a.voted_for),
        log=logs,
        commit_index=tuple(int(x) for x in a.commit),
        votes_responded=tuple(int(x) for x in a.votes_resp),
        votes_granted=tuple(int(x) for x in a.votes_gran),
        next_index=tuple(tuple(int(x) for x in row) for row in a.next_idx),
        match_index=tuple(tuple(int(x) for x in row) for row in a.match_idx),
        messages=bag)


# ---------------------------------------------------------------------------
# Flat row form: the BFS queues store states as [state_width] uint8 rows
# (one concatenation of every field); cheap reshape/concat both ways.
#
# uint8 is sufficient for every field under the target bounds (terms <=
# MaxTerm, log values <= |Value|, nextIndex <= Lmax+1, N<=8 vote bitmasks
# <= 255) and packs 4x more states per byte of HBM/ICI than int32.  The one
# field that can be negative is message payload column 4 (mprevLogIndex,
# raft.tla:454 — SmokeInt reaches -1, Smokeraft.tla:14-15): it is stored
# two's-complement (-1 -> 255) and sign-extended on decode; every other
# field is unsigned and < 128 under any budgeted run (a Smokeraft diameter
# budget of 100 bounds term growth at ~103).

ROW_DTYPE = np.uint8


def _msg_value_cols(dims: RaftDims):
    """Message-row columns that carry log-entry VALUES (dims.py layout):
    the AEReq entry value at 8 and the RVResp mlog value lanes at
    [6+L, 6+2L) — deduplicated (they overlap at L == 2, where column 8
    is both the AEReq entry value and an mlog value lane)."""
    L = dims.max_log
    return tuple(sorted({8, *range(6 + L, 6 + 2 * L)}))


def state_width(dims: RaftDims) -> int:
    n, L, M, W = (dims.n_servers, dims.max_log, dims.n_msg_slots,
                  dims.msg_width)
    base = n * 7 + 2 * n * L + 2 * n * n + M * W + M
    if dims.value_bytes == 2:
        # High-byte planes for log values [N,L] and the message value
        # columns [M, L+1], appended after the base layout.
        base += n * L + M * len(_msg_value_cols(dims))
    return base


def build_pack_guard(dims: RaftDims):
    """Per-state predicate: every unbounded-growth field still fits the
    uint8 row.  Terms grow via Timeout (raft.tla:146), bag counts via
    DuplicateMessage (:410), and message terms follow sender terms; all
    other fields are bounded by dims by construction.  Engines OR the
    negation into their overflow mask, so wrap-around is a hard error,
    never silent state aliasing."""
    import jax.numpy as jnp

    if dims.value_bytes == 2:
        vcols = jnp.asarray(_msg_value_cols(dims))

        def pack_ok(st: StateBatch):
            return (jnp.all(st.term <= 255)
                    & jnp.all(st.msg_cnt <= 255)
                    & jnp.all(st.msg[:, 3] <= 255)
                    & jnp.all(st.msg[:, 4] <= 127)
                    & jnp.all(st.log_val <= 65535)
                    & jnp.all(st.msg[:, vcols] <= 65535))

        return pack_ok

    def pack_ok(st: StateBatch):
        # Column 4 is sign-extended on decode (mprevLogIndex for AEReq, but
        # mlastLogTerm for RVReq), so values >= 128 there would corrupt to
        # negatives: bound it at 127, unlike the unsigned 255 elsewhere.
        return (jnp.all(st.term <= 255)
                & jnp.all(st.msg_cnt <= 255)
                & jnp.all(st.msg[:, 3] <= 255)
                & jnp.all(st.msg[:, 4] <= 127))

    return pack_ok


def flatten_state(st: StateBatch, dims: RaftDims):
    """StateBatch (single state) -> [state_width] uint8 row.  Works under
    vmap for batches.  Import-free of jax: uses the array namespace of its
    inputs (numpy or jnp).  Under ``dims.value_bytes == 2`` the row ends
    with high-byte planes for the value-carrying lanes (log values, AEReq
    entry value, RVResp mlog values) so variant values up to 65535 —
    reconfiguration entries — survive the uint8 packing."""
    parts = [st.term, st.role, st.voted_for, st.log_term.reshape(-1),
             st.log_val.reshape(-1), st.log_len, st.commit, st.votes_resp,
             st.votes_gran, st.next_idx.reshape(-1),
             st.match_idx.reshape(-1), st.msg.reshape(-1), st.msg_cnt]
    if dims.value_bytes == 2:
        cols = list(_msg_value_cols(dims))
        parts.append((st.log_val.reshape(-1) >> 8))
        parts.append((st.msg[:, cols] >> 8).reshape(-1))
    if isinstance(st.term, np.ndarray):
        return np.concatenate([np.asarray(p, np.int32).reshape(-1)
                               for p in parts]).astype(ROW_DTYPE)
    import jax.numpy as jnp  # jax arrays and tracers
    return jnp.concatenate(parts).astype(jnp.uint8)


def flatten_states(st: StateBatch, dims: RaftDims) -> np.ndarray:
    """A batch of states (``stack_states``: numpy, one leading axis) ->
    ``[n, state_width]`` uint8 rows, each what ``flatten_state`` makes of
    that state, in one concatenation."""
    n = len(st.term)
    parts = list(st)        # the row's order is the fields' own
    if dims.value_bytes == 2:
        parts += [st.log_val >> 8,
                  st.msg[:, :, list(_msg_value_cols(dims))] >> 8]
    return np.concatenate([np.asarray(p, np.int32).reshape(n, -1)
                           for p in parts], axis=1).astype(ROW_DTYPE)


def unflatten_state(row, dims: RaftDims) -> StateBatch:
    """[state_width] uint8 row -> StateBatch (int32 fields).  Works under
    vmap.  Tolerates int32 input rows (pre-packing callers) — the signed
    fix-up below is a no-op for values already < 128, and the value
    high-byte reassembly (value_bytes == 2) is likewise a no-op for rows
    whose high planes are zero."""
    n, L, M, W = (dims.n_servers, dims.max_log, dims.n_msg_slots,
                  dims.msg_width)
    if isinstance(row, np.ndarray):
        import numpy as xp
    else:
        import jax.numpy as xp
    row = row.astype(xp.int32)
    sizes = [n, n, n, n * L, n * L, n, n, n, n, n * n, n * n, M * W, M]
    shapes = [(n,), (n,), (n,), (n, L), (n, L), (n,), (n,), (n,), (n,),
              (n, n), (n, n), (M, W), (M,)]
    out, off = [], 0
    for sz, shp in zip(sizes, shapes):
        out.append(row[off:off + sz].reshape(shp))
        off += sz
    # Sign-extend message payload column 4 (mprevLogIndex — the only field
    # that can be negative; stored two's-complement in the uint8 row).
    msg = out[11]
    col4 = (xp.arange(W) == 4)[None, :]
    msg = xp.where(col4 & (msg >= 128), msg - 256, msg)
    if dims.value_bytes == 2:
        cols = list(_msg_value_cols(dims))
        lv_hi = row[off:off + n * L].reshape((n, L))
        off += n * L
        mv_hi = row[off:off + M * len(cols)].reshape((M, len(cols)))
        # Reassemble value = (low byte of the base lane) + (high plane
        # << 8).  Masking the base lane to its low byte keeps this a
        # no-op for int32 pre-packing rows, whose base lane carries the
        # full value AND whose high plane carries the same bits.
        vmask = np.zeros((W,), bool)
        vmask[cols] = True
        if isinstance(row, np.ndarray):
            full_hi = np.zeros((M, W), np.int32)
            full_hi[:, cols] = mv_hi
        else:
            full_hi = xp.zeros((M, W), xp.int32)
            for k, c in enumerate(cols):
                full_hi = full_hi.at[:, c].set(mv_hi[:, k])
            vmask = xp.asarray(vmask)
        msg = xp.where(vmask[None, :], (msg & 0xFF) + (full_hi << 8), msg)
        out[4] = (out[4] & 0xFF) + (lv_hi << 8)
    out[11] = msg
    return StateBatch(*out)
