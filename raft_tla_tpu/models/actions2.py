"""The v2 (delta) successor pipeline — guards first, construction last.

The first TPU profile of the v1 chunk (2026-07-31, B=2048) showed 85% of the 89 ms/batch in three stages that all
scale with *full candidate-state construction over every B*G lane*:

    expand (36.6 ms)       builds a complete ~473-field successor struct
                           for all 270,336 lanes, ~88% of them masked off;
    compact (+21 ms)       a 270k-lane scatter;
    materialize (+24.6 ms) gathers the full candidate tree for K lanes.

This module restructures the work so the per-lane cost before compaction
is *guards only* (a few dozen scalar ops), and full successors are
constructed for exactly the K compacted lanes:

1. ``masks(state) -> (enabled [G], overflow [G])`` — the action guards of
   models/actions.py with zero state construction.  Bit-identical to v1's
   (enabled, overflow) by construction and by property test.
2. ``parent_hash(state) -> PH`` — the fingerprint's internal sums for one
   parent: the ordered-part sum ``base`` and the commutative bag sum
   ``msum`` per lane, plus the per-slot hashes.  The ops/fingerprint.py
   design (avalanche-then-SUM over positions; ``sum(slot_h * count)`` over
   the bag) makes the hash *incremental*: an action that changes k
   positions shifts ``base`` by k avalanche terms, and every bag edit is a
   ±``slot_h`` adjustment.  u32 modular arithmetic keeps this exact, so v2
   fingerprints are bit-identical to v1's (property-tested).
3. ``lane_out(state, ph, g) -> (hi, lo, successor)`` — for ONE compacted
   lane: the delta fingerprint plus the successor struct, written
   *sparsely* (only the fields family ``g`` touches; untouched leaves pass
   through by reference).

Semantics are transcribed from models/actions.py (same raft.tla citations,
same deliberate bug replications: the AppendEntriesAlreadyDone hidden
guard raft.tla:309+:317, UpdateTerm leaving the message in flight :378,
one-entry truncation :323-324).  Spec variants with ``extra_families``
ride the same pipeline when they implement ``dims.build_extra_v2``
(models/reconfig.py does).  A family declared a :class:`~.dims.LogAppend`
is written through ``lane_out``'s one log-append write, ClientRequest's,
and runs nothing of its own there but its value; a family given as a
general ``lane_fn`` runs on every lane and has its deltas/successors
folded into ``lane_out`` by family id.  Extra-family MASKS come from the
variant's guards-only ``build_extra_masks_v2`` kernels when provided
(one ``pack_ok(parent)`` per parent, no per-lane successors); absent
that, the masks pass falls back to running the variant's full v1 kernels
with ``enabled & ~pack_ok(successor)`` folded, exactly as the v1 chunk
does.  A variant without v2 kernels makes ``build_v2`` raise
:class:`V2Unavailable`, and the engines fall back to the v1 expand path
under ``pipeline="auto"``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.fingerprint import SENTINEL, fmix32
from .dims import (AEQ, AER, CANDIDATE, FOLLOWER, LEADER, NIL, RVQ, RVR,
                   LogAppend, RaftDims)
from .actions import _add1, _sel, _set1, _set2, _setrow
from .schema import StateBatch

_U32 = jnp.uint32
_I32 = jnp.int32


class V2Unavailable(NotImplementedError):
    """This dims variant has no v2 kernels (no/partial ``build_extra_v2``).

    A dedicated type so ``pipeline="auto"`` resolution can fall back to v1
    on exactly this condition — an *accidental* NotImplementedError deep in
    a variant's kernel construction must propagate, not silently select
    the slow path (advisor r4 finding)."""


class ParentHash(NamedTuple):
    """Fingerprint internals of one parent state (both 32-bit lanes)."""

    base0: jnp.ndarray   # [] u32 — ordered-part avalanche sum, lane 0
    base1: jnp.ndarray   # [] u32
    msum0: jnp.ndarray   # [] u32 — commutative bag sum, lane 0
    msum1: jnp.ndarray   # [] u32
    sh0: jnp.ndarray     # [M] u32 — per-slot row hash, lane 0
    sh1: jnp.ndarray     # [M] u32


class V2Pipeline(NamedTuple):
    masks: object        # state -> (enabled [G], overflow [G])
    parent_hash: object  # state -> ParentHash
    parent_fp: object    # ParentHash -> (hi, lo)
    lane_out: object     # (state, ParentHash, g) -> (hi, lo, StateBatch)


def build_v2(dims: RaftDims) -> V2Pipeline:
    N, V, L, M, W = (dims.n_servers, dims.n_values, dims.max_log,
                     dims.n_msg_slots, dims.msg_width)
    # The quorum rule and the variant's families run under scopes of
    # their own, ``quorum`` and ``extra``, wherever ``masks`` and
    # ``lane_out`` use them: nested in the chunk's ``masks`` stage and in
    # ``construct/lane_out`` (engine/chunk.py), so a capture says what a
    # variant's hooks cost there.
    quorum_fn = dims.build_quorum()

    def quorum(st, i, member):
        with jax.named_scope("quorum"):
            return quorum_fn(st, i, member)

    # Fingerprint constants — MUST match ops/fingerprint.py exactly (same
    # fixed seed, same draw order) for bit-identical fingerprints.
    d_ordered = N * (7 + 2 * L) + 2 * N * N
    rng = np.random.RandomState(0x7A57)
    consts = {}
    for lane in (0, 1):
        consts[lane] = (
            jnp.asarray(rng.randint(0, 1 << 32, d_ordered,
                                    dtype=np.uint64).astype(np.uint32) | 1),
            jnp.asarray(rng.randint(0, 1 << 32, W,
                                    dtype=np.uint64).astype(np.uint32) | 1),
            _U32(rng.randint(1, 1 << 32, dtype=np.uint64) | 1),
        )

    # Ordered-part flat offsets (ops/fingerprint.py _flat_ordered order).
    O_TERM = 0
    O_ROLE = N
    O_VOTED = 2 * N
    O_LT = 3 * N
    O_LV = 3 * N + N * L
    O_LL = 3 * N + 2 * N * L
    O_CI = 4 * N + 2 * N * L
    O_VR = 5 * N + 2 * N * L
    O_VG = 6 * N + 2 * N * L
    O_NI = 7 * N + 2 * N * L
    O_MI = 7 * N + 2 * N * L + N * N

    def _u(x):
        return jnp.asarray(x).astype(_U32)

    # -- delta helpers ----------------------------------------------------
    # d* return the (lane0, lane1) u32 base-sum shift for changed ordered
    # positions; old == new contributes 0 automatically (terms cancel).

    def _contrib(pos, val, lane):
        c_ord, _, seed = consts[lane]
        return fmix32(_u(val) * c_ord[pos] + seed)

    def dpos(pos, old, new):
        return tuple(_contrib(pos, new, ln) - _contrib(pos, old, ln)
                     for ln in (0, 1))

    def dvec(start, olds, news, count):
        """Delta for ``count`` consecutive positions from ``start``.

        The window of multipliers comes out of ``c_ord`` by an
        arange-mask select (the idiom of models/actions.py ``_set1``),
        NOT a traced-start ``dynamic_slice``: under ``vmap`` that is a
        gather of ``count``-wide slices, which the TPU compiler runs as
        a sequential loop over the lanes (5 servers: 4 loops x K trips
        a pass).
        """
        sel = (start + jnp.arange(count, dtype=jnp.int32))[:, None] \
            == jnp.arange(d_ordered, dtype=jnp.int32)[None, :]
        out = []
        for ln in (0, 1):
            c_ord, _, seed = consts[ln]
            cs = jnp.sum(jnp.where(sel, c_ord[None, :], _U32(0)), axis=1,
                         dtype=_U32)
            out.append(jnp.sum(fmix32(_u(news) * cs + seed)
                               - fmix32(_u(olds) * cs + seed), dtype=_U32))
        return tuple(out)

    def dsum(*deltas):
        d0 = _U32(0)
        d1 = _U32(0)
        for a, b in deltas:
            d0 = d0 + a
            d1 = d1 + b
        return d0, d1

    ZD = (_U32(0), _U32(0))

    def row_hash(mvec, lane):
        """Per-slot hash of one [W] row — ops/fingerprint.py slot_h."""
        _, c_msg, seed = consts[lane]
        return fmix32(fmix32(jnp.sum(_u(mvec) * c_msg, dtype=_U32) ^ seed)
                      * _U32(0x85EBCA6B) + seed)

    # Delta toolkit handed to spec variants (dims.build_extra_v2) so
    # their extra families can contribute exact fingerprint-sum deltas.
    import types
    fp_helpers = types.SimpleNamespace(
        dpos=dpos, dvec=dvec, dsum=dsum, ZD=ZD, L=L,
        O_TERM=O_TERM, O_ROLE=O_ROLE, O_VOTED=O_VOTED, O_LT=O_LT,
        O_LV=O_LV, O_LL=O_LL, O_CI=O_CI, O_VR=O_VR, O_VG=O_VG,
        O_NI=O_NI, O_MI=O_MI)
    extra_v2 = dims.build_extra_v2(fp_helpers)
    if extra_v2 is None or len(extra_v2) != len(dims.extra_families):
        raise V2Unavailable(
            f"dims {type(dims).__name__} does not provide v2 kernels for "
            "its extra families (build_extra_v2); use the v1 pipeline")
    extra_v1 = dims.build_extra_kernels()
    # The two forms an entry may take (dims.build_extra_v2), each with its
    # family id and the family's parameter arrays: declared appends ride
    # lane_out's own log write, general lane_fns are folded in after it.
    appends = [(10 + e, entry.value_fn, [np.asarray(a) for a in params])
               for e, (entry, (params, _k1)) in enumerate(
                   zip(extra_v2, extra_v1)) if isinstance(entry, LogAppend)]
    generals = [(10 + e, params, entry)
                for e, (entry, (params, _k1)) in enumerate(
                    zip(extra_v2, extra_v1))
                if not isinstance(entry, LogAppend)]
    extra_masks = dims.build_extra_masks_v2()
    if extra_masks is not None and len(extra_masks) != len(extra_v1):
        raise ValueError(
            f"{type(dims).__name__}.build_extra_masks_v2 returned "
            f"{len(extra_masks)} kernels for {len(extra_v1)} extra families")
    from .schema import build_pack_guard
    pack_ok_fn = build_pack_guard(dims)

    def finalize(base, msum, lane):
        seed = consts[lane][2]
        return fmix32(base + fmix32(msum + seed) * _U32(0x9E3779B9))

    def parent_hash(st: StateBatch) -> ParentHash:
        parts = [st.term, st.role, st.voted_for, st.log_term.reshape(-1),
                 st.log_val.reshape(-1), st.log_len, st.commit,
                 st.votes_resp, st.votes_gran, st.next_idx.reshape(-1),
                 st.match_idx.reshape(-1)]
        flat = jnp.concatenate([p.astype(_I32) for p in parts]).view(_U32)
        occupied = st.msg_cnt > 0
        out = {}
        for ln in (0, 1):
            c_ord, c_msg, seed = consts[ln]
            base = jnp.sum(fmix32(flat * c_ord + seed), dtype=_U32)
            rows = st.msg.view(_U32) if st.msg.dtype != jnp.uint32 else st.msg
            sh = fmix32(fmix32(jnp.sum(rows * c_msg[None, :], axis=1,
                                       dtype=_U32) ^ seed)
                        * _U32(0x85EBCA6B) + seed)
            msum = jnp.sum(jnp.where(occupied,
                                     sh * st.msg_cnt.astype(_U32), _U32(0)),
                           dtype=_U32)
            out[ln] = (base, msum, sh)
        return ParentHash(base0=out[0][0], base1=out[1][0],
                          msum0=out[0][1], msum1=out[1][1],
                          sh0=out[0][2], sh1=out[1][2])

    def parent_fp(ph: ParentHash):
        hi = finalize(ph.base0, ph.msum0, 0)
        lo = finalize(ph.base1, ph.msum1, 1)
        is_sent = (hi == SENTINEL) & (lo == SENTINEL)
        return hi, jnp.where(is_sent, _U32(0xFFFFFFFE), lo)

    # -- shared guard/value helpers (mirroring actions.py) ----------------
    def last_term(st, i):
        ln = st.log_len[i]
        return jnp.where(ln > 0, st.log_term[i, jnp.clip(ln - 1, 0, L - 1)],
                         0)

    def base_msg(mtype, src, dst, mterm):
        m = jnp.zeros((W,), _I32)
        return m.at[0].set(mtype + 1).at[1].set(src + 1).at[2].set(dst + 1) \
                .at[3].set(mterm)

    def send_ctx(st, mvec, skip_slot=None, skip_gate=None):
        """Slot resolution for Send(mvec) — raft.tla:95 via actions.py
        bag_send — optionally on the post-Discard view of the bag
        (``skip_slot``/``skip_gate`` model Reply's atomic discard+send,
        raft.tla:102-103).  Returns a dict: ok, overflow-of-packing,
        target index, eq flag, count after, and the msum delta."""
        cnt = st.msg_cnt
        if skip_slot is not None:
            dec = jnp.where(skip_gate, 1, 0)
            cnt = _add1(cnt, skip_slot, -dec)
        # Rows are unchanged by a discard except the zeroed empty row,
        # which can never equal mvec (mvec[0] = mtype+1 > 0): gating eq on
        # cnt > 0 reproduces the post-discard comparison exactly.
        eq = jnp.all(st.msg == mvec[None, :], axis=1) & (cnt > 0)
        has_eq = jnp.any(eq)
        free = cnt == 0
        ok = has_eq | jnp.any(free)
        idx = jnp.where(has_eq, jnp.argmax(eq), jnp.argmax(free))
        new_cnt = cnt[idx] + 1          # 0 + 1 on a free slot
        # pack guard (schema.build_pack_guard): successor msg_cnt <= 255.
        pack_bad = ok & (new_cnt > 255)
        return {"ok": ok, "idx": idx, "has_eq": has_eq, "new_cnt": new_cnt,
                "pack_bad": pack_bad, "cnt_view": cnt}

    def send_dmsum(st, ph, ctx, mvec):
        """±slot_h contribution of Send: +h(existing row) when the count
        increments, +h(mvec) when a free slot is claimed."""
        out = []
        for ln, sh in ((0, ph.sh0), (1, ph.sh1)):
            fresh = row_hash(mvec, ln)
            out.append(jnp.where(ctx["has_eq"], sh[ctx["idx"]], fresh))
        return tuple(out)

    def discard_dmsum(ph, s):
        return (-ph.sh0[s], -ph.sh1[s])

    def apply_send(msg, cnt, ctx, mvec):
        """bag_send's writes on (msg, cnt) — actions.py:89-100 exactly
        (row kept when eq or not-ok; count +1 only when ok)."""
        idx, has_eq, ok = ctx["idx"], ctx["has_eq"], ctx["ok"]
        row = jnp.where(has_eq | ~ok, msg[idx], mvec)
        return (_setrow(msg, idx, row),
                _add1(cnt, idx, jnp.where(ok, 1, 0)))

    def apply_discard(msg, cnt, s):
        """bag_discard_slot — actions.py:102-107 (zero the row at 0)."""
        new_cnt = _add1(cnt, s, -1)
        row = jnp.where(new_cnt[s] > 0, msg[s], jnp.zeros((W,), _I32))
        return _setrow(msg, s, row), new_cnt

    # -- receive context (guards + derived values, no construction) -------
    def receive_ctx(st, s):
        """Everything Receive(m@slot s) needs — raft.tla:388-403 dispatch
        exactly as actions.py receive(), but split from state writes so
        the masks pass pays for guards only (XLA DCE drops the unused
        outputs there)."""
        mvec = st.msg[s]
        occ = st.msg_cnt[s] > 0
        mtype = mvec[0] - 1
        j = jnp.clip(mvec[1] - 1, 0, N - 1)
        i = jnp.clip(mvec[2] - 1, 0, N - 1)
        mterm = mvec[3]
        t_i = st.term[i]
        role_i = st.role[i]
        ln = st.log_len[i]

        en_ut = occ & (mterm > t_i)
        le = occ & (mterm <= t_i)

        # HandleRequestVoteRequest — raft.tla:244-263.
        lt = last_term(st, i)
        rvq_logok = (mvec[4] > lt) | ((mvec[4] == lt) & (mvec[5] >= ln))
        grant = (mterm == t_i) & rvq_logok & \
            ((st.voted_for[i] == NIL) | (st.voted_for[i] == j + 1))
        rvr_resp = base_msg(RVR, i, j, t_i) \
            .at[4].set(grant.astype(_I32)).at[5].set(ln)
        rvr_resp = jax.lax.dynamic_update_slice(rvr_resp, st.log_term[i],
                                                (6,))
        rvr_resp = jax.lax.dynamic_update_slice(rvr_resp, st.log_val[i],
                                                (6 + L,))
        en_rvq = le & (mtype == RVQ)
        rvq_send = send_ctx(st, rvr_resp, skip_slot=s,
                            skip_gate=st.msg_cnt[s] == 1)

        en_rvr_drop = le & (mtype == RVR) & (mterm < t_i)
        en_rvr = le & (mtype == RVR) & (mterm == t_i)

        # AppendEntriesRequest — raft.tla:347-356.
        prev, pterm, n_ent = mvec[4], mvec[5], mvec[6]
        eterm, eval_, mcommit = mvec[7], mvec[8], mvec[9]
        aeq_logok = (prev == 0) | \
            ((prev > 0) & (prev <= ln)
             & (pterm == st.log_term[i, jnp.clip(prev - 1, 0, L - 1)]))
        en_aeq = le & (mtype == AEQ)
        en_rej = en_aeq & ((mterm < t_i)
                           | ((mterm == t_i) & (role_i == FOLLOWER)
                              & ~aeq_logok))
        rej_resp = base_msg(AER, i, j, t_i)
        rej_send = send_ctx(st, rej_resp, skip_slot=s,
                            skip_gate=st.msg_cnt[s] == 1)
        en_rtf = en_aeq & (mterm == t_i) & (role_i == CANDIDATE)
        acc = en_aeq & (mterm == t_i) & (role_i == FOLLOWER) & aeq_logok
        index = prev + 1
        have_at = ln >= index
        term_at = st.log_term[i, jnp.clip(index - 1, 0, L - 1)]
        done_shape = (n_ent == 0) | (have_at & (term_at == eterm))
        en_done = acc & done_shape & (mcommit == st.commit[i])   # :317 bug
        done_resp = base_msg(AER, i, j, t_i) \
            .at[4].set(1).at[5].set(prev + n_ent)
        done_send = send_ctx(st, done_resp, skip_slot=s,
                             skip_gate=st.msg_cnt[s] == 1)
        en_conf = acc & (n_ent > 0) & have_at & (term_at != eterm)
        fits = ln < L
        en_noc = acc & (n_ent > 0) & (ln == prev)

        en_aer_drop = le & (mtype == AER) & (mterm < t_i)
        en_aer = le & (mtype == AER) & (mterm == t_i)

        overflow = (en_rvq & ~rvq_send["ok"]) | (en_rej & ~rej_send["ok"]) \
            | (en_done & ~done_send["ok"]) | (en_noc & ~fits)
        enabled = (en_ut | en_rvq | en_rvr_drop | en_rvr | en_rej | en_rtf
                   | en_done | en_conf | en_noc | en_aer_drop | en_aer) \
            & ~overflow
        # pack guard on the reply's count bump (chunk-level pack_ok in v1).
        pack_bad = (en_rvq & rvq_send["pack_bad"]) \
            | (en_rej & rej_send["pack_bad"]) \
            | (en_done & done_send["pack_bad"])
        return dict(
            mvec=mvec, i=i, j=j, mterm=mterm, t_i=t_i, ln=ln,
            grant=grant, rvr_resp=rvr_resp, rej_resp=rej_resp,
            done_resp=done_resp, rvq_send=rvq_send, rej_send=rej_send,
            done_send=done_send, prev=prev, n_ent=n_ent, eterm=eterm,
            eval_=eval_, mcommit=mcommit,
            en_ut=en_ut, en_rvq=en_rvq, en_rvr_drop=en_rvr_drop,
            en_rvr=en_rvr, en_rej=en_rej, en_rtf=en_rtf, en_done=en_done,
            en_conf=en_conf, en_noc=en_noc, en_aer_drop=en_aer_drop,
            en_aer=en_aer, enabled=enabled, overflow=overflow,
            pack_bad=pack_bad)

    # -- per-family guards (masks pass) -----------------------------------
    def masks(st: StateBatch):
        """(enabled [G], overflow [G]) — v1 expand's masks, with the
        chunk-level pack guard folded in as extra *overflow* bits exactly
        where v1's ``en & ~pack_ok(cand)`` would fire (enabled stays
        true for pack violations, as in engine/chunk.py:66-67)."""
        en_parts, ovf_parts = [], []
        # Restart — always enabled.
        en_parts.append(jnp.ones((N,), bool))
        ovf_parts.append(jnp.zeros((N,), bool))
        # Timeout — role check + term pack guard.
        roleF = st.role == FOLLOWER
        roleC = st.role == CANDIDATE
        en_t = roleF | roleC
        en_parts.append(en_t)
        ovf_parts.append(en_t & (st.term + 1 > 255))
        # RequestVote(i, j) — candidate, j not yet responded; send ok;
        # pack guard on col4 (mlastLogTerm > 127 breaks the signed row
        # packing) and on the eq-slot count bump.
        lt_all = jax.vmap(lambda i: last_term(st, i))(
            jnp.arange(N, dtype=_I32))
        def rv_one(i, j):
            en = (st.role[i] == CANDIDATE) \
                & (((st.votes_resp[i] >> j) & 1) == 0)
            m = base_msg(RVQ, i, j, st.term[i]) \
                .at[4].set(lt_all[i]).at[5].set(st.log_len[i])
            ctx = send_ctx(st, m)
            pack = ctx["pack_bad"] | (lt_all[i] > 127)
            return en & ctx["ok"], (en & ~ctx["ok"]) | (en & ctx["ok"] & pack)
        ii = jnp.repeat(jnp.arange(N, dtype=_I32), N)
        jj = jnp.tile(jnp.arange(N, dtype=_I32), N)
        en_rv, ovf_rv = jax.vmap(rv_one)(ii, jj)
        en_parts.append(en_rv)
        ovf_parts.append(ovf_rv)
        # BecomeLeader.
        def bl_one(i):
            member = ((st.votes_gran[i] >> jnp.arange(N, dtype=_I32)) & 1) > 0
            return (st.role[i] == CANDIDATE) & quorum(st, i, member)
        en_bl = jax.vmap(bl_one)(jnp.arange(N, dtype=_I32))
        en_parts.append(en_bl)
        ovf_parts.append(jnp.zeros((N,), bool))
        # ClientRequest(i, v).
        isL = st.role == LEADER
        fits = st.log_len < L
        en_cr = jnp.repeat(isL & fits, V)
        ovf_cr = jnp.repeat(isL & ~fits, V)
        en_parts.append(en_cr)
        ovf_parts.append(ovf_cr)
        # AdvanceCommitIndex.
        en_parts.append(isL)
        ovf_parts.append(jnp.zeros((N,), bool))
        # AppendEntries(i, j).
        def ae_one(i, j):
            en = (i != j) & (st.role[i] == LEADER)
            ln = st.log_len[i]
            ni = st.next_idx[i, j]
            prev = ni - 1
            prev_term = jnp.where(
                (prev > 0) & (prev <= ln),
                st.log_term[i, jnp.clip(prev - 1, 0, L - 1)], 0)
            last_entry = jnp.minimum(ln, ni)
            n_ent = (ln >= ni).astype(_I32)
            eterm = jnp.where(n_ent > 0,
                              st.log_term[i, jnp.clip(ni - 1, 0, L - 1)], 0)
            eval_ = jnp.where(n_ent > 0,
                              st.log_val[i, jnp.clip(ni - 1, 0, L - 1)], 0)
            m = base_msg(AEQ, i, j, st.term[i]) \
                .at[4].set(prev).at[5].set(prev_term).at[6].set(n_ent) \
                .at[7].set(eterm).at[8].set(eval_) \
                .at[9].set(jnp.minimum(st.commit[i], last_entry))
            ctx = send_ctx(st, m)
            return en & ctx["ok"], \
                (en & ~ctx["ok"]) | (en & ctx["ok"] & ctx["pack_bad"])
        en_ae, ovf_ae = jax.vmap(ae_one)(ii, jj)
        en_parts.append(en_ae)
        ovf_parts.append(ovf_ae)
        # Receive(slot).
        def rc_one(s):
            c = receive_ctx(st, s)
            return c["enabled"], c["overflow"] | c["pack_bad"]
        en_rc, ovf_rc = jax.vmap(rc_one)(jnp.arange(M, dtype=_I32))
        en_parts.append(en_rc)
        ovf_parts.append(ovf_rc)
        # Duplicate / Drop — occupancy; dup has the count pack guard.
        occ = st.msg_cnt > 0
        en_parts.append(occ)
        ovf_parts.append(occ & (st.msg_cnt + 1 > 255))
        en_parts.append(occ)
        ovf_parts.append(jnp.zeros((M,), bool))
        # Extra families: guards-only mask kernels when the variant
        # provides them (dims.build_extra_masks_v2 — one pack_ok over the
        # PARENT, no per-lane successor construction, preserving the
        # guards-only design of this pass); otherwise fall back to the
        # variant's full v1 kernels with the pack guard folded on their
        # successors exactly as the v1 chunk does (engine/chunk.py:
        # ovf |= en & ~pack_ok) — enforced generically so a future
        # variant whose extras touch a packed-bound field cannot
        # silently diverge between pipelines.
        with jax.named_scope("extra"):
            if extra_masks is not None and extra_v1:
                pk_parent = pack_ok_fn(st)
                for (params, _kern), mask_fn in zip(extra_v1, extra_masks):
                    in_axes = (None, None) + (0,) * len(params)
                    en_e, ovf_e = jax.vmap(mask_fn, in_axes)(
                        st, pk_parent, *params)
                    en_parts.append(en_e)
                    ovf_parts.append(ovf_e)
            else:
                for params, kern in extra_v1:
                    in_axes = (None,) + (0,) * len(params)
                    en_e, ovf_e, succ_e = jax.vmap(kern, in_axes)(
                        st, *params)
                    pk_e = jax.vmap(pack_ok_fn)(succ_e)
                    en_parts.append(en_e)
                    ovf_parts.append(ovf_e | (en_e & ~pk_e))
        return jnp.concatenate(en_parts), jnp.concatenate(ovf_parts)

    # -- per-lane delta fingerprint + sparse successor --------------------
    # Static grid decode tables.
    offs = dims.family_offsets
    sizes = dims.family_sizes
    G = dims.n_instances
    fam_np = np.zeros(G, np.int32)
    p1_np = np.zeros(G, np.int32)   # i (server) or slot
    p2_np = np.zeros(G, np.int32)   # j, or value, or unused
    for fam, (off, size) in enumerate(zip(offs, sizes)):
        for k in range(size):
            g = off + k
            fam_np[g] = fam
            if fam in (0, 1, 3, 5):            # i-indexed families
                p1_np[g] = k
            elif fam in (2, 6):                # (i, j)
                p1_np[g], p2_np[g] = k // N, k % N
            elif fam == 4:                     # (i, v)
                p1_np[g], p2_np[g] = k // V, k % V + 1
            else:                              # slot families
                p1_np[g] = k
    # A declared append's instances decode to the family's own
    # parameters, (i) or (i, one more), as ClientRequest's do to (i, v).
    for fam, _value_fn, params in appends:
        if not 1 <= len(params) <= 2:
            raise ValueError(
                f"{dims.family_names[fam]}: a LogAppend family takes "
                f"(i) or (i, one more), not {len(params)} parameters")
        grid = slice(offs[fam], offs[fam] + sizes[fam])
        p1_np[grid] = params[0]
        if len(params) == 2:
            p2_np[grid] = params[1]
    fam_t = jnp.asarray(fam_np)
    p1_t = jnp.asarray(p1_np)
    p2_t = jnp.asarray(p2_np)

    def lane_out(st: StateBatch, ph: ParentHash, g):
        """Delta fingerprint + sparse successor for grid instance ``g`` of
        parent ``st``.  Only meaningful when lane ``g`` is enabled; on
        disabled lanes the outputs are arbitrary finite values (the chunk
        masks them with kvalid, as v1 masks its gathered garbage)."""
        fam = fam_t[g]
        i = p1_t[g]
        jv = p2_t[g]
        s = p1_t[g]          # slot for Receive/Duplicate/Drop lanes

        rc = receive_ctx(st, s)

        is_restart = fam == 0
        is_timeout = fam == 1
        is_rv = fam == 2
        is_bl = fam == 3
        is_cr = fam == 4
        is_ac = fam == 5
        is_ae = fam == 6
        is_recv = fam == 7
        is_dup = fam == 8
        is_drop = fam == 9

        # ---- scalar successor values per touched field ----
        term_i = st.term[i]
        role_i = st.role[i]
        ln_i = st.log_len[i]

        # Receive destination server (may differ from the grid's i).
        ri = rc["i"]
        rj = rc["j"]

        # term: Timeout(+1) on i; UpdateTerm(mterm) on ri.
        ut_fire = is_recv & rc["en_ut"]
        term_tgt = jnp.where(is_timeout, i, ri)
        term_new = jnp.where(is_timeout, term_i + 1, rc["mterm"])
        term_wr = is_timeout | ut_fire

        # role.
        role_tgt = jnp.where(is_recv, ri, i)
        role_new = jnp.where(
            is_restart, FOLLOWER,
            jnp.where(is_timeout, CANDIDATE,
                      jnp.where(is_bl, LEADER,
                                jnp.where(ut_fire, FOLLOWER, FOLLOWER))))
        role_wr = is_restart | is_timeout | is_bl \
            | (is_recv & (rc["en_ut"] | rc["en_rtf"]))

        # votedFor: Timeout -> NIL; UpdateTerm -> NIL; RVQ grant -> j+1.
        grant_fire = is_recv & rc["en_rvq"] & rc["grant"]
        voted_tgt = jnp.where(is_timeout, i, ri)
        voted_new = jnp.where(grant_fire, rj + 1, NIL)
        voted_wr = is_timeout | ut_fire | grant_fire

        # log cell + length: ClientRequest append / Conflict truncate /
        # NoConflict append.
        cr_k = jnp.clip(ln_i, 0, L - 1)
        conf_k = jnp.clip(rc["ln"] - 1, 0, L - 1)
        noc_k = jnp.clip(rc["ln"], 0, L - 1)
        conf_fire = is_recv & rc["en_conf"]
        noc_fire = is_recv & rc["en_noc"]
        # A variant's declared appends (dims.LogAppend) are this same
        # write with another value: ``is_app`` and ``app_v`` take them
        # in, and everything below covers them with ClientRequest.
        is_app, app_v = is_cr, jv
        with jax.named_scope("extra"):
            for fam_e, value_fn, params in appends:
                is_e = fam == fam_e
                app_v = jnp.where(
                    is_e, value_fn(st, i, *(jv,) * (len(params) - 1)), app_v)
                is_app = is_app | is_e
        log_tgt_i = jnp.where(is_app, i, ri)
        log_k = jnp.where(is_app, cr_k, jnp.where(conf_fire, conf_k, noc_k))
        log_t_new = jnp.where(is_app, term_i,
                              jnp.where(conf_fire, 0, rc["eterm"]))
        log_v_new = jnp.where(is_app, app_v,
                              jnp.where(conf_fire, 0, rc["eval_"]))
        ll_new = jnp.where(conf_fire, rc["ln"] - 1,
                           jnp.where(is_app, ln_i + 1, rc["ln"] + 1))
        log_wr = is_app | conf_fire | noc_fire

        # commit: Restart -> 0; AdvanceCommitIndex -> rule; Done -> mcommit.
        idxs = jnp.arange(1, L + 1, dtype=_I32)
        member = ((st.match_idx[i][None, :] >= idxs[:, None])
                  | (jnp.arange(N)[None, :] == i))
        agree_ok = jax.vmap(lambda mem: quorum(st, i, mem))(member) \
            & (idxs <= ln_i)
        any_ok = jnp.any(agree_ok)
        max_agree = jnp.max(jnp.where(agree_ok, idxs, 0))
        own_term = st.log_term[i, jnp.clip(max_agree - 1, 0, L - 1)] \
            == term_i
        ac_commit = jnp.where(any_ok & own_term, max_agree, st.commit[i])
        done_fire = is_recv & rc["en_done"]
        commit_tgt = jnp.where(is_recv, ri, i)
        commit_new = jnp.where(is_restart, 0,
                               jnp.where(is_ac, ac_commit, rc["mcommit"]))
        commit_wr = is_restart | is_ac | done_fire

        # vote sets: Restart/Timeout clear; HandleRVR accumulates.
        rvr_fire = is_recv & rc["en_rvr"]
        granted_bit = jnp.where(rc["mvec"][4] > 0, 1, 0) << rj
        vr_tgt = jnp.where(is_recv, ri, i)
        vr_new = jnp.where(rvr_fire, st.votes_resp[ri] | (1 << rj), 0)
        vg_new = jnp.where(rvr_fire, st.votes_gran[ri] | granted_bit, 0)
        votes_wr = is_restart | is_timeout | rvr_fire

        # nextIndex/matchIndex rows: Restart/BecomeLeader; cell: AER.
        ni_row_new = jnp.where(is_restart,
                               jnp.ones((N,), _I32),
                               jnp.broadcast_to(ln_i + 1, (N,)).astype(_I32))
        mi_row_new = jnp.zeros((N,), _I32)
        rows_wr = is_restart | is_bl
        aer_fire = is_recv & rc["en_aer"]
        succ_flag = rc["mvec"][4] > 0
        mmatch = rc["mvec"][5]
        ni_cell_new = jnp.where(succ_flag, mmatch + 1,
                                jnp.maximum(st.next_idx[ri, rj] - 1, 1))
        mi_cell_new = jnp.where(succ_flag, mmatch, st.match_idx[ri, rj])

        # ---- bag edits ----
        # Sends (RequestVote / AppendEntries) rebuild the same mvec the
        # masks pass used; receive replies use rc's resp rows + ctxs.
        rv_m = base_msg(RVQ, i, jv, term_i) \
            .at[4].set(last_term(st, i)).at[5].set(ln_i)
        ni_ij = st.next_idx[i, jv]
        ae_prev = ni_ij - 1
        ae_pterm = jnp.where(
            (ae_prev > 0) & (ae_prev <= ln_i),
            st.log_term[i, jnp.clip(ae_prev - 1, 0, L - 1)], 0)
        ae_nent = (ln_i >= ni_ij).astype(_I32)
        ae_m = base_msg(AEQ, i, jv, term_i) \
            .at[4].set(ae_prev).at[5].set(ae_pterm).at[6].set(ae_nent) \
            .at[7].set(jnp.where(ae_nent > 0,
                                 st.log_term[i, jnp.clip(ni_ij - 1, 0,
                                                         L - 1)], 0)) \
            .at[8].set(jnp.where(ae_nent > 0,
                                 st.log_val[i, jnp.clip(ni_ij - 1, 0,
                                                        L - 1)], 0)) \
            .at[9].set(jnp.minimum(st.commit[i], jnp.minimum(ln_i, ni_ij)))

        rvq_fire = is_recv & rc["en_rvq"]
        rej_fire = is_recv & rc["en_rej"]
        reply_fire = rvq_fire | rej_fire | done_fire
        disc_only = is_recv & (rc["en_rvr_drop"] | rc["en_rvr"]
                               | rc["en_aer_drop"] | rc["en_aer"])
        do_discard = reply_fire | disc_only | is_drop
        do_send = is_rv | is_ae | reply_fire

        send_row = jnp.where(
            is_rv, rv_m,
            jnp.where(is_ae, ae_m,
                      jnp.where(rvq_fire, rc["rvr_resp"],
                                jnp.where(rej_fire, rc["rej_resp"],
                                          rc["done_resp"]))))
        plain_ctx = send_ctx(st, send_row)
        reply_ctx = {
            k: jnp.where(
                rvq_fire, rc["rvq_send"][k],
                jnp.where(rej_fire, rc["rej_send"][k],
                          rc["done_send"][k]))
            for k in ("ok", "idx", "has_eq", "new_cnt", "pack_bad",
                      "cnt_view")}
        sctx = {k: jnp.where(reply_fire, reply_ctx[k], plain_ctx[k])
                for k in reply_ctx}

        # ---- delta fingerprint ----
        d_term = dpos(O_TERM + term_tgt, st.term[term_tgt],
                      jnp.where(term_wr, term_new, st.term[term_tgt]))
        d_role = dpos(O_ROLE + role_tgt, st.role[role_tgt],
                      jnp.where(role_wr, role_new, st.role[role_tgt]))
        d_voted = dpos(O_VOTED + voted_tgt, st.voted_for[voted_tgt],
                       jnp.where(voted_wr, voted_new,
                                 st.voted_for[voted_tgt]))
        lt_pos = O_LT + log_tgt_i * L + log_k
        lv_pos = O_LV + log_tgt_i * L + log_k
        ll_pos = O_LL + log_tgt_i
        old_lt = st.log_term[log_tgt_i, log_k]
        old_lv = st.log_val[log_tgt_i, log_k]
        old_ll = st.log_len[log_tgt_i]
        d_lt = dpos(lt_pos, old_lt, jnp.where(log_wr, log_t_new, old_lt))
        d_lv = dpos(lv_pos, old_lv, jnp.where(log_wr, log_v_new, old_lv))
        d_ll = dpos(ll_pos, old_ll, jnp.where(log_wr, ll_new, old_ll))
        d_ci = dpos(O_CI + commit_tgt, st.commit[commit_tgt],
                    jnp.where(commit_wr, commit_new,
                              st.commit[commit_tgt]))
        d_vr = dpos(O_VR + vr_tgt, st.votes_resp[vr_tgt],
                    jnp.where(votes_wr, vr_new, st.votes_resp[vr_tgt]))
        d_vg = dpos(O_VG + vr_tgt, st.votes_gran[vr_tgt],
                    jnp.where(votes_wr, vg_new, st.votes_gran[vr_tgt]))
        old_ni_row = st.next_idx[i]
        old_mi_row = st.match_idx[i]
        d_ni_row = dvec(O_NI + i * N, old_ni_row,
                        jnp.where(rows_wr, ni_row_new, old_ni_row), N)
        d_mi_row = dvec(O_MI + i * N, old_mi_row,
                        jnp.where(rows_wr, mi_row_new, old_mi_row), N)
        ni_cell_pos = O_NI + ri * N + rj
        mi_cell_pos = O_MI + ri * N + rj
        old_ni_c = st.next_idx[ri, rj]
        old_mi_c = st.match_idx[ri, rj]
        d_ni_c = dpos(ni_cell_pos, old_ni_c,
                      jnp.where(aer_fire, ni_cell_new, old_ni_c))
        d_mi_c = dpos(mi_cell_pos, old_mi_c,
                      jnp.where(aer_fire, mi_cell_new, old_mi_c))
        d_base = dsum(d_term, d_role, d_voted, d_lt, d_lv, d_ll, d_ci,
                      d_vr, d_vg, d_ni_row, d_mi_row, d_ni_c, d_mi_c)

        d_disc = discard_dmsum(ph, s)
        d_send = send_dmsum(st, ph, sctx, send_row)
        d_dup = (ph.sh0[s], ph.sh1[s])
        # Drop's -slot_h rides the do_discard term; Duplicate adds +slot_h.
        dm0 = jnp.where(do_discard, d_disc[0], _U32(0)) \
            + jnp.where(do_send & sctx["ok"], d_send[0], _U32(0)) \
            + jnp.where(is_dup, d_dup[0], _U32(0))
        dm1 = jnp.where(do_discard, d_disc[1], _U32(0)) \
            + jnp.where(do_send & sctx["ok"], d_send[1], _U32(0)) \
            + jnp.where(is_dup, d_dup[1], _U32(0))

        # General extra-family lanes (an entry that is a ``lane_fn``): on
        # base-family lanes every *_wr gate above is False, so the base
        # deltas are zero and the base successor is the parent — fold the
        # variant kernels' deltas/successors in by family id.  A declared
        # append has nothing to do here: the log write above was its.
        db0, db1 = d_base
        extra_folds = []
        with jax.named_scope("extra"):
            for fam_e, params_e, lane_fn in generals:
                is_e = fam == fam_e
                off_e, size_e = offs[fam_e], sizes[fam_e]
                local = jnp.clip(g - off_e, 0, size_e - 1)
                pe = tuple(arr[local] for arr in params_e)
                dbe, dme, succ_e = lane_fn(st, *pe)
                db0 = db0 + jnp.where(is_e, dbe[0], _U32(0))
                db1 = db1 + jnp.where(is_e, dbe[1], _U32(0))
                dm0 = dm0 + jnp.where(is_e, dme[0], _U32(0))
                dm1 = dm1 + jnp.where(is_e, dme[1], _U32(0))
                extra_folds.append((is_e, succ_e))
        d_base = (db0, db1)

        hi = finalize(ph.base0 + d_base[0], ph.msum0 + dm0, 0)
        lo = finalize(ph.base1 + d_base[1], ph.msum1 + dm1, 1)
        is_sent = (hi == SENTINEL) & (lo == SENTINEL)
        lo = jnp.where(is_sent, _U32(0xFFFFFFFE), lo)

        # ---- sparse successor construction ----
        term_o = jnp.where(term_wr,
                           _set1(st.term, term_tgt, term_new), st.term)
        role_o = jnp.where(role_wr,
                           _set1(st.role, role_tgt, role_new), st.role)
        voted_o = jnp.where(voted_wr,
                            _set1(st.voted_for, voted_tgt, voted_new),
                            st.voted_for)
        lt_o = jnp.where(log_wr,
                         _set2(st.log_term, log_tgt_i, log_k, log_t_new),
                         st.log_term)
        lv_o = jnp.where(log_wr,
                         _set2(st.log_val, log_tgt_i, log_k, log_v_new),
                         st.log_val)
        ll_o = jnp.where(log_wr, _set1(st.log_len, log_tgt_i, ll_new),
                         st.log_len)
        ci_o = jnp.where(commit_wr,
                         _set1(st.commit, commit_tgt, commit_new),
                         st.commit)
        vr_o = jnp.where(votes_wr, _set1(st.votes_resp, vr_tgt, vr_new),
                         st.votes_resp)
        vg_o = jnp.where(votes_wr, _set1(st.votes_gran, vr_tgt, vg_new),
                         st.votes_gran)
        ni_o = jnp.where(rows_wr, _setrow(st.next_idx, i, ni_row_new),
                         jnp.where(aer_fire,
                                   _set2(st.next_idx, ri, rj, ni_cell_new),
                                   st.next_idx))
        mi_o = jnp.where(rows_wr, _setrow(st.match_idx, i, mi_row_new),
                         jnp.where(aer_fire,
                                   _set2(st.match_idx, ri, rj, mi_cell_new),
                                   st.match_idx))

        msg_o, cnt_o = st.msg, st.msg_cnt
        d_msg, d_cnt = apply_discard(msg_o, cnt_o, s)
        msg_o = jnp.where(do_discard, d_msg, msg_o)
        cnt_o = jnp.where(do_discard, d_cnt, cnt_o)
        s_msg, s_cnt = apply_send(msg_o, cnt_o, sctx, send_row)
        msg_o = jnp.where(do_send, s_msg, msg_o)
        cnt_o = jnp.where(do_send, s_cnt, cnt_o)
        cnt_o = jnp.where(is_dup, _add1(cnt_o, s, 1), cnt_o)

        succ = StateBatch(term=term_o, role=role_o, voted_for=voted_o,
                          log_term=lt_o, log_val=lv_o, log_len=ll_o,
                          commit=ci_o, votes_resp=vr_o, votes_gran=vg_o,
                          next_idx=ni_o, match_idx=mi_o,
                          msg=msg_o, msg_cnt=cnt_o)
        with jax.named_scope("extra"):
            for is_e, succ_e in extra_folds:
                succ = _sel(is_e, succ_e, succ)
        return hi, lo, succ

    return V2Pipeline(masks=masks, parent_hash=parent_hash,
                      parent_fp=parent_fp, lane_out=lane_out)
