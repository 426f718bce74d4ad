"""Joint-consensus membership reconfiguration — the configs[4] spec variant.

The reference spec models a *fixed* membership (``Server`` is constant —
/root/reference/raft.tla:11, and the changelog note at raft.tla:1188-1190
says membership changes were removed from the dissertation spec).  The
BASELINE.json target list nonetheless names "Raft + joint-consensus
reconfiguration (dynamic membership) state space" as a checking
configuration, so this module extends the transition system with the Raft
paper's joint-consensus (C_old,new) scheme, the way a TLA+ author would
extend the module — new log-entry kind + two new actions — while every
existing action stays textually untouched (they dispatch through the
``RaftDims`` variant hooks).

Modeling rules (standard joint consensus):

- **Configurations ride in the log.**  A config entry's value encodes one
  or two membership bitmasks: ``CFG_BASE + (old << 8) + new`` is the joint
  configuration C_old,new, and ``CFG_BASE + new`` (old bits zero) is a
  final configuration C_new.  Client values 1..V are untouched, so config
  entries replicate, conflict, and truncate through ``AppendEntries``
  exactly like any other entry — no new message machinery.
- **A server uses the latest configuration in its log** (committed or not;
  the Raft rule), falling back to the initial full membership when its log
  has none.  Truncation by ``ConflictAppendEntriesRequest`` reverts it.
- **Quorums**: under a joint configuration, elections and commitment both
  require a majority of C_old *and* a majority of C_new; under a final
  configuration, a majority of that configuration.  This replaces the
  simple-majority ``Quorum`` (raft.tla:79-81) via ``build_quorum``/
  ``quorum_py``.
- **InitiateReconfig(i, c)**: a leader whose current configuration is
  final (no change in progress — the one-at-a-time rule) appends the joint
  entry C_current,c for a target configuration ``c != current``.
- **FinalizeReconfig(i)**: a leader whose current configuration is the
  joint C_old,new *and whose commitIndex has reached that entry* appends
  the final entry C_new.
- Deliberately permissive (like the base spec): servers outside the
  current configuration still time out, campaign, and vote — their votes
  simply only count toward quorums of configurations that include them;
  a leader excluded by C_new keeps acting until some other action (e.g.
  a higher term) displaces it.  Allowed target configurations are the
  model constant ``TargetConfigs`` (a finite set of bitmasks), the
  analogue of binding ``Server``/``Value`` in MCraft.tla:15-21.

The state schema, fingerprints, and engines are unchanged: a
``ReconfigDims`` is a ``RaftDims`` whose hooks widen the action grid, the
quorum rule, and the TypeOK value domain.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from .dims import LEADER, LogAppend, RaftDims

# Log-entry values >= CFG_BASE are configuration entries; below are client
# values.  Layout: CFG_BASE + (old_mask << 8) + new_mask, old_mask == 0
# meaning a final (non-joint) configuration.  Masks fit 7 bits (N <= 7,
# enforced by ReconfigDims.__post_init__) so the joint encoding fits the
# 2-byte packed value lanes.
CFG_BASE = 1 << 12

A_INITRECONFIG = 10
A_FINALIZE = 11


def joint_value(old_mask: int, new_mask: int) -> int:
    """Log value of the joint entry C_old,new."""
    return CFG_BASE + (old_mask << 8) + new_mask


def final_value(new_mask: int) -> int:
    """Log value of the final entry C_new."""
    return CFG_BASE + new_mask


def config_of_py(log, n: int) -> Tuple[int, int, int]:
    """(old_mask, new_mask, index) of the latest config entry in ``log``;
    old_mask == 0 means final.  Default: initial full membership at
    index 0."""
    for idx in range(len(log), 0, -1):
        v = log[idx - 1][1]
        if v >= CFG_BASE:
            enc = v - CFG_BASE
            return (enc >> 8) & 0xFF, enc & 0xFF, idx
    return 0, (1 << n) - 1, 0


@dataclasses.dataclass(frozen=True)
class ReconfigDims(RaftDims):
    """RaftDims + joint-consensus reconfiguration over ``targets`` (the
    TargetConfigs membership bitmasks a leader may move to)."""

    targets: Tuple[int, ...] = ()

    def __post_init__(self):
        full = (1 << self.n_servers) - 1
        if self.n_servers > 7:
            # joint_value(old, new) = CFG_BASE + (old << 8) + new must fit
            # the 2-byte value lanes (value_bytes below): with 8-bit masks
            # the joint encoding needs 17 bits, so cap membership at 7.
            # Checked BEFORE super().__post_init__ so this message (the
            # rule) is what the user sees, not the generic lane audit's
            # (which would also catch it via max_log_value > 65535).
            raise ValueError("ReconfigDims supports at most 7 servers "
                             "(2-byte log-value packing)")
        super().__post_init__()
        if not self.targets:
            raise ValueError("ReconfigDims needs at least one target config")
        for c in self.targets:
            if not (1 <= c <= full):
                raise ValueError(
                    f"target config {c:#x} not a nonempty subset of the "
                    f"{self.n_servers} servers")

    @property
    def max_log_value(self) -> int:
        """Largest encoded value: a joint entry with both masks full —
        CFG_BASE + (full << 8) + full <= 36,735 for n <= 7.  The lane
        audit (schema.audit_lane_widths) checks this against the 2-byte
        value lanes at construction."""
        full = (1 << self.n_servers) - 1
        return CFG_BASE + (full << 8) + full

    @property
    def value_bytes(self) -> int:
        """Configuration entries (CFG_BASE + (old << 8) + new <= 36,735
        for n <= 7) exceed uint8: the packed row carries value high
        bytes.  Without this, config entries WRAP mod 256 in the queue
        rows — old<<8 and CFG_BASE are multiples of 256, so a joint or
        final entry silently aliases to the client value ``new_mask``,
        corrupting every state past a leader's first InitiateReconfig
        (caught 2026-07-31 by a leader-seeded depth-2 differential)."""
        return 2

    # -- grid -------------------------------------------------------------
    @property
    def extra_families(self) -> tuple:
        n, c = self.n_servers, len(self.targets)
        return (("InitiateReconfig", n * c), ("FinalizeReconfig", n))

    def instance_info(self, g: int) -> tuple:
        base = sum(sz for _n, sz in zip(
            range(10), RaftDims.family_sizes.fget(self)[:10]))
        if g < base:
            return super().instance_info(g)
        k = g - base
        nc = self.n_servers * len(self.targets)
        if k < nc:
            i, t = divmod(k, len(self.targets))
            return A_INITRECONFIG, {"i": i, "c": self.targets[t]}
        k -= nc
        if k < self.n_servers:
            return A_FINALIZE, {"i": k}
        raise IndexError(g)

    # -- quorum (joint rule) ----------------------------------------------
    def build_quorum(self):
        import jax.numpy as jnp

        config_scan = _build_config_scan(self)
        N = self.n_servers

        def maj(member, mask):
            bits = ((mask >> jnp.arange(N, dtype=jnp.int32)) & 1) > 0
            return (2 * jnp.sum((member & bits).astype(jnp.int32))
                    > jnp.sum(bits.astype(jnp.int32)))

        def quorum(st, i, member):
            old, new, _idx = config_scan(st, i)
            return jnp.where(old > 0, maj(member, old) & maj(member, new),
                             maj(member, new))

        return quorum

    def quorum_py(self, s, i: int, mask: int) -> bool:
        old, new, _idx = config_of_py(s.log[i], self.n_servers)

        def maj(cfg: int) -> bool:
            return 2 * bin(mask & cfg).count("1") > bin(cfg).count("1")

        return (maj(old) and maj(new)) if old else maj(new)

    # -- new actions ------------------------------------------------------
    def _append_entry(self, st, i, val):
        """The v1 extra kernels' log-append: (fits, successor) for
        appending ``(term[i], val)`` to log[i]."""
        import jax.numpy as jnp

        from .actions import _add1, _set2
        L = self.max_log
        ln = st.log_len[i]
        kpos = jnp.clip(ln, 0, L - 1)
        return ln < L, st._replace(
            log_term=_set2(st.log_term, i, kpos, st.term[i]),
            log_val=_set2(st.log_val, i, kpos, val),
            log_len=_add1(st.log_len, i, 1))

    def _build_guards(self):
        """``((enabled, value), (enabled, value))`` closures for
        InitiateReconfig and FinalizeReconfig — the ONE source of the
        guard and appended-value expressions, used by all three kernel
        builders (v1 kernels, v2 declarations, v2 guards-only masks) so
        the pipelines cannot drift.  Apart, so that a caller traces only
        the half it uses: the masks pass no value, ``lane_out`` no
        guard."""
        config_scan = _build_config_scan(self)

        def initiate_en(st, i, c):
            """Leader with a final config (no change in progress)."""
            old, new, _idx = config_scan(st, i)
            return (st.role[i] == LEADER) & (old == 0) & (c != new)

        def initiate_val(st, i, c):
            """The joint entry C_current,c it appends."""
            _old, new, _idx = config_scan(st, i)
            return CFG_BASE + (new << 8) + c

        def finalize_en(st, i):
            """Leader whose joint config C_old,new is committed."""
            old, _new, idx = config_scan(st, i)
            return ((st.role[i] == LEADER) & (old > 0)
                    & (st.commit[i] >= idx))

        def finalize_val(st, i):
            """The final entry C_new it appends."""
            _old, new, _idx = config_scan(st, i)
            return CFG_BASE + new

        return (initiate_en, initiate_val), (finalize_en, finalize_val)

    def build_extra_kernels(self):
        import jax.numpy as jnp

        (init_en, init_val), (fin_en, fin_val) = self._build_guards()
        N = self.n_servers
        i32 = jnp.int32

        def initiate(st, i, c):
            en = init_en(st, i, c)
            fits, new_st = self._append_entry(st, i, init_val(st, i, c))
            return en & fits, en & ~fits, new_st

        def finalize(st, i):
            en = fin_en(st, i)
            fits, new_st = self._append_entry(st, i, fin_val(st, i))
            return en & fits, en & ~fits, new_st

        targets = jnp.asarray(self.targets, i32)
        c_count = len(self.targets)
        ii = jnp.repeat(jnp.arange(N, dtype=i32), c_count)
        cc = jnp.tile(targets, N)
        servers = jnp.arange(N, dtype=i32)
        return [((ii, cc), initiate), ((servers,), finalize)]

    def build_extra_v2(self, fp):
        """Delta-pipeline entries (models/dims.py ``build_extra_v2``
        contract): both extra actions append ONE log entry at
        (i, Len(log[i])) — the same footprint as ClientRequest — so each
        is a :class:`LogAppend` declaration and ``lane_out`` writes the
        lane through ClientRequest's append: the same three
        ordered-position shifts, the bag untouched.  What a lane runs for
        them is the appended value, ``_build_guards``' (``fp``, the delta
        toolkit, is not needed)."""
        (_en0, init_val), (_en1, fin_val) = self._build_guards()
        return [LogAppend(init_val), LogAppend(fin_val)]

    def build_extra_masks_v2(self):
        """Guards-only masks (dims.build_extra_masks_v2 contract): both
        extras append one log entry whose written fields always fit their
        lanes — the value is <= CFG_BASE + (127 << 8) + 127 = 36,735
        against 2-byte value lanes, the entry term is ``term[i]`` which
        the whole-state pack guard already bounds, and ``log_len`` is
        capped by ``max_log`` — so ``pack_ok(successor) ==
        pack_ok(parent)`` exactly and the per-lane successor + pack-guard
        evaluation of the v1 fallback is pure overhead.  Bit-identity
        with that fallback is property-tested (tests/test_actions2.py)."""
        (init_en, _val0), (fin_en, _val1) = self._build_guards()
        L = self.max_log

        def _append_masks(en, st, i, pk_parent):
            fits = st.log_len[i] < L
            return en & fits, (en & ~fits) | (en & fits & ~pk_parent)

        def initiate(st, pk_parent, i, c):
            return _append_masks(init_en(st, i, c), st, i, pk_parent)

        def finalize(st, pk_parent, i):
            return _append_masks(fin_en(st, i), st, i, pk_parent)

        return [initiate, finalize]

    def extra_successors_py(self, s):
        n = self.n_servers
        out = []
        for i in range(n):
            if s.role[i] != LEADER:
                continue
            old, new, idx = config_of_py(s.log[i], n)
            if old == 0:
                for c in self.targets:
                    if c != new:
                        t = s.replace(log=_append(
                            s.log, i, (s.current_term[i],
                                       joint_value(new, c))))
                        out.append(((A_INITRECONFIG, (i, c)), t))
            elif s.commit_index[i] >= idx:
                t = s.replace(log=_append(
                    s.log, i, (s.current_term[i], final_value(new))))
                out.append(((A_FINALIZE, (i,)), t))
        return out

    # -- TypeOK value domain ----------------------------------------------
    def build_value_ok(self):
        import jax.numpy as jnp

        v, n = self.n_values, self.n_servers
        full = (1 << n) - 1

        def value_ok(vals):
            client = (vals >= 1) & (vals <= v)
            enc = vals - CFG_BASE
            old = (enc >> 8) & 0xFF
            new = enc & 0xFF
            cfg = ((vals >= CFG_BASE)
                   & (enc <= (full << 8) + full)
                   & (new >= 1) & (new <= full) & (old <= full))
            return client | cfg

        return value_ok

    def value_ok_py(self, val: int) -> bool:
        if 1 <= val <= self.n_values:
            return True
        if val >= CFG_BASE:
            enc = val - CFG_BASE
            old, new = (enc >> 8) & 0xFF, enc & 0xFF
            full = (1 << self.n_servers) - 1
            return enc >> 16 == 0 and 1 <= new <= full and old <= full
        return False


def _build_config_scan(dims: "ReconfigDims"):
    """JAX kernel: latest config entry of server i's log ->
    (old_mask, new_mask, 1-based index); default (0, full, 0)."""
    import jax.numpy as jnp

    N, L = dims.n_servers, dims.max_log
    i32 = jnp.int32
    full = (1 << N) - 1

    def config_scan(st, i):
        # Row i and position k are read by compare, select and sum (the
        # idiom of models/safety.py ``_pick``), not ``table[traced]``:
        # under ``vmap`` that is a gather, which ``lane_out`` would pay
        # on every one of the K lanes.
        row = jnp.arange(N, dtype=i32) == i
        vals = jnp.sum(jnp.where(row[:, None], st.log_val, 0), axis=0)
        ln = jnp.sum(jnp.where(row, st.log_len, 0))
        lanes = jnp.arange(L, dtype=i32)
        is_cfg = (lanes < ln) & (vals >= CFG_BASE)
        has = jnp.any(is_cfg)
        k = jnp.max(jnp.where(is_cfg, lanes, -1))
        enc = jnp.sum(jnp.where(lanes == k, vals, 0)) - CFG_BASE
        old = jnp.where(has, (enc >> 8) & 0xFF, 0)
        new = jnp.where(has, enc & 0xFF, full)
        return old, new, jnp.where(has, k + 1, 0)

    return config_scan


def _append(logs, i, entry):
    return logs[:i] + (logs[i] + (entry,),) + logs[i + 1:]
