"""Mesh-sharded BFS — distributed TLC over a jax device mesh.

TLC scales with a multi-threaded worker pool and an RMI-based distributed
mode [TLC semantics — external; SURVEY §2.4 R7].  The TPU-native equivalent
shards the level-synchronous BFS over a 1-D ``jax.sharding.Mesh`` with
``shard_map``; collectives ride ICI (and DCN across hosts, transparently —
the program is identical):

- the frontier queue, next-level queue, and FPSet are sharded per chip;
- each chip expands its local batch and fingerprints its candidates;
- **fingerprint-owner dedup**: candidate fps are routed to their owner chip
  (``fp_hi mod n``) with one ``all_to_all``; the owner runs the same
  batched hash-table insert (ops/fpset.py) as the single-chip engine on the
  union of arriving queries, then a reverse ``all_to_all`` returns one
  novelty bit per query.  Exactly one copy of each globally-new state gets
  the bit, so states enqueue on the chip that *generated* them — only
  8-byte fingerprints ever cross the interconnect, never state rows;
- stats (new/generated/overflow/deadlock/violation) combine with ``psum``.

Runtime parity with the single-chip engine (engine/bfs.py):

- **device-resident chunk loop**: up to ``sync_every`` batches run per host
  round-trip inside a ``lax.while_loop`` whose continue condition is a
  replicated psum-reduction (all chips iterate in lockstep — a collective
  inside the body requires every chip to take the same trip count);
- **host spill**: when any chip's next-level queue passes its watermark the
  chunk exits and the host drains ALL chips' queues into one host pool
  (TLC's disk queue); pool segments re-upload *balanced* across chips, so
  spill doubles as load rebalancing;
- **level-boundary deal-out**: rows enqueue where they were generated, so
  from one root they would never leave its chip; a frontier whose fullest
  chip holds over a batch more than its even share is drained and
  re-uploaded balanced before the level is expanded (``_deal_out``);
- **seen-set growth**: when any shard passes half load the host pulls its
  keys and rebuilds every shard at double capacity (owner = fp mod n is
  unchanged, so keys stay on their chips);
- **checkpoint/resume**: level-boundary snapshots in the SAME format as the
  single-chip engine (frontier rows + flat key set) — a run checkpointed on
  the mesh can resume single-chip and vice versa; the key→owner and
  frontier layouts are recomputed on load, so even the device count may
  change across a resume.

Tested on a virtual 8-device CPU mesh (SURVEY §4.5); the program is
identical on a real TPU slice.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax.experimental.xla_metadata import set_xla_metadata

from ..engine.chunk import build_chunk_body, tag_stages
from ..engine.replay import ReplayScan
from ..engine.bfs import (BFSEngine, EngineConfig, EngineResult, TraceStore,
                          Violation, _exit_condition_hit, _family_groups_meta,
                          _progress_line, _TraceFlush, budget_call_size,
                          build_root_check, fetch_lengths, find_root_violation,
                          make_trace_store, store_growth, watch_compiles)
from ..models.actions import build_expand
from ..models.dims import RaftDims
from ..models.invariants import build_inv_id
from ..models.pystate import PyState
from ..models.schema import (ROW_DTYPE, build_pack_guard, check_packable,
                             decode_state, encode_state, flatten_states,
                             stack_states, state_width, unflatten_state)
from ..obs import MetricsRegistry, RunEventLog, events_path
from ..ops import compact as compact_mod
from ..ops import fpset
from ..ops.fingerprint import SENTINEL, build_fingerprint
from ..resilience import faults as _faults

_I32 = jnp.int32
_U32 = jnp.uint32

# What the mesh adds to a pass, as named scopes of its own inside the
# shared body's ``insert`` stage (engine/chunk.py STAGES) and around the
# loop: ``exchange`` (bucket by owner, the forward all_to_all of the two
# fingerprint halves), ``owner_insert`` (the shard's insert on the union
# of arriving queries), ``return`` (the reverse all_to_all of novelty
# bits and the un-bucketing), ``agree`` (the psums of the loop condition
# and of the statistics).  Scope names are not in the compile-cache key
# (chunk.py STAGES_TAG rationale), so the mesh programs carry a tag of
# their own beside the shared one: change it with these names, and the
# one-chip programs keep their keys.
MESH_STAGES = ("exchange", "owner_insert", "return", "agree")
MESH_STAGES_TAG = "m1"

# Rows of one frontier upload step.
UPLOAD_ROWS = 1 << 16

# Keys a chip of one piece of a shard rebuild (``_shards_from_keys``), a
# power of two of lanes (``fpset`` design note 2): from
# ``fpset.from_host_keys``' 2^15, the small program for a shallow
# snapshot, up to 2^18, few round trips for a deep one.
KEY_PIECE_MIN, KEY_PIECE_MAX = 1 << 15, 1 << 18


def _chip(shard) -> int:
    """The chip (row of the mesh) an addressable shard of a ``P("x")``
    array lies on.  A one-device mesh's only shard spans the axis, and
    its slice has no start."""
    return shard.index[0].start or 0


@functools.partial(jax.jit, static_argnums=2)
def _fetch_shard(cols, start, length):
    """The trace flush's fetch program (engine/bfs.py ``_TraceFlush``):
    ``length`` entries from ``start`` of one chip's five trace columns,
    each that chip's own ``[1, TA]`` shard.  A one-device program on the
    chip the shard lies on, so no controller waits for another's; one
    ``jit`` for every engine of the process, so a seen-set growth's
    rebuilt programs find it compiled."""
    return tuple(jax.lax.dynamic_slice(x, (0, start), (1, length))[0]
                 for x in cols)


class _KeyInserts:
    """The pieces one ``_shards_from_keys`` call dispatched and nobody
    has waited for yet: their count, when the first went out, and the
    rebuild's status (``fpset.rebuild_piece``: a row a chip), carried
    through the pieces and still on the chips."""

    def __init__(self, overflow: str, status):
        self._overflow, self._status = overflow, status
        self.pieces = 0
        self.since = None       # time.time() at the first dispatch's return
        self.rounds = self.lane_rounds = None    # known after ``wait``

    def dispatched(self, status) -> None:
        self._status, self.pieces = status, self.pieces + 1
        if self.since is None:
            self.since = time.time()

    def wait(self) -> None:
        """Block until the last piece is in its shard and read the
        status, once: ``rounds`` is the most probe rounds a chip here
        ran, ``lane_rounds`` the lanes all of them ran on; a shard that
        could not take its keys raises."""
        failed, rounds, lane_rounds = np.concatenate(
            [np.asarray(s.data) for s in self._status.addressable_shards]).T
        self.rounds, self.lane_rounds = int(rounds.max()), int(
            lane_rounds.sum())
        if failed.any():
            raise RuntimeError(self._overflow)


def tag_mesh_stages(count):
    """``count`` through an ``add 0`` that carries both tags (chunk.py
    ``tag_stages``): the shared stage names' and the mesh's own."""
    with set_xla_metadata(mesh_stages_tag=MESH_STAGES_TAG):
        return tag_stages(count) + 0


class MeshBFSEngine:
    """Exhaustive checker sharded over an n-device mesh."""

    def __init__(self, dims: RaftDims,
                 invariants: Optional[Dict[str, Callable]] = None,
                 constraint: Optional[Callable] = None,
                 config: Optional[EngineConfig] = None,
                 devices=None):
        self.dims = dims
        self.config = config or EngineConfig()
        cfg = self.config
        # Telemetry spine (obs/), shared with the single-chip engine.
        # ``_rebuild_programs`` re-enters __init__ MID-RUN (seen-set
        # growth), so an existing registry and open event log must
        # survive the re-init — losing them would silently drop every
        # phase total and event recorded before the first growth.
        self.metrics = (cfg.metrics or getattr(self, "metrics", None)
                        or MetricsRegistry())
        if not hasattr(self, "_evlog"):
            self._evlog = RunEventLog(None)
            self._phase_base = {}
        # Span tracer (obs/tracing.py): survives the re-entrant re-init
        # like the registry; attached to the registry it mirrors every
        # phase_timer block into a Chrome-trace span.  Multi-host runs
        # get one trace per controller (piece suffix, like event logs).
        if not hasattr(self, "tracer"):
            from ..obs import SpanTracer
            trace_out = cfg.trace_out
            if trace_out is not None:
                try:
                    pi, pc = jax.process_index(), jax.process_count()
                except Exception:
                    pi, pc = 0, 1
                if pc > 1:
                    root, ext = os.path.splitext(trace_out)
                    trace_out = f"{root}.p{pi}of{pc}{ext or '.json'}"
            self.tracer = SpanTracer(
                trace_out, annotate=jax.profiler.TraceAnnotation)
        self.metrics.tracer = self.tracer
        watch_compiles()    # ``run_end.compiles`` (engine/bfs.py)
        if cfg.checkpoint_dir:
            # Fail at construction, not at the first level-boundary write.
            from ..engine import checkpoint as _ckpt
            _ckpt.check_dims_checkpointable(dims)
        devices = devices if devices is not None else jax.devices()
        self.n_dev = n = len(devices)
        self.mesh = Mesh(np.asarray(devices), ("x",))
        self.inv_names = list((invariants or {}).keys())
        self._inv_fns = inv_fns = list((invariants or {}).values())
        self._constraint = constraint
        expand = build_expand(dims)
        fingerprint = build_fingerprint(dims)
        pack_ok = build_pack_guard(dims)
        from ..engine.bfs import (_resolve_pipeline, por_device_arrays,
                                  resolve_por)
        self._v2 = _resolve_pipeline(cfg.pipeline, dims)
        self._pipeline_name = "v2" if self._v2 is not None else "v1"
        # POR reduction table (analysis/por.py): resolved/verified once
        # on the host; the [G] mask/priority arrays are closed over by
        # the chunk body below, so shard_map replicates them to every
        # chip (the mask broadcast) — each chip applies the identical
        # reduction, keeping the engines' bit-identical-per-batch
        # contract intact.
        if not hasattr(self, "_por_table"):   # growth-path re-init reuses
            self._por_table = resolve_por(
                cfg, dims, dict(zip(self.inv_names, inv_fns)), constraint)
        por_mask, por_priority = por_device_arrays(self._por_table)
        sw = state_width(dims)
        B, G = cfg.batch, dims.n_instances
        # Compacted-candidate lanes per chip (ops/compact.py): only K
        # lanes go through owner routing, the hash insert, row
        # materialization, and enqueue — and only K fingerprints per chip
        # cross the ICI per batch, not B*G.
        K = compact_mod.choose_k(B, G, cfg.compact_lanes)
        self._check_deadlock = (True if cfg.check_deadlock is None
                                else cfg.check_deadlock)
        # Per-chip capacities; None resolves through the same HBM
        # auto-sizing as the single-chip engine (per-chip budget).
        from ..engine.bfs import _auto_capacities
        qreq, sreq = cfg.queue_capacity, cfg.seen_capacity
        if qreq is None or sreq is None:
            auto_q, auto_s = _auto_capacities(sw, B, cfg.record_trace)
            qreq = auto_q if qreq is None else qreq
            sreq = auto_s if sreq is None else sreq
        # Queue: batch-multiple, floored at one worst-case batch (K new
        # rows) — a batch can never overflow mid-chunk; the watermark
        # below spills *between* batches (engine/bfs.py invariant).  The
        # allocation carries PAD extra rows: B of slice overrun + K of
        # scatter trash (distinct per-lane addresses for masked-off
        # enqueue lanes — ops/fpset.py design note 3).
        per_chip = -(-qreq // n)
        QL = max(-(-per_chip // B) * B, K)
        PAD = max(B, K)
        # Seen shard: each chip receives n blocks of K owner-routed
        # lanes per batch — up to n*K queries in the worst case, ~K on
        # average — and its insert pays for the queries, a window of K
        # lanes at a time (``route_insert``), not for the blocks'
        # padding; the same 8-batch floor as the single-chip engine
        # keeps the growth threshold (half load) safely ahead of probe
        # failure.
        CL = fpset._capacity(max(-(-sreq // n), 8 * K))
        self._sw, self._B, self._G, self._QL, self._CL = sw, B, G, QL, CL
        self._K, self._PAD = K, PAD
        # Every chip runs the body on its own K lanes (engine/bfs.py
        # WORK_COUNTERS ``inv_lanes``).
        self._inv_lanes_a_pass = n * K if inv_fns else 0
        self._QTH = QL - K
        CH = self._CH = max(1, cfg.sync_every)
        record_static = cfg.record_trace
        TQ = QL + K if record_static else 8
        self._TQ = TQ
        self._TA = TQ + K if record_static else 8
        # The trace flush's fetch lengths (``_TraceFlush``); ``_run_impl``
        # runs ``_fetch`` at each on every chip once in warm-up.
        self._fetch_lens = fetch_lengths(self._TA)
        check_deadlock_static = self._check_deadlock
        # pmin keeps every chip's offset advance identical — the chunk
        # body contains collectives, so trip counts must agree.
        compactor = compact_mod.build_compactor(
            B, G, K, reduce_p=lambda p: jax.lax.pmin(p, "x"))

        def route_insert(seen_windows, fph, fpl, valid):
            """Cross-chip owner dedup: route each valid fingerprint to its
            owner chip (fp_hi mod n) with one all_to_all, insert the union
            of arrivals into the local shard, route the novelty bits back.
            Exactly one copy of each globally-new key (across all chips)
            gets the bit.  ``seen_windows`` is the shard and, beside it,
            the count of insert windows run so far (the shared body passes
            both through as its ``seen``): the n blocks of k lanes that
            arrive hold about k queries between them, and the owner's
            insert runs on windows of k lanes over the queries
            (``fpset.insert_windowed``), one nearly always, never on the
            blocks' padding.  The trip count is this chip's own: no
            collective runs inside ``owner_insert``."""
            seen_local, windows = seen_windows
            k = fph.shape[0]
            with jax.named_scope("exchange"):
                fph = jnp.where(valid, fph, SENTINEL)
                fpl = jnp.where(valid, fpl, SENTINEL)
                owner = (fph % _U32(n)).astype(_I32)
                perm = jnp.argsort(owner, stable=True)
                osort = owner[perm]
                q_hi, q_lo = fph[perm], fpl[perm]
                block_start = jnp.searchsorted(
                    osort, jnp.arange(n, dtype=_I32))
                rank = jnp.arange(k, dtype=_I32) - block_start[osort]
                bh = jnp.full((n, k), SENTINEL, _U32).at[
                    osort, rank].set(q_hi)
                bl = jnp.full((n, k), SENTINEL, _U32).at[
                    osort, rank].set(q_lo)
                bh = jax.lax.all_to_all(bh, "x", 0, 0, tiled=True)
                bl = jax.lax.all_to_all(bl, "x", 0, 0, tiled=True)
            with jax.named_scope("owner_insert"):
                rh, rl = bh.reshape(-1), bl.reshape(-1)
                rvalid = ~((rh == SENTINEL) & (rl == SENTINEL))
                seen_local, qnew, fail, ran = fpset.insert_windowed(
                    seen_local, rh, rl, rvalid, k)
            with jax.named_scope("return"):
                nov = jax.lax.all_to_all(qnew.reshape(n, k), "x", 0, 0,
                                         tiled=True)
                new_sortpos = nov[osort, rank]
                new = jnp.zeros((k,), bool).at[perm].set(new_sortpos)
            return (seen_local, windows + ran), new, fail

        def local_absorb(crows, cands, en, parent_hi, parent_lo, actions,
                         qnext, next_count, seen_local, tbuf, tcount):
            """Per-chip tail with cross-chip owner dedup.  All arrays are
            this chip's shard (no leading device axis).  Ingest-sized (k
            <= B); the chunk path below compacts first."""
            k = crows.shape[0]
            fph, fpl = jax.vmap(fingerprint)(cands)
            (seen_local, _windows), new, fail = route_insert(
                (seen_local, jnp.int32(0)), fph, fpl, en)
            fph = jnp.where(en, fph, SENTINEL)
            fpl = jnp.where(en, fpl, SENTINEL)

            n_new = jnp.sum(new, dtype=_I32)      # local share of global new

            if inv_fns:
                inv = jax.vmap(build_inv_id(inv_fns))(cands)
            else:
                inv = jnp.full((k,), -1, _I32)
            viol = new & (inv >= 0)
            viol_any = jnp.any(viol)
            vpos = jnp.argmax(viol)

            if constraint is not None:
                cons_ok = jax.vmap(constraint)(cands)
            else:
                cons_ok = jnp.ones((k,), bool)
            enq = new & cons_ok
            pos = next_count + jnp.cumsum(enq.astype(_I32)) - 1
            # Per-lane trash rows past QL (PAD = max(B, K) >= k): a single
            # shared trash index serializes the scatter on TPU (ops/fpset.py
            # design note 3).
            pos = jnp.where(enq, pos, QL + jnp.arange(k, dtype=_I32))
            qnext = qnext.at[pos].set(crows, mode="drop")
            next_count = next_count + jnp.sum(enq, dtype=_I32)

            if record_static:
                tpos = jnp.where(
                    new, tcount + jnp.cumsum(new.astype(_I32)) - 1,
                    TQ + jnp.arange(k, dtype=_I32))  # TA = TQ + K >= TQ + k
                tbuf = tuple(
                    buf.at[tpos].set(col, mode="drop")
                    for buf, col in zip(
                        tbuf, (fph, fpl, parent_hi, parent_lo, actions)))
                tcount = tcount + n_new

            vinfo = (viol_any, inv[vpos], crows[vpos], fph[vpos], fpl[vpos])
            return (qnext, next_count, seen_local, tbuf, tcount, n_new,
                    fail, vinfo)

        # The per-batch pipeline body is shared with the single-chip
        # engine (engine/chunk.py); here the insert routes fingerprints
        # to their owner chips, and P is pmin-replicated via the
        # compactor's reduce_p hook so all chips advance in lockstep.
        chunk_body = build_chunk_body(
            dims=dims, expand=expand, fingerprint=fingerprint,
            pack_ok=pack_ok, inv_fns=inv_fns, constraint=constraint,
            B=B, G=G, K=K, Q=QL, TQ=TQ, record_static=record_static,
            compactor=compactor, insert_fn=route_insert, v2=self._v2,
            por_mask=por_mask, por_priority=por_priority)

        def agreed_stats(offset, steps, gen, newc, ovfc, fail_any, max_count,
                         ncnt_l, cnt_l, seen_l, windows, tcnt_l, viol_any,
                         vinv, vrow, vhi, vlo, dead_any, drow, expanded,
                         fam_counts, fam_new, fam_pruned):
            """What a chunk call hands the host, the same on every chip:
            the psum/pmax-combined statistics, the violation/deadlock
            rows broadcast from the lowest-indexed flagged chip (no
            per-chip inspection on the host side), and last each chip's
            own (parents expanded, next-level rows, shard keys, insert
            windows run, trace records written)."""
            g_gen = jax.lax.psum(gen, "x")
            g_new = jax.lax.psum(newc, "x")
            g_ovf = jax.lax.psum(ovfc, "x")
            g_fail = jax.lax.psum(fail_any.astype(_I32), "x")
            from .multihost import bcast_lowest_flagged
            v_any, vinv_g, vrow_g, vhi_g, vlo_g = bcast_lowest_flagged(
                "x", viol_any, vinv, vrow, vhi, vlo)
            d_any, drow_g = bcast_lowest_flagged("x", dead_any, drow)
            # Packed replicated stats: one host fetch per call
            # (engine/bfs.py contract).  Layout documented at the read
            # site in run().
            stats = jnp.concatenate([
                jnp.stack([offset, steps, g_gen, g_new, g_ovf, g_fail,
                           max_count,
                           jax.lax.pmax(ncnt_l, "x"),
                           jax.lax.psum(ncnt_l, "x"),
                           jax.lax.psum(
                               jnp.maximum(cnt_l - offset, 0), "x"),
                           jax.lax.pmax(seen_l.size, "x"),
                           v_any.astype(_I32),
                           d_any.astype(_I32),
                           vinv_g,
                           jax.lax.psum(cnt_l, "x"),
                           jax.lax.psum(expanded, "x")]),
                jax.lax.psum(fam_counts, "x"),
                jax.lax.psum(fam_new, "x"),
                jax.lax.psum(fam_pruned, "x"),
                jax.lax.all_gather(
                    jnp.stack([expanded, ncnt_l, seen_l.size, windows,
                               tcnt_l]),
                    "x").T.reshape(-1)])
            return stats, drow_g, vrow_g, vhi_g, vlo_g

        def sharded_chunk(qcur, cur_counts, offset0, qnext, next_counts,
                          shi, slo, ssize, tbuf, tcount0, max_steps):
            # Shapes inside shard_map: leading device axis of size 1.
            qcur_l, qnext_l = qcur[0], qnext[0]
            cnt_l = cur_counts[0]
            ncnt_l = tag_mesh_stages(next_counts[0])
            # The level width is derived IN-program (pmax over chips), so
            # the host never needs a global view of the per-chip counts —
            # a multi-controller requirement (parallel/multihost.py).
            with jax.named_scope("agree"):
                max_count = jax.lax.pmax(cnt_l, "x")
            seen_l = fpset.FPSet(hi=shi[0], lo=slo[0], size=ssize[0])
            tbuf_l = tuple(t[0] for t in tbuf)
            init = (offset0, jnp.int32(0), qnext_l, ncnt_l,
                    (seen_l, jnp.int32(0)), tbuf_l,
                    tcount0[0], jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.bool_(False), jnp.zeros((sw,), jnp.uint8),
                    jnp.bool_(False), jnp.int32(-1),
                    jnp.zeros((sw,), jnp.uint8),
                    jnp.uint32(0), jnp.uint32(0), jnp.bool_(False),
                    jnp.zeros((len(dims.family_sizes),), _I32),
                    jnp.zeros((len(dims.family_sizes),), _I32),
                    jnp.int32(0),
                    jnp.zeros((len(dims.family_sizes),), _I32))

            def cond(c):
                (offset, steps, _qn, ncnt_c, (seen_c, _win), _tb, tcnt_c,
                 _g, _n, ovfc, dead_any, _dr, viol_any, _vi, _vr, _vh,
                 _vl, fail_any, _fam, _famn, _exp, _famp) = c
                # Every term is reduced to a REPLICATED bool so all chips
                # take the same trip count (the body contains all_to_all).
                more = (offset < max_count) & (steps < max_steps)
                blocked = (ncnt_c > QL - K).astype(_I32) \
                    + (seen_c.size > CL // 2).astype(_I32)
                stop = viol_any.astype(_I32) + (ovfc > 0).astype(_I32) \
                    + fail_any.astype(_I32)
                if check_deadlock_static:
                    stop = stop + dead_any.astype(_I32)
                if record_static:
                    blocked = blocked + (tcnt_c > TQ - K).astype(_I32)
                with jax.named_scope("agree"):
                    return more & (jax.lax.psum(blocked + stop, "x") == 0)

            out = jax.lax.while_loop(
                cond, lambda c: chunk_body(qcur_l, cnt_l, c), init)
            (offset, steps, qnext_l, ncnt_l, (seen_l, windows), tbuf_l,
             tcnt_l, gen, newc, ovfc, dead_any, drow, viol_any, vinv, vrow,
             vhi, vlo, fail_any, fam_counts, fam_new, expanded,
             fam_pruned) = out
            with jax.named_scope("agree"):
                stats, drow_g, vrow_g, vhi_g, vlo_g = agreed_stats(
                    offset, steps, gen, newc, ovfc, fail_any, max_count,
                    ncnt_l, cnt_l, seen_l, windows, tcnt_l, viol_any, vinv,
                    vrow, vhi, vlo, dead_any, drow, expanded, fam_counts,
                    fam_new, fam_pruned)
            vfp_g = jnp.stack([vhi_g, vlo_g])
            return (qnext_l[None], ncnt_l[None], seen_l.hi[None],
                    seen_l.lo[None], seen_l.size[None],
                    tuple(t[None] for t in tbuf_l), tcnt_l[None],
                    stats, drow_g, vrow_g, vfp_g)

        def sharded_ingest(rows, valid, qnext, next_counts, shi, slo, ssize,
                           tbuf, tcount0):
            rows_l, valid_l = rows[0], valid[0]
            states = jax.vmap(unflatten_state, (0, None))(rows_l, dims)
            sent = jnp.zeros(rows_l.shape[:1], _U32)
            acts = jnp.full(rows_l.shape[:1], -1, _I32)
            seen_l = fpset.FPSet(hi=shi[0], lo=slo[0], size=ssize[0])
            tbuf_l = tuple(t[0] for t in tbuf)
            (qnext_l, ncnt_l, seen_l, tbuf_l, tcnt_l, n_new, fail,
             vinfo) = local_absorb(
                rows_l, states, valid_l, sent, sent, acts,
                qnext[0], tag_mesh_stages(next_counts[0]), seen_l, tbuf_l,
                tcount0[0])
            viol_any, vinv, vrow, vhi, vlo = vinfo
            # Replicated stats + lowest-flagged-chip violation broadcast
            # (sharded_chunk rationale): the host reads no per-chip values.
            from .multihost import bcast_lowest_flagged
            with jax.named_scope("agree"):
                v_any, vinv_g, vrow_g, vhi_g, vlo_g = bcast_lowest_flagged(
                    "x", viol_any, vinv, vrow, vhi, vlo)
                stats = jnp.stack([
                    jax.lax.psum(n_new, "x"),
                    jax.lax.psum(fail.astype(_I32), "x"),
                    jax.lax.pmax(ncnt_l, "x"),
                    jax.lax.psum(ncnt_l, "x"),
                    v_any.astype(_I32),
                    vinv_g,
                    jax.lax.pmax(seen_l.size, "x")])
            vfp = jnp.stack([vhi_g, vlo_g])
            return (qnext_l[None], ncnt_l[None], seen_l.hi[None],
                    seen_l.lo[None], seen_l.size[None],
                    tuple(t[None] for t in tbuf_l), tcnt_l[None],
                    stats, vrow_g, vfp)

        shard = functools.partial(jax.shard_map, mesh=self.mesh,
                                  check_vma=False)
        sx = P("x")
        rep = P()
        self._chunk = jax.jit(shard(
            sharded_chunk,
            in_specs=(sx, sx, rep, sx, sx, sx, sx, sx, sx, sx, rep),
            out_specs=(sx, sx, sx, sx, sx, (sx,) * 5, sx, rep, rep, rep,
                       rep)),
            donate_argnums=(3, 5, 6, 7, 8))
        self._ingest = jax.jit(shard(
            sharded_ingest,
            in_specs=(sx, sx, sx, sx, sx, sx, sx, sx, sx),
            out_specs=(sx, sx, sx, sx, sx, (sx,) * 5, sx, rep, rep, rep)),
            donate_argnums=(2, 4, 5, 6, 7))
        self._last_skew = None

        def fp_rows(rows):
            return jax.vmap(fingerprint)(
                jax.vmap(unflatten_state, (0, None))(rows, dims))

        self._fp_rows = jax.jit(fp_rows)

        # The host loop's own small programs: a shard's keys inserted
        # where the shard lies, every shard at once (_shards_from_keys);
        # a step of frontier rows written into a queue (_upload_segment);
        # the fills of _sharded_full, one per (shape, dtype, value).
        self._full_fns = {}

        def insert_keys(hi, lo, size, qh, ql, valid, status):
            s, status = fpset.rebuild_piece(
                fpset.FPSet(hi=hi[0], lo=lo[0], size=size[0]), status[0],
                qh[0], ql[0], valid[0])
            return s.hi[None], s.lo[None], s.size[None], status[None]

        self._insert_keys = jax.jit(
            shard(insert_keys, in_specs=(sx,) * 7, out_specs=(sx,) * 4),
            donate_argnums=(0, 1, 2, 6))
        self._write_rows = jax.jit(
            lambda q, rows, at: jax.lax.dynamic_update_slice(
                q, rows, (jnp.int32(0), at, jnp.int32(0))),
            donate_argnums=(0,))
        self._replay_scan = ReplayScan(dims, self.metrics)
        self._expand1 = jax.jit(expand)
        self._fp_batch = jax.jit(jax.vmap(fingerprint))
        self._root_check = (build_root_check(inv_fns, fingerprint)
                            if inv_fns else None)

    def chunk_avals(self) -> tuple:
        """The mesh chunk program's arguments as shapes (leading axis =
        chips): what the launch model traces and what a compile for a
        described mesh lowers."""
        n = self.n_dev
        i32s = jax.ShapeDtypeStruct((n,), _I32)
        scalar = jax.ShapeDtypeStruct((), _I32)
        qav = jax.ShapeDtypeStruct((n, self._QL + self._PAD, self._sw),
                                   jnp.uint8)
        sh_av = jax.ShapeDtypeStruct((n, self._CL), _U32)
        tbuf_av = tuple(
            jax.ShapeDtypeStruct((n, self._TA), d)
            for d in (jnp.uint32, jnp.uint32, jnp.uint32,
                      jnp.uint32, _I32))
        return (qav, i32s, scalar, qav, i32s, sh_av, sh_av, i32s,
                tbuf_av, i32s, scalar)

    # ------------------------------------------------------------------
    def _sharded_full(self, shape, dtype, fill=0):
        """An array of ``fill`` allocated ALREADY SHARDED over the mesh.
        One program per (shape, dtype, fill), kept: the loop asks for a
        zeroed trace count after every chunk call, and a new ``jit`` of
        a new lambda each time was a compile each time."""
        key = (tuple(shape), jnp.dtype(dtype).name, int(fill))
        if key not in self._full_fns:
            self._full_fns[key] = jax.jit(
                lambda: jnp.full(shape, fill, dtype),
                out_shardings=NamedSharding(self.mesh, P("x")))
        return self._full_fns[key]()

    def _grow_seen(self, shi, slo, most, new_cl=None):
        """Rebuild this controller's shards at double (or given) capacity.
        Owner assignment (fp_hi mod n) is capacity-independent, so keys
        stay on their chips; every controller rehashes only its
        addressable shards and the arrays are reassembled shard-by-shard
        (multi-controller rule 3).  ``most`` is the fullest shard's size
        as every controller read it (the statistics' pmax).  The chunk
        program recompiles for the new shape — identically everywhere."""
        keys = self.shard_keys(shi, slo)
        self._CL = fpset._capacity(new_cl or 2 * self._CL)
        self._rebuild_programs()
        shi, slo, ssize, inserts = self._shards_from_keys(
            np.concatenate([hi for hi, _lo in keys.values()]),
            np.concatenate([lo for _hi, lo in keys.values()]), most)
        inserts.wait()
        self._rebuild_counts = (inserts.rounds, inserts.lane_rounds)
        return shi, slo, ssize

    def _shards_from_keys(self, keys_hi, keys_lo, most):
        """Rebuild the sharded FPSet arrays from a flat key set (owner =
        fp_hi mod n) and DO NOT WAIT for them: returns ``(shi, slo,
        ssize, inserts)`` with the pieces dispatched, and the caller does
        what else it has for the host before ``inserts.wait()``, which
        blocks on the last piece and raises where a shard overflowed.

        Each controller supplies only the keys of its addressable
        shards; every shard is built ON the chip that owns it, all of
        them side by side by one program over the mesh (``fpset.rebuild_piece``
        under ``shard_map``), a piece of keys a chip at a time, and never
        comes to the host (a 2^25-slot shard is 268 MB; four of them
        built on chip 0, fetched and sent up again were most of a deep
        resume).  The host buckets the keys by owner a SLAB at a time (a
        little over one piece an owner) and dispatches a piece as soon as
        every owner here has one: slab k+1 is bucketed while the chips
        insert piece k, so they wait for one slab's host work, not for
        the whole set's (1.0 s of idle chips a level-12 resume).  What a
        slab holds past an owner's piece is carried over, in order, so
        owner ``d``'s ``k``-th piece is keys ``[k*piece, (k+1)*piece)`` of
        its own, however the slabs fell.

        ``most`` is the fullest shard's key count over ALL the mesh's
        shards, as every controller knows it (the resume counts the
        checkpoint's owners, a growth reads the statistics' pmax): it
        alone sets the piece's length and the number of dispatches, so
        controllers that hold different keys still make the same calls.
        An owner here with keys left after them was not counted in it:
        an error."""
        n, cl = self.n_dev, self._CL
        keys_hi = np.asarray(keys_hi).astype(np.uint32, copy=False)
        keys_lo = np.asarray(keys_lo).astype(np.uint32, copy=False)
        piece = min(max(fpset._capacity(most), KEY_PIECE_MIN), KEY_PIECE_MAX)
        # A sixteenth over a piece an owner: hashed owners fall within a
        # hundredth of even, so one slab feeds one dispatch.
        slab = n * (piece + piece // 16)
        me = jax.process_index()
        no_keys = np.zeros((0,), np.uint32)
        held = {d: (no_keys, no_keys)
                for d, dev in enumerate(self.mesh.devices.flat)
                if dev.process_index == me}
        read = 0

        def bucket_slab():
            nonlocal read
            hi, lo = keys_hi[read:read + slab], keys_lo[read:read + slab]
            read += len(hi)
            owner = hi % np.uint32(n)
            for d, (h, l) in held.items():
                at = np.flatnonzero(owner == d)
                held[d] = (np.concatenate([h, hi.take(at)]),
                           np.concatenate([l, lo.take(at)]))

        sh = NamedSharding(self.mesh, P("x"))
        shi, slo = (self._sharded_full((n, cl), _U32, SENTINEL)
                    for _ in range(2))
        ssize = self._sharded_full((n,), _I32)
        status = self._sharded_full((n, 3), _I32)
        inserts = _KeyInserts(
            f"FPSet rebuild overflow: {most} keys into a shard of {cl}",
            status)
        for _ in range(-(-most // piece)):
            while read < len(keys_hi) and min(
                    len(h) for h, _l in held.values()) < piece:
                bucket_slab()
            part = {d: (h[:piece], l[:piece]) for d, (h, l) in held.items()}
            held = {d: (h[piece:], l[piece:]) for d, (h, l) in held.items()}

            def column(col, part=part):
                def one(idx):
                    keys = part[idx[0].start or 0][col]
                    return np.pad(keys, (0, piece - len(keys)))[None]
                return jax.make_array_from_callback((n, piece), sh, one)

            valid = jax.make_array_from_callback(
                (n, piece), sh, lambda idx, part=part: (
                    np.arange(piece) < len(part[idx[0].start or 0][0]))[None])
            shi, slo, ssize, status = self._insert_keys(
                shi, slo, ssize, column(0), column(1), valid, status)
            inserts.dispatched(status)
        while read < len(keys_hi):
            bucket_slab()
        left = {d: len(h) for d, (h, _l) in held.items() if len(h)}
        if left:
            raise RuntimeError(
                f"FPSet rebuild: keys left after the pieces that a fullest "
                f"shard of {most} keys asks for, by chip: {left}")
        return shi, slo, ssize, inserts

    def _rebuild_programs(self):
        """Re-trace chunk/ingest for a changed seen-shard shape."""
        MeshBFSEngine.__init__(
            self, self.dims,
            invariants=dict(zip(self.inv_names, self._inv_fns)),
            constraint=self._constraint,
            config=self._cfg_with_seen(self._CL * self.n_dev),
            devices=list(self.mesh.devices.ravel()))

    def _cfg_with_seen(self, total):
        import dataclasses as _dc
        return _dc.replace(self.config, seen_capacity=total)

    # ------------------------------------------------------------------
    def run(self, init_states: Optional[List[PyState]] = None,
            resume=None) -> EngineResult:
        """Telemetry wrapper (engine/bfs.py rationale): run_start/run_end
        events bracket the run, phases are scoped to it.  Shared via duck
        typing, like replay() — as is the OOM degradation wrapper
        (single-controller only; a process group re-raises and the
        supervisor restarts the whole fleet)."""
        from ..engine.bfs import BFSEngine

        def impl(states, resume=None):
            return BFSEngine._run_degradable(self, states, resume=resume)

        return BFSEngine._telemetry_run(self, impl, init_states,
                                        resume=resume)

    def _rebuild_at_batch(self, new_batch: int) -> None:
        """Recompile the mesh programs at a smaller batch (the re-entrant
        __init__ path growth already uses); registry/event log survive."""
        import dataclasses as _dc
        MeshBFSEngine.__init__(
            self, self.dims,
            invariants=dict(zip(self.inv_names, self._inv_fns)),
            constraint=self._constraint,
            config=_dc.replace(self.config, batch=new_batch),
            devices=list(self.mesh.devices.ravel()))

    def _events_path(self):
        """One event-log piece per controller (multi-host checkpoint
        model); single-controller resolution is unchanged."""
        return events_path(self.config.events_out,
                           self.config.checkpoint_dir,
                           jax.process_index(), jax.process_count())

    def _postmortem_path(self):
        """One postmortem piece per controller (the event-log model):
        two crashing controllers on a shared filesystem must never race
        one dump file."""
        from ..engine.bfs import BFSEngine
        base = BFSEngine._postmortem_path(self)
        if base is None:
            return None
        return events_path(base, None, jax.process_index(),
                           jax.process_count())

    def _xla_profile_dir(self):
        from ..engine.bfs import BFSEngine
        return BFSEngine._xla_profile_dir(self)

    def _start_capacity(self, resume) -> dict:
        """Nothing for ``run_start``: the shards are rebuilt in place
        when they grow (``_grow``) and every run takes them as they are,
        so no run starts at another size than the engine holds."""
        return {}

    # The level event, the run-end report, the level span, the loop's
    # work counters and the replay are the single-chip engine's own
    # (they touch nothing of its).
    _emit_level_event = BFSEngine._emit_level_event
    _end_run = BFSEngine._end_run
    _level_fields = BFSEngine._level_fields
    _open_level_span = BFSEngine._open_level_span
    _close_level_span = BFSEngine._close_level_span
    _count_chunk_call = BFSEngine._count_chunk_call
    _generated_by_family = BFSEngine._generated_by_family
    _budget_fields = BFSEngine._budget_fields
    _note_family_base = BFSEngine._note_family_base
    _replay = BFSEngine._replay
    _replay_step = BFSEngine._replay_step
    _canonical_instance = BFSEngine._canonical_instance

    def _sample_skew(self, res, next_counts, ssize) -> None:
        """Per-shard balance telemetry, sampled at each level boundary
        (ROADMAP item 5's first observability surface): this
        controller's shard next-level counts and seen-set sizes ->
        ``mesh/*`` balance gauges, skew fields on the level_complete
        event (via ``_last_skew``, read by the shared emit), and a
        ``skew`` WARNING event when max/mean frontier imbalance reaches
        ``EngineConfig.skew_warn_ratio``.  Host-side reads of a handful
        of addressable-shard ints per level — observational by
        construction (the telemetry itself is held by
        tests/test_mesh.py).
        Caveats: under a process group each controller samples its own
        shards (the union is the global picture, one event log piece
        each); a level whose rows were already drained to the host pool
        samples the device-resident remainder only."""
        try:
            fr = self._local_counts(next_counts)
            sz = self._local_counts(ssize)
        except Exception:
            self._last_skew = None
            return
        vals = [int(v) for _k, v in sorted(fr.items())]
        sizes = [int(v) for _k, v in sorted(sz.items())]

        def ratio(xs):
            mean = sum(xs) / len(xs) if xs else 0.0
            return round(max(xs) / mean, 4) if mean > 0 else None

        fsk, ssk = ratio(vals), ratio(sizes)
        mt = self.metrics
        if vals:
            mt.gauge("mesh/shard_frontier_max", max(vals))
            mt.gauge("mesh/shard_frontier_min", min(vals))
        if fsk is not None:
            mt.gauge("mesh/frontier_skew", fsk)
        if sizes:
            mt.gauge("mesh/shard_seen_max", max(sizes))
        if ssk is not None:
            mt.gauge("mesh/seen_skew", ssk)
        self._last_skew = {"frontier_skew": fsk, "seen_skew": ssk,
                           "shard_frontier": vals, "shard_seen": sizes}
        thr = self.config.skew_warn_ratio
        if fsk is not None and thr and fsk >= thr:
            mt.counter("mesh/skew_warnings")
            self._evlog.emit("skew", balance={
                "level": res.diameter, "frontier_skew": fsk,
                "seen_skew": ssk, "shard_frontier": vals,
                "threshold": thr})

    def _counterexample_base(self) -> str:
        """Per-controller counterexample file stem (the event-log piece
        model): under a process group every controller renders — each
        merged its siblings' trace pieces at replay, so the contents
        agree — but two controllers must never race one filename on the
        shared filesystem.  Single-controller resolution is unchanged."""
        if jax.process_count() <= 1:
            return "counterexample"
        return (f"counterexample.p{jax.process_index()}"
                f"of{jax.process_count()}")

    def _run_impl(self, init_states: Optional[List[PyState]] = None,
                  resume=None) -> EngineResult:
        from ..engine import checkpoint as ckpt_mod
        from . import multihost as mh
        dims, cfg = self.dims, self.config
        n, sw, B, QL = self.n_dev, self._sw, self._B, self._QL
        if resume is not None and isinstance(resume, str):
            resume_path = resume
            resume = ckpt_mod.load(resume)
            if mh.is_multiprocess():
                # latest() reads a host-local directory listing, which can
                # lag on a shared filesystem (NFS attribute caching) — all
                # controllers must resume the SAME snapshot or the
                # replicated counters diverge (multihost.py rule 4).  The
                # oldest level any controller found is the safe agreement.
                agreed = mh.build_min(self.mesh)(resume.diameter)
                if agreed != resume.diameter:
                    import glob as _glob
                    import os as _os
                    d = _os.path.dirname(_os.path.abspath(resume_path))
                    # The agreed level's snapshot may be a piece group
                    # from ANY writer count (load() resolves siblings
                    # from any one piece) or a single file.
                    cands = sorted(_glob.glob(_os.path.join(
                        d, f"level_{agreed:05d}.p0of*.npz")))
                    alt = cands[0] if cands else _os.path.join(
                        d, f"level_{agreed:05d}.npz")
                    resume = ckpt_mod.load(alt)
        if resume is not None and resume.dims != dims:
            raise ValueError(
                f"checkpoint dims {resume.dims} != engine dims {dims}")
        if resume is None and init_states is None:
            raise ValueError("need init_states or resume")
        mp = mh.is_multiprocess()
        if mp:
            # Multi-controller trace recording: each controller's store
            # accumulates its own chips' records (_trace_parts) and the
            # stores are exchanged as per-controller piece files on the
            # shared filesystem (same R8 assumption as multi-host
            # checkpoints), merged lazily at replay().  That exchange
            # needs a directory every controller can see — require the
            # checkpoint_dir rather than silently recording a trace no
            # replay could complete.
            if cfg.record_trace and not (cfg.trace_dir
                                         or cfg.checkpoint_dir):
                raise NotImplementedError(
                    "multi-host trace recording needs trace_dir (or "
                    "checkpoint_dir) — a shared filesystem path, as for "
                    "multi-host checkpoints: controllers exchange their "
                    "trace stores as piece files there.  Alternatively "
                    "run with record_trace=False and pass the "
                    "violation's .state to engine.check.path_to_state "
                    "on one host — BFS order makes the result a "
                    "minimal-depth trace")
        # Collective agreement on host-local facts (clocks); identical-
        # everywhere decisions skip the round trip (multihost.py rule 4).
        any_flag = mh.build_any(self.mesh) if mp else None
        budget_agree = mh.build_budget_agree(self.mesh) if mp else None
        # TLCGet("queue") consults the per-controller pools; under a
        # process group the totals are psum-agreed (one extra round trip
        # per check — only paid when a queue budget is actually set).
        has_queue_budget = any(c == "queue" for c, _t in cfg.exit_conditions)
        pool_sum = (mh.build_sum(self.mesh)
                    if mp and has_queue_budget else None)
        if mp and cfg.record_trace:
            # Per-run piece-file id, agreed across controllers (min of
            # local clocks): a reused trace/checkpoint directory can
            # then never alias this run's pieces with a previous run's.
            # int32 — the agreement primitive's width; millisecond
            # clocks mod 2^31 collide across runs only at the same ms
            # within a ~24-day wrap, and only in a REUSED directory.
            self._trace_run_id = mh.build_min(self.mesh)(
                int(time.time() * 1000) & 0x7FFFFFFF)
        res = EngineResult(
            pipeline=self._pipeline_name,
            por_instances=(self._por_table.certified
                           if self._por_table is not None else 0),
            family_groups=_family_groups_meta(self.dims))
        self._cur_res = res     # run_end event reads it on error exits
        mt, evlog = self.metrics, self._evlog
        # What run_end adds for the mesh (``_run_end_extra``), gathered
        # from the chips by the chunk program itself: parents each chip
        # expanded and windows its owner insert ran (``route_insert``:
        # one a pass where K lanes hold a pass's arrivals) over the run;
        # each chip's next-level rows and shard keys as the last chunk
        # call left them.
        self._mesh_counts = {
            "chip_parents_expanded": [0] * n, "chip_next_count": [0] * n,
            "chip_shard_keys": [0] * n, "chip_insert_windows": [0] * n}
        self._growth_stalls = res.growth_stalls
        # TLC-style per-action coverage (obs/coverage.py); stats are
        # psum-replicated, so every controller accumulates identical
        # global counts.
        from ..obs import ActionCoverage
        coverage = self.coverage = ActionCoverage(dims.family_names,
                                                  dims.family_sizes)
        t_enter = time.time()
        trace = make_trace_store() if cfg.record_trace else TraceStore()
        self.trace = trace

        if resume is not None:
            # Shards must hold the checkpointed keys at <= half load.
            # The fullest owner's count is the same on every controller
            # (each loaded the whole key set): the restore's pieces go
            # by it.
            owner = np.asarray(resume.seen_hi, np.uint32) % np.uint32(n)
            most_keys = max(int(np.count_nonzero(owner == d))
                            for d in range(n))
            del owner
            while most_keys > self._CL // 2:
                self._CL *= 2
                self._rebuild_programs()

        CL = self._CL
        QLA = QL + self._PAD     # live rows + slice-overrun/scatter trash

        # Every device-resident buffer is allocated ALREADY SHARDED over
        # the mesh (zeros/fills jitted with explicit out_shardings): a
        # plain jnp.zeros would land the full n-chip array on one device
        # — invisible on the virtual CPU mesh, an instant OOM on a real
        # pod where per-chip capacities are sized to chip HBM.
        sharded_full = self._sharded_full

        qcur = sharded_full((n, QLA, sw), jnp.uint8)
        qnext = sharded_full((n, QLA, sw), jnp.uint8)
        shi = sharded_full((n, CL), _U32, SENTINEL)
        slo = sharded_full((n, CL), _U32, SENTINEL)
        ssize = sharded_full((n,), _I32)
        next_counts = sharded_full((n,), _I32)
        tbuf = tuple(sharded_full((n, self._TA), d)
                     for d in (jnp.uint32, jnp.uint32, jnp.uint32,
                               jnp.uint32, _I32))
        tcount = sharded_full((n,), _I32)
        from ..engine.spillpool import SpillPool
        pending = SpillPool(cfg.spill_dir)   # host pool (rows), global
        spill_next = SpillPool(cfg.spill_dir)
        # Async spill (engine/bfs.py): drains ride behind compute via a
        # spare next-queue; resolved at the next drain or level boundary.
        free_q: List = [sharded_full((n, QLA, sw), jnp.uint8)]
        inflight: List = []              # [(device array, per-chip counts)]

        def resolve_spill():
            while inflight:
                with mt.phase_timer("spill"):
                    arr, cnts = inflight.pop(0)
                    # _drain copies per-chip slices (np.concatenate), so
                    # no view into the recycled buffer survives.  A
                    # controller whose shards were all empty contributes
                    # no segment.
                    rows = self._drain(arr, cnts)
                    if len(rows):
                        spill_next.append(rows)
                    free_q.append(arr)

        if resume is None:
            with mt.phase_timer("roots_encode"):
                roots = stack_states(
                    [encode_state(s, dims) for s in init_states])
            if self._root_check is not None:
                with mt.phase_timer("root_check"):
                    v = find_root_violation(self._root_check, roots,
                                            init_states, B, self.inv_names)
                if v is not None:   # before warm-up: no checking time spent
                    if cfg.record_trace:
                        # Depth-0 counterexample must stay replayable:
                        # register the violating root under the Violation's
                        # fingerprint (engine/bfs.py rationale), and under
                        # a process group ALSO write this controller's
                        # trace piece — every controller takes this same
                        # early return (roots are replicated), and a
                        # sibling's replay() would otherwise block in
                        # _merge_trace_pieces waiting for a piece that was
                        # never written.
                        trace.roots.setdefault(v.fingerprint, v.state)
                        if mp:
                            self._write_trace_piece(trace)
                            self._trace_merged = False
                    res.violation = v
                    res.stop_reason = "violation"
                    res.levels.append(0)
                    res.wall_seconds = time.time() - t_enter
                    evlog.emit("violation", invariant=v.invariant,
                               fingerprint=hex(v.fingerprint), level=0)
                    return res
            with mt.phase_timer("roots_encode"):
                check_packable(roots, dims)   # silently-aliasing roots
                rows_np = flatten_states(roots, dims)
            if cfg.record_trace:
                with mt.phase_timer("root_check"):
                    rhi, rlo = (np.asarray(x) for x in
                                self._fp_rows(jnp.asarray(rows_np)))
                    for idx, s in enumerate(init_states):
                        trace.roots.setdefault(
                            (int(rhi[idx]) << 32) | int(rlo[idx]), s)

        # Warm-up compilation before the duration clock starts.  Inputs go
        # through put_global so each controller materializes only its own
        # shards (multihost.py rule 3; identical single-host).
        zero_counts = mh.put_global(np.zeros((n,), np.int32),
                                    self.mesh, P("x"))
        with mt.phase_timer("warmup"):
            out = self._ingest(
                mh.put_global(np.zeros((n, B, sw), ROW_DTYPE),
                              self.mesh, P("x")),
                mh.put_global(np.zeros((n, B), bool), self.mesh, P("x")),
                qnext, next_counts, shi, slo, ssize, tbuf, tcount)
            qnext, next_counts, shi, slo, ssize, tbuf = out[:6]
            out = self._chunk(qcur, zero_counts, jnp.int32(0),
                              qnext, next_counts, shi, slo, ssize, tbuf,
                              tcount, jnp.int32(self._CH))
            qnext, next_counts, shi, slo, ssize, tbuf = out[:6]
            # Placement-fixpoint second call (engine/bfs.py warm-up
            # rationale): free when outputs already carry the input
            # shardings, and pre-compiles the output-placement variant
            # when they don't.
            out = self._chunk(qcur, zero_counts, jnp.int32(0),
                              qnext, next_counts, shi, slo, ssize, tbuf,
                              tcount, jnp.int32(self._CH))
            qnext, next_counts, shi, slo, ssize, tbuf = out[:6]
            # The trace flush's programs, on every chip's buffers as the
            # chunk hands them back (engine/bfs.py warm-up rationale).
            if cfg.record_trace:
                for cols, _m in self._trace_parts(tbuf, [0] * n):
                    for length in self._fetch_lens:
                        self._fetch(cols, np.int32(0), length)
        flush = _TraceFlush(self, trace)
        calls = self._calls     # one row a device call (obs/calls.py)
        t0 = time.time()
        last_progress = t0
        self._batch_ema = 0.0

        if resume is not None:
            if cfg.record_trace:
                if resume.distinct > 0 and resume.trace_fps.size == 0:
                    raise ValueError(
                        "checkpoint was written with trace recording "
                        "disabled; resume with record_trace=False or "
                        "restart from scratch")
            elif resume.trace_fps.size > 0 and cfg.checkpoint_dir is not None:
                raise ValueError(
                    "resuming a trace-carrying checkpoint with trace "
                    "recording disabled would write trace-less snapshots "
                    "into the same directory, shadowing the intact ones "
                    "for any later trace-on resume; use a different "
                    "checkpoint_dir or keep tracing enabled")
            # The resume never holds the chips for host work they can
            # be given something to do under.  The frontier's first
            # segment goes up FIRST, into ``qcur``, which no insert
            # touches, and is not waited for: dispatched behind inserts
            # in flight, its ``_write_rows`` steps are held back by the
            # runtime one by one until the inserts have drained (a chip
            # measured 3.0 s in that upload against 0.05 s here).  Then
            # the key inserts are dispatched, each piece bucketed while
            # the chips insert the one before; the trace store's refill
            # (the host's alone) runs while they are in flight, and the
            # one wait comes last.  Every key is in its shard before the
            # first chunk call, and an overflow raises there.
            fr = np.ascontiguousarray(resume.frontier).astype(
                ROW_DTYPE, casting="safe", copy=False)
            level_rows = len(fr)
            if mp:
                # Disjoint frontier slices per controller; the union is
                # the checkpointed frontier.
                fr = fr[jax.process_index()::jax.process_count()]
            # Segment granularity = what one upload can take: this
            # controller's chips x QL rows (global n*QL single-host) — a
            # larger pre-split would make the consume loop's remainder
            # re-insert rewrite the pool head on every upload.
            seg_cap = QL * sum(
                1 for d in self.mesh.devices.flat
                if d.process_index == jax.process_index())
            # Pre-split into upload-sized segments (views).
            for i in range(0, len(fr), seg_cap):
                pending.append(fr[i:i + seg_cap])
            # The first segment goes up here, balanced across the chips;
            # the level loop uploads the others as it reaches them.
            with mt.phase_timer("restore_frontier"):
                qcur, cur_counts_dev = self._upload_segment(pending, qcur)
            # Rebuild shards from the flat key set: owner = fp_hi mod n.
            # Each controller materializes only its addressable shards, so
            # a checkpoint written by M controllers (piece group, merged
            # by checkpoint.load) resumes on any process count.
            with mt.phase_timer("restore_keys"):
                shi, slo, ssize, inserts = self._shards_from_keys(
                    resume.seen_hi, resume.seen_lo, most_keys)
            res.distinct = resume.distinct
            res.generated = resume.generated
            res.diameter = resume.diameter
            res.levels = list(resume.levels)
            res.action_counts = dict(resume.action_counts)
            self._note_family_base(resume)
            # Coverage-only resume seeding (engine/bfs.py rule: registry
            # counters are process-cumulative and must not be re-seeded).
            coverage.seed_generated(resume.action_counts)
            t0 -= resume.wall_seconds
            if cfg.record_trace:
                with mt.phase_timer("restore_trace"):
                    trace.add_batch(resume.trace_fps, resume.trace_parents,
                                    resume.trace_actions)
                    trace.roots.update(resume.roots)
                    self._mesh_counts.update(
                        store_growth(trace, "restore_"))
            # ``restore_keys`` a second time (a phase entered twice
            # accumulates): what of the inserts the host's work did not
            # cover.
            t_wait = time.time()
            with mt.phase_timer("restore_keys"):
                inserts.wait()
                cur_counts_dev.block_until_ready()
            # How much of the resume the chips paced (run_end): a wait
            # near nothing says the host still is the pace.  And what
            # the chips ran: the most probe rounds of any, and the lanes
            # of all (over the checkpoint's keys: 1.1 is the floor).
            self._mesh_counts.update(
                restore_pieces=inserts.pieces,
                restore_rounds=inserts.rounds,
                restore_lane_rounds=inserts.lane_rounds,
                restore_host_s=round(
                    t_wait - (inserts.since or t_wait), 3),
                restore_wait_s=round(time.time() - t_wait, 3))
            calls.start()       # the restore's spans are its own
        else:
            # Ingest roots round-robin across chips in B-sized waves.
            per_chip = [rows_np[i::n] for i in range(n)]
            max_chunks = max((-(-len(p) // B) for p in per_chip), default=0)
            drained = 0       # next-level rows pushed to host pools (global)
            cur_sum = 0       # next-level rows on device (replicated psum)
            self._open_level_span(0)
            calls.start()
            for c in range(max_chunks):
                # StopAfter covers ingest; the first wave always runs
                # (engine/bfs.py rationale).  Clock decisions are agreed
                # collectively under multi-controller.
                if c and cfg.max_seconds is not None:
                    over = time.time() - t0 > cfg.max_seconds
                    if any_flag is not None:
                        over = any_flag(over)
                    if over:
                        res.stop_reason = "duration_budget"
                        break
                if c and cfg.exit_conditions:
                    # "queue" during ingest: enqueued + landed spills +
                    # roots not yet ingested (engine/bfs.py rationale);
                    # pool rows psum-agreed under a process group.
                    pools = spill_next.total_rows()
                    if pool_sum is not None:
                        pools = pool_sum(pools)
                    hit = _exit_condition_hit(
                        cfg.exit_conditions, res,
                        cur_sum + pools
                        + sum(max(0, len(p) - c * B) for p in per_chip))
                    if hit:
                        res.stop_reason = hit
                        break
                wave = np.zeros((n, B, sw), ROW_DTYPE)
                valid = np.zeros((n, B), bool)
                for d in range(n):
                    part = per_chip[d][c * B:(c + 1) * B]
                    wave[d, :len(part)] = part
                    valid[d, :len(part)] = True
                calls.dispatch()
                with mt.phase_timer("ingest") as ingest_span:
                    out = self._ingest(
                        mh.put_global(wave, self.mesh, P("x")),
                        mh.put_global(valid, self.mesh, P("x")),
                        qnext, next_counts, shi, slo, ssize,
                        tbuf, tcount)
                    (qnext, next_counts, shi, slo, ssize, tbuf, tcount,
                     istats, ivrow, ivfp) = out
                    ist = np.asarray(istats)
                res.distinct += int(ist[0])
                mt.counter("engine/ingest_calls")
                mt.counter("engine/distinct", int(ist[0]))
                cur_sum = int(ist[3])
                if int(ist[1]):
                    raise RuntimeError("seen-set probe failure during "
                                       "ingest; raise seen_capacity")
                flush_s = 0.0
                if cfg.record_trace and int(ist[0]):
                    # Roots, outside every window: at once.
                    flush.start(self._trace_parts(
                        tbuf, self._local_counts(tcount)))
                    flush_s = flush.finish("flush_drained")
                # A wave's ingest dispatches and fetches in one span.
                calls.row("ingest", "ingest", 0, ingest_span.seconds, 0.0,
                          flush_s, 0.0, c + 1, 0, 1, int(valid.sum()),
                          int(ist[0]), distinct=res.distinct,
                          generated=res.generated, diameter=0,
                          frontier=len(rows_np), next_count=cur_sum,
                          seen_size=int(ist[6]))
                tcount = sharded_full((n,), _I32)
                (shi, slo, ssize, qnext, next_counts, tbuf,
                 t0) = self._grow_precompiled(shi, slo, ssize, qcur, qnext,
                                              next_counts, tbuf, tcount,
                                              t0, int(ist[6]))
                if int(ist[2]) > self._QTH:  # ingest adds <= B per wave
                    with mt.phase_timer("spill"):
                        rows = self._drain(
                            qnext, self._local_counts(next_counts))
                        if len(rows):
                            spill_next.append(rows)
                    evlog.emit("spill", rows=cur_sum, level=0,
                               where="ingest")
                    drained += cur_sum
                    cur_sum = 0
                    next_counts = sharded_full((n,), _I32)
                if self._check_violation_ingest(res, ist, ivrow, ivfp):
                    break
            level_rows = drained + cur_sum
            res.levels.append(level_rows)
            # Seen gauges refreshed BEFORE the level-0 emit (engine/
            # bfs.py rationale): its level_stats snapshot reads them,
            # and a warm shared registry would otherwise leak the
            # previous run's values into this run's level-0 row.  Same
            # per-chip convention as the chunk loop's gauge updates.
            mt.gauge("engine/seen_capacity", self._CL)
            mt.gauge("engine/seen_size", int(ist[6]))
            self._sample_skew(res, next_counts, ssize)
            self._emit_level_event(res, level_rows)
            qcur, qnext = qnext, qcur
            cur_counts_dev = next_counts
            next_counts = sharded_full((n,), _I32)
            pending, spill_next = spill_next, pending

        skip_ckpt_level = resume.diameter if resume is not None else -1
        last_ckpt = time.time() if resume is not None else float("-inf")
        while level_rows > 0 \
                and res.violation is None and res.stop_reason == "exhausted":
            if cfg.checkpoint_dir is not None \
                    and res.diameter % max(1, cfg.checkpoint_every) == 0 \
                    and res.diameter != skip_ckpt_level:
                want_ckpt = (time.time() - last_ckpt
                             >= cfg.checkpoint_interval_seconds)
                if any_flag is not None:
                    # Interval clocks differ per host; a piece group is
                    # only resumable when EVERY controller wrote its piece
                    # — agree, so groups are always complete.
                    want_ckpt = any_flag(want_ckpt)
                if want_ckpt:
                    with mt.phase_timer("checkpoint"):
                        self._write_checkpoint(qcur, cur_counts_dev,
                                               pending, shi, slo, res,
                                               trace,
                                               wall=time.time() - t0)
                    last_ckpt = time.time()
                    evlog.emit("checkpoint", level=res.diameter,
                               distinct=res.distinct)
            if cfg.max_diameter is not None \
                    and res.diameter >= cfg.max_diameter:
                res.stop_reason = "diameter_budget"
                break
            self._open_level_span(res.diameter + 1)
            # Level loop over segments: device-resident rows first, then
            # host-pool segments (balanced re-uploads).  Budgeted runs
            # slow-start each level (engine/bfs.py rationale).  The level
            # width is derived in-program (pmax), so the sub-loop is
            # do-while: one call, then loop while the replicated offset
            # has not crossed the replicated width.
            calls_in_level = 0
            drained = 0
            cur_sum = 0
            while True:
                offset = 0
                while True:
                    allowed, rule = self._CH, "full"
                    if cfg.max_seconds is not None:
                        remaining = cfg.max_seconds - (time.time() - t0)
                        over = remaining <= 0
                        # Half-window sizing + per-level slow-start
                        # (engine/bfs.py budget_call_size)
                        allowed, rule = budget_call_size(
                            self._CH, remaining, self._batch_ema,
                            calls_in_level)
                        if budget_agree is not None:
                            # allowed is an input to a collective program:
                            # all controllers must pass the same value —
                            # one fused round trip agrees both the stop
                            # flag and the chunk budget.
                            over, allowed = budget_agree(over, allowed)
                            allowed = max(1, allowed)
                        if over:
                            res.stop_reason = "duration_budget"
                            break
                        if rule != "ramp":
                            # The level's width is the program's to
                            # know: a deadline call here may be one the
                            # level's end cut shorter still.
                            mt.counter(f"engine/{rule}_calls")
                    calls_in_level += 1
                    # The registry's count pairs the ``chunk`` span with
                    # the ``account`` span and the row of the same call
                    # (engine/bfs.py).
                    call = int(mt.counter_value("engine/chunk_calls")) + 1
                    if _faults.ACTIVE:
                        # Same deterministic sites as the single-chip
                        # loop (resilience/): mid-level kill, simulated
                        # RESOURCE_EXHAUSTED, a stall between two calls.
                        _faults.fire("kill", level=res.diameter,
                                     chunk=calls_in_level)
                        _faults.fire("oom", level=res.diameter,
                                     chunk=calls_in_level)
                        _faults.fire("stall", phase="gap", call=call,
                                     level=res.diameter,
                                     chunk=calls_in_level)
                    calls.dispatch()
                    t_call = time.time()
                    # Device-profiler window (--xla-profile): the mesh
                    # brackets its sharded dispatch exactly like the
                    # single-chip loop — same "chunk" span name, same
                    # per-run capture object from _telemetry_run, one
                    # call site (profiled/unprofiled must not diverge).
                    cap = getattr(self, "_xla_capture", None)
                    step_cm = (cap.step() if cap is not None
                               and not cap.done
                               else contextlib.nullcontext())
                    with mt.phase_timer("chunk", call=call) as chunk_span, \
                            step_cm:
                        out = self._chunk(
                            qcur, cur_counts_dev,
                            jnp.int32(offset), qnext, next_counts, shi,
                            slo, ssize, tbuf, tcount, jnp.int32(allowed))
                        (qnext, next_counts, shi, slo, ssize, tbuf,
                         tcount, stats, drow_g, vrow_g, vfp_g) = out
                    # The host half of the previous call's flush, while
                    # the chips run this one.
                    flush_s = flush.finish("flush_overlapped")
                    # One blocking sync per chunk call (engine/bfs.py):
                    # this phase is the mesh's device compute + collective
                    # time.
                    with mt.phase_timer("stats_fetch") as fetch_span:
                        if _faults.ACTIVE:
                            _faults.fire("stall", phase="wait", call=call,
                                         level=res.diameter,
                                         chunk=calls_in_level)
                        st = np.asarray(stats)
                    passes = int(st[1])
                    if passes < allowed:
                        rule = "level_end"      # engine/bfs.py
                    account = mt.open_span(
                        "account", call=call, passes=passes, rule=rule,
                        parents=int(st[15]), new=int(st[3]))
                    if _faults.ACTIVE:
                        _faults.fire("stall", phase="host", call=call,
                                     level=res.diameter,
                                     chunk=calls_in_level)
                    self._count_chunk_call(passes, int(st[15]))
                    written = self._count_per_chip(st)
                    if int(st[1]):
                        per = (time.time() - t_call) / int(st[1])
                        # Conservative: jump up instantly, decay slowly
                        # (engine/bfs.py rationale).
                        self._batch_ema = (
                            per if not self._batch_ema else
                            max(per, 0.5 * self._batch_ema + 0.5 * per))
                    offset = int(st[0])
                    max_count = int(st[6])
                    cur_sum = int(st[8])
                    res.generated += int(st[2])
                    res.distinct += int(st[3])
                    # Packed-stats fetch feeds the registry (the one live
                    # counter source — engine/bfs.py rationale).
                    mt.counter("engine/generated", int(st[2]))
                    mt.counter("engine/distinct", int(st[3]))
                    mt.gauge("engine/seen_size", int(st[10]))
                    mt.gauge("engine/seen_capacity", self._CL)
                    mt.gauge("engine/next_count", cur_sum)
                    mt.gauge("engine/diameter", res.diameter)
                    F = len(dims.family_sizes)
                    if int(st[2]):
                        for name, c in zip(dims.family_names,
                                           st[16:16 + F]):
                            res.action_counts[name] = (
                                res.action_counts.get(name, 0) + int(c))
                    # Coverage from the same psum'd packed stats
                    # (obs/coverage.py; engine/bfs.py rationale).
                    coverage.add_chunk(int(st[15]), st[16:16 + F],
                                       st[16 + F:16 + 2 * F],
                                       st[16 + 2 * F:16 + 3 * F])
                    account.close()
                    # The call's one record (obs/calls.py): the mesh
                    # feeds the same rows, and through them the same
                    # watch/postmortem view, as the single-chip loop.
                    calls.row("chunk", rule, passes, chunk_span.seconds,
                              fetch_span.seconds, flush_s, account.seconds,
                              call, res.diameter + 1, allowed, int(st[15]),
                              int(st[3]), distinct=res.distinct,
                              generated=res.generated,
                              diameter=res.diameter, frontier=int(st[9]),
                              offset=offset, next_count=cur_sum,
                              seen_size=int(st[10]))
                    if cfg.record_trace and written.any():
                        # The device half only (engine/bfs.py): ahead in
                        # every chip's stream of the next call, which
                        # donates ``tbuf``.
                        flush.start(self._trace_parts(tbuf, written))
                    if int(st[4]):
                        raise RuntimeError(
                            f"{int(st[4])} successors exceeded fixed-width "
                            f"capacity (max_log={dims.max_log}, n_msg_slots"
                            f"={dims.n_msg_slots}) or wrapped the uint8 "
                            f"row; rerun with larger capacities/bounds")
                    if int(st[5]):
                        raise RuntimeError(
                            "seen-set probe failure (load spiked within "
                            "one chunk); raise seen_capacity or lower "
                            "sync_every")
                    tcount = sharded_full((n,), _I32)
                    if int(st[10]) > self._CL // 2:
                        # The shards grow: a rehash on the host, then
                        # rebuilt programs that hand back another ``tbuf``.
                        flush.finish("flush_drained")
                    (shi, slo, ssize, qnext, next_counts, tbuf,
                     t0) = self._grow_precompiled(
                        shi, slo, ssize, qcur, qnext, next_counts, tbuf,
                        tcount, t0, int(st[10]))
                    if int(st[7]) > self._QTH:
                        # Watermark (replicated pmax): drain unless this is
                        # the level's very last chunk — then the boundary
                        # swap is cheaper.  "More segments?" is host-local
                        # state, agreed collectively when it matters.
                        more_here = offset < max_count
                        if not more_here:
                            more_here = (any_flag(bool(pending))
                                         if any_flag is not None
                                         else bool(pending))
                        if more_here:
                            flush.finish("flush_drained")
                            resolve_spill()
                            with mt.phase_timer("spill"):
                                cnts = self._local_counts(next_counts)
                                qnext.copy_to_host_async()
                                inflight.append((qnext, cnts))
                                qnext = free_q.pop()
                                next_counts = sharded_full((n,), _I32)
                            evlog.emit("spill", rows=cur_sum,
                                       level=res.diameter,
                                       where="chunk_loop")
                            drained += cur_sum
                            cur_sum = 0
                    if int(st[11]):
                        vf = np.asarray(vfp_g)
                        res.violation = Violation(
                            invariant=self.inv_names[int(st[13])],
                            state=decode_state(unflatten_state(
                                np.asarray(vrow_g), dims), dims),
                            fingerprint=(int(vf[0]) << 32) | int(vf[1]))
                        res.stop_reason = "violation"
                        evlog.emit(
                            "violation",
                            invariant=res.violation.invariant,
                            fingerprint=hex(res.violation.fingerprint),
                            level=res.diameter)
                        break
                    if int(st[12]) and self._check_deadlock:
                        res.deadlock = decode_state(unflatten_state(
                            np.asarray(drow_g), dims), dims)
                        res.stop_reason = "deadlock"
                        evlog.emit("deadlock", level=res.diameter)
                        break
                    want_progress = bool(
                        cfg.progress_interval_seconds
                        and time.time() - last_progress
                        >= cfg.progress_interval_seconds)
                    if cfg.exit_conditions or want_progress:
                        # "queue" counts the FULL unexplored queue: this
                        # level's remainder (replicated psum) + next-level
                        # rows + landed and in-flight spill segments.
                        # Pool rows are per-controller; psum-agree them
                        # when a queue budget needs the global total.
                        local_pools = (
                            pending.total_rows() + spill_next.total_rows()
                            + sum(sum(c.values()) for _b, c in inflight))
                        if pool_sum is not None:
                            local_pools = pool_sum(local_pools)
                        queue_rows = (
                            int(st[9]) + cur_sum + local_pools)
                        if want_progress:
                            _progress_line(res, t0, queue_rows,
                                           int(st[14]), metrics=mt)
                            # Coverage on the same cadence (engine/
                            # bfs.py): registry gauges + one event.
                            coverage.feed_metrics(mt)
                            evlog.emit("coverage", level=res.diameter,
                                       actions=coverage.snapshot())
                            last_progress = time.time()
                        # Last: a violation/deadlock in the same chunk
                        # outranks a budget stop (engine/bfs.py rationale).
                        hit = _exit_condition_hit(
                            cfg.exit_conditions, res, queue_rows)
                        if hit:
                            res.stop_reason = hit
                            break
                    if offset >= max_count:
                        break
                more_segments = (any_flag(bool(pending))
                                 if any_flag is not None else bool(pending))
                if res.stop_reason != "exhausted" \
                        or res.violation is not None or not more_segments:
                    break
                # Upload the next host segment, balanced across this
                # controller's chips (each controller re-uploads its own
                # pool; the segment cap keeps any one upload within QL
                # rows per chip).
                with mt.phase_timer("upload"):
                    qcur, cur_counts_dev = self._upload_segment(pending,
                                                                qcur)
            # The level is built, or the run stops: what follows reads
            # the store (a snapshot, a replay, a trace piece).
            flush.finish("flush_drained")
            if res.stop_reason != "exhausted" or res.violation is not None:
                break
            resolve_spill()      # level boundary: all drains must land
            res.diameter += 1
            level_rows = drained + cur_sum
            res.levels.append(level_rows)
            self._sample_skew(res, next_counts, ssize)
            self._emit_level_event(res, level_rows)
            qcur, qnext = qnext, qcur
            cur_counts_dev = next_counts
            next_counts = sharded_full((n,), _I32)
            pending, spill_next = spill_next, pending
            qcur, cur_counts_dev = self._deal_out(
                qcur, cur_counts_dev, pending, res.diameter)

        res.wall_seconds = time.time() - t0
        if mp and cfg.record_trace:
            # Every controller reaches this exit (stop decisions are
            # collectively agreed), so the piece group is always
            # complete; replay() merges the siblings on demand.
            self._write_trace_piece(trace)
            self._trace_merged = False
        return res

    # ------------------------------------------------------------------
    def _deal_out(self, qcur, cur_counts, pending, level):
        """A row lies on the chip that generated it, so the rows of a
        check that starts from one root would all lie on one chip, which
        would then do every expansion (the others answering dedup
        queries only) and hold the whole frontier.  At a level boundary
        a frontier whose fullest chip holds over a batch more than its
        even share is therefore drained and dealt out evenly, by the
        drain and the balanced upload a spill uses.  Single-controller
        runs only: under a process group the steps of an upload are
        collective calls, and the controllers' shares differ.
        Returns (qcur, per-chip counts)."""
        cnts = self._local_counts(cur_counts)
        rows = sum(cnts.values())
        if (jax.process_count() > 1 or not cnts
                or max(cnts.values()) - -(-rows // len(cnts)) <= self._B):
            return qcur, cur_counts
        with self.metrics.phase_timer("rebalance"):
            pending.insert(0, self._drain(qcur, cnts))
            qcur, cur_counts = self._upload_segment(pending, qcur)
        self._evlog.emit("rebalance", level=level, rows=rows,
                         fullest=max(cnts.values()))
        return qcur, cur_counts

    def _upload_segment(self, pending, qcur):
        """The next host segment of the current level into ``qcur``,
        balanced across this controller's chips (each controller
        re-uploads its own pool; a segment is cut to QL rows a chip).
        Only the rows go up, in steps of ``UPLOAD_ROWS`` a chip written
        into the queue that is already there: rows past a chip's count
        are never read (the slice stage masks them), so nothing is
        zeroed and no queue-sized buffer is built on the host.
        Returns (qcur, per-chip counts)."""
        n, sw, QL = self.n_dev, self._sw, self._QL
        QLA = QL + self._PAD
        my_rows = [i for i, d in enumerate(self.mesh.devices.flat)
                   if d.process_index == jax.process_index()]
        cap = len(my_rows) * QL
        seg = pending.pop(0) if pending else np.zeros((0, sw), ROW_DTYPE)
        while len(seg) > cap:
            pending.insert(0, seg[cap:])
            seg = seg[:cap]
        share = -(-len(seg) // len(my_rows)) if len(seg) else 0
        parts = {di: seg[k * share:(k + 1) * share]
                 for k, di in enumerate(my_rows)}
        cnts = np.zeros((n,), np.int32)
        for di, part in parts.items():
            cnts[di] = len(part)
        shq = NamedSharding(self.mesh, P("x"))
        step = min(UPLOAD_ROWS, QLA)
        for base in range(0, share, step):
            # The last step is moved back to end with the queue, and
            # writes some rows a second time.
            at = min(base, QLA - step)

            def rows_of(idx, at=at):
                part = parts[idx[0].start or 0][at:at + step]
                if len(part) == step:
                    return part[None]
                buf = np.zeros((1, step, sw), ROW_DTYPE)
                buf[0, :len(part)] = part
                return buf
            qcur = self._write_rows(
                qcur, jax.make_array_from_callback((n, step, sw), shq,
                                                   rows_of),
                jnp.int32(at))
        return qcur, jax.make_array_from_callback(
            (n,), shq, lambda idx: cnts[idx[0].start:idx[0].stop])

    def _count_per_chip(self, st) -> np.ndarray:
        """One chunk call's share of the per-chip counts: the tail of
        the statistics just fetched is each chip's own (parents
        expanded, next-level rows, shard keys, insert windows run, trace
        records written).  Returns the last, by chip: what the call's
        flush fetches, with no further copy from any chip."""
        n, mc = self.n_dev, self._mesh_counts
        per_chip = np.asarray(st[len(st) - 5 * n:]).reshape(5, n)
        for key, row in (("chip_parents_expanded", per_chip[0]),
                         ("chip_insert_windows", per_chip[3])):
            mc[key] = [a + int(b) for a, b in zip(mc[key], row)]
        mc["chip_next_count"] = [int(v) for v in per_chip[1]]
        mc["chip_shard_keys"] = [int(v) for v in per_chip[2]]
        return per_chip[4]

    def _run_end_extra(self) -> dict:
        return dict(getattr(self, "_mesh_counts", {}))

    def _local_counts(self, counts) -> dict:
        """{global chip row -> count} for THIS controller's addressable
        shards (single-controller: all chips — behavior unchanged)."""
        return {_chip(s): int(np.asarray(s.data)[0])
                for s in counts.addressable_shards}

    def _drain(self, qnext, cnts: dict) -> np.ndarray:
        """This controller's queued rows -> one host array (spill).  Each
        controller drains only its addressable shards; the union across
        controllers is the global queue (multi-controller rule 2)."""
        segs = []
        for s in sorted(qnext.addressable_shards, key=_chip):
            c = cnts.get(_chip(s), 0)
            if c:
                # Sliced on the chip: a queue is QL rows long whatever
                # it holds.
                segs.append(np.asarray(s.data[:, :c])[0])
        return np.concatenate(segs) if segs else \
            np.zeros((0, self._sw), ROW_DTYPE)

    def _grow_precompiled(self, shi, slo, ssize, qcur, qnext, next_counts,
                          tbuf, tcount, t0, max_ssize):
        """Grow the seen shards when loaded past threshold, pre-compile
        the rebuilt programs at the new shape with a zero-trip call, and
        keep the rehash + compile off the duration clock (engine/bfs.py
        rule).  ``max_ssize`` is the psum-replicated pmax of shard loads
        (from the packed stats), so every controller takes the same
        branch.  Returns (shi, slo, ssize, qnext, next_counts, tbuf, t0)."""
        if max_ssize <= self._CL // 2:
            return shi, slo, ssize, qnext, next_counts, tbuf, t0
        # A span, as on one chip: the host loop's rows take what of a gap
        # lay in spans of the loop's own for named work (obs/calls.py).
        with self.metrics.phase_timer("grow") as grow:
            self._grow_attempts = getattr(self, "_grow_attempts", 0) + 1
            if _faults.ACTIVE:
                # A growth OOM here propagates to the shared degradation
                # wrapper (halve batch + resume); the per-shard rebuild
                # has no safe mid-way retry point, unlike the single-chip
                # table.
                _faults.fire("oom", grow=self._grow_attempts)
            shi, slo, ssize = self._grow_seen(shi, slo, max_ssize)
            from . import multihost as mh
            zero_counts = mh.put_global(
                np.zeros((self.n_dev,), np.int32), self.mesh, P("x"))
            out = self._chunk(
                qcur, zero_counts, jnp.int32(0), qnext,
                next_counts, shi, slo, ssize, tbuf, tcount,
                jnp.int32(1))
            qnext, next_counts, shi, slo, ssize, tbuf = out[:6]
        # Off the clock, but recorded (engine/bfs.py rationale): mesh
        # growth additionally re-inits + retraces both programs, the
        # expensive path VERDICT r3 weak #7 wants measured on silicon.
        # The stall IS the span (rehash + retrace + precompile).
        stall = grow.seconds
        t0 += stall
        self._growth_stalls.append(
            (self.n_dev * self._CL, round(stall, 3)))
        from ..obs import device_memory_stats
        self.metrics.counter("engine/fpset_resizes")
        rounds, lane_rounds = self._rebuild_counts
        self._evlog.emit("fpset_resize",
                         capacity=self.n_dev * self._CL,
                         stall_seconds=round(stall, 3),
                         rebuild_rounds=rounds,
                         rebuild_lane_rounds=lane_rounds,
                         memory=device_memory_stats())
        return shi, slo, ssize, qnext, next_counts, tbuf, t0

    def shard_keys(self, shi, slo) -> dict:
        """{chip: (hi, lo)} — the keys each of this controller's shards
        holds, as they lie on it."""
        out = {}
        for s_hi, s_lo in zip(
                sorted(shi.addressable_shards, key=_chip),
                sorted(slo.addressable_shards, key=_chip)):
            hi_h = np.asarray(s_hi.data)[0]
            lo_h = np.asarray(s_lo.data)[0]
            real = ~((hi_h == SENTINEL) & (lo_h == SENTINEL))
            out[_chip(s_hi)] = (hi_h[real], lo_h[real])
        return out

    def _write_checkpoint(self, qcur, cur_counts, pending, shi, slo, res,
                          trace, wall):
        """Same snapshot format as the single-chip engine: flat frontier +
        flat key set (chip assignment is recomputed on resume)."""
        from ..engine import checkpoint as ckpt_mod
        import os
        if self.config.record_trace:
            tf, tp, ta = trace.export()
            roots = dict(trace.roots)
        else:
            tf = np.empty(0, np.uint64)
            tp = np.empty(0, np.uint64)
            ta = np.empty(0, np.int32)
            roots = {}
        # This controller's share only: its pool + device shards + seen
        # shards.  Multi-host writes one piece per controller (identical
        # replicated counters in each); checkpoint.load merges the group.
        frontier, front_cleanup = pending.concat_with(
            self._drain(qcur, self._local_counts(cur_counts)))
        shards = self.shard_keys(shi, slo)
        # Sorted by (hi, lo), as one 64-bit key.
        keys = np.concatenate(
            [(h.astype(np.uint64) << np.uint64(32)) | l
             for h, l in shards.values()] or [np.empty(0, np.uint64)])
        keys.sort()
        ck = ckpt_mod.Checkpoint(
            dims=self.dims, frontier=frontier,
            seen_hi=(keys >> np.uint64(32)).astype(np.uint32),
            seen_lo=keys.astype(np.uint32),
            distinct=res.distinct, generated=res.generated,
            diameter=res.diameter, levels=tuple(res.levels),
            action_counts=dict(res.action_counts),
            wall_seconds=wall,
            trace_fps=tf, trace_parents=tp, trace_actions=ta, roots=roots)
        if jax.process_count() > 1:
            path = ckpt_mod.piece_path(self.config.checkpoint_dir,
                                       res.diameter, jax.process_index(),
                                       jax.process_count())
        else:
            path = os.path.join(self.config.checkpoint_dir,
                                f"level_{res.diameter:05d}.npz")
        try:
            # Counted there (this controller's piece); the parts before
            # the deflate are spans of the one-chip engine only.
            ckpt_mod.save(path, ck, metrics=self.metrics)
        finally:
            front_cleanup()
        # Retention after the successful write (engine/bfs.py rule).
        # Under a process group every controller runs the same gc over
        # the shared dir; deletions race benignly (missing files are
        # skipped) and only complete intact groups count toward keep.
        removed = ckpt_mod.gc(self.config.checkpoint_dir,
                              self.config.keep_checkpoints)
        if removed:
            self.metrics.counter("engine/checkpoints_gcd", removed)

    # What ``_TraceFlush`` (engine/bfs.py) takes from this engine: the
    # fetch program, the one-chip engine's way of recording a fetched
    # piece, and one plan for each chip.
    _fetch = staticmethod(_fetch_shard)
    _record = staticmethod(BFSEngine._record)

    def _trace_parts(self, tbuf, counts) -> list:
        """``[(a chip's five trace columns, its records)]`` for this
        controller's ADDRESSABLE chips only, in chip order; ``counts`` is
        indexed by chip.  Under a process group, fetching the global
        arrays would be a cross-host gather; instead each controller's
        store accumulates the records its own chips produced, fetched by
        one-device programs no other controller takes part in, and the
        stores are merged through per-controller piece files at replay
        time (:meth:`_merge_trace_pieces`)."""
        comps = [sorted(x.addressable_shards, key=_chip) for x in tbuf]
        return [(tuple(s.data for s in cols),
                 int(counts[_chip(cols[0])]))
                for cols in zip(*comps)]

    # -- multi-host trace exchange (shared filesystem, like R8) ---------
    @property
    def _trace_exchange_dir(self) -> str:
        return self.config.trace_dir or self.config.checkpoint_dir

    def _trace_piece_path(self, i: int, m: int) -> str:
        # The collectively-agreed per-run id in the name keeps a reused
        # directory safe: without it, a controller's merge poll could
        # match a PREVIOUS run's piece (same (dir, i, m) name) written
        # before a slower sibling finishes fsyncing the current one, and
        # replay would silently miss that sibling's new records.
        return os.path.join(
            self._trace_exchange_dir,
            f"trace_run_{self._trace_run_id:08x}.p{i}of{m}.npz")

    def _write_trace_piece(self, trace) -> None:
        """One piece per controller, written at every run exit (all
        controllers take the same exit — control flow is collectively
        agreed), so the union of pieces is the global trace.  Same
        shared-filesystem assumption as multi-host checkpoints (R8) —
        which record_trace under a process group therefore requires
        (``trace_dir``, defaulting to ``checkpoint_dir``)."""
        tf, tp, ta = trace.export()
        if _faults.ACTIVE:
            # Injected slow sibling: exercises _merge_trace_pieces'
            # poll/deadline path without needing a genuinely slow host.
            _faults.fire("trace_piece_delay",
                         piece=jax.process_index())
        d = self._trace_exchange_dir
        os.makedirs(d, exist_ok=True)
        path = self._trace_piece_path(
            jax.process_index(), jax.process_count())
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez_compressed(f, fps=tf, parents=tp, actions=ta)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _merge_trace_pieces(self, timeout_s: Optional[float] = None) -> None:
        """Fold every sibling controller's trace piece into this store
        (idempotent; records are keyed by fingerprint).  Sibling files
        appear within the skew of the collective run exit; poll rather
        than requiring an extra barrier.

        Deadline: ``EngineConfig.trace_merge_timeout_seconds`` when set;
        otherwise a 30 s base plus an allowance proportional to THIS
        controller's piece size — pieces are written at the same exit
        with similar record counts, so a big local piece predicts
        siblings still compressing/fsyncing theirs (~8 MB/s floor)."""
        m = jax.process_count()
        my_piece = self._trace_piece_path(jax.process_index(), m)
        try:
            my_bytes = os.path.getsize(my_piece)
        except OSError:
            my_bytes = 0
        if timeout_s is None:
            timeout_s = self.config.trace_merge_timeout_seconds
        if timeout_s is None:
            timeout_s = 30.0 + my_bytes / (8 << 20)
        deadline = time.time() + timeout_s
        for i in range(m):
            if i == jax.process_index():
                continue
            path = self._trace_piece_path(i, m)
            while not os.path.exists(path):
                if time.time() > deadline:
                    raise FileNotFoundError(
                        f"trace piece {path} not written within "
                        f"{timeout_s:.0f}s — controller {i} may still be "
                        f"compressing its piece (this controller's was "
                        f"{my_bytes} bytes; larger traces take longer), "
                        f"or it exited the run abnormally.  If it is just "
                        f"slow, raise "
                        f"EngineConfig.trace_merge_timeout_seconds")
                time.sleep(0.05)
            with self.metrics.phase_timer("trace_merge"):
                with np.load(path) as z:
                    self.trace.add_batch(z["fps"], z["parents"],
                                         z["actions"])

    def _check_violation_ingest(self, res, ist, vrow, vfp) -> bool:
        """``ist``/``vrow``/``vfp`` are the ingest program's replicated
        stats and lowest-flagged-chip violation broadcast."""
        if not int(ist[4]):
            return False
        vf = np.asarray(vfp)
        res.violation = Violation(
            invariant=self.inv_names[int(ist[5])],
            state=decode_state(
                unflatten_state(np.asarray(vrow), self.dims), self.dims),
            fingerprint=(int(vf[0]) << 32) | int(vf[1]))
        res.stop_reason = "violation"
        # Same event every other violation path emits — consumers filter
        # on event=="violation" for the counterexample record.
        self._evlog.emit("violation", invariant=res.violation.invariant,
                         fingerprint=hex(res.violation.fingerprint),
                         level=0)
        return True

    # Replay shares the single-engine mechanism.  Under a process group
    # the trace chain crosses controllers (a child inserted on this
    # host's chips may have a parent recorded by another controller), so
    # the sibling piece files are folded in first — once.
    def replay(self, fp: int):
        from ..engine.bfs import BFSEngine  # reuse logic via duck typing
        from . import multihost as mh
        if (mh.is_multiprocess() and self.config.record_trace
                and not getattr(self, "_trace_merged", True)):
            self._merge_trace_pieces()
            self._trace_merged = True
        return BFSEngine.replay(self, fp)
