"""Swarm mode — W deterministic randomized walks per device in lockstep.

The second product tier (ROADMAP item 5): where the exhaustive engines
prove, the swarm *hunts*.  A swarm run advances W independent walkers
one action per step through the same BLEST-grouped expand kernels the
BFS engines use, with three structural differences that remove every
host round-trip from the hot loop:

- **no global seen-set** — each walk dedups against a fixed-size ring
  of its own last R accepted fingerprints (ops/walk_kernels.py), so
  throughput never pays the sorted-FPSet merge or its growth stalls;
- **counter-based PRNG** — every decision (successor draw, restart
  root) is a pure hash of ``(seed, walk, step)``, never a split-chain
  key.  A (seed, walks, depth) run therefore has a bit-identical
  visited-fingerprint multiset and an identical verdict across runs
  AND across device batch-size changes (tests/test_swarm.py pins it),
  and a violating walk is exactly replayable;
- **per-walk violation latch** — the same (root, action-ring) latch the
  simulator carries, extended with the global step index so the
  reported violation is the *globally first* one in (step, walk) order
  — partition-invariant, not a race between device slices.

Checking semantics match the simulator's TLC ``-simulate`` shape: every
step evaluates the registered invariants on the chosen successor,
walks restart on dead ends / pack overflow / constraint stops / ring
revisits / the depth bound, and a latched violation replays host-side
through the expand kernel into a full ``[(action, PyState)]`` trace —
``engine/explain.py`` renders it through the identical
``write_counterexample`` path as the exhaustive engines (this class
duck-types ``replay``/``dims``).

Telemetry speaks the swarm dialect of the house schema: ``swarm/steps``
/ ``swarm/walks`` / ``swarm/visited`` counters, ``swarm_progress`` run
events (payload object ``swarm``; registered in obs/events.py), a
statespace report with an embedded ``swarm`` block, and a ``run_end``
carrying the same ``swarm`` payload — so ``validate_run_events``, the
history ledger (``kind=swarm``) and the serving layer's job surface
consume swarm runs unchanged.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from ..models.actions import build_expand
from ..models.dims import RaftDims
from ..models.invariants import build_inv_id
from ..models.pystate import PyState
from ..models.schema import (build_pack_guard, check_packable,
                             decode_state, encode_state, flatten_state,
                             flatten_states, stack_states, unflatten_state)
from ..obs import (MetricsRegistry, RunEventLog, SpanTracer,
                   device_memory_stats, events_path, phase_delta)
from ..obs.calls import CallLog
from ..obs.flight import RECORDER as _FLIGHT
from ..obs.metrics import SCOPE_PREFIX, process_record
from ..ops.fingerprint import build_fingerprint
from ..resilience import faults as _faults
from ..ops.walk_kernels import (CHOICE_STREAM, FAMILY_STREAM, INIT_STREAM,
                                ROOT_STREAM, bloom_init, bloom_probe,
                                bloom_push, family_subset, preferred_choice,
                                ring_init, ring_probe, ring_push, ring_reset,
                                walk_bits)
from .replay import ReplayScan
from .bfs import (Violation, _resolve_pipeline, compiles_by_span,
                  watch_compiles)

_I32 = jnp.int32
_U32 = jnp.uint32

# The stages of one lockstep step, in order, as they are named in the
# compiled walk chunk (named scopes, as engine/chunk.py STAGES): the
# guards of every instance (``masks``), the counter-hash draw among the
# enabled ones (``choose``), the chosen successor and its packed row
# (``lane_out``), its key (``fingerprint``), the invariants and the
# first-violation latch (``latch``), the constraint, the ring probe and
# push, the action record and the restart (``ring``), the observatory's
# tallies (``hunt``).  Scope names are not in the compile-cache key
# (chunk.py STAGES_TAG rationale), so the walk chunk carries a tag of its
# own: change it with these names, and the BFS and mesh programs keep
# their keys.
WALK_STAGES = ("masks", "choose", "lane_out", "fingerprint", "latch",
               "ring", "hunt")
WALK_STAGES_TAG = "w1"

# What the host loop counts where it does the work: ``run_end`` carries a
# run's own, the registry ``swarm/<name>`` the process's sum.
# ``chunk_calls`` dispatches of the walk chunk (one a slice a round),
# ``slices`` the run's slices, ``fetches`` blocking device-to-host copies
# the loop made, ``steps`` lockstep walk-steps, ``latch_step`` the global
# step of the reported violation (-1: none), ``steps_past_latch`` the
# walk-steps computed at or after it, ``restarts`` traces begun after the
# first W, ``reconstruct_scans`` calls of the replay's fused program
# (engine/replay.py), ``reconstruct_steps`` expand round trips of the
# replay: one a step before that program, none since, the key kept for
# who reads ``run_end`` by it.
SWARM_COUNTERS = ("chunk_calls", "slices", "fetches", "steps", "latch_step",
                  "steps_past_latch", "restarts", "reconstruct_steps",
                  "reconstruct_scans")


@dataclasses.dataclass
class SwarmResult:
    """Swarm run outcome — swarm-native counters plus the EngineResult
    surface (stop_reason/distinct/generated/diameter/wall_seconds/
    pipeline/report/violation/counterexample) the history
    ledger, serving layer, and explainer already consume.  The ledger
    dialect: ``distinct`` is accepted (ring-fresh) state visits,
    ``generated`` is lockstep walk-steps executed."""
    walks: int = 0
    steps: int = 0              # lockstep walk-steps executed (W x rounds)
    visited: int = 0            # accepted state visits (ring-deduped)
    traces: int = 0             # walks started (W + restarts)
    distinct: int = 0           # = visited
    generated: int = 0          # = steps
    diameter: int = 0           # deepest trace depth any walk reached
    levels: List[int] = dataclasses.field(default_factory=list)
    stop_reason: str = "steps"
    wall_seconds: float = 0.0
    pipeline: str = ""
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    report: Dict = dataclasses.field(default_factory=dict)
    violation: Optional[Violation] = None
    violation_trace: Optional[List[Tuple[int, PyState]]] = None
    #: Wall-clock seconds into the run when the violation latched — the
    #: swarm's headline "time to first counterexample" metric.
    violation_at_seconds: Optional[float] = None
    #: The latched violation's lockstep step and walker: the first in
    #: (step, walk) order over all W walkers, whatever the slicing.
    violation_step: Optional[int] = None
    violation_walk: Optional[int] = None
    counterexample: Dict = dataclasses.field(default_factory=dict)
    #: The visited-fingerprint multiset as an [N, 2] uint32 (hi, lo)
    #: array, ONLY when the engine was built with
    #: ``collect_fingerprints=True`` (the determinism tests) — a
    #: throughput run must not ship every fingerprint to the host.
    visited_fingerprints: Optional[np.ndarray] = None

    @property
    def steps_per_second(self) -> float:
        return self.steps / self.wall_seconds if self.wall_seconds else 0.0

    @property
    def walks_per_second(self) -> float:
        return self.traces / self.wall_seconds if self.wall_seconds else 0.0

    @property
    def states_per_second(self) -> float:
        return (self.visited / self.wall_seconds
                if self.wall_seconds else 0.0)


def build_swarm_chunk(dims: RaftDims, inv_fns, constraint, D: int, R: int,
                      chunk: int, pipeline: str = "auto",
                      hunt: bool = False):
    """Returns ``chunk_fn(rows, roots, tstep, cur_root, abuf, ring_hi,
    ring_lo, ring_pos, epoch, walk_ids, seed, k0, k_limit)`` — one
    jitted scan advancing every lane ``chunk`` lockstep steps from
    global step ``k0``.  Lane count is taken from ``rows``, so one
    builder serves the full slices and the remainder slice alike.
    Steps at or past ``k_limit`` are frozen no-ops (carry unchanged,
    nothing accepted, nothing latched): the host can run an exact
    ``num_steps`` budget in chunk-sized dispatches without a remainder
    recompile.  Every operation of a step runs under one of the named
    scopes ``WALK_STAGES``, which change no bit.

    With ``hunt=True`` (the hunt observatory, obs/hunt.py) the
    signature grows two trailing args ``(bloom1, bloom2)`` — the
    persistent seen>=1 / seen>=2 Bloom filters — the carry gains a
    13th element of analytics tallies (updated filters, fresh/promote/
    restart-reason scalars, the final-depth histogram, per-family
    efficacy counters), and ``ys`` gains per-step fresh/accept counts.
    Every hunt value is DERIVED from the walk decisions and feeds
    nothing back: choice, accept, latch and the fingerprint stream are
    bit-identical with hunt off (tests/test_swarm.py pins it).
    Per-species observation counts are exact within a dispatch (an
    O(lanes^2) same-fingerprint prior count joins the filters), so the
    Good-Turing totals are partition-invariant up to Bloom collisions.

    The successor draw is **family-diversified** (Holzmann swarm
    style): each (walk, trace) draws a keep-subset of the model's
    action families from the ``FAMILY_STREAM`` counter hash keyed on
    the lane's trace ``epoch`` (restart count), and chooses uniformly
    among enabled instances of kept families, falling back to all
    enabled when the subset is empty there.  A uniform instance draw
    lets the biggest families (raft's 96 message-handling lanes of 132)
    flood the hunt; the per-trace subset makes each trace a focused
    walk through a random sub-model — time-to-counterexample on the
    NoLeaderElected canary drops ~20x.  The mask is a pure function of
    (seed, walk, epoch), so replayability and partition invariance are
    untouched."""
    expand = build_expand(dims)
    pack_ok = build_pack_guard(dims)
    inv_id = build_inv_id(inv_fns)
    fingerprint = build_fingerprint(dims)
    v2 = _resolve_pipeline(pipeline, dims)
    fam = jnp.asarray(np.repeat(
        np.arange(len(dims.family_sizes), dtype=np.int32),
        dims.family_sizes))
    n_fam = len(dims.family_sizes)

    def chunk_fn(rows, roots, tstep, cur_root, abuf, ring_hi, ring_lo,
                 ring_pos, epoch, walk_ids, seed, k0, k_limit,
                 *hunt_state):
        B = rows.shape[0]
        lanes = jnp.arange(B)

        def body(carry, k):
            (rows, tstep, cur_root, abuf, rh, rl, rp, epoch, restarts,
             visited, depth_max, latch) = carry[:12]
            act = k < k_limit
            with jax.named_scope("masks"):
                states = jax.vmap(unflatten_state, (0, None))(rows, dims)
                if v2 is None:
                    cands, en, ovf = jax.vmap(expand)(states)
                    # uint8-row wrap counts as overflow (simulator rule):
                    # restart rather than step through an aliased row.
                    ovf = ovf | (en & ~jax.vmap(jax.vmap(pack_ok))(cands))
                else:
                    en, ovf = jax.vmap(v2.masks)(states)  # pack guard folded
            with jax.named_scope("choose"):
                bits = walk_bits(seed, walk_ids, k, CHOICE_STREAM)
                mbits = walk_bits(seed, walk_ids, epoch, FAMILY_STREAM)
                choice = preferred_choice(bits, en,
                                          family_subset(mbits, fam))
                can_step = jnp.any(en, axis=1) & act
            with jax.named_scope("lane_out"):
                if v2 is None:
                    nxt = jax.tree.map(lambda a: a[lanes, choice], cands)
                else:
                    ph = jax.vmap(v2.parent_hash)(states)  # DCE'd: unused
                    _h, _l, nxt = jax.vmap(v2.lane_out)(
                        states, ph, choice.astype(_I32))
                nrows = jax.vmap(flatten_state, (0, None))(nxt, dims)
            with jax.named_scope("fingerprint"):
                fp_hi, fp_lo = jax.vmap(fingerprint)(nxt)

            with jax.named_scope("latch"):
                if inv_fns:
                    inv = jax.vmap(inv_id)(nxt)
                else:
                    inv = jnp.full((B,), -1, _I32)
                bad = can_step & (inv >= 0)
                # Latch the slice's FIRST violation: first step with any
                # bad lane, lowest lane at that step.  The step index
                # rides along so the host can pick the global (step,
                # walk) minimum across slices — the partition-invariant
                # verdict.
                (vf, vinv, vroot, vlen, vacts, vchoice,
                 vwalk, vstep, vhi, vlo) = latch
                any_new = jnp.any(bad) & ~vf
                w = jnp.argmax(bad)
                latch = (vf | jnp.any(bad),
                         jnp.where(any_new, inv[w], vinv),
                         jnp.where(any_new, cur_root[w], vroot),
                         jnp.where(any_new, tstep[w], vlen),
                         jnp.where(any_new, abuf[w], vacts),
                         jnp.where(any_new, choice[w].astype(_I32),
                                   vchoice),
                         jnp.where(any_new, walk_ids[w].astype(_I32),
                                   vwalk),
                         jnp.where(any_new, k.astype(_I32), vstep),
                         jnp.where(any_new, fp_hi[w], vhi),
                         jnp.where(any_new, fp_lo[w], vlo))

            with jax.named_scope("ring"):
                if constraint is not None:
                    cons_ok = jax.vmap(constraint)(nxt)
                else:
                    cons_ok = jnp.ones((B,), bool)
                seen = ring_probe(rh, rl, fp_hi, fp_lo)
                accept = (can_step & ~jnp.any(ovf, axis=1) & cons_ok
                          & ~seen)
                # Record the action taken since the last restart (before
                # the restart decision, mirroring the simulator's abuf
                # contract).
                abuf = abuf.at[lanes, jnp.clip(tstep, 0, D - 1)].set(
                    jnp.where(can_step, choice.astype(_I32), -1))
                rh, rl, rp = ring_push(rh, rl, rp, fp_hi, fp_lo, accept)
                # Restart on: dead end, overflow, constraint stop, ring
                # revisit (all folded into ~accept) or the depth bound.
                restart = (~accept | (tstep + 1 >= D)) & act

            if hunt:
                with jax.named_scope("hunt"):
                    # Hunt observatory tallies — every value below is
                    # derived from the decisions already made above and
                    # feeds NOTHING back into them (the on/off bit-identity
                    # contract).  Species accounting: the two persistent
                    # Bloom filters give each accepted visit's prior
                    # observation count (capped at 2), exact within this
                    # dispatch via the same-fingerprint prior count over
                    # earlier lanes of the same step.
                    (b1, b2, fresh_t, promote_t, revisit_t, dead_t, povf_t,
                     cons_t, dbound_t, dhist, fch, fac, ffr) = carry[12]
                    in1 = bloom_probe(b1, fp_hi, fp_lo)
                    in2 = bloom_probe(b2, fp_hi, fp_lo)
                    eqm = ((fp_hi[:, None] == fp_hi[None, :])
                           & (fp_lo[:, None] == fp_lo[None, :])
                           & accept[None, :])
                    prior = jnp.sum(jnp.tril(eqm, -1), axis=1, dtype=_I32)
                    nobs = in1.astype(_I32) + in2.astype(_I32) + prior
                    fresh = accept & (nobs == 0)
                    promote = accept & (nobs == 1)
                    b1 = bloom_push(b1, fp_hi, fp_lo, accept)
                    b2 = bloom_push(b2, fp_hi, fp_lo, accept & (nobs >= 1))
                    # Restart-reason census, in the engine's decision order
                    # (the first failing rule owns the restart): together
                    # with the depth bound these partition ``restart``.
                    anyovf = jnp.any(ovf, axis=1)
                    deadend = ~can_step & act
                    ovfstop = can_step & anyovf
                    consstop = can_step & ~anyovf & ~cons_ok
                    revisit = can_step & ~anyovf & cons_ok & seen
                    dbound = accept & (tstep + 1 >= D)
                    # Final depth of each completed trace (masked lanes
                    # contribute an add of 0 — scatter-add, never a branch).
                    dfin = jnp.clip(jnp.where(accept, tstep + 1, tstep),
                                    0, D)
                    dhist = dhist.at[dfin].add(restart.astype(_I32))
                    # Per-family efficacy: which diversification families
                    # get chosen, land accepted states, and find FRESH ones.
                    fidx = fam[choice]
                    fch = fch.at[fidx].add(can_step.astype(_I32))
                    fac = fac.at[fidx].add(accept.astype(_I32))
                    ffr = ffr.at[fidx].add(fresh.astype(_I32))
                    hcarry = (b1, b2,
                              fresh_t + jnp.sum(fresh, dtype=_I32),
                              promote_t + jnp.sum(promote, dtype=_I32),
                              revisit_t + jnp.sum(revisit, dtype=_I32),
                              dead_t + jnp.sum(deadend, dtype=_I32),
                              povf_t + jnp.sum(ovfstop, dtype=_I32),
                              cons_t + jnp.sum(consstop, dtype=_I32),
                              dbound_t + jnp.sum(dbound, dtype=_I32),
                              dhist, fch, fac, ffr)
                    hys = (jnp.sum(fresh, dtype=_I32),
                           jnp.sum(accept, dtype=_I32))
            with jax.named_scope("ring"):
                root_idx = (walk_bits(seed, walk_ids, k, ROOT_STREAM)
                            % _U32(roots.shape[0])).astype(_I32)
                rows = jnp.where(restart[:, None], roots[root_idx],
                                 jnp.where(accept[:, None], nrows, rows))
                cur_root = jnp.where(restart, root_idx, cur_root)
                rh, rl, rp = ring_reset(rh, rl, rp, restart)
                depth_max = jnp.maximum(
                    depth_max, jnp.max(jnp.where(accept, tstep + 1, 0)))
                tstep = jnp.where(restart, 0,
                                  jnp.where(accept, tstep + 1, tstep))
                # A restart begins the walk's next trace: bump its epoch
                # so the FAMILY_STREAM mask re-draws — every trace hunts
                # a fresh random sub-model.
                epoch = epoch + restart.astype(_I32)
                restarts = restarts + jnp.sum(restart, dtype=_I32)
                visited = visited + jnp.sum(accept, dtype=_I32)
            out = (rows, tstep, cur_root, abuf, rh, rl, rp, epoch,
                   restarts, visited, depth_max, latch)
            if hunt:
                return out + (hcarry,), (fp_hi, fp_lo, accept) + hys
            return out, (fp_hi, fp_lo, accept)

        latch0 = (jnp.bool_(False), jnp.int32(-1), jnp.int32(0),
                  jnp.int32(0), jnp.zeros((D,), _I32), jnp.int32(-1),
                  jnp.int32(-1), jnp.int32(-1), _U32(0), _U32(0))
        carry0 = (rows, tstep, cur_root, abuf, ring_hi, ring_lo, ring_pos,
                  epoch, jnp.int32(0), jnp.int32(0), jnp.int32(0), latch0)
        if hunt:
            bloom1, bloom2 = hunt_state
            z = jnp.int32(0)
            carry0 = carry0 + ((bloom1, bloom2, z, z, z, z, z, z, z,
                                jnp.zeros((D + 1,), _I32),
                                jnp.zeros((n_fam,), _I32),
                                jnp.zeros((n_fam,), _I32),
                                jnp.zeros((n_fam,), _I32)),)
        # WALK_STAGES_TAG on an ``add 0`` XLA folds away: in what jax
        # hashes for the compile cache, as the stage names are not.
        with set_xla_metadata(walk_stages_tag=WALK_STAGES_TAG):
            k0 = k0 + 0
        ks = k0 + jnp.arange(chunk, dtype=_I32)
        return jax.lax.scan(body, carry0, ks)

    return chunk_fn


class SwarmEngine:
    """W lockstep randomized walks; see the module docstring.

    ``batch`` caps lanes per device dispatch (walks are sliced across
    dispatches; slicing never changes any walk's trajectory).  ``ring``
    is the per-walk dedup capacity R.  ``chunk`` is scan steps per
    dispatch — it bounds how far past a violation the run computes, but
    neither the verdict nor an exact ``num_steps`` multiset depends on
    it."""

    def __init__(self, dims: RaftDims,
                 invariants: Optional[Dict[str, Callable]] = None,
                 constraint: Optional[Callable] = None, *,
                 walks: int = 1024, max_depth: int = 128,
                 batch: Optional[int] = None, chunk: int = 32,
                 ring: int = 16, pipeline: str = "auto", metrics=None,
                 events_out: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 postmortem_dir: Optional[str] = None,
                 counterexample_dir: Optional[str] = None,
                 collect_fingerprints: bool = False,
                 progress_seconds: float = 5.0,
                 run_context_extra: Optional[dict] = None,
                 hunt: bool = True, hunt_cells: int = 1 << 20,
                 xla_profile_chunks: Optional[int] = None,
                 xla_profile_dir: Optional[str] = None):
        if walks < 1:
            raise ValueError(f"walks must be >= 1, got {walks}")
        if max_depth < 1:
            raise ValueError(f"max_depth must be >= 1, got {max_depth}")
        self.dims = dims
        self.metrics = metrics or MetricsRegistry()
        # Spans, as the BFS engines attach theirs (obs/tracing.py): every
        # phase_timer and scope of a run is ``raft.<name>`` on the host's
        # line of a profiler capture; a registry shared with another
        # engine keeps the tracer it has.
        if self.metrics.tracer is None:
            self.metrics.tracer = SpanTracer(
                None, annotate=jax.profiler.TraceAnnotation)
        self.tracer = self.metrics.tracer
        watch_compiles()
        self.inv_names = list((invariants or {}).keys())
        inv_fns = list((invariants or {}).values())
        self.walks, self.max_depth, self.ring = walks, max_depth, ring
        self.batch = min(batch or walks, walks)
        self.chunk = chunk
        self.events_out = events_out
        self.checkpoint_dir = checkpoint_dir
        self.postmortem_dir = postmortem_dir
        self.counterexample_dir = counterexample_dir
        self.collect_fingerprints = collect_fingerprints
        self.progress_seconds = progress_seconds
        self.run_context_extra = run_context_extra
        #: Hunt observatory (obs/hunt.py): ON by default — the tallies
        #: are a handful of scalars per chunk and the saturation gauge
        #: is the product's whole "when to stop" answer.  ``hunt=False``
        #: builds the bare chunk program (the bit-identity reference
        #: and the throughput ceiling).
        self.hunt = hunt
        self.hunt_cells = int(hunt_cells)
        self._hunt_acc = None
        self.pipeline_name = ("v2" if _resolve_pipeline(pipeline, dims)
                              is not None else "v1")
        inv_id = build_inv_id(inv_fns)
        self._chunk = jax.jit(build_swarm_chunk(
            dims, inv_fns, constraint, max_depth, ring, chunk,
            pipeline=pipeline, hunt=hunt))
        self._xla_chunks = xla_profile_chunks
        self._xla_dir = xla_profile_dir
        self._xla_capture = None

        def roots_inv(batch):
            # Unpacked int32 StateBatch (simulator rule): uint8 packing
            # wraps out-of-range roots, masking a root TypeOK violation.
            if inv_fns:
                return jax.vmap(inv_id)(batch)
            return jnp.full(batch.term.shape[:1], -1, _I32)

        self._roots_inv = jax.jit(roots_inv)
        self._replay_scan = ReplayScan(dims, self.metrics)
        self._fp1 = jax.jit(build_fingerprint(dims))
        self._last_trace: Optional[List[Tuple[int, PyState]]] = None
        # The last run's walkers, kept on the device for
        # ``walk_transcripts``: (roots as given, slices).
        self._walkers = None
        self._counts = dict.fromkeys(SWARM_COUNTERS, 0)

    def chunk_avals(self, n_roots: int = 2) -> tuple:
        """The walk chunk program's arguments as shapes (one full-width
        slice of ``self.batch`` lanes): what a compile for a described
        chip lowers."""
        from ..models.schema import state_width
        B, sw = self.batch, state_width(self.dims)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        u32 = jax.ShapeDtypeStruct((), jnp.uint32)
        li32 = jax.ShapeDtypeStruct((B,), jnp.int32)
        ring = jax.ShapeDtypeStruct((B, self.ring), jnp.uint32)
        avals = (jax.ShapeDtypeStruct((B, sw), jnp.uint8),
                 jax.ShapeDtypeStruct((n_roots, sw), jnp.uint8),
                 li32, li32,
                 jax.ShapeDtypeStruct((B, self.max_depth), jnp.int32),
                 ring, ring, li32, li32, li32, u32, i32, i32)
        if self.hunt:
            bl = jax.ShapeDtypeStruct((self.hunt_cells,), jnp.uint8)
            avals = avals + (bl, bl)
        return avals

    # -- explain.py duck-type surface ----------------------------------
    def replay(self, fp: int) -> List[Tuple[int, PyState]]:
        """The explainer contract (engine/bfs.py replay): the traced
        violation's full ``[(action_id, PyState)]`` path root-first.
        The swarm reconstructs its single latched trace at violation
        time; only that fingerprint is replayable."""
        if self._last_trace is None:
            raise KeyError(f"no traced violation to replay ({fp:#x})")
        return list(self._last_trace)

    def _postmortem_path(self):
        d = self.postmortem_dir or self.checkpoint_dir
        return os.path.join(d, "postmortem.json") if d else None

    # -- run -----------------------------------------------------------
    def run(self, roots: List[PyState], *, seed: int = 0,
            num_steps: Optional[int] = None,
            max_seconds: Optional[float] = None) -> SwarmResult:
        """Run the swarm: every walk advances in lockstep until the
        first latched violation, the ``max_seconds`` budget, or
        ``num_steps`` steps per walk (default ``max_depth`` when no
        time budget is given — one depth-budget's worth of steps)."""
        res = SwarmResult(walks=self.walks, pipeline=self.pipeline_name)
        mt = self.metrics
        if num_steps is None and max_seconds is None:
            num_steps = self.max_depth
        # Per-run telemetry state (warm engines reuse the compiled
        # programs).
        self._hunt_acc = None
        self._xla_capture = None
        if self._xla_chunks:
            from ..obs import XlaProfileCapture
            self._xla_capture = XlaProfileCapture(
                self._xla_dir or os.path.join(
                    self.checkpoint_dir or ".", "xla_profile"),
                self._xla_chunks)
        t0 = time.time()
        # One identifier for the spans of one verdict, as the BFS
        # engines': every span below carries ``run=<n>``.
        self.tracer.run = self._run_id = getattr(self, "_run_id", 0) + 1
        run_span = mt.open_span("run", SCOPE_PREFIX, mode="swarm")
        self._counts = dict.fromkeys(SWARM_COUNTERS, 0)
        self._counts["latch_step"] = -1
        self._walkers = None
        compile_base = compiles_by_span(mt)
        jit_base = process_record().jit_reading()
        # One row a chunk of lockstep steps (obs/calls.py).
        self._calls = CallLog(self._run_id)
        evlog = RunEventLog(events_path(self.events_out,
                                        self.checkpoint_dir))
        phase_base = mt.phase_seconds()
        _FLIGHT.arm(self._postmortem_path(), metrics=mt, context={
            "engine": type(self).__name__, "mode": "swarm",
            "dims": repr(self.dims), "walks": self.walks,
            "max_depth": self.max_depth, "batch": self.batch,
            "ring": self.ring, "pipeline": self.pipeline_name,
            **dict(self.run_context_extra or {})})
        _FLIGHT.set_live_evlog(evlog)
        evlog.emit("run_start", engine=type(self).__name__, mode="swarm",
                   dims=repr(self.dims), walks=self.walks,
                   max_depth=self.max_depth, batch=self.batch,
                   ring=self.ring, seed=seed, num_steps=num_steps,
                   memory=device_memory_stats(),
                   process=process_record().run_start())
        err = None
        try:
            self._run_impl(roots, res, seed, num_steps, max_seconds,
                           evlog, t0)
            return res
        except BaseException as e:
            err = e
            raise
        finally:
            try:
                with mt.phase_timer("run_end"):
                    self._run_end(res, err, evlog, t0, phase_base,
                                  compile_base, jit_base)
            finally:
                run_span.close()

    def _run_end(self, res, err, evlog, t0, phase_base, compile_base,
                 jit_base):
        """What every run pays after its loop: the counterexample files,
        the capture's and the observatory's reports, ``run_end``."""
        mt = self.metrics
        res.wall_seconds = time.time() - t0
        res.distinct, res.generated = res.visited, res.steps
        res.phases = phase_delta(mt.phase_seconds(), phase_base)
        ce_path = None
        ce_dir = self.counterexample_dir or self.checkpoint_dir
        if err is None and res.violation is not None and ce_dir:
            try:
                from .explain import write_counterexample
                res.counterexample = write_counterexample(
                    self, res, ce_dir)
                ce_path = res.counterexample["txt"]
            except Exception as e:
                import sys as _sys
                print(f"counterexample render failed: "
                      f"{type(e).__name__}: {e}", file=_sys.stderr)
        # The capture window closes whether the run lived or died.
        if self._xla_capture is not None:
            self._xla_capture.finish(evlog)
        # The hunt report (obs/hunt.py): the swarm sibling of the
        # statespace report, riding the same surfaces — its own
        # ``hunt`` run event, the report dict, gauges, flight ring.
        hunt_report = None
        if self._hunt_acc is not None and err is None:
            from ..obs import hunt as hunt_mod
            hunt_report = hunt_mod.build_report(
                self._hunt_acc,
                violation_at_seconds=res.violation_at_seconds,
                wall_seconds=res.wall_seconds)
            evlog.emit("hunt", hunt=hunt_report)
            hunt_mod.feed_metrics(hunt_report, mt)
            _FLIGHT.record("hunt", **self._hunt_acc.snapshot())
        swarm_block = self._swarm_block(res)
        if err is None:
            res.report = {
                "collision": {"calculated": 0.0},
                "diameter": res.diameter,
                "verdict": ("violation" if res.violation is not None
                            else "ok"),
                "levels": [],
                "mode": "swarm",
                "swarm": swarm_block,
            }
            if hunt_report is not None:
                res.report["hunt"] = hunt_report
            evlog.emit("statespace", report=res.report)
        pm_path = None
        if err is not None:
            pm_path = _FLIGHT.dump(
                f"swarm run error: {type(err).__name__}: {err}")
        counts = self._counts
        counts["steps"] = res.steps
        # ``swarm/steps`` is counted in the loop; the latch's step is a
        # position, not a sum.
        mt.gauge("swarm/latch_step", counts["latch_step"])
        for name in SWARM_COUNTERS:
            if name not in ("steps", "latch_step"):
                mt.counter("swarm/" + name, counts[name])
        call_fields = self._calls.run_end_fields(evlog)
        evlog.emit(
            "run_end",
            stop_reason=(res.stop_reason if err is None else "error"),
            error=(f"{type(err).__name__}: {err}"
                   if err is not None else None),
            postmortem_path=pm_path,
            counterexample_path=ce_path,
            distinct=res.distinct, generated=res.generated,
            diameter=res.diameter, levels=[],
            wall_seconds=res.wall_seconds,
            phase_seconds=res.phases, swarm=swarm_block,
            # As a BFS engine's ``run_end``: the calls reduced, the
            # collections.
            **call_fields,
            # Counted in the loop (SWARM_COUNTERS), and the compiles and
            # cache loads of this run by the span they fell in.
            **counts,
            compiles=compiles_by_span(mt, compile_base),
            jit=process_record().jit_since(jit_base),
            memory=device_memory_stats())
        _FLIGHT.set_live_evlog(None)
        _FLIGHT.disarm()
        evlog.close()

    def _swarm_block(self, res: SwarmResult) -> dict:
        """The ``swarm`` payload object shared by ``swarm_progress``,
        ``run_end``, and the statespace report.  Hunt-enabled runs
        embed the live hunt snapshot (saturation, unseen mass, recent
        novelty) so a ``watch`` stream answers "when to stop" from the
        progress line alone."""
        out = {"walks": res.walks, "steps": res.steps,
               "visited": res.visited, "traces": res.traces,
               "max_depth": self.max_depth, "ring": self.ring,
               "steps_per_sec": round(res.steps_per_second, 1),
               "walks_per_sec": round(res.walks_per_second, 1),
               "visited_per_sec": round(res.states_per_second, 1),
               "violation_at_seconds": res.violation_at_seconds}
        if self._hunt_acc is not None:
            out["hunt"] = self._hunt_acc.snapshot()
        return out

    def _prepare_roots(self, roots: List[PyState], res: SwarmResult):
        """TLC checks invariants on initial states too: a violating
        root ends the run immediately with a length-1 trace."""
        dims = self.dims
        encoded = [encode_state(s, dims) for s in roots]
        stacked = stack_states(encoded)
        rinv = np.asarray(self._roots_inv(stacked))
        if (rinv >= 0).any():
            idx = int(np.argmax(rinv >= 0))
            hi, lo = self._fp1(encoded[idx])
            fp = (int(hi) << 32) | int(lo)
            res.violation = Violation(
                invariant=self.inv_names[int(rinv[idx])],
                state=roots[idx], fingerprint=fp)
            res.violation_trace = [(-1, roots[idx])]
            self._last_trace = res.violation_trace
            res.stop_reason = "violation"
            res.violation_at_seconds = 0.0
            return None
        check_packable(stacked, dims)
        return flatten_states(stacked, dims)

    def _init_slices(self, roots, res, seed_j, dev):
        """(roots on the device, slices, the hunt's two filters), or
        None where a root already violates an invariant.  Walk slices:
        global walk ids 0..W-1 in ``batch``-lane device dispatches.
        Everything per-walk depends only on (seed, walk_id, step), so
        the slicing is invisible to the walks."""
        W, D, B = self.walks, self.max_depth, self.batch
        roots_np = self._prepare_roots(roots, res)
        if roots_np is None:
            return None
        roots_j = jax.device_put(jnp.asarray(roots_np), dev)
        n_roots = roots_np.shape[0]
        slices = []
        for off in range(0, W, B):
            ids = np.arange(off, min(off + B, W), dtype=np.int32)
            lanes = len(ids)
            walk_ids = jax.device_put(jnp.asarray(ids), dev)
            root0 = (np.asarray(walk_bits(seed_j, walk_ids, 0,
                                          INIT_STREAM))
                     % n_roots).astype(np.int32)
            rh, rl, rp = ring_init(lanes, self.ring)
            slices.append({
                "walk_ids": walk_ids,
                "rows": jax.device_put(roots_j[jnp.asarray(root0)], dev),
                "tstep": jax.device_put(jnp.zeros((lanes,), _I32), dev),
                "cur_root": jax.device_put(jnp.asarray(root0), dev),
                "abuf": jax.device_put(jnp.zeros((lanes, D), _I32), dev),
                "ring_hi": jax.device_put(rh, dev),
                "ring_lo": jax.device_put(rl, dev),
                "ring_pos": jax.device_put(rp, dev),
                "epoch": jax.device_put(jnp.zeros((lanes,), _I32), dev),
                "visited": 0, "latch": None, "ys": None,
            })
        hunt_args = ()
        if self.hunt:
            from ..obs import hunt as hunt_mod
            self._hunt_acc = hunt_mod.HuntAccumulator(
                self.dims.family_names, D,
                bloom_cells=self.hunt_cells)
            # The filters are SHARED across slices, threaded through
            # the sequential dispatches: the Good-Turing totals then
            # see one global observation stream regardless of how the
            # walks were sliced (only the per-step series reorders).
            hunt_args = (jax.device_put(bloom_init(self.hunt_cells), dev),
                         jax.device_put(bloom_init(self.hunt_cells), dev))
        return roots_j, slices, hunt_args

    def _run_impl(self, roots, res, seed, num_steps, max_seconds,
                  evlog, t0):
        W = self.walks
        mt, counts = self.metrics, self._counts
        dev = jax.devices()[0]
        k_limit = jnp.int32(num_steps if num_steps is not None
                            else np.iinfo(np.int32).max)
        seed_j = _U32(np.uint32(seed & 0xFFFFFFFF))
        with mt.phase_timer("swarm_init"):
            made = self._init_slices(roots, res, seed_j, dev)
        if made is None:
            return
        roots_j, slices, hunt_args = made
        self._walkers = (list(roots), slices)
        counts["slices"] = len(slices)
        res.traces = W
        mt.counter("swarm/walks", W)
        mt.gauge("swarm/active_walks", W)
        hacc = self._hunt_acc
        cap = self._xla_capture

        def fetch(a):
            """One blocking device-to-host copy, counted."""
            counts["fetches"] += 1
            return np.asarray(a)

        fps_acc: List[np.ndarray] = []
        k0 = 0
        depth_max = 0
        last_progress = t0
        calls = self._calls
        calls.start()
        saturation = {}
        while True:
            if _faults.ACTIVE:
                _faults.fire("stall", phase="gap", call=calls.n + 1)
            calls.dispatch()
            with mt.phase_timer("swarm_chunk", step=k0) as chunk_span:
                step_cm = cap.step() if cap is not None else None
                if step_cm is not None:
                    step_cm.__enter__()
                try:
                    for s in slices:
                        carry, ys = self._chunk(
                            s["rows"], roots_j, s["tstep"],
                            s["cur_root"], s["abuf"], s["ring_hi"],
                            s["ring_lo"], s["ring_pos"], s["epoch"],
                            s["walk_ids"], seed_j, jnp.int32(k0),
                            k_limit, *hunt_args)
                        (s["rows"], s["tstep"], s["cur_root"], s["abuf"],
                         s["ring_hi"], s["ring_lo"], s["ring_pos"],
                         s["epoch"], s["restarts"], s["visited_d"],
                         s["depth_d"], s["latch"]) = carry[:12]
                        s["ys"] = ys
                        if self.hunt:
                            s["hunt"] = carry[12]
                            hunt_args = carry[12][:2]
                finally:
                    if step_cm is not None:
                        step_cm.__exit__(None, None, None)
            counts["chunk_calls"] += len(slices)
            stepped = min(self.chunk,
                          max(0, int(k_limit) - k0)) if num_steps \
                else self.chunk
            k_start = k0
            k0 += self.chunk
            res.steps += W * stepped
            fired = []
            novel_steps = accept_steps = None
            visited_was = res.visited
            with mt.phase_timer("swarm_fetch", step=k_start) as fetch_span:
                if _faults.ACTIVE:
                    _faults.fire("stall", phase="wait", call=calls.n + 1)
                for s in slices:
                    restarts = int(fetch(s["restarts"]))
                    res.traces += restarts
                    counts["restarts"] += restarts
                    mt.counter("swarm/walks", restarts)
                    v = int(fetch(s["visited_d"]))
                    res.visited += v
                    mt.counter("swarm/visited", v)
                    depth_max = max(depth_max, int(fetch(s["depth_d"])))
                    vf = bool(fetch(s["latch"][0]))
                    if vf:
                        fired.append(s["latch"])
                    if hacc is not None:
                        hc = s["hunt"]
                        hacc.add_slice(
                            fresh=int(fetch(hc[2])),
                            promote=int(fetch(hc[3])),
                            # RESTART_REASONS order: deadend, overflow,
                            # constraint, revisit, depth_bound.
                            reasons=tuple(int(fetch(hc[i]))
                                          for i in (5, 6, 7, 4, 8)),
                            depth_hist=fetch(hc[9]),
                            fam_chosen=fetch(hc[10]),
                            fam_accept=fetch(hc[11]),
                            fam_fresh=fetch(hc[12]))
                        nv = fetch(s["ys"][3])
                        av = fetch(s["ys"][4])
                        novel_steps = (nv if novel_steps is None
                                       else novel_steps + nv)
                        accept_steps = (av if accept_steps is None
                                        else accept_steps + av)
                    if self.collect_fingerprints:
                        hi, lo, acc = (fetch(a) for a in s["ys"][:3])
                        m = acc.reshape(-1)
                        fps_acc.append(np.stack(
                            [hi.reshape(-1)[m], lo.reshape(-1)[m]],
                            axis=1))
                # Globally first violation in (step, walk) order — the
                # partition-invariant pick across slices.
                latched = [(int(fetch(lt[7])), int(fetch(lt[6])), lt)
                           for lt in fired]
            t_host = time.perf_counter()
            if _faults.ACTIVE:
                _faults.fire("stall", phase="host", call=calls.n + 1)
            if hacc is not None and stepped:
                hacc.add_steps(k_start + stepped, W * stepped,
                               novel_steps[:stepped],
                               accept_steps[:stepped])
            mt.counter("swarm/steps", W * stepped)
            res.diameter = depth_max
            now = time.time()
            if (k0 == self.chunk
                    or now - last_progress >= self.progress_seconds):
                last_progress = now
                res.wall_seconds = now - t0
                evlog.emit("swarm_progress", depth=k0,
                           swarm=self._swarm_block(res))
                if hacc is not None:
                    snap = hacc.snapshot()
                    mt.gauge("hunt/saturation", snap["saturation"])
                    mt.gauge("hunt/unseen_mass", snap["unseen_mass"])
                    mt.gauge("hunt/distinct_observed",
                             snap["distinct_observed"])
                    mt.gauge("hunt/novel_rate",
                             snap["novel_rate_recent"])
                    mt.gauge("hunt/revisit_rate", snap["revisit_rate"])
                    _FLIGHT.record("hunt", steps=res.steps, **snap)
                    saturation = {"saturation": snap["saturation"]}
            # The chunk's one record (obs/calls.py): ``passes`` are its
            # lockstep steps, one dispatch a slice under one span, and
            # the watch console's view of a hunt is read from it.  The
            # accounting above is under no span, and this loop opens
            # none between two chunks: ``named_s`` stays 0.
            calls.row("swarm_chunk", "steps", stepped, chunk_span.seconds,
                      fetch_span.seconds, 0.0,
                      time.perf_counter() - t_host, calls.n + 1, k_start,
                      self.chunk, W * stepped, res.visited - visited_was,
                      mode="swarm", steps=res.steps, visited=res.visited,
                      traces=res.traces, **saturation)
            if latched:
                vstep, vwalk, latch = min(latched, key=lambda f: f[:2])
                counts["latch_step"] = res.violation_step = vstep
                res.violation_walk = vwalk
                counts["steps_past_latch"] = W * (k_start + stepped
                                                  - vstep)
                with mt.scope("reconstruct", step=vstep, walk=vwalk):
                    self._reconstruct(res, roots, latch)
                res.stop_reason = "violation"
                res.violation_at_seconds = round(time.time() - t0, 6)
                evlog.emit("violation",
                           invariant=(res.violation.invariant
                                      if res.violation else "?"),
                           fingerprint=(hex(res.violation.fingerprint)
                                        if res.violation else None),
                           walk=vwalk, step=vstep,
                           at_seconds=res.violation_at_seconds)
                break
            if max_seconds is not None and time.time() - t0 > max_seconds:
                res.stop_reason = "max_seconds"
                break
            if num_steps is not None and k0 >= num_steps:
                res.stop_reason = "steps"
                break
        if hacc is not None and hunt_args:
            b1 = np.asarray(hunt_args[0])
            hacc.bloom_load = float(np.count_nonzero(b1)) / b1.size
        if self.collect_fingerprints:
            res.visited_fingerprints = (
                np.concatenate(fps_acc, axis=0) if fps_acc
                else np.zeros((0, 2), np.uint32))

    def replay_actions(self, root: PyState, actions) -> list:
        """``[(action, PyState), ...]`` from ``root`` (action -1) through
        the instance ids ``actions``, in one device call (engine/
        replay.py; ``ceil(len / capacity)`` for a trace longer than its
        buffer); stops at an id that is negative or not enabled.  The
        program threads the ENCODED successor row, never a re-encoded
        state (the simulator's slot-aliasing rule: re-encoding reassigns
        message slots, and slot-indexed action ids would then address
        the wrong message)."""
        rows, _keys, calls = self._replay_scan(root, actions)
        self._counts["reconstruct_scans"] += calls
        return [(-1, root)] + [
            (int(g), decode_state(unflatten_state(row, self.dims),
                                  self.dims))
            for g, row in zip(actions, rows)]

    def _reconstruct(self, res: SwarmResult, roots, latch):
        """Replay the latched (root, action sequence) through the expand
        kernel — the simulator's reconstruction."""
        (_vf, vinv, vroot, vlen, vacts, vchoice, _vwalk, _vstep,
         vhi, vlo) = latch
        vinv, vroot, vlen = int(vinv), int(vroot), int(vlen)
        trace = self.replay_actions(
            roots[vroot], list(np.asarray(vacts)[:vlen]) + [int(vchoice)])
        fp = (int(vhi) << 32) | int(vlo)
        res.violation = Violation(
            invariant=(self.inv_names[vinv]
                       if 0 <= vinv < len(self.inv_names) else "?"),
            state=trace[-1][1], fingerprint=fp)
        res.violation_trace = trace
        self._last_trace = trace

    def walk_transcripts(self, ids) -> list:
        """``[(root, actions, row), ...]`` of the walkers with the global
        ids ``ids`` as the last run's last chunk left them: the root of
        the walker's current trace (a ``PyState`` of the run's roots),
        the instance ids it took since that restart, and its packed row
        on the device.  ``replay_actions(root, actions)`` must end in the
        state ``row`` decodes to."""
        if self._walkers is None:
            raise RuntimeError("no finished run to read walkers from")
        roots, slices = self._walkers
        host = {}       # slice index -> its four arrays, copied once
        out = []
        for w in ids:
            i, lane = int(w) // self.batch, int(w) % self.batch
            if i not in host:
                host[i] = [np.asarray(slices[i][k]) for k in
                           ("tstep", "cur_root", "abuf", "rows")]
            tstep, cur_root, abuf, rows = host[i]
            out.append((roots[int(cur_root[lane])],
                        [int(g) for g in abuf[lane, :int(tstep[lane])]],
                        rows[lane]))
        return out
