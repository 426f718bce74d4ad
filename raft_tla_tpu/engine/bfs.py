"""Level-synchronous BFS — TLC's exhaustive mode as a data-parallel device loop.

The classical TLC loop (one state at a time: enumerate actions, fingerprint,
probe the FPSet, enqueue — SURVEY §1 L6) becomes a batched pipeline compiled
to one XLA program per step:

    slice B states off the current-level queue
      -> vmap(expand): all G action instances of all B states   [B,G]
      -> vmap(fingerprint) over the B*G candidates (cheap reduce per lane)
      -> COMPACT the enabled lanes to K << B*G slots (prefix-sum scatter;
         measured fan-out is ~6% of G, so K = 16*B loses nothing, and a
         fan-out burst just advances fewer parents that step)
      -> batched hash-table insert (ops/fpset.py) on the K compacted keys:
         in-batch dedup + HBM seen-set probe/update in one pass
      -> gather the K candidate states; materialize uint8 rows, evaluate
         invariants + the state constraint, scatter the new rows into the
         next-level queue — all O(K), never O(B*G)
      -> deadlock mask, violation/overflow reporting

Everything device-resident: the two level queues (flat uint8 state rows),
the FPSet, and all masks.  The host loop only advances offsets, swaps queues
between levels, reads back a handful of scalars per batch, and appends
(fingerprint -> parent fingerprint, action id) records to the trace store —
exactly the host/device split the SURVEY's north star prescribes.

TLC-semantics notes:
- constraint-violating states are counted distinct and invariant-checked but
  not enqueued (CONSTRAINT behavior; SURVEY §2.4 R9);
- a state with no successors at all is a deadlock (reported unless
  ``check_deadlock=False``, Smokeraft.cfg:48);
- the run stops at the first invariant violation, like TLC; counterexamples
  are reconstructed by fingerprint walk-back plus *kernel replay* (the trace
  stores (parent fp, action instance id); running the recorded instances
  forward from the root, in one device call, yields each next state
  bit-exactly: ``replay``, engine/replay.py);
- ``generated`` counts every enabled successor evaluation (TLC's "states
  generated"), ``distinct`` counts FPSet insertions.

Budgets (``max_seconds``/``max_diameter``) reproduce the Smokeraft StopAfter
control channel (TLCGet("duration")/TLCGet("diameter") — Smokeraft.tla:88-92)
at batch granularity.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.dims import RaftDims
from ..models.actions import build_expand
from ..models.invariants import build_inv_id
from ..models.pystate import PyState
from ..models.schema import (ROW_DTYPE, StateBatch, build_pack_guard,
                             check_packable, decode_message, decode_state,
                             encode_state, flatten_states, stack_states,
                             state_width, unflatten_state)
from ..obs import (ActionCoverage, MetricsRegistry, RunEventLog,
                   SpanTracer, all_device_memory_stats,
                   device_memory_stats, events_path, peak_host_rss_bytes,
                   phase_delta)
from ..obs.calls import CallLog
from ..obs.metrics import SCOPE_PREFIX, process_record, watch_compiles
from ..obs.flight import RECORDER as _FLIGHT
from ..resilience import faults as _faults
from ..resilience.faults import is_resource_exhausted
from ..ops import compact as compact_mod
from ..ops import fpset
from ..ops.fingerprint import build_fingerprint
from ..utils.cfg import check_pipeline
from .chunk import build_chunk_body, named_stage, tag_stages
from .replay import ReplayScan, leading_true

_I32 = jnp.int32

# What the host loop counts where it does the work, as the registry's
# ``engine/<name>`` counters (process-cumulative, like every counter):
# ``run_end`` carries a run's share, ``level_complete`` each level's.
# ``inv_lanes``: compacted lanes the chunk evaluated the invariants on,
# K a pass on every chip (duplicates and empty lanes included; TLC
# evaluates an invariant once a new distinct state).  ``deadline_calls``
# and ``probe_calls``: chunk calls of a budgeted run whose size the
# time left set, and those of one batch made for want of an estimate
# (``budget_call_size``).  ``flush_*`` and ``level_closes_*``: trace
# flushes and level closes by where their host half ran, behind a
# dispatched call or with the device empty (``_TraceFlush``,
# ``_LevelClose``).  ``checkpoints_written`` and ``checkpoint_bytes_*``:
# snapshots acknowledged, their arrays' bytes in memory and their files'
# on disk (counted by ``checkpoint.save``).  ``checkpoints_overlapped``
# and ``checkpoints_drained``: snapshots by whether their file was made
# while the loop went on or the loop waited for it, ``checkpoint_wait_s``
# the seconds it waited (``_SnapshotSave``).
WORK_COUNTERS = ("chunk_calls", "passes", "inv_lanes", "ingest_calls",
                 "parents_expanded", "flush_overlapped", "flush_drained",
                 "level_closes_overlapped", "level_closes_drained",
                 "deadline_calls", "probe_calls", "checkpoints_written",
                 "checkpoint_bytes_raw", "checkpoint_bytes_written",
                 "checkpoints_overlapped", "checkpoints_drained",
                 "checkpoint_wait_s")


def work_counts(metrics, base: Optional[dict] = None) -> dict:
    """{name: count} of ``WORK_COUNTERS``, less an earlier reading; a
    name that ends in ``_s`` counts seconds."""
    base = base or {}
    out = {}
    for k in WORK_COUNTERS:
        n = metrics.counter_value("engine/" + k) - base.get(k, 0)
        out[k] = round(n, 6) if k.endswith("_s") else int(n)
    return out


def resume_fields(resume) -> dict:
    """``run_start``'s ``resume_level`` and ``resume_path`` on a resumed
    run (nothing otherwise): the snapshot's level, by its file's name
    where the run was given a path (``run_start`` is written before the
    file is read) and by its own count where it was given the loaded
    image, whose path is then None."""
    if resume is None:
        return {}
    if isinstance(resume, str):
        from . import checkpoint as ckpt_mod
        return {"resume_level": ckpt_mod.level_of(resume),
                "resume_path": resume}
    return {"resume_level": int(resume.diameter), "resume_path": None}


def budget_call_size(ch: int, remaining: float, batch_ema: float,
                     calls_in_level: int) -> Tuple[int, str]:
    """Batches the next chunk call of a duration-budgeted run may take,
    and the rule that set them.  With no cost estimate yet, ``probe``:
    one batch, so the first call cannot blow the deadline by a whole
    ``sync_every`` chunk.  Else half the time left at the measured cost
    a batch (``deadline``), under the per-level slow-start ramp, which
    starts at 2 batches so that the call's host round trip amortizes
    over the probe and does not lock the (jump-up, decay-slow) estimator
    at a cost the round trip dominates (``ramp``, which also names a
    call that ``sync_every`` capped)."""
    if not batch_ema:
        return 1, "probe"
    ramp = min(ch, 2 << min(calls_in_level, 9))
    deadline = int(remaining / (2 * batch_ema))
    if deadline < ramp:
        return max(1, deadline), "deadline"
    return ramp, "ramp"


# -- compiles by span ------------------------------------------------------
# jax reports every trace, lowering and backend compile (or load from
# the persistent cache) through process-wide monitoring hooks.  The
# listeners (obs/metrics.py ``watch_compiles``) count each in the
# process record and charge it to the innermost span open on the
# compiling thread, in that span's registry: ``compile/<span>`` (how
# many backend compiles and cache loads) and ``compile_seconds/<span>``,
# with ``trace_seconds/<span>`` and ``lower_seconds/<span>`` beside them.
# ``run_end.compiles`` is a run's share of the first two, ``run_end.jit``
# its share of all stages by program.


def compiles_by_span(metrics, base: Optional[dict] = None) -> dict:
    """{span: [compiles, seconds]} from a registry's compile counters,
    less an earlier reading of the same."""
    counts = metrics.counters("compile/")
    seconds = metrics.counters("compile_seconds/")
    base = base or {}
    out = {}
    for name, n in counts.items():
        n0, s0 = base.get(name, (0, 0.0))
        if n > n0:
            out[name] = [int(n - n0),
                         round(seconds.get(name, 0.0) - s0, 6)]
    return out


# -- the trace flush, in two halves ------------------------------------------
# Records one fetch program moves (an engine's ``_fetch``: five slices of
# a fixed length at a traced start, compiled in warm-up), smallest first.
# A flush takes the smallest that holds its records in one piece, else
# walks them in pieces of the largest: whatever a call admitted, its
# flush compiles nothing and moves under one piece more than it needs.
FLUSH_PIECES = (1 << 11, 1 << 14, 1 << 17)


def fetch_lengths(size: int) -> List[int]:
    """The lengths an engine warms its fetch program up at, for trace
    buffers of ``size`` entries: no piece is longer than the buffer."""
    return sorted({min(x, size) for x in FLUSH_PIECES})


def flush_plan(n: int, lengths, size: int) -> List[Tuple[int, int, int, int]]:
    """``[(start, length, lo, hi)]``: the fetches that bring entries
    ``[0, n)`` of a ``size``-entry buffer, in order, and the part
    ``[lo, hi)`` of each that is wanted.  The last piece starts early
    rather than past ``size - length``, where a device slice would be
    moved back without a word."""
    length = next((x for x in lengths if x >= n), lengths[-1])
    plan = []
    for s in range(0, n, length):
        start = min(s, size - length)
        plan.append((start, length, s - start, min(s + length, n) - start))
    return plan


def store_growth(trace, prefix: str) -> dict:
    """``run_end``'s ``<prefix>rehashes`` and ``<prefix>rehash_s``: what
    growing has cost a trace store so far (``stats``).  ``trace_`` at a
    run's end, roots' records and a resume's refill included;
    ``restore_`` right after that refill.  Zeros for a run that made no
    store, and from the dict fallback."""
    grown = (trace.stats() if trace is not None
             else {"rehashes": 0, "rehash_s": 0.0})
    return {prefix + "rehashes": grown["rehashes"],
            prefix + "rehash_s": round(grown["rehash_s"], 6)}


class _TraceFlush:
    """One call's trace records on their way to the host store, in two
    halves, so that the device need not wait for the host's.  ``start``
    enqueues the fetch programs on the call's trace buffers and starts
    their copies to the host: device work, ahead in the stream of
    whatever is dispatched next, which may donate those buffers.
    ``finish`` waits for the copies and hands the records to the store.
    The loop calls it behind the next chunk dispatch (counted as
    ``flush_overlapped``), the first call of the next level included, or
    with the device empty where something other than a plain next call
    comes first: a snapshot due at a level's end, any stop, the run's end
    (``flush_drained``; the mesh loop drains at every level's end).  So
    at most one flush is owed, and records reach the store in the order
    the calls ran.  A run that raises takes what it owed with it:
    ``_run_degradable`` resumes into a new store.

    ``finish`` returns the seconds its host half took (0.0 with nothing
    owed), for the call's row.

    One class for both host loops.  What differs comes from the engine
    that runs: its ``_fetch(buffer, start, length)`` (five columns of
    ``length`` entries of one buffer), the lengths that program was
    warmed up at (``_fetch_lens``) and the buffer's size (``_TA``), its
    ``_record``; and the loop gives ``start`` one ``(buffer, records)``
    a plan: ``BFSEngine`` its trace buffer, ``MeshBFSEngine`` one for
    each chip of this controller, in chip order (``_trace_parts``)."""

    def __init__(self, engine, trace):
        self._eng, self._trace, self._owed = engine, trace, None

    def start(self, parts) -> None:
        eng = self._eng
        assert self._owed is None, "a flush is owed already"
        with eng.metrics.phase_timer("trace_flush"):
            owed = []
            for buf, n in parts:
                for start, length, lo, hi in flush_plan(
                        n, eng._fetch_lens, eng._TA):
                    cols = eng._fetch(buf, np.int32(start), length)
                    for col in cols:
                        col.copy_to_host_async()
                    owed.append((cols, lo, hi))
            self._owed = owed or None

    def finish(self, counter: str) -> float:
        if self._owed is None:
            return 0.0
        eng = self._eng
        with eng.metrics.phase_timer("trace_flush") as span:
            owed, self._owed = self._owed, None
            for cols, lo, hi in owed:
                eng._record(self._trace, cols, lo, hi)
        eng.metrics.counter("engine/" + counter)
        return span.seconds


class _LevelClose:
    """A level's ``level_complete`` on its way out, in two halves like
    the flush it follows, so that the next level's first call need not
    wait for it.  ``owe``, at the boundary, reads everything the event
    says (``BFSEngine._level_fields``).  ``finish`` writes the line, as
    the ``level_end`` span.  ``BFSEngine``'s loop calls it right after
    the flush's ``finish``: behind the first dispatch of the next level
    (``level_closes_overlapped``), or with the device empty where the
    boundary is followed by anything else, a snapshot, a stop, the run's
    end (``level_closes_drained``).  So at most one close is owed, it is
    written before any event of the next level, and a run that raises
    takes it along as it does its flush.

    ``finish`` returns the seconds of its span (0.0 with nothing owed),
    for the call's row."""

    def __init__(self, engine):
        self._eng, self._owed = engine, None

    @property
    def owed(self) -> bool:
        return self._owed is not None

    def owe(self, res, frontier_rows) -> None:
        assert self._owed is None, "a level's close is owed already"
        eng = self._eng
        self._owed = (eng._level_fields(res, frontier_rows),
                      eng._level_span)

    def finish(self, counter: str) -> float:
        if self._owed is None:
            return 0.0
        eng = self._eng
        (fields, level_span), self._owed = self._owed, None
        with eng.metrics.phase_timer("level_end") as span:
            eng._evlog.emit("level_complete", **fields)
        eng.metrics.counter("engine/" + counter)
        if eng._level_span is level_span:
            # No next level was opened: the close is the last span
            # inside the level's own.
            eng._close_level_span()
        if eng.tracer.enabled:
            # Level-boundary durability: a crash loses at most the
            # current level's spans (atomic rewrite, off the hot loop).
            eng.tracer.write()
        return span.seconds


class _SnapshotSave:
    """A level-boundary snapshot on its way to the disk, in two halves
    like the flush and the close, so that the device need not wait for
    the file.  ``capture``, at the boundary, on the loop's thread, inside
    the ``checkpoint`` phase, reads what the next call donates or the
    next level changes and nothing else: the trace store's records and
    roots, the seen-set's two arrays as they stand, the level's rows, the
    counts.  The image is the boundary's.  ``_commit`` makes the file of
    it on a thread of this save's own, which calls nothing of jax: the
    keys masked and sorted (``ckpt_sort``), ``checkpoint.save`` (the
    deflate; the file, its ``fsync``, the rename, the directory's
    ``fsync``; the counters), the retention, and then the ``checkpoint``
    event, the acknowledgement.  Its spans are that thread's and lie
    after the phase's end.

    At most one save is in flight.  ``finish`` takes it off the books:
    the loop calls it with ``wait=False`` after every call's accounting,
    which finds a commit that ended behind the calls
    (``checkpoints_overlapped``), and waits (``checkpoints_drained``,
    ``checkpoint_wait_s``; a ``checkpoint`` phase too: the device waits
    for a save) before the next capture and where the run ends, by an
    exception too (``_run_impl``).  So acknowledgements come in level
    order and no ``run()`` leaves a file half made.  A boundary followed
    by the run's end waits at once: the save as it was before it had two
    halves.  Either way a commit that raised is raised from the loop, and
    nothing acknowledges that level.

    The event's ``seconds`` run from the start of the capture to the
    acknowledgement, its ``stall_seconds`` are what the loop was held
    for, the capture and any wait: ``phase_seconds.checkpoint`` is their
    sum.  So a save the loop waits for is acknowledged by the loop, when
    the wait is over, and every other by its own thread."""

    def __init__(self, engine):
        self._eng, self._thread = engine, None
        self._lock = threading.Lock()

    def capture(self, qcur, cur_count, pending, seen, res, trace,
                wall) -> None:
        assert self._thread is None, "a snapshot is in flight already"
        eng = self._eng
        mt, cfg = eng.metrics, eng.config
        self._t0 = time.perf_counter()
        self._parts_base = mt.part_seconds()
        with mt.phase_timer("checkpoint") as span:
            # The copies to the host all start now and move side by side
            # (and beside the store's export); each part waits for its own.
            rows = qcur[:cur_count]
            for arr in (seen.hi, seen.lo, rows):
                arr.copy_to_host_async()
            if cfg.record_trace:
                with mt.part_timer("ckpt_export"):
                    tf, tp, ta = trace.export()
                    roots = dict(trace.roots)
            else:
                tf = np.empty(0, np.uint64)
                tp = np.empty(0, np.uint64)
                ta = np.empty(0, np.int32)
                roots = {}
            with mt.part_timer("ckpt_keys"):
                # The table as it stands, empty slots and all.  (Where
                # this is a view of the device's buffer, on a CPU
                # backend, the view keeps the next insert from writing
                # the buffer in place.)
                table = np.asarray(seen.hi), np.asarray(seen.lo)
            with mt.part_timer("ckpt_frontier"):
                frontier, cleanup = pending.concat_with(np.asarray(rows))
            image = dict(
                dims=eng.dims, frontier=frontier,
                distinct=res.distinct, generated=res.generated,
                diameter=res.diameter, levels=tuple(res.levels),
                action_counts=dict(res.action_counts), wall_seconds=wall,
                trace_fps=tf, trace_parents=tp, trace_actions=ta,
                roots=roots)
        self._stall = span.seconds
        self._done = self._waited = False
        self._error = self._written = None
        # What the acknowledgement says besides the file's own fields,
        # and the log it goes to: the run's, as long as the run lasts.
        self._ack = (eng._evlog, res.diameter, res.distinct)
        path = os.path.join(cfg.checkpoint_dir,
                            f"level_{res.diameter:05d}.npz")
        self._thread = threading.Thread(
            target=self._commit, name="raft-snapshot",
            args=(path, image, table, cleanup))
        self._thread.start()

    def _commit(self, path, image, table, cleanup) -> None:
        from . import checkpoint as ckpt_mod
        eng = self._eng
        mt, cfg = eng.metrics, eng.config
        written = error = None
        try:
            try:
                with mt.part_timer("ckpt_sort"):
                    seen_hi, seen_lo = fpset.sorted_keys(*table)
                size = ckpt_mod.save(
                    path, ckpt_mod.Checkpoint(
                        seen_hi=seen_hi, seen_lo=seen_lo, **image),
                    metrics=mt)
            finally:
                cleanup()
            # Retention AFTER the successful write: the newest snapshot
            # must land before any older one is considered surplus.
            with mt.part_timer("ckpt_gc"):
                removed = ckpt_mod.gc(cfg.checkpoint_dir,
                                      cfg.keep_checkpoints)
            if removed:
                mt.counter("engine/checkpoints_gcd", removed)
            parts = phase_delta(mt.part_seconds(), self._parts_base)
            # ``bytes_raw`` and ``bytes_written`` left out where an
            # injected fault skipped the write.
            written = {"path": path, **(size or {}),
                       "parts": {k: round(v, 6) for k, v in parts.items()}}
        except BaseException as e:      # the loop raises it (``finish``)
            error = e
        with self._lock:
            self._written, self._error = written, error
            if error is None and not self._waited:
                self._acknowledge()
            self._done = True

    def _acknowledge(self) -> None:
        """The ``checkpoint`` event: the file is fsynced, renamed and its
        directory fsynced, the retention has run."""
        evlog, level, distinct = self._ack
        evlog.emit("checkpoint", level=level, distinct=distinct,
                   seconds=round(time.perf_counter() - self._t0, 6),
                   stall_seconds=round(self._stall, 6), **self._written)

    def finish(self, wait: bool = True, quiet: bool = False) -> None:
        """Take the save in flight off the books if its commit has ended,
        or wait for that (``wait``).  Raises what the commit raised,
        unless another exception is on its way out already (``quiet``)."""
        thread = self._thread
        if thread is None:
            return
        mt = self._eng.metrics
        with self._lock:
            done = self._done
            if not done:
                if not wait:
                    return
                self._waited = True
        if done:
            thread.join()
            mt.counter("engine/checkpoints_overlapped")
        else:
            with mt.phase_timer("checkpoint") as span:
                thread.join()
            self._stall += span.seconds
            mt.counter("engine/checkpoints_drained")
            mt.counter("engine/checkpoint_wait_s", span.seconds)
            if self._error is None:
                self._acknowledge()
        self._thread = None
        error, self._error = self._error, None
        if error is not None:
            if not quiet:
                raise error
            import sys as _sys
            print(f"snapshot of level {self._ack[1]} failed as the run "
                  f"ended: {type(error).__name__}: {error}",
                  file=_sys.stderr)


@dataclasses.dataclass
class EngineConfig:
    batch: int = 256             # states expanded per device step
    # None => size from the device's reported HBM (see _auto_capacities).
    # Neither is a hard limit on the state space: the frontier spills to
    # host memory when the device queue fills (TLC's disk queue), and the
    # seen-set grows by rehashing when its load factor passes the
    # threshold; these set the *device-resident* working set.
    queue_capacity: Optional[int] = 1 << 16
    seen_capacity: Optional[int] = 1 << 18
    # Width (lanes) of the compacted-candidate buffer: the B*G enabled
    # masks are prefix-summed into this many lanes before the hash insert,
    # row materialization, invariant/constraint evaluation, and enqueue —
    # so those stages cost O(K), not O(B*G).  Enabled fraction is typically
    # well under 10% (measured fan-out ~8 of G=132 on MCraft_bounded), so
    # the default of 16 lanes per frontier state loses nothing; when a
    # batch's fan-out does exceed K the device loop simply takes fewer
    # parents that step (progress-limited, never dropped).  None => auto
    # (16*batch); any value is floored at max(G, batch) and rounded to a
    # power of two (ops/compact.py choose_k).
    compact_lanes: Optional[int] = None
    # Successor pipeline: "auto" = the v2 delta pipeline (models/
    # actions2.py: guards-only masks, delta fingerprints, K-lane sparse
    # construction) wherever it applies (base action alphabet), v1 expand
    # for a spec variant without v2 kernels.  "v1"/"v2" force one path
    # (v2 raises on such a variant); the tests use v1 as v2's reference.
    pipeline: str = "auto"
    # Statically-certified partial-order reduction (analysis/por.py).
    # ``por=True`` certifies in-process at engine construction (traces
    # the kernels once, proving the ample certificates against THIS
    # run's invariants + constraint); ``por_table`` supplies a
    # pre-certified table instead — a PorTable object or a path to the
    # versioned artifact `analyze --passes por --por-artifact` writes.
    # Every table is admission-checked (fingerprint, model signature,
    # predicate coverage) before the mask is applied; a hand-edited or
    # mismatched certificate raises instead of silently reducing.
    por: bool = False
    por_table: Optional[object] = None
    # None = defer to the cfg file (make_engine fills it in); a bool from
    # the caller always wins — the documented precedence chain.
    check_deadlock: Optional[bool] = None
    record_trace: bool = True
    sync_every: int = 32         # device batches per host round-trip
    max_seconds: Optional[float] = None   # StopAfter duration budget
    max_diameter: Optional[int] = None    # StopAfter diameter budget
    # Further TLCGet-consulting budgets as (counter, threshold) pairs over
    # "distinct" / "generated" / "queue" (utils/cfg.py EXIT_COUNTERS) —
    # the general metrics-control coupling (SURVEY §5.5): checked against
    # live counters after every chunk stats fetch, stop_reason
    # "<counter>_budget".  duration/diameter ride the two fields above.
    exit_conditions: tuple = ()
    # TLC prints a progress line roughly every minute; 0 disables.  The
    # CLI defaults this to 60 for `check` runs (SURVEY §5.1: duration,
    # diameter, states/sec, queue as live counters).
    progress_interval_seconds: float = 0.0
    checkpoint_dir: Optional[str] = None  # R8: level-boundary snapshots
    # Shared-filesystem directory for MULTI-HOST trace piece exchange
    # (parallel/mesh.py): controllers write their per-host trace stores
    # there and replay() merges the group.  None defers to
    # checkpoint_dir; setting it alone gives multi-host tracing WITHOUT
    # enabling periodic checkpoint snapshots.
    trace_dir: Optional[str] = None
    checkpoint_every: int = 1             # snapshot every k levels...
    checkpoint_interval_seconds: float = 0.0  # ...but at most this often.
    # Retention: after each successful snapshot, delete all but the
    # newest N intact snapshots/piece groups (checkpoint.gc).  None/0 =
    # keep all — the historical behavior; long supervised runs should
    # set a small N so the states/ dir stays bounded.
    keep_checkpoints: Optional[int] = None
    # Snapshot cost is O(seen states), so a per-level cadence is quadratic
    # over a long run; big runs should set a TLC-style time cadence (TLC
    # defaults to ~30 min between states/ checkpoints) and the CLI does.
    #
    # Directory for spilled level segments (TLC's disk-backed state
    # queue): None keeps them in host RAM; a path memory-maps them to
    # disk so frontiers larger than host memory survive (spillpool.py).
    spill_dir: Optional[str] = None
    # -- telemetry (obs/) ----------------------------------------------
    # JSONL run-event log (run_start / level_complete / fpset_resize /
    # spill / checkpoint / violation / deadlock / run_end).  None defers
    # to ``<checkpoint_dir>/events.jsonl`` when checkpointing is on,
    # else disabled.  Multi-host runs write one file per controller
    # (obs/events.py events_path).
    events_out: Optional[str] = None
    # Shared MetricsRegistry (obs/metrics.py); None gives the engine its
    # own.  Pass one to aggregate several runs (the checker service
    # does) or to read live gauges from another thread.
    metrics: Optional[object] = None
    # Chrome trace-event span log (obs/tracing.py): every phase_timer
    # block, a span per BFS level, and the whole run serialize to this
    # file at run end — opens directly in Perfetto/chrome://tracing.
    # None disables (zero overhead: the tracer no-ops).
    trace_out: Optional[str] = None
    # Mesh skew telemetry (parallel/mesh.py): emit a ``skew`` warning
    # event when the per-shard frontier imbalance (max/mean of this
    # controller's shard next-level counts) reaches this ratio at a
    # level boundary.  The balance gauges + level_complete fields are
    # always on (a handful of host ints per level); only the warning
    # threshold is configurable.
    skew_warn_ratio: float = 2.0
    # Deadline for collecting sibling controllers' trace piece files at
    # replay (parallel/mesh.py _merge_trace_pieces).  None = auto: a 30 s
    # base plus a size-proportional allowance — the sibling of a large
    # local piece is probably still compressing its own.
    trace_merge_timeout_seconds: Optional[float] = None
    # -- flight recorder / live introspection (obs/flight.py) ----------
    # Directory for the crash postmortem dump (postmortem.json, written
    # on an exception escaping the run, SIGTERM, or a fault-injected
    # hard kill — never on a completed run).  None defers to
    # checkpoint_dir; with neither set the dump is disabled (the
    # in-memory flight ring still feeds watch/metrics-port attach).
    postmortem_dir: Optional[str] = None
    # Extra key/values merged into the flight recorder's ``run_context``
    # record when the run arms (serving/: the job manager tags each
    # server-executed run with ``{"job_id": ..., "tenant": ...}`` so
    # ring snapshots, watch consoles, and postmortem dumps attribute
    # device time to the job that spent it).  Host-side only — safe to
    # set per-request on a warm cached engine, like the budgets.
    run_context_extra: Optional[dict] = None
    # Device-profiler capture (obs/profile.py XlaProfileCapture;
    # --xla-profile[=N] / XLA_PROFILE directive): bracket the first N
    # chunk calls of the run in a jax.profiler trace window, correlated
    # with the SpanTracer's "chunk" spans by shared span name +
    # step_num.  Artifacts land under xla_profile_dir (None =
    # "<checkpoint_dir>/xla_profile", or "./xla_profile" without a
    # checkpoint dir).  Observational: engine results are bit-identical
    # with the capture on or off; a profiler that cannot start records
    # its failure in the xla_profile event instead of raising.
    xla_profile_chunks: Optional[int] = None
    xla_profile_dir: Optional[str] = None
    # -- semantic observability (obs/report.py, engine/explain.py) -----
    # TLC-parity run report: assembled HOST-SIDE at run end from
    # counters the loop already fetched (fingerprint collision
    # probability, per-level frontier table, out-degree summary,
    # seen-set load), emitted as a ``statespace`` run event, rendered
    # as the TLC-style stderr block on progress-enabled runs, and
    # surfaced on ``EngineResult.report`` / bench JSON / the server
    # ``check`` response + ``statespace/*`` gauges.  Purely
    # observational — engine counts are bit-identical with the report
    # on or off (tested); False drops every surface.
    statespace_report: bool = True
    # Where the rendered counterexample (counterexample.txt + .json,
    # engine/explain.py) is written automatically when a traced run
    # finds a violation.  None defers to checkpoint_dir; with neither
    # set the auto-write is disabled (CLI `check --render-trace` and
    # the `explain` subcommand still render from the in-memory trace).
    counterexample_dir: Optional[str] = None
    # -- graceful degradation (resilience/) ----------------------------
    # Catch RESOURCE_EXHAUSTED from the run (chunk dispatch, buffer
    # allocation, seen-set growth): rebuild the engine at HALF the batch
    # and continue from the newest intact snapshot (or from scratch when
    # none exists) instead of aborting — an out-of-memory failure
    # becomes a slow-but-correct run, recorded as a
    # ``degraded`` obs event.  Halving stops at min_batch; multi-host
    # process groups re-raise instead (one controller cannot rebuild
    # alone while its siblings wait in collectives — crash-level
    # recovery there is the supervisor's job).
    degrade_on_oom: bool = True
    min_batch: int = 32

    def __post_init__(self):
        check_pipeline(self.pipeline)


@dataclasses.dataclass
class Violation:
    invariant: str
    state: PyState
    fingerprint: int


@dataclasses.dataclass
class EngineResult:
    distinct: int = 0
    generated: int = 0
    diameter: int = 0
    levels: List[int] = dataclasses.field(default_factory=list)
    # Enabled-successor count per action family (TLC's per-action
    # statistics; family name -> count; sums to ``generated``).
    action_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    # TLC-coverage snapshot (obs/coverage.py): {family: {generated,
    # distinct, disabled}}.  ``generated`` here is the same series as
    # ``action_counts`` (one packed-stats source), ``distinct`` counts
    # first FPSet insertions per family, ``disabled`` the false guard
    # evaluations.  Populated by the engines at run end.
    coverage: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    violation: Optional[Violation] = None
    deadlock: Optional[PyState] = None
    stop_reason: str = "exhausted"
    wall_seconds: float = 0.0
    # Seen-set growth events as (capacity-after, stall-seconds) — off the
    # duration clock, recorded as evidence for up-front SEEN_CAPACITY
    # sizing (each is a rehash + retrace on the growing engine).
    growth_stalls: List = dataclasses.field(default_factory=list)
    # Which successor pipeline actually ran ("v1"/"v2") —
    # makes an ``auto`` fallback observable instead of a silent slowdown.
    pipeline: str = ""
    # Certified ample instances the run's POR table carried (0 = POR off
    # or an all-conservative certificate — either way, full expansion).
    por_instances: int = 0
    # BLEST-batched expansion grouping (models/actions.py
    # family_groups): which action families share each stacked dense
    # kernel and how many lanes each group contributes — static
    # metadata, recorded so the batched-expansion win is attributable
    # per family in the statespace report and the history ledger
    # (ROADMAP item 2a's coverage tables).  [] before the grouping.
    family_groups: List = dataclasses.field(default_factory=list)
    # Host-side per-phase wall-time breakdown for this run
    # ({phase: seconds}; obs/metrics.py phase timers): chunk dispatch,
    # stats fetch, trace flush, spill, fpset growth, checkpoint, ... —
    # embedded in bench JSON and the run_end event.
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    # TLC-parity statespace report (obs/report.py build_report):
    # collision probability, per-level table, out-degree, seen-set
    # load.  {} when EngineConfig.statespace_report is off.
    report: Dict = dataclasses.field(default_factory=dict)
    # Per-level boundary snapshots feeding the report's level table
    # ({level, frontier, distinct, generated, seen_size,
    # seen_capacity}), appended by _level_fields.  A resumed run's
    # pre-resume levels appear in the report with frontier width only.
    level_stats: List = dataclasses.field(default_factory=list)
    # Paths of the auto-rendered counterexample artifacts
    # (engine/explain.py write_counterexample): {"txt": ..., "json":
    # ..., "depth": n}, {} when no traced violation was rendered.
    counterexample: Dict = dataclasses.field(default_factory=dict)

    @property
    def states_per_second(self) -> float:
        return self.distinct / self.wall_seconds if self.wall_seconds else 0.0


# Trace stores (C++-backed with Python fallback) live in engine/trace.py;
# re-exported here for compatibility.
from .trace import PyTraceStore as TraceStore  # noqa: E402
from .trace import make_trace_store  # noqa: E402


def _progress_line(res, t0, queue_rows, level_frontier, metrics=None):
    """TLC-style progress line (its ~per-minute report: states generated,
    distinct states, states left on queue), written to stderr by the
    engines when progress_interval_seconds is set, with the TLC-parity
    extras: distinct/s, generated/s, queue depth, and the fpset load
    factor.  Totals render from THIS run's result object — the registry
    can be shared across runs (the server's process-global one, warm
    engines) and its counters are cumulative, which is exactly what a
    per-run progress line must not print.  The per-run rates/gauges are
    pushed to the registry first; the load factor reads the seen-set
    gauges the engines keep current (run-scoped by construction)."""
    import sys as _sys
    dt = max(time.time() - t0, 1e-9)
    load = 0.0
    if metrics is not None:
        metrics.gauge("engine/queue_rows", queue_rows)
        metrics.gauge("engine/level_frontier", level_frontier)
        metrics.gauge("engine/states_per_sec", res.distinct / dt)
        metrics.gauge("engine/generated_per_sec", res.generated / dt)
        seen_cap = metrics.gauge_value("engine/seen_capacity")
        load = (metrics.gauge_value("engine/seen_size") / seen_cap
                if seen_cap else 0.0)
    print(f"progress: {res.generated:,} generated "
          f"({res.generated / dt:,.0f}/s), "
          f"{res.distinct:,} distinct ({res.distinct / dt:,.0f}/s), "
          f"diameter {res.diameter} (expanding {level_frontier:,}), queue "
          f"{queue_rows:,}, fpset load {load:.2f}, elapsed {dt:,.0f}s",
          file=_sys.stderr)


def _exit_condition_hit(conds, res, queue_rows):
    """First tripped TLCGet budget, as its stop_reason — or None.
    ``conds`` holds only the counters without native budget fields
    (utils/cfg.py routes duration/diameter to max_seconds/max_diameter)."""
    live = {"distinct": res.distinct, "generated": res.generated,
            "queue": queue_rows}
    for counter, threshold in conds:
        if live[counter] > threshold:
            return f"{counter}_budget"
    return None


def build_root_check(inv_fns, fingerprint):
    """jit'd ``StateBatch batch -> (inv ids, fp_hi, fp_lo)``.

    Root states are invariant-checked on their *unpacked* int32 encoding:
    the uint8 row packing wraps out-of-range values (a hand-crafted or
    randomized root with matchIndex = -1 becomes 255, a legal Nat), so a
    post-packing TypeOK check would miss them.  TLC checks invariants on
    initial states before exploration; the engines do the same, on the
    exact values given.  Kernel-produced successors are in-range by
    construction and need no such pass."""
    def check(batch):
        inv = jax.vmap(build_inv_id(inv_fns))(batch)
        fph, fpl = jax.vmap(fingerprint)(batch)
        return inv, fph, fpl
    return jax.jit(check)


def _auto_capacities(sw: int, batch: int,
                     record_trace: bool) -> Tuple[int, int]:
    """(queue rows, seen keys) sized from the device's reported HBM.

    Budget (after a 25% headroom for XLA temporaries and the candidate
    buffers): half to the three level queues (current, next, and the
    async-spill spare; + trace buffer when tracing), a quarter to the
    fingerprint table (8 B/slot).  TLC has no equivalent — its queue and
    FPSet page to disk; here the spill path plays that role and these
    sizes only set the device-resident working set.  The CPU backend
    reports no limit and gets modest defaults; an accelerator that
    reports none is an error (no memory size is assumed for it)."""
    dev = jax.devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if not limit:
        if dev.platform != "cpu":
            raise RuntimeError(
                f"device {dev} ({dev.device_kind}) reports no "
                f"memory_stats()['bytes_limit']; pass explicit "
                f"queue_capacity / seen_capacity")
        return 1 << 20, 1 << 22
    usable = int(limit * 0.75)
    row_cost = 3 * sw + (20 if record_trace else 0)   # queues + trace row
    q = max(batch, min(usable // 2 // row_cost, 1 << 25))
    s = max(1 << 18, min(usable // 4 // 8, 1 << 28))
    return q, s


def resolve_por(cfg: EngineConfig, dims, invariants, constraint):
    """EngineConfig.por/por_table -> a verified analysis.por.PorTable or
    None (POR off).  Shared by the single-chip and mesh engines.

    A path loads the versioned artifact (fingerprint-checked — a
    hand-edited mask is rejected there); ``por=True`` without a table
    certifies in-process against exactly this run's invariants and
    constraint.  Either way ``check_table`` gates admission: model
    signature, instance count, and predicate coverage must match the
    run, so a certificate can never be applied outside the conditions
    it was proved under."""
    if not cfg.por and cfg.por_table is None:
        return None
    from ..analysis import por as por_mod
    table = cfg.por_table
    if isinstance(table, str):
        table = por_mod.load_table(table)
    if table is None:
        table = por_mod.build_table(dims, invariants=dict(invariants),
                                    constraint=constraint)
    por_mod.check_table(table, dims,
                        invariant_names=list(invariants),
                        has_constraint=constraint is not None)
    return table


def por_device_arrays(table):
    """(mask, priority) jnp arrays for a verified table, or (None, None)
    when there is nothing to mask — an all-conservative certificate
    (certified == 0) compiles the EXACT pre-POR chunk program, paying
    zero hot-path arithmetic for a mask that provably changes nothing.
    Shared by both engines so the fast-path rule can never drift."""
    if table is None or not table.certified:
        return None, None
    return jnp.asarray(table.ample_mask), jnp.asarray(table.priority)


def _resolve_pipeline(requested: str, dims):
    """EngineConfig.pipeline -> a v2 pipeline object or None (v1).

    Under ``auto``, only :class:`~..models.actions2.V2Unavailable` (the
    variant genuinely lacks v2 kernels) selects v1 — any other error from
    kernel construction propagates, so a bug in a variant's
    ``build_extra_v2`` can never silently degrade to the slow path.  The
    resolved choice is recorded on ``EngineResult.pipeline``."""
    from ..models.actions2 import V2Unavailable, build_v2
    check_pipeline(requested)
    if requested == "v1":
        return None
    if requested == "v2":
        return build_v2(dims)   # raises if a variant lacks v2 kernels
    try:
        return build_v2(dims)
    except V2Unavailable:
        return None             # variant without build_extra_v2 -> v1


def _family_groups_meta(dims, _v2=None):
    """Static BLEST grouping metadata (models/actions.py
    family_groups) for EngineResult/report/ledger attribution.
    Fail-soft: a variant the grouper cannot describe yields [] — the
    grouping is observability, never a failed engine build."""
    try:
        from ..models.actions import family_groups
        return family_groups(dims)
    except Exception:  # noqa: BLE001 — metadata only
        return []


def find_root_violation(root_check, roots, init_states, batch_size,
                        inv_names) -> Optional[Violation]:
    """Run ``build_root_check``'s program over the stacked encoded roots
    (``stack_states``) in fixed-size chunks (padding by repeating the
    last root so one program shape serves any root count); first
    violation wins, like TLC."""
    n = len(init_states)
    for base in range(0, n, batch_size):
        at = np.minimum(np.arange(base, base + batch_size), n - 1)
        inv, fph, fpl = root_check(StateBatch(*(x[at] for x in roots)))
        inv = np.asarray(inv)[:n - base]
        if (inv >= 0).any():
            i = int(np.argmax(inv >= 0))
            fp = (int(np.asarray(fph)[i]) << 32) | int(np.asarray(fpl)[i])
            return Violation(invariant=inv_names[int(inv[i])],
                             state=init_states[base + i], fingerprint=fp)
    return None


class BFSEngine:
    """Exhaustive checker for one compiled (dims, invariants, constraint)."""

    def __init__(self, dims: RaftDims,
                 invariants: Optional[Dict[str, Callable]] = None,
                 constraint: Optional[Callable] = None,
                 config: Optional[EngineConfig] = None):
        self.dims = dims
        self.config = config or EngineConfig()
        cfg = self.config
        # Telemetry spine (obs/): one registry per engine unless the
        # caller shares one; the event log is opened per run.
        # ``_rebuild_at_batch`` re-enters __init__ MID-RUN (OOM
        # degradation), so an existing registry and open event log must
        # survive the re-init (parallel/mesh.py growth-path rule).
        self.metrics = (cfg.metrics or getattr(self, "metrics", None)
                        or MetricsRegistry())
        if not hasattr(self, "_evlog"):
            self._evlog = RunEventLog(None)
            self._phase_base = {}
        # Span tracer (obs/tracing.py): survives re-entrant re-inits like
        # the registry; attaching it to the registry mirrors every
        # phase_timer block into a Chrome-trace span.
        if not hasattr(self, "tracer"):
            self.tracer = SpanTracer(cfg.trace_out,
                                     annotate=jax.profiler.TraceAnnotation)
        self.metrics.tracer = self.tracer
        watch_compiles()
        # Device-profiler capture is created per run (_telemetry_run);
        # the attribute must exist (and survive re-entrant re-inits) so
        # the chunk loop can always read it.
        if not hasattr(self, "_xla_capture"):
            self._xla_capture = None
        if cfg.checkpoint_dir:
            # Fail at construction, not at the first level-boundary write.
            from . import checkpoint as _ckpt
            _ckpt.check_dims_checkpointable(dims)
        self.inv_names = list((invariants or {}).keys())
        self._inv_fns = inv_fns = list((invariants or {}).values())
        self._constraint = constraint
        expand = build_expand(dims)
        fingerprint = build_fingerprint(dims)
        pack_ok = build_pack_guard(dims)
        self._v2 = _resolve_pipeline(cfg.pipeline, dims)
        self._pipeline_name = "v2" if self._v2 is not None else "v1"
        # Partial-order reduction table (analysis/por.py): verified
        # before any mask is applied; None = full expansion.  Survives
        # the re-entrant OOM-degrade __init__ (same rule as the registry
        # above): the verified table is batch-independent, and
        # re-resolving mid-degrade would re-trace every kernel — or
        # re-read an artifact file that may be gone — exactly while the
        # process is under memory pressure.
        if not hasattr(self, "_por_table"):
            self._por_table = resolve_por(
                cfg, dims, dict(zip(self.inv_names, inv_fns)), constraint)
        por_mask, por_priority = por_device_arrays(self._por_table)
        sw = state_width(dims)
        B, G = cfg.batch, dims.n_instances
        # Compacted-candidate lanes (ops/compact.py owns the invariants).
        K = compact_mod.choose_k(B, G, cfg.compact_lanes)
        qreq, sreq = cfg.queue_capacity, cfg.seen_capacity
        if qreq is None or sreq is None:
            auto_q, auto_s = _auto_capacities(sw, B, cfg.record_trace)
            qreq = auto_q if qreq is None else qreq
            sreq = auto_s if sreq is None else sreq
        # The table is floored at 8 worst-case batches of keys: the device
        # loop stops for growth at half-full, so a single batch can then
        # push the load at most to 1/2 + 1/8 — far from where double-hash
        # probes start failing.  (fpset rounds up to a power of two.)
        self._seen_cap = max(sreq, 8 * K)
        # What a fresh run() starts its table at: the capacity the last
        # run from roots needed (``_keep_capacity``), so that the next run
        # of the same roots pays no growth again.  Set here, so a build
        # (the degrade rebuild's re-entrant one too) knows only its own
        # size; ``chunk_avals`` and a resume keep ``_seen_cap``.
        self._seen_cap_kept = fpset._capacity(self._seen_cap)
        # Queue capacity: floored at one worst-case batch (K rows, every
        # compacted candidate new) — a batch entering at/below the spill
        # watermark (Q - K) can then never overflow.  Rounded to a multiple
        # of B for tidy level slicing.  The device allocation carries PAD
        # extra rows past Q: B so the batch dynamic_slice near the queue
        # end never clamps (a clamp would silently re-window the slice),
        # and K of scatter "trash" so masked-off enqueue lanes each write
        # to their own distinct address beyond the live region — a shared
        # drop index serializes the TPU scatter (ops/fpset.py design note
        # 3).  Rounded copies kept on self — the config is not mutated.
        Q = max(-(-qreq // B) * B, K)
        PAD = max(B, K)
        self._sw, self._B, self._G, self._Q = sw, B, G, Q
        self._K, self._PAD = K, PAD
        self._inv_lanes_a_pass = K if inv_fns else 0

        # The ingest program's stages carry the chunk's names
        # (engine/chunk.py STAGES), so one reduction reads both.
        @functools.partial(named_stage, "construct")
        def construct(crows):
            cands = jax.vmap(unflatten_state, (0, None))(crows, dims)
            fph, fpl = jax.vmap(fingerprint)(cands)
            if inv_fns:
                inv = jax.vmap(build_inv_id(inv_fns))(cands)
            else:
                inv = jnp.full(crows.shape[:1], -1, _I32)
            if constraint is not None:
                cons_ok = jax.vmap(constraint)(cands)
            else:
                cons_ok = jnp.ones(crows.shape[:1], bool)
            return fph, fpl, inv, cons_ok

        @functools.partial(named_stage, "enqueue")
        def enqueue(qnext, next_count, crows, new, cons_ok):
            k = crows.shape[0]
            enq = new & cons_ok
            pos = next_count + jnp.cumsum(enq.astype(_I32)) - 1
            # Disabled lanes scatter to distinct trash rows past Q (PAD =
            # max(B, K) >= k guarantees room) — a single shared trash index
            # would serialize the scatter on TPU (ops/fpset.py design note 3).
            pos = jnp.where(enq, pos, Q + jnp.arange(k, dtype=_I32))
            qnext = qnext.at[pos].set(crows, mode="drop")
            return qnext, next_count + jnp.sum(enq, dtype=_I32)

        @functools.partial(named_stage, "record")
        def record(new, cols):
            # Compacted trace records for the n_new fresh states.  Non-new
            # lanes spread over k..2k-1 trash slots (sliced off below) — a
            # single shared drop index would serialize the five scatters
            # (ops/fpset.py design note 3).
            k = new.shape[0]
            tpos = jnp.where(new, jnp.cumsum(new.astype(_I32)) - 1,
                             k + jnp.arange(k, dtype=_I32))
            return tuple(jnp.zeros((2 * k,), x.dtype).at[tpos].set(x)[:k]
                         for x in cols)

        @functools.partial(named_stage, "stats")
        def stats(new, inv, crows, fph, fpl):
            viol = new & (inv >= 0)
            vpos = jnp.argmax(viol)
            return jnp.sum(new, dtype=_I32), (
                jnp.any(viol), inv[vpos], crows[vpos], fph[vpos], fpl[vpos])

        insert = named_stage("insert", fpset.insert)

        def absorb(crows, en, parent_hi, parent_lo, actions,
                   qnext, next_count, seen):
            """Shared tail: hash-insert candidates (which both dedups the
            batch and probes/updates the FPSet in one pass — no sorts),
            enqueue, report.  ``crows`` [K,SW] flat rows, ``en`` [K]
            validity.  The StateBatch views are re-sliced from ``crows`` so
            the rows are the only materialized candidate buffer."""
            fph, fpl, inv, cons_ok = construct(crows)
            seen, new, fail = insert(seen, fph, fpl, en)
            qnext, next_count = enqueue(qnext, next_count, crows, new,
                                        cons_ok)
            tr = record(new, (fph, fpl, parent_hi, parent_lo, actions))
            n_new, vinfo = stats(new, inv, crows, fph, fpl)
            return qnext, next_count, seen, n_new, fail, tr, vinfo

        def ingest(rows, valid, qnext, next_count, seen):
            sent = jnp.zeros(rows.shape[:1], jnp.uint32)
            acts = jnp.full(rows.shape[:1], -1, _I32)
            return absorb(rows, valid, sent, sent, acts,
                          qnext, tag_stages(next_count), seen)

        # -- the device-resident level loop --------------------------------
        # One host round-trip costs far more than one batch of device
        # work, so the per-level batch loop
        # runs ON DEVICE as a lax.while_loop processing up to
        # ``sync_every`` batches per call, accumulating every scalar the
        # host needs into ONE packed int32 stats vector (a single fetch).
        # Trace records accumulate in a device buffer flushed per chunk.
        # The loop exits early on violation / deadlock / overflow /
        # trace-buffer pressure; the host inspects the packed stats and
        # fetches the few relevant rows only when a flag is set.
        CH = self._CH = max(1, cfg.sync_every)
        # Trace-buffer rows: enough that a fresh chunk (tcount=0) always
        # has room for >= 1 batch (<= K new states), else the loop could
        # make no progress.  With tracing off the buffers shrink to stubs
        # and every trace scatter (and the parents-only fingerprint pass)
        # compiles out — raw-throughput runs pay nothing for the feature.
        record_static = cfg.record_trace
        TQ = Q + K if record_static else 8
        # None (config default) = TLC's default: deadlock checking on.
        self._check_deadlock = (True if cfg.check_deadlock is None
                                else cfg.check_deadlock)
        check_deadlock_static = self._check_deadlock
        # The next-level queue must always have room for one worst-case
        # batch (every compacted candidate new): the device loop stops at
        # this watermark and the host spills the queue to its memory
        # (TLC's disk-backed state queue, SURVEY §2.4 R8).  Q >= K, so a
        # batch always runs when the count is at/below the watermark and
        # can never overflow; when Q == K exactly (tiny test configs)
        # every batch triggers a spill — correct, just not fast.
        QTH = Q - K
        self._QTH = QTH
        compactor = compact_mod.build_compactor(B, G, K)
        # The per-batch pipeline body is shared with the mesh engine
        # (engine/chunk.py) — only the insert function differs.
        chunk_body = build_chunk_body(
            dims=dims, expand=expand, fingerprint=fingerprint,
            pack_ok=pack_ok, inv_fns=inv_fns, constraint=constraint,
            B=B, G=G, K=K, Q=Q, TQ=TQ, record_static=record_static,
            compactor=compactor, insert_fn=fpset.insert, v2=self._v2,
            por_mask=por_mask, por_priority=por_priority)

        # What sits outside the ``while``: the counters' start, and the
        # packing of what the host fetches.  The pools themselves go
        # into and out of the loop untouched by either.
        @functools.partial(named_stage, "prologue")
        def prologue():
            F = len(dims.family_sizes)
            return (jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.bool_(False), jnp.zeros((sw,), jnp.uint8),
                    jnp.bool_(False), jnp.int32(-1),
                    jnp.zeros((sw,), jnp.uint8),
                    jnp.uint32(0), jnp.uint32(0), jnp.bool_(False),
                    jnp.zeros((F,), _I32), jnp.zeros((F,), _I32),
                    jnp.int32(0), jnp.zeros((F,), _I32))

        @functools.partial(named_stage, "epilogue")
        def epilogue(offset, steps, next_count, seen_size, tcount, gen,
                     newc, ovfc, dead_any, viol_any, vinv, vhi, vlo,
                     fail_any, fam_counts, fam_new, expanded, fam_pruned):
            # fam_counts/fam_new/expanded/fam_pruned ride in the SAME
            # packed vector — the loop's one-fetch-per-call contract is
            # load-bearing.  Layout: 13 scalars, then
            # the per-family generated counts, then the per-family novel
            # counts, then the per-family POR-pruned counts
            # (obs/coverage.py reads the host side).
            stats = jnp.concatenate([jnp.stack([
                offset, steps, next_count, seen_size, tcount, gen, newc,
                ovfc, dead_any.astype(_I32), viol_any.astype(_I32), vinv,
                fail_any.astype(_I32), expanded]), fam_counts, fam_new,
                fam_pruned])
            return stats, jnp.stack([vhi, vlo])

        def chunk(qcur, cur_count, offset0, qnext, next_count, seen,
                  tbuf, tcount0, max_steps):
            # ``max_steps`` (<= CH) is a runtime argument: near a duration
            # budget the host shrinks it so the deadline is honored to
            # within ~one batch, not one whole chunk (TLCGet("duration")
            # promptness — Smokeraft.tla:90).
            init = (offset0, jnp.int32(0), qnext, tag_stages(next_count),
                    seen, tbuf, tcount0, *prologue())

            def cond(c):
                (offset, steps, _qn, next_count, seen_c, _tb, tcount,
                 _g, _n, ovfc, dead_any, _dr, viol_any, _vi, _vr, _vh,
                 _vl, fail_any, _fam, _famn, _exp, _famp) = c
                more = (offset < cur_count) & (steps < max_steps)
                qroom = next_count <= QTH       # host spills past this
                # Stop for growth at half-full: the host doubles the table
                # before the load can reach probe-failure territory.  A
                # chunk always enters at <= half-full (growth guarantees
                # it), so its first batch always runs.
                sroom = seen_c.size <= seen_c.hi.shape[0] // 2
                stop = viol_any | (ovfc > 0) | fail_any
                if check_deadlock_static:
                    stop = stop | dead_any
                cont = more & qroom & sroom & ~stop
                if record_static:
                    cont = cont & (tcount <= TQ - K)
                return cont

            out = jax.lax.while_loop(
                cond, lambda c: chunk_body(qcur, cur_count, c), init)
            (offset, steps, qnext, next_count, seen, tbuf, tcount,
             gen, newc, ovfc, dead_any, drow, viol_any, vinv, vrow,
             vhi, vlo, fail_any, fam_counts, fam_new, expanded,
             fam_pruned) = out
            stats, vhl = epilogue(
                offset, steps, next_count, seen.size, tcount, gen, newc,
                ovfc, dead_any, viol_any, vinv, vhi, vlo, fail_any,
                fam_counts, fam_new, expanded, fam_pruned)
            return qnext, seen, tbuf, stats, drow, vrow, vhl

        def fp_rows(rows):
            return jax.vmap(fingerprint)(
                jax.vmap(unflatten_state, (0, None))(rows, dims))

        self._chunk = jax.jit(chunk, donate_argnums=(3, 5, 6))
        self._ingest = jax.jit(ingest, donate_argnums=(2, 4))
        self._TQ = TQ
        # Allocated trace rows: live region + K trash slots for the
        # masked-off scatter lanes (stub when tracing is off).
        self._TA = TQ + K if record_static else 8
        # The trace flush's fetch programs (``_TraceFlush``), one per
        # length; ``_run_impl`` runs each once in warm-up.
        self._fetch_lens = fetch_lengths(self._TA)
        self._fetch = jax.jit(
            lambda tbuf, start, length: tuple(
                jax.lax.dynamic_slice(x, (start,), (length,))
                for x in tbuf),
            static_argnums=2)
        self._fp_rows = jax.jit(fp_rows)
        # The replay: a whole trace in one call (engine/replay.py), and
        # the per-step matcher's two programs for what it cannot hold.
        self._replay_scan = ReplayScan(dims, self.metrics)
        self._expand1 = jax.jit(expand)
        self._fp_batch = jax.jit(jax.vmap(fingerprint))
        self._root_check = (build_root_check(inv_fns, fingerprint)
                            if inv_fns else None)

    def chunk_avals(self) -> tuple:
        """The chunk program's arguments as shapes (``self._chunk``'s
        signature at this build's sizes): what the launch model traces
        and what a compile for a described chip lowers."""
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        qav = jax.ShapeDtypeStruct((self._Q + self._PAD, self._sw),
                                   jnp.uint8)
        seen_av = jax.eval_shape(lambda: fpset.empty(self._seen_cap))
        tbuf_av = tuple(
            jax.ShapeDtypeStruct((self._TA,), d)
            for d in (jnp.uint32, jnp.uint32, jnp.uint32,
                      jnp.uint32, _I32))
        return (qav, i32, i32, qav, i32, seen_av, tbuf_av, i32, i32)

    # ------------------------------------------------------------------
    def run(self, init_states: Optional[List[PyState]] = None,
            resume=None) -> EngineResult:
        """Run to exhaustion (or budget/violation).  Pass either
        ``init_states`` for a fresh run or ``resume`` (a
        ``checkpoint.Checkpoint`` or a path to one) to continue an
        interrupted run from its last level-boundary snapshot.

        Telemetry wrapper: opens the run event log (EngineConfig.
        events_out), brackets the run with run_start/run_end events, and
        scopes the per-phase wall-time breakdown to this run
        (``EngineResult.phases``) even on a warm, reused engine."""
        return self._telemetry_run(self._run_degradable, init_states,
                                   resume=resume)

    # ------------------------------------------------------------------
    def _run_degradable(self, init_states, resume=None):
        """Graceful degradation under resource exhaustion (resilience/):
        retry ``_run_impl`` at half the batch when the device reports
        RESOURCE_EXHAUSTED, continuing from the newest intact snapshot —
        slow-but-correct instead of dead.  Shared with the mesh engine
        via duck typing (``_rebuild_at_batch`` is per-class).

        Restarting from a checkpoint is the only SAFE recovery: the
        chunk/ingest programs donate the next-queue, seen-set, and trace
        buffers, so after a failed dispatch the in-flight device state
        is gone — a level-boundary snapshot (or the original roots) is
        the nearest consistent image."""
        from . import checkpoint as ckpt_mod
        from ..parallel import multihost as mh
        cfg = self.config
        # Stale-dir guard (supervisor.py rule): snapshot names already in
        # the dir belong to a PREVIOUS run unless the caller asked to
        # resume — a fresh run must never degrade into a foreign image
        # (load() validates only dims, not cfg/bounds).  Names, not
        # contents: listdir is cheap enough to pay on every run.
        user_resume = resume is not None
        preexisting = (set(os.listdir(cfg.checkpoint_dir))
                       if cfg.checkpoint_dir
                       and os.path.isdir(cfg.checkpoint_dir) else set())
        while True:
            try:
                return self._run_impl(init_states, resume=resume)
            except Exception as e:
                if not (cfg.degrade_on_oom and is_resource_exhausted(e)):
                    raise
                if mh.is_multiprocess():
                    # One controller rebuilding alone would deadlock its
                    # siblings' collectives; the supervisor restarts the
                    # whole process group instead.
                    raise
                new_batch = self.config.batch // 2
                if new_batch < max(1, cfg.min_batch):
                    raise
                ck = (ckpt_mod.latest(cfg.checkpoint_dir)
                      if cfg.checkpoint_dir else None)
                if ck is not None and not user_resume \
                        and os.path.basename(ck) in preexisting:
                    ck = None          # foreign snapshot: scratch restart
                if ck is not None:
                    resume = ck
                elif resume is None and init_states is None:
                    raise       # resumed run, snapshot gone: nothing left
                self._evlog.emit(
                    "degraded", reason="resource_exhausted",
                    error=f"{type(e).__name__}: {str(e)[:300]}",
                    batch=self.config.batch, new_batch=new_batch,
                    resume_from=ck, memory=device_memory_stats())
                self.metrics.counter("engine/degraded")
                import sys as _sys
                print(f"degraded: RESOURCE_EXHAUSTED; retrying at batch "
                      f"{new_batch}"
                      + (f", resuming {ck}" if ck else ""),
                      file=_sys.stderr)
                with self.metrics.phase_timer("degrade_rebuild"):
                    self._rebuild_at_batch(new_batch)

    def _rebuild_at_batch(self, new_batch: int) -> None:
        """Recompile every program at a smaller batch (re-entrant
        __init__, the parallel/mesh.py growth-path pattern); the open
        event log / metrics registry survive."""
        BFSEngine.__init__(
            self, self.dims,
            invariants=dict(zip(self.inv_names, self._inv_fns)),
            constraint=self._constraint,
            config=dataclasses.replace(self.config, batch=new_batch))

    def _telemetry_run(self, impl, init_states, resume=None):
        """Shared run_start/run_end bracketing (single-chip and mesh):
        event log, run/level spans, coverage run-end
        reporting, the Chrome-trace write-out — and the flight
        recorder's arm/disarm cycle: the black box is armed for the
        whole run (postmortem on any abnormal death), and disarmed on
        every completed run regardless of stop_reason."""
        cfg, mt = self.config, self.metrics
        if self.tracer.enabled:
            self.tracer.reset()     # one trace file = one run
        # One identifier for the spans of one verdict: this run's, and
        # those of the replay of what it finds.
        self.tracer.run = self._run_id = getattr(self, "_run_id", 0) + 1
        run_span = mt.open_span("run", SCOPE_PREFIX,
                                resume=resume is not None)
        self._level_span = None
        self._evlog = evlog = RunEventLog(self._events_path())
        self._phase_base = mt.phase_seconds()
        self._compile_base = compiles_by_span(mt)
        self._jit_base = process_record().jit_reading()
        self._work_base = self._work_level_base = work_counts(mt)
        # One row a device call (obs/calls.py).  And no store of an
        # earlier run's: ``_run_impl`` makes this run's own.
        self._calls = CallLog(self._run_id)
        self.trace = None
        # ``generated_by_family`` of this run's events is the run's own
        # share: what a resumed snapshot carried is taken where
        # ``_run_impl`` loads it (``_note_family_base``).
        self._family_base = None if resume is not None else {}
        # Observed-collision base: the counter is process-cumulative
        # (shared registries — server, warm engines), the report's
        # "observed dual-key collisions" is per-run.
        self._collision_base = mt.counter_value("engine/fp_collisions")
        self.coverage = None        # _run_impl installs this run's own
        # Device-HBM watermark (level-correlated OOM evidence): per-run
        # high-water mark, re-armed here so a warm shared registry
        # never carries a previous run's peak into this run's levels.
        self._hbm_watermark = 0
        # Black box armed before the first event so run_start itself is
        # in the ring; the context snapshot is what the watch console
        # shows as "what is running".
        _FLIGHT.arm(
            self._postmortem_path(), metrics=mt,
            context={
                "engine": type(self).__name__, "dims": repr(self.dims),
                "batch": cfg.batch, "resume": resume is not None,
                "pipeline": self._pipeline_name,
                # Caller-attributed identity (job/tenant tags from the
                # serving layer) rides the same context record.
                **dict(cfg.run_context_extra or {})})
        _FLIGHT.set_live_evlog(evlog)
        # Device-profiler capture is per-run (the window opens at the
        # first chunk call, after warm-up compilation).
        if cfg.xla_profile_chunks:
            from ..obs import XlaProfileCapture
            self._xla_capture = XlaProfileCapture(
                self._xla_profile_dir(), cfg.xla_profile_chunks)
        else:
            self._xla_capture = None
        self._run_roots = len(init_states or ())
        self._restore_counts = {}   # a resume's rebuild fills it
        self._start_caps = start_caps = self._start_capacity(resume)
        if start_caps and (start_caps["seen_capacity"]
                           > start_caps["seen_capacity_configured"]):
            mt.counter("engine/seen_capacity_kept")
        evlog.emit(
            "run_start", engine=type(self).__name__, dims=repr(self.dims),
            batch=cfg.batch, sync_every=cfg.sync_every,
            record_trace=cfg.record_trace, resume=resume is not None,
            # States the run was given: 1 from Init, more from roots past
            # it; 0 on a resume, whose snapshot brings its own.
            roots=self._run_roots,
            **resume_fields(resume),
            **start_caps,
            memory=device_memory_stats(),
            process=process_record().run_start())
        self._cur_res = None
        err = None
        try:
            res = impl(init_states, resume=resume)
            return res
        except BaseException as e:
            err = e
            raise
        finally:
            try:
                self._end_run(err)
            finally:
                # The exception's traceback holds this frame: a reference
                # kept here would be a cycle, and the failed run's device
                # pools (the locals of ``_run_impl``, which the traceback
                # holds too) would outlive the caller's handler until a
                # collection, under a caller that runs again at once
                # (a resume on this engine after a crash).
                err = None
                run_span.close()
                if self.tracer.enabled:
                    self.tracer.write()

    def _end_run(self, err) -> None:
        """What ``_telemetry_run`` owes a run that ended, however it
        ended: the open level span closed, the run-end reports, the
        ``run_end`` event, the flight recorder disarmed."""
        cfg, mt, evlog = self.config, self.metrics, self._evlog
        self._close_level_span()
        with mt.phase_timer("run_end"):
            res = self._cur_res
            phases = phase_delta(mt.phase_seconds(), self._phase_base)
            if res is not None:
                res.phases = phases
            cov = self.coverage
            if res is not None and cov is not None:
                res.coverage = cov.snapshot()
                cov.feed_metrics(mt)
                if cov.total_generated:
                    # Final coverage snapshot: the series the progress-
                    # interval events sampled, closed at run end.
                    evlog.emit("coverage", final=True,
                               level=res.diameter, actions=res.coverage)
                if cfg.progress_interval_seconds:
                    # TLC prints its coverage statistics at the end of a
                    # run with reporting enabled; same cadence knob here.
                    import sys as _sys
                    print(cov.render_table(), file=_sys.stderr)
        # Between the two ``run_end`` spans, because the render replays
        # the trace and ``replay`` brings its own spans.
        # Counterexample auto-render (engine/explain.py): a traced
        # violation writes <workdir>/counterexample.{txt,json}
        # BEFORE the run_end emit so the event carries the path.
        # A render failure (e.g. a detected fingerprint collision
        # diverging the replay) is reported, never allowed to mask
        # the run's own verdict.
        ce_path = None
        ce_dir = cfg.counterexample_dir or cfg.checkpoint_dir
        if (err is None and res is not None
                and res.violation is not None
                and cfg.record_trace and ce_dir):
            try:
                from .explain import write_counterexample
                res.counterexample = write_counterexample(
                    self, res, ce_dir,
                    basename=self._counterexample_base())
                ce_path = res.counterexample["txt"]
            except Exception as e:
                import sys as _sys
                print(f"counterexample render failed: "
                      f"{type(e).__name__}: {e}", file=_sys.stderr)
        with mt.phase_timer("run_end"):
            # TLC-parity statespace report (obs/report.py): host-side
            # assembly over counters the loop already fetched — its own
            # ``statespace`` event, ``statespace/*`` gauges, and the
            # TLC-style stderr block on progress-enabled runs (the same
            # cadence rule as the coverage table above).
            if cfg.statespace_report and res is not None and err is None:
                from ..obs import report as report_mod
                observed = int(mt.counter_value("engine/fp_collisions")
                               - self._collision_base)
                res.report = report_mod.build_report(
                    res, coverage=cov, level_stats=res.level_stats,
                    seen_capacity=int(mt.gauge_value(
                        "engine/seen_capacity")) or None,
                    seen_size=int(mt.gauge_value("engine/seen_size")),
                    seen_start=self._start_caps,
                    observed_collisions=observed)
                report_mod.feed_metrics(res.report, mt)
                evlog.emit("statespace", report=res.report)
                if cfg.progress_interval_seconds:
                    import sys as _sys
                    print(report_mod.render_report(res.report),
                          file=_sys.stderr)
            # Device-profiler window: close it (early-exit runs) and
            # land the xla_profile event whether the run lived or died.
            cap = getattr(self, "_xla_capture", None)
            if cap is not None:
                cap.finish(evlog)
            # Postmortem: an exception escaping the run is an ABNORMAL
            # end — dump the black box and stamp the path into run_end
            # so the dump is discoverable from the event log alone.
            # (SIGTERM / fault-kill deaths never reach here; their
            # dumps come from the signal handler / faults._die.)
            pm_path = None
            if err is not None:
                pm_path = _FLIGHT.dump(
                    f"run error: {type(err).__name__}: {err}")
                if pm_path is not None:
                    evlog.emit("postmortem", dump={
                        "path": pm_path, "reason": "run_error"})
            call_fields = self._calls.run_end_fields(evlog)
            evlog.emit(
                "run_end",
                stop_reason=(getattr(res, "stop_reason", None)
                             if err is None else "error"),
                error=(f"{type(err).__name__}: {err}" if err is not None
                       else None),
                postmortem_path=pm_path,
                # Where the rendered counterexample landed (None when no
                # traced violation was rendered) — the event log alone
                # locates the artifact, like postmortem_path.
                counterexample_path=ce_path,
                distinct=getattr(res, "distinct", None),
                generated=getattr(res, "generated", None),
                diameter=getattr(res, "diameter", None),
                # Full per-level frontier sizes: chaos_check.py compares
                # supervised vs. uninterrupted runs on this field.
                levels=list(getattr(res, "levels", None) or []),
                wall_seconds=getattr(res, "wall_seconds", None),
                **self._budget_fields(res),
                growth_stalls=len(getattr(res, "growth_stalls", ())),
                phase_seconds=phases,
                # The run's calls reduced (no row here), its collections;
                # what growing cost its trace store (a resume's refill:
                # ``restore_*``).
                **call_fields,
                **store_growth(self.trace, "trace_"),
                # Counted in the loop (WORK_COUNTERS), and the compiles
                # and cache loads of this run by the span they fell in.
                **work_counts(mt, self._work_base),
                generated_by_family=self._generated_by_family(res),
                **self._run_end_extra(),
                compiles=compiles_by_span(mt, self._compile_base),
                jit=process_record().jit_since(self._jit_base),
                memory=device_memory_stats(),
                # Peak host RSS + one probe per visible device; CPU-only
                # platforms report {} per device rather than omitting
                # the field (obs/events.py guards).
                host_rss_peak_bytes=peak_host_rss_bytes(),
                devices_memory=all_device_memory_stats())
            _FLIGHT.set_live_evlog(None)
            _FLIGHT.disarm()     # completed or already-dumped: no atexit dump
            evlog.close()
            self._evlog = RunEventLog(None)

    def _budget_fields(self, res) -> dict:
        """``run_end``'s ``roots`` (the states the run was given, as
        ``run_start`` has them), ``budget_s`` (the duration budget it ran
        under, None without one) and ``budget_overshoot_s`` (the wall
        past that budget of a run the budget stopped, else None)."""
        budget = self.config.max_seconds
        over = None
        if getattr(res, "stop_reason", None) == "duration_budget":
            over = round(res.wall_seconds - budget, 6)
        return {"roots": self._run_roots, "budget_s": budget,
                "budget_overshoot_s": over}

    def _generated_by_family(self, res) -> dict:
        """Successors this run generated, by action family: the packed
        statistics' per-family counts (``res.action_counts``) less what
        a resumed snapshot carried.  Sums to the run's own generated."""
        counts = getattr(res, "action_counts", None) or {}
        base = self._family_base or {}
        return {name: int(counts.get(name, 0)) - int(base.get(name, 0))
                for name in self.dims.family_names}

    def _note_family_base(self, resume) -> None:
        """The snapshot the USER resumed gives the base; one the run
        itself resumes after a degradation holds the run's own counts."""
        if self._family_base is None:
            self._family_base = dict(resume.action_counts)

    def _start_capacity(self, resume) -> dict:
        """``run_start``'s ``seen_capacity`` (slots of the table a run
        from roots starts at: what the last such run of this engine
        needed, ``_keep_capacity``) and ``seen_capacity_configured`` (the
        build's own).  Neither for a resume, whose table is sized from
        its snapshot's keys when ``restore`` has loaded them, nor from
        the mesh engine, whose shards stay as grown."""
        if resume is not None:
            return {}
        return {"seen_capacity": self._seen_cap_kept,
                "seen_capacity_configured": fpset._capacity(self._seen_cap)}

    def _keep_capacity(self, seen) -> None:
        """At the end of a run from roots: the next one starts its table
        at what this one needed, the power of two that holds its final
        keys at a load of 1/2 or less.  No smaller than the configured
        size, and no larger than the table the run ended with, whose
        programs are loaded.  So the size follows the traffic down as
        well as up: a shallow check after a deep one on the same engine
        (the server's cache) probes a small table again."""
        need = fpset._capacity(2 * int(seen.size))
        self._seen_cap_kept = min(
            len(seen.hi), max(need, fpset._capacity(self._seen_cap)))

    def _run_end_extra(self) -> dict:
        """Further fields of ``run_end``: what a resume's seen-set
        rebuild ran (``fpset.from_host_keys``: probe rounds, and the
        lanes they ran on); the mesh engine's are its exchange and
        per-chip counts and its own rebuild's."""
        return dict(self._restore_counts)

    def _events_path(self):
        """Single-controller resolution; the mesh engine overrides with
        per-host piece suffixes."""
        return events_path(self.config.events_out,
                           self.config.checkpoint_dir)

    def _postmortem_path(self):
        """Where the flight recorder dumps on an abnormal death: next to
        the checkpoints unless postmortem_dir overrides; None (no dir at
        all) disables the dump.  The mesh engine overrides with per-host
        piece suffixes, like the event log."""
        d = self.config.postmortem_dir or self.config.checkpoint_dir
        return os.path.join(d, "postmortem.json") if d else None

    def _xla_profile_dir(self):
        """--xla-profile artifact directory: explicit > next to the
        checkpoints > ./xla_profile."""
        cfg = self.config
        if cfg.xla_profile_dir:
            return cfg.xla_profile_dir
        return os.path.join(cfg.checkpoint_dir or ".", "xla_profile")

    def _counterexample_base(self) -> str:
        """Basename stem for the auto-rendered counterexample files;
        the mesh engine suffixes the controller piece id (the event-log
        model) so two controllers on a shared filesystem never race one
        file."""
        return "counterexample"

    def _emit_level_event(self, res, frontier_rows):
        """A level's close, both halves at once, with the device empty
        (``_LevelClose``): the ``level_end`` span is the last inside the
        level's own, which is then closed (one ``level`` span per BFS
        level).  The mesh loop's boundary.  ``BFSEngine``'s loop owes
        the close instead and finishes it behind the next level's first
        dispatch: level L's ``level_end`` span (after its last
        ``trace_flush``) then lies inside level L+1's ``level`` span,
        between that level's first ``chunk`` span and its first
        ``stats_fetch``, and level L's own span ends where L+1's
        begins."""
        close = _LevelClose(self)
        close.owe(res, frontier_rows)
        close.finish("level_closes_drained")

    def _open_level_span(self, level: int) -> None:
        """The ``level`` span of the level about to be built, which ends
        the one before it.  Loop-shaped, so held open on the engine; the
        level's close ends it where no next level follows
        (``_LevelClose.finish``), or ``_end_run`` when the level is left
        unfinished."""
        self._close_level_span()
        self._level_span = self.metrics.open_span(
            "level", SCOPE_PREFIX, level=level)

    def _close_level_span(self) -> None:
        span = getattr(self, "_level_span", None)
        if span is not None:
            span.close()
            self._level_span = None

    def _count_chunk_call(self, passes: int, parents: int) -> None:
        """One chunk call's work, from the statistics just fetched."""
        mt = self.metrics
        mt.counter("engine/chunk_calls")
        mt.counter("engine/passes", passes)
        mt.counter("engine/inv_lanes", passes * self._inv_lanes_a_pass)
        mt.counter("engine/parents_expanded", parents)

    def _level_fields(self, res, frontier_rows) -> dict:
        """What ``level_complete`` says of the level just built: live
        counters + cumulative per-phase wall-time breakdown, all read
        HERE, at the boundary (the next call's dispatch moves the phase
        seconds, the budget counters and the device's memory in use).
        ``unattributed_seconds`` closes the accounting — phases +
        unattributed == elapsed since run_start — so a phase that
        silently stops being timed shows up as growing slack, not a
        plausible-looking breakdown."""
        evlog = self._evlog
        # Per-level device-HBM watermark: run_end's one-shot
        # devices_memory probe cannot say WHICH level drove the peak —
        # sampling here lets an OOM-degradation event be correlated
        # with the level that caused it.  Caveat jaxlib semantics:
        # ``peak_bytes_in_use`` is a PROCESS-LIFETIME allocator peak
        # (a warm engine inherits a bigger previous run's value and
        # the column then never moves), so the per-level CURRENT
        # ``bytes_in_use`` is recorded alongside it — within one run
        # the peak column says where the high-water rose, and on warm
        # processes the bytes_in_use series is the level-correlatable
        # signal.  CPU/virtual devices report no stats: the fields
        # stay None, the gauge untouched.
        mem = device_memory_stats()
        hbm_peak = mem.get("peak_bytes_in_use")
        if hbm_peak is not None:
            self._hbm_watermark = max(
                getattr(self, "_hbm_watermark", 0), int(hbm_peak))
            self.metrics.gauge("engine/device_hbm_peak_bytes",
                               self._hbm_watermark)
        # Mesh skew telemetry (parallel/mesh.py stamps _last_skew just
        # before the boundary; None on the single-chip engine).
        skew = getattr(self, "_last_skew", None)
        # Level snapshot for the statespace report's per-level table
        # (obs/report.py): frontier width + cumulative counters + the
        # seen-set gauges the chunk loop keeps current.  Host-side dict
        # appends — observational by construction.
        if self.config.statespace_report:
            row = {
                "level": res.diameter,
                "frontier": int(frontier_rows),
                "distinct": res.distinct,
                "generated": res.generated,
                "seen_size": int(self.metrics.gauge_value(
                    "engine/seen_size")),
                "seen_capacity": int(self.metrics.gauge_value(
                    "engine/seen_capacity")),
                "hbm_peak_bytes": (int(hbm_peak)
                                   if hbm_peak is not None else None),
                "hbm_bytes_in_use": (int(mem["bytes_in_use"])
                                     if mem.get("bytes_in_use")
                                     is not None else None)}
            if skew is not None:
                row["frontier_skew"] = skew.get("frontier_skew")
                row["seen_skew"] = skew.get("seen_skew")
                row["shard_frontier"] = skew.get("shard_frontier")
            res.level_stats.append(row)
        # No enabled-check: emit() mirrors every event into the flight
        # ring even on a file-less log, and the watch console's level
        # rows come from exactly this record.  The per-level phase_delta
        # below is a dict subtraction — noise next to a level of chunks.
        phases = phase_delta(self.metrics.phase_seconds(),
                             self._phase_base)
        elapsed = evlog.elapsed()
        extra = {}
        if skew is not None:
            extra = {"frontier_skew": skew.get("frontier_skew"),
                     "seen_skew": skew.get("seen_skew"),
                     "shard_frontier": skew.get("shard_frontier")}
        now = work_counts(self.metrics)
        work = {k: round(n - self._work_level_base[k], 6)
                for k, n in now.items()}
        self._work_level_base = now
        return dict(
            level=res.diameter,
            frontier_rows=frontier_rows, distinct=res.distinct,
            generated=res.generated,
            generated_by_family=self._generated_by_family(res),
            phase_seconds=phases,
            unattributed_seconds=round(
                elapsed - sum(phases.values()), 6),
            memory=mem, **work, **extra)

    def _run_impl(self, init_states: Optional[List[PyState]] = None,
                  resume=None) -> EngineResult:
        """One attempt at the run (``_run_levels``).  However it ends, no
        snapshot is left in flight: a return has waited for it, and an
        exception on its way out waits here, so that whoever looks at the
        directory next (``checkpoint.latest`` of a supervisor, or of
        ``_run_degradable``) finds it quiet."""
        saves = _SnapshotSave(self)
        try:
            return self._run_levels(init_states, resume, saves)
        except BaseException:
            saves.finish(quiet=True)
            raise

    def _run_levels(self, init_states, resume, saves) -> EngineResult:
        from . import checkpoint as ckpt_mod
        dims, cfg = self.dims, self.config
        sw, B, Q = self._sw, self._B, self._Q
        if resume is not None:
            if isinstance(resume, str):
                # What ``--resume`` and a supervisor's restart pay before
                # the restore: the file read, its pieces inflated.
                with self.metrics.phase_timer("checkpoint_load"):
                    resume = ckpt_mod.load(resume)
            if resume.dims != dims:
                raise ValueError(
                    f"checkpoint dims {resume.dims} != engine dims {dims}")
        elif init_states is None:
            raise ValueError("need init_states or resume")
        res = EngineResult(
            pipeline=self._pipeline_name,
            por_instances=(self._por_table.certified
                           if self._por_table is not None else 0),
            family_groups=_family_groups_meta(dims, self._v2))
        self._cur_res = res     # run_end event reads it on error exits
        mt, evlog = self.metrics, self._evlog
        self._growth_stalls = res.growth_stalls
        # TLC-style per-action coverage for this run (obs/coverage.py):
        # fed from the packed chunk stats, reported at every progress
        # interval and at run end (_telemetry_run).
        coverage = self.coverage = ActionCoverage(dims.family_names,
                                                  dims.family_sizes)
        t_enter = time.time()   # for early returns before the budget clock
        # Trace recording off => plain dict store (never written); avoids
        # triggering the native build for runs that measure raw throughput.
        trace = make_trace_store() if cfg.record_trace else TraceStore()
        self.trace = trace

        if resume is None:
            # Root handling before warm-up: neither the root check's XLA
            # compile nor a violating root charges the duration budget (TLC
            # reports an init-state violation without starting the clock).
            with mt.phase_timer("roots_encode"):
                roots = stack_states(
                    [encode_state(s, dims) for s in init_states])
            if self._root_check is not None:
                with mt.phase_timer("root_check"):
                    v = find_root_violation(self._root_check, roots,
                                            init_states, B, self.inv_names)
                if v is not None:
                    if cfg.record_trace:
                        # Depth-0 counterexample: register the violating
                        # root under the fingerprint the Violation carries
                        # so replay() yields the one-state trace instead
                        # of a KeyError.
                        trace.roots.setdefault(v.fingerprint, v.state)
                    res.violation = v
                    res.stop_reason = "violation"
                    res.levels.append(0)
                    res.wall_seconds = time.time() - t_enter
                    evlog.emit("violation", invariant=v.invariant,
                               fingerprint=hex(v.fingerprint), level=0)
                    return res
            # Only now reject unpackable roots (see schema.check_packable:
            # an invariant-flagged root is a violation, not an error).
            with mt.phase_timer("roots_encode"):
                check_packable(roots, dims)
                rows_np = flatten_states(roots, dims)
            # Root fingerprints for the trace store — computed (and their
            # program compiled) BEFORE the duration clock starts; root
            # registration is setup, like the warm-up below.
            if cfg.record_trace:
                with mt.phase_timer("root_check"):
                    rhi, rlo = (np.asarray(x) for x in
                                self._fp_rows(jnp.asarray(rows_np)))
                    for idx, s in enumerate(init_states):
                        fp = (int(rhi[idx]) << 32) | int(rlo[idx])
                        trace.roots.setdefault(fp, s)

        # Queues carry PAD rows past Q: slice overrun + scatter trash
        # (see the capacity comment in __init__).  Every queue buffer is
        # COMMITTED to the device explicitly: the jit cache keys on arg
        # placement, so an uncommitted jnp.zeros entering _chunk (e.g.
        # the async-spill spare at the first swap) retraces and RECOMPILES
        # the whole chunk program mid-run — ~10 s of silently charged
        # wall time on a cold compilation cache.
        with mt.phase_timer("run_init"):
            dev = jax.devices()[0]
            QA = Q + self._PAD
            qcur = jax.device_put(jnp.zeros((QA, sw), jnp.uint8), dev)
            qnext = jax.device_put(jnp.zeros((QA, sw), jnp.uint8), dev)
            # A fresh run starts at what the last one needed; a resume
            # sizes its own table from the snapshot (``restore``).
            seen = jax.device_put(
                fpset.empty(self._seen_cap_kept if resume is None
                            else self._seen_cap), dev)
            # Committed, as ingest hands it back: see the warm-up.
            next_count = jax.device_put(jnp.int32(0), dev)
            # The async spill's spare queue (below).
            free_q: List = [
                jax.device_put(jnp.zeros((QA, sw), jnp.uint8), dev)]
            TA = self._TA
            tbuf = jax.device_put(
                (jnp.zeros((TA,), jnp.uint32), jnp.zeros((TA,), jnp.uint32),
                 jnp.zeros((TA,), jnp.uint32), jnp.zeros((TA,), jnp.uint32),
                 jnp.zeros((TA,), _I32)), dev)
        # Host-resident level segments: the part of the current level that
        # does not fit the device queue (``pending``) and next-level
        # overflow drained mid-level (``spill_next``) — TLC's disk-backed
        # state queue (host RAM by default; memory-mapped files under
        # ``spill_dir`` for frontiers beyond host memory).
        from .spillpool import SpillPool
        pending = SpillPool(cfg.spill_dir)
        spill_next = SpillPool(cfg.spill_dir)
        # Async spill: a watermark drain kicks off a non-blocking D2H of
        # the full next-queue and swaps in a spare buffer, so the drain
        # overlaps the following chunks' compute; the transfer is resolved
        # (and the buffer recycled) at the next drain or level boundary.
        inflight: List = []        # [(device array, row count)]

        def resolve_spill():
            while inflight:
                with mt.phase_timer("spill"):
                    arr, cnt = inflight.pop(0)
                    host = np.asarray(arr)  # completes the async copy
                    # copy=True: on CPU backends np.asarray can be a
                    # zero-copy VIEW of the device buffer, which is about
                    # to be recycled and donated — and a view would also
                    # pin all QA rows.  (Disk-backed pools copy into
                    # their memmap regardless.)
                    spill_next.append(host[:cnt], copy=True)
                    free_q.append(arr)

        # Warm-up: run both programs once with empty inputs (no semantic
        # effect: all-invalid masks insert nothing, zero-trip chunk) so XLA
        # compilation does not count against the StopAfter duration budget —
        # TLC's TLCGet("duration") measures checking, not compilation.
        # Timed as phase "warmup": compilation is off the budget clock but
        # on the telemetry one, so event phase sums still cover the wall.
        with mt.phase_timer("warmup"):
            # ``next_count`` goes in committed, as every real ingest call
            # gets it: the last call's output, or after a spill of the
            # roots a zero that is committed too.  The jit cache keys on
            # argument placement: a fresh jnp.int32(0) anywhere is a second
            # ingest program, and the first real call with the other then
            # compiles ON the StopAfter clock (~5 s on a cold cache,
            # measured 2026-07-31: why the literal Smokeraft.cfg's 1-second
            # budget landed at ~4 s, VERDICT r4 weak #4).  One placement
            # is also one program a capacity, which a growth loads for the
            # table it makes (``_grow_precompiled``).
            qnext, next_count, seen = self._ingest_nothing(
                qnext, next_count, seen)
            # Once more on the first call's outputs, as the chunk below:
            # what a real call passes back in.
            qnext, next_count, seen = self._ingest_nothing(
                qnext, next_count, seen)
            # The chunk takes its counts as the level loop passes them,
            # fresh scalars, for the same reason: not ingest's committed
            # ``next_count``.
            out = self._chunk(qcur, jnp.int32(0), jnp.int32(0),
                              qnext, jnp.int32(0), seen, tbuf, jnp.int32(0),
                              jnp.int32(self._CH))
            qnext, seen, tbuf = out[0], out[1], out[2]
            # Second zero-trip call with the first call's OUTPUTS: jit
            # caches key on argument placement, and outputs carry
            # committed shardings that fresh allocations may not —
            # without this fixpoint call, the first real batch silently
            # recompiles the whole chunk program (~10 s) inside the
            # budget window.
            out = self._chunk(qcur, jnp.int32(0), jnp.int32(0),
                              qnext, jnp.int32(0), seen, tbuf,
                              jnp.int32(0), jnp.int32(self._CH))
            qnext, seen, tbuf = out[0], out[1], out[2]
            # The trace flush's programs, on the buffer as the chunk
            # hands it back: no call's flush compiles, whatever it admitted.
            if cfg.record_trace:
                for length in self._fetch_lens:
                    self._fetch(tbuf, np.int32(0), length)
        flush = _TraceFlush(self, trace)
        close = _LevelClose(self)

        def settle():
            """With the device empty: the flush and the level's close
            that are owed, before anything reads the store, snapshots
            the run or leaves the loop."""
            flush.finish("flush_drained")
            close.finish("level_closes_drained")

        calls = self._calls
        t0 = time.time()
        last_progress = t0
        self._batch_ema = 0.0   # measured seconds per device batch

        if resume is not None:
            # Restore the level-boundary image: re-insert the saved keys
            # into a fresh hash table, reload the frontier, counters, and
            # trace records/roots.
            n_keys = resume.seen_hi.shape[0]
            with mt.phase_timer("restore"):
                cap = self._seen_cap
                while n_keys > fpset._capacity(cap) // 2:
                    cap *= 2
                seen, rounds, lane_rounds = fpset.from_host_keys(
                    resume.seen_hi, resume.seen_lo, cap)
                self._restore_counts = {"restore_rounds": rounds,
                                        "restore_lane_rounds": lane_rounds}
                fr = np.ascontiguousarray(resume.frontier).astype(
                    ROW_DTYPE, casting="safe")
                # A frontier larger than the device queue resumes as device
                # rows + host segments (same split the spill path produces).
                for i in range(Q, len(fr), Q):
                    # Views, not copies: the disk-backed pool copies into
                    # its memmap anyway, and the RAM pool holding views
                    # keeps the resume peak at one frontier (fr stays
                    # pinned via fr[:Q]).
                    pending.append(fr[i:i + Q])
                fr = fr[:Q]
                qcur = jax.device_put(
                    jnp.zeros((QA, sw), jnp.uint8).at[:len(fr)].set(
                        jnp.asarray(fr)), dev)
                cur_count = len(fr)
                res.distinct = resume.distinct
                res.generated = resume.generated
                res.diameter = resume.diameter
                res.levels = list(resume.levels)
                res.action_counts = dict(resume.action_counts)
                self._note_family_base(resume)
                # Coverage resumes its generated series from the checkpoint
                # so the run-end table still matches generated_by_action
                # (distinct/expanded are not checkpointed; see
                # coverage.disabled).  The registry counters are NOT seeded:
                # they are process-cumulative, and an in-process degrade
                # resume already accumulated the pre-crash increments — the
                # progress line renders per-run totals from res instead.
                coverage.seed_generated(resume.action_counts)
                # Duration (TLCGet("duration")-style) accumulates across
                # restarts: back-date t0 so wall_seconds, states/sec, and the
                # max_seconds budget all measure total checking time.
                t0 -= resume.wall_seconds
                if cfg.record_trace:
                    if resume.distinct > 0 and resume.trace_fps.size == 0:
                        raise ValueError(
                            "checkpoint was written with trace recording "
                            "disabled; counterexample replay could never "
                            "reach a root — resume with record_trace=False "
                            "(--no-trace) or restart from scratch")
                    trace.add_batch(resume.trace_fps, resume.trace_parents,
                                    resume.trace_actions)
                    trace.roots.update(resume.roots)
                    self._restore_counts.update(
                        store_growth(trace, "restore_"))
                elif resume.trace_fps.size > 0 \
                        and cfg.checkpoint_dir is not None:
                    raise ValueError(
                        "resuming a trace-carrying checkpoint with trace "
                        "recording disabled would write trace-less snapshots "
                        "into the same directory, shadowing the intact ones "
                        "for any later trace-on resume; use a different "
                        "checkpoint_dir or keep tracing enabled")
        else:
            # Ingest initial states in B-sized chunks (roots registered
            # above, before the clock).
            self._open_level_span(0)
            calls.start()
            for base in range(0, len(rows_np), B):
                # StopAfter applies during root ingest too (a k=4 smoke
                # run has 262k roots — TLCGet("duration") doesn't wait
                # for them).  The first wave always runs: TLC generates
                # initial states before any constraint can stop it.
                if base and cfg.max_seconds is not None \
                        and time.time() - t0 > cfg.max_seconds:
                    res.stop_reason = "duration_budget"
                    break
                if base and cfg.exit_conditions:
                    # "queue" during ingest: enqueued rows + landed spills
                    # + the roots not yet ingested.
                    hit = _exit_condition_hit(
                        cfg.exit_conditions, res,
                        int(next_count) + spill_next.total_rows()
                        + (len(rows_np) - base))
                    if hit:
                        res.stop_reason = hit
                        break
                calls.dispatch()
                with mt.phase_timer("ingest") as ingest_span:
                    chunk = rows_np[base:base + B]
                    pad = np.zeros((B - len(chunk), sw), ROW_DTYPE)
                    valid = np.arange(B) < len(chunk)
                    (qnext, next_count, seen, n_new, fail, tr,
                     vinfo) = self._ingest(
                        jnp.asarray(np.concatenate([chunk, pad])),
                        jnp.asarray(valid), qnext, next_count, seen)
                    n_new = int(n_new)
                    res.distinct += n_new
                mt.counter("engine/ingest_calls")
                mt.counter("engine/distinct", n_new)
                flush_s = 0.0
                if cfg.record_trace and n_new:
                    with mt.phase_timer("trace_flush") as flush_span:
                        self._record(trace, tr, 0, n_new)
                    flush_s = flush_span.seconds
                # The roots' ingest dispatches and fetches in one span.
                calls.row("ingest", "ingest", 0, ingest_span.seconds, 0.0,
                          flush_s, 0.0, base // B + 1, 0, 1, len(chunk),
                          n_new, distinct=res.distinct,
                          generated=res.generated, diameter=0,
                          frontier=len(rows_np), offset=base + len(chunk))
                if bool(fail):
                    raise RuntimeError(
                        "seen-set probe failure during ingest; raise "
                        "seen_capacity")
                seen, qnext, tbuf, t0 = self._grow_precompiled(
                    seen, int(seen.size), qcur, qnext, int(next_count),
                    tbuf, t0)
                nc = int(next_count)
                if nc > self._QTH:      # spill: ingest adds <= B per call,
                    with mt.phase_timer("spill"):
                        spill_next.append(  # watermark is never blown
                            np.asarray(qnext[:nc]), copy=True)
                        # The warm-up's placement, not a fresh scalar.
                        next_count = jax.device_put(jnp.int32(0), dev)
                    evlog.emit("spill", rows=nc, level=0, where="ingest")
                if self._check_violation(res, vinfo):
                    break

            # levels[] counts enqueued (constraint-passing) states per
            # level, mirroring the oracle's frontier sizes.
            res.levels.append(int(next_count)
                              + spill_next.total_rows())
            # Seen gauges refreshed BEFORE the level-0 emit: its
            # level_stats snapshot reads them, and on a warm shared
            # registry the stale previous-run values would otherwise
            # leak into this run's level-0 row.
            mt.gauge("engine/seen_capacity", len(seen.hi))
            mt.gauge("engine/seen_size", int(seen.size))
            close.owe(res, res.levels[-1])
            qcur, qnext = qnext, qcur
            cur_count = int(next_count)
            pending, spill_next = spill_next, pending
            next_count = jnp.int32(0)

        # Seen-set gauges for the registry-rendered progress line (load
        # factor = seen_size / seen_capacity); kept current per chunk.
        mt.gauge("engine/seen_capacity", len(seen.hi))
        mt.gauge("engine/seen_size", int(seen.size))
        # A resumed run must not rewrite the snapshot it just loaded (a
        # trace-off resume would overwrite a trace-carrying file with an
        # empty trace), and its interval clock starts at the restart.
        skip_ckpt_level = resume.diameter if resume is not None else -1
        last_ckpt = time.time() if resume is not None else float("-inf")
        if resume is not None:
            calls.start()       # the restore is a span of its own
        while (cur_count > 0 or pending) and res.violation is None \
                and res.stop_reason == "exhausted":
            if cfg.checkpoint_dir is not None \
                    and res.diameter % max(1, cfg.checkpoint_every) == 0 \
                    and res.diameter != skip_ckpt_level \
                    and (time.time() - last_ckpt
                         >= cfg.checkpoint_interval_seconds):
                # One save in flight, acknowledged in level order; then
                # the store as the level left it, which the capture reads.
                saves.finish()
                settle()
                saves.capture(qcur, cur_count, pending, seen, res, trace,
                              wall=time.time() - t0)
                last_ckpt = time.time()
            if cfg.max_diameter is not None \
                    and res.diameter >= cfg.max_diameter:
                res.stop_reason = "diameter_budget"
                break
            self._open_level_span(res.diameter + 1)
            # Level loop: each _chunk call runs up to sync_every batches on
            # device; ONE packed stats fetch (plus a trace flush) per call
            # is the only host traffic — the host round-trip no longer
            # bounds states/sec.  The outer loop walks the level's
            # segments: first the device-resident rows, then any host
            # segments left by the previous level's spill.
            next_count_h = 0
            # Budgeted runs slow-start each level: batch cost is
            # data-dependent (probe-round early exits, frontier density)
            # and roughly homogeneous WITHIN a level but can jump 100x
            # between levels — so the first call of a level probes with
            # two batches (amortizing the host round-trip) to re-measure,
            # then the ramp doubles under the remaining-time bound.
            # Overshoot is thereby bounded by ~two batches at the current
            # level's cost.
            calls_in_level = 0
            while True:
                offset = 0
                while offset < cur_count:
                    # Duration-budget promptness: size this chunk call (in
                    # batches) from the measured per-batch cost so the run
                    # stops within ~one batch of the deadline, not one
                    # whole sync_every chunk past it.
                    allowed, rule = self._CH, "full"
                    if cfg.max_seconds is not None:
                        remaining = cfg.max_seconds - (time.time() - t0)
                        if remaining <= 0:
                            res.stop_reason = "duration_budget"
                            break
                        allowed, rule = budget_call_size(
                            self._CH, remaining, self._batch_ema,
                            calls_in_level)
                        if rule == "probe":
                            mt.counter("engine/probe_calls")
                        elif rule == "deadline" and allowed * B \
                                < cur_count - offset:
                            # The time left, and not the level's end,
                            # cut this call short.
                            mt.counter("engine/deadline_calls")
                    calls_in_level += 1
                    # The registry's count: what pairs this call's
                    # ``chunk`` span with its ``account`` span and its row.
                    call = int(mt.counter_value("engine/chunk_calls")) + 1
                    if _faults.ACTIVE:
                        # Deterministic injection sites (resilience/):
                        # "kill" dies here (mid-level, past the level's
                        # snapshot), "oom" raises a simulated
                        # RESOURCE_EXHAUSTED into the degradation path,
                        # "stall" loses time between two calls here.
                        _faults.fire("kill", level=res.diameter,
                                     chunk=calls_in_level)
                        _faults.fire("oom", level=res.diameter,
                                     chunk=calls_in_level)
                        _faults.fire("stall", phase="gap", call=call,
                                     level=res.diameter,
                                     chunk=calls_in_level)
                    # Device-profiler window (--xla-profile): the
                    # capture starts at the first dispatch and stops
                    # itself after N (obs/profile.py).  One call site:
                    # the profiled and unprofiled paths must never
                    # diverge.
                    cap = self._xla_capture
                    step_cm = (cap.step() if cap is not None
                               and not cap.done
                               else contextlib.nullcontext())
                    calls.dispatch()
                    t_call = time.perf_counter()
                    with mt.phase_timer("chunk", call=call) as chunk_span, \
                            step_cm:
                        out = self._chunk(qcur, jnp.int32(cur_count),
                                          jnp.int32(offset), qnext,
                                          jnp.int32(next_count_h), seen,
                                          tbuf, jnp.int32(0),
                                          jnp.int32(allowed))
                        qnext, seen, tbuf = out[0], out[1], out[2]
                    # The host half of the previous call's flush, while
                    # the device runs this one; behind a level's first
                    # call, the close of the level before it too.
                    flush_s = (flush.finish("flush_overlapped")
                               + close.finish("level_closes_overlapped"))
                    # The packed-stats fetch is the loop's one blocking
                    # device sync — its phase time IS the device compute
                    # the dispatch above overlapped.
                    with mt.phase_timer("stats_fetch") as fetch_span:
                        if _faults.ACTIVE:
                            _faults.fire("stall", phase="wait", call=call,
                                         level=res.diameter,
                                         chunk=calls_in_level)
                        st = np.asarray(out[3])
                    # What the call cost the loop, from its dispatch to
                    # its statistics on the host: the flush that ran
                    # under it is inside, where it outlasts the device.
                    call_seconds = time.perf_counter() - t_call
                    passes, n_new = int(st[1]), int(st[6])
                    if passes < allowed:
                        # The level's end (or a full queue, a loaded
                        # seen-set) and no rule ended this call.
                        rule = "level_end"
                    # The host bookkeeping of one call, under the numbers
                    # the fetch brought: what a reader of a profiler
                    # capture matches the call's device time to.
                    account = mt.open_span(
                        "account", call=call, passes=passes, rule=rule,
                        parents=int(st[12]), new=n_new)
                    if _faults.ACTIVE:
                        _faults.fire("stall", phase="host", call=call,
                                     level=res.diameter,
                                     chunk=calls_in_level)
                    self._count_chunk_call(passes, int(st[12]))
                    if passes:           # st fetch synced: timing is real
                        per = call_seconds / passes
                        # Conservative estimator: jumps up to the latest
                        # cost instantly, decays slowly — per-batch cost
                        # grows with level depth (fuller probe chains,
                        # busier frontiers), and an under-estimate lets
                        # one deadline-sized chunk call overshoot the
                        # duration budget by the whole error factor.
                        self._batch_ema = (
                            per if not self._batch_ema else
                            max(per, 0.5 * self._batch_ema + 0.5 * per))
                    offset, next_count_h = int(st[0]), int(st[2])
                    seen_size, tcount = int(st[3]), int(st[4])
                    n_gen, n_ovf = int(st[5]), int(st[7])
                    dead_any, viol_any = bool(st[8]), bool(st[9])
                    vinv, fail = int(st[10]), bool(st[11])
                    res.distinct += n_new
                    res.generated += n_gen
                    # The packed-stats fetch feeds the registry — the one
                    # place every consumer (progress line, events, bench,
                    # server stats) reads live engine counters from.
                    mt.counter("engine/distinct", n_new)
                    mt.counter("engine/generated", n_gen)
                    mt.gauge("engine/seen_size", seen_size)
                    mt.gauge("engine/seen_capacity", len(seen.hi))
                    mt.gauge("engine/next_count", next_count_h)
                    mt.gauge("engine/diameter", res.diameter)
                    F = len(dims.family_sizes)
                    if n_gen:
                        for name, c in zip(dims.family_names,
                                           st[13:13 + F]):
                            res.action_counts[name] = (
                                res.action_counts.get(name, 0) + int(c))
                    # TLC-style coverage (obs/coverage.py): same packed
                    # stats, attributed per family — generated/distinct/
                    # disabled/pruned all derive from this one fetch.
                    coverage.add_chunk(int(st[12]), st[13:13 + F],
                                       st[13 + F:13 + 2 * F],
                                       st[13 + 2 * F:13 + 3 * F])
                    account.close()
                    # The call's one record (obs/calls.py): where its
                    # time lay, and the run's state as the watch console
                    # and a postmortem dump show it, with or without
                    # --progress-interval.
                    calls.row("chunk", rule, passes, chunk_span.seconds,
                              fetch_span.seconds, flush_s, account.seconds,
                              call, res.diameter + 1, allowed, int(st[12]),
                              n_new, distinct=res.distinct,
                              generated=res.generated,
                              diameter=res.diameter, frontier=cur_count,
                              offset=offset, next_count=next_count_h,
                              seen_size=seen_size)
                    if cfg.record_trace and tcount:
                        # The device half only: everything below may
                        # raise, branch or dispatch with the flush owed.
                        flush.start(((tbuf, tcount),))
                    # A snapshot whose file was made behind this call or
                    # an earlier one; a commit that failed fails the run.
                    saves.finish(wait=False)
                    if n_ovf:
                        raise RuntimeError(
                            f"{n_ovf} successors exceeded fixed-width "
                            f"capacity (max_log={dims.max_log}, n_msg_slots"
                            f"={dims.n_msg_slots}) or wrapped the uint8 "
                            f"row; rerun with larger capacities/bounds")
                    if fail:
                        raise RuntimeError(
                            "seen-set probe failure (load spiked past the "
                            "growth threshold within one chunk); raise "
                            "seen_capacity or lower sync_every")
                    seen, qnext, tbuf, t0 = self._grow_precompiled(
                        seen, seen_size, qcur, qnext, next_count_h, tbuf,
                        t0)
                    if next_count_h > self._QTH \
                            and (offset < cur_count or pending):
                        # Next-level queue at the watermark with more of
                        # this level still to expand: drain it to host
                        # (TLC's disk queue) asynchronously — swap in the
                        # spare buffer and let the D2H ride behind the
                        # next chunks' compute.
                        resolve_spill()
                        with mt.phase_timer("spill"):
                            qnext.copy_to_host_async()
                            inflight.append((qnext, next_count_h))
                            qnext = free_q.pop()
                        evlog.emit("spill", rows=next_count_h,
                                   level=res.diameter, where="chunk_loop")
                        next_count_h = 0
                    if viol_any:
                        vrow, vhl = np.asarray(out[5]), np.asarray(out[6])
                        res.violation = Violation(
                            invariant=self.inv_names[vinv],
                            state=decode_state(
                                unflatten_state(vrow, dims), dims),
                            fingerprint=(int(vhl[0]) << 32) | int(vhl[1]))
                        res.stop_reason = "violation"
                        evlog.emit(
                            "violation",
                            invariant=res.violation.invariant,
                            fingerprint=hex(res.violation.fingerprint),
                            level=res.diameter)
                        break
                    if dead_any and self._check_deadlock:
                        res.deadlock = decode_state(
                            unflatten_state(np.asarray(out[4]), dims), dims)
                        res.stop_reason = "deadlock"
                        evlog.emit("deadlock", level=res.diameter)
                        break
                    want_progress = bool(
                        cfg.progress_interval_seconds
                        and time.time() - last_progress
                        >= cfg.progress_interval_seconds)
                    if cfg.exit_conditions or want_progress:
                        # TLC's "queue" counter is the FULL unexplored-
                        # state queue: the unexpanded remainder of this
                        # level (device rows + host segments) plus
                        # everything enqueued for the next (device rows +
                        # landed and in-flight spills).
                        # offset advances in batch multiples and may
                        # overshoot cur_count on the level's last chunk.
                        queue_rows = (
                            max(0, cur_count - offset)
                            + pending.total_rows()
                            + next_count_h + spill_next.total_rows()
                            + sum(c for _b, c in inflight))
                        if want_progress:
                            _progress_line(res, t0, queue_rows, cur_count,
                                           metrics=mt)
                            # Coverage rides the same cadence (TLC's
                            # -coverage interval): registry gauges plus
                            # one structured event per interval.
                            coverage.feed_metrics(mt)
                            evlog.emit("coverage", level=res.diameter,
                                       actions=coverage.snapshot())
                            last_progress = time.time()
                        # Checked last: a violation or deadlock in the same
                        # chunk outranks a budget stop (TLC reports the
                        # error, not the exit).
                        hit = _exit_condition_hit(
                            cfg.exit_conditions, res, queue_rows)
                        if hit:
                            res.stop_reason = hit
                            break
                if res.stop_reason != "exhausted" \
                        or res.violation is not None or not pending:
                    break
                # Upload the next host segment of this level (a level
                # that begins with one: its predecessor's close first).
                if close.owed:
                    settle()
                with mt.phase_timer("upload"):
                    seg = pending.pop(0)
                    buf = np.zeros((QA, sw), ROW_DTYPE)
                    buf[:len(seg)] = seg
                    qcur = jax.device_put(buf, qcur.devices().pop())
                    cur_count = len(seg)
            if res.stop_reason != "exhausted" or res.violation is not None:
                break  # aborted mid-level: diameter counts completed levels
            resolve_spill()      # level boundary: all drains must land
            res.diameter += 1
            res.levels.append(next_count_h
                              + spill_next.total_rows())
            # The level is built.  Its last flush and its close stay
            # owed: where the next level follows they ride behind its
            # first dispatch, and everything else settles them first.
            close.owe(res, res.levels[-1])
            qcur, qnext = qnext, qcur
            cur_count = next_count_h
            pending, spill_next = spill_next, pending

        # The run stops: what follows reads the store (a replay) or ends
        # the run, and no run ends with a snapshot unacknowledged.
        settle()
        saves.finish()
        res.wall_seconds = time.time() - t0
        if resume is None:
            self._keep_capacity(seen)
        return res

    # ------------------------------------------------------------------
    def replay(self, fp: int) -> List[Tuple[int, PyState]]:
        """Counterexample reconstruction: walk the trace back to a root,
        then run the recorded action instances forward from it in ONE
        device call (engine/replay.py: the successor's row threaded from
        step to step, as the chunk program built it), and hold every step
        to its record: the instance enabled, the successor's 64-bit key
        the recorded child's.  Returns [(action_id, state)] root-first
        (root action = -1).

        The recorded id of a slot-indexed action (Receive/Duplicate/Drop)
        addresses the message slot of the kernel's arrangement, which
        queue rows keep; the id returned addresses the same message in
        the canonical parent (sorted slots, schema.encode_state: the
        order a rendered state lists its messages in).

        From the first step that fails either test (a chain whose rows
        were re-encoded: a resumed snapshot's frontier, a witness) the
        rest goes step by step through ``_replay_step``, which re-encodes
        the parent canonically and matches the child by its key among
        all enabled candidates, preferring the recorded id."""
        with self.metrics.scope("replay"):
            return self._replay(fp)

    def _replay(self, fp: int) -> List[Tuple[int, PyState]]:
        mt = self.metrics
        with mt.phase_timer("trace_chain"):
            chain = self.trace.chain(fp)
        if not chain:
            if fp in self.trace.roots:
                # Depth-0 counterexample: the violating state IS a root —
                # the one-state trace, no kernel replay needed.
                return [(-1, self.trace.roots[fp])]
            raise KeyError(f"fingerprint {fp:#x} not in trace")
        root_fp, g0 = chain[0]
        if g0 >= 0:
            raise KeyError("trace chain does not reach a root")
        state = self.trace.roots[root_fp]
        out = [(-1, state)]
        steps = chain[1:]
        rows, keys, _calls = self._replay_scan(state,
                                               [g for _fp, g in steps])
        want = np.fromiter((f for f, _g in steps), np.uint64, len(steps))
        held = leading_true(keys == want[:len(keys)])
        parent = None       # the parent as the kernel arranged it
        for row, (_fp, g_rec) in zip(rows[:held], steps):
            succ = unflatten_state(row, self.dims)
            out.append((self._canonical_instance(g_rec, parent, state),
                        decode_state(succ, self.dims)))
            parent, state = succ, out[-1][1]
        for step, (child_fp, g_rec) in enumerate(steps[held:], held + 1):
            mt.counter("engine/replay_fallback_steps")
            with mt.phase_timer("replay_step", step=step):
                state, g = self._replay_step(state, child_fp, g_rec)
            out.append((g, state))
        return out

    def _canonical_instance(self, g: int, parent, state: PyState) -> int:
        """Instance ``g`` of ``parent`` (a ``StateBatch`` in the kernel's
        slot arrangement; None: the root, encoded canonically) as the
        instance of ``state``, the same parent decoded, under
        ``encode_state``'s sorted slots: a slot-indexed instance moves to
        its message's rank in the sorted bag, any other is itself."""
        slot = self.dims.instance_info(g)[1].get("slot")
        if slot is None or parent is None:
            return g
        message = decode_message(np.asarray(parent.msg[slot]), self.dims)
        return g - slot + sorted(m for m, _c in state.messages).index(message)

    def _replay_step(self, state, child_fp: int, g_rec: int):
        """The successor of ``state`` whose fingerprint is ``child_fp``,
        and the action instance that gives it."""
        st = encode_state(state, self.dims)
        cands, en, _ovf = self._expand1(st)
        fph, fpl = self._fp_batch(cands)
        fps = (np.asarray(fph).astype(np.uint64) << np.uint64(32)) \
            | np.asarray(fpl).astype(np.uint64)
        ok = np.asarray(en) & (fps == np.uint64(child_fp))
        if not ok.any():
            # A replay that cannot reproduce a recorded child is the
            # one place a 64-bit fingerprint collision becomes HOST-
            # OBSERVABLE — counted so the statespace report's
            # "observed dual-key collisions" reflects detections,
            # not just the calculated probability.
            self.metrics.counter("engine/fp_collisions")
            raise RuntimeError(
                f"replay divergence: no enabled candidate matches "
                f"fp {child_fp:#018x} (recorded action {g_rec})")
        g = g_rec if 0 <= g_rec < ok.shape[0] and ok[g_rec] \
            else int(np.argmax(ok))
        row = jax.tree.map(lambda a: np.asarray(a)[g], cands)
        return decode_state(StateBatch(*row), self.dims), g

    # ------------------------------------------------------------------
    def _ingest_nothing(self, qnext, next_count, seen):
        """The ingest program on a batch with no valid row: nothing is
        inserted or enqueued, and the program for this table's capacity
        is compiled or loaded.  ``next_count`` committed, as ingest's own
        output is.  Returns (qnext, next_count, seen)."""
        out = self._ingest(jnp.zeros((self._B, self._sw), jnp.uint8),
                           jnp.zeros((self._B,), bool),
                           qnext, next_count, seen)
        return out[0], out[1], out[2]

    def _grow_precompiled(self, seen, size, qcur, qnext, next_count, tbuf,
                          t0):
        """Grow the seen set when loaded past threshold, pre-compile the
        chunk program at the new table shape with a zero-trip call, and
        keep the rehash + compile off the duration clock — the StopAfter
        budget measures checking time, not compilation (same rule as the
        warm-up).  The engine's next run from roots may start at the
        capacity reached (``_keep_capacity``), so the ingest program, which
        that run's roots go through, is loaded for the new table here too.
        Returns (seen, qnext, tbuf, t0)."""
        if self._seen_overloaded(seen, size):
            with self.metrics.phase_timer("grow") as grow:
                # Committed, like every buffer the chunk takes (see
                # ``run_init``): the rebuilt table comes out of jits of
                # uncommitted inputs, the chunk hands it back committed,
                # and the jit cache keys on that — the first real call
                # after a growth compiled the chunk again, on the clock.
                dev = qcur.devices().pop()
                seen = jax.device_put(self._maybe_grow_seen(seen, size), dev)
                qnext, _count, seen = self._ingest_nothing(
                    qnext, jax.device_put(jnp.int32(next_count), dev), seen)
                out = self._chunk(qcur, jnp.int32(0), jnp.int32(0), qnext,
                                  jnp.int32(next_count), seen, tbuf,
                                  jnp.int32(0), jnp.int32(1))
                qnext, seen, tbuf = out[0], out[1], out[2]
            stall = grow.seconds
            t0 += stall
            # Off the clock, but recorded: a run that starts undersized
            # pays one of these per doubling — the evidence for sizing
            # SEEN_CAPACITY up front.  The stall IS the span (rehash +
            # precompile).
            self._growth_stalls.append((len(seen.hi), round(stall, 3)))
            self.metrics.counter("engine/fpset_resizes")
            # The growth_stall event BENCH_r05 had to infer from outside:
            # capacity after, off-clock stall, live memory.
            rounds, lane_rounds = self._rebuild_counts
            self._evlog.emit("fpset_resize", capacity=len(seen.hi),
                             stall_seconds=round(stall, 3),
                             rebuild_rounds=rounds,
                             rebuild_lane_rounds=lane_rounds,
                             memory=device_memory_stats())
        return seen, qnext, tbuf, t0

    @staticmethod
    def _seen_overloaded(seen, size=None) -> bool:
        """Load past 0.5: time to grow (``_maybe_grow_seen``)."""
        return ((int(seen.size) if size is None else size)
                > seen.hi.shape[0] // 2)

    def _maybe_grow_seen(self, seen, size=None):
        """Double the FPSet (rehash through host keys) once load passes
        0.5 — early enough that the insertions of the next chunk (checked
        only at host sync points) fit the free half without pushing the
        load where probes start failing.  The chunk program recompiles for
        the new table shape, so growth costs one compile per doubling;
        auto-sized tables (seen_capacity=None) start large enough that
        most runs never grow."""
        if not self._seen_overloaded(seen, size):
            return seen
        C = seen.hi.shape[0]
        hi, lo = fpset.to_host_keys(seen)
        self._grow_attempts = getattr(self, "_grow_attempts", 0) + 1
        try:
            if _faults.ACTIVE:
                _faults.fire("oom", grow=self._grow_attempts)
            return self._rebuilt(hi, lo, 2 * C)
        except Exception as e:
            if not (self.config.degrade_on_oom
                    and is_resource_exhausted(e)):
                raise
            # Degraded growth retry: the keys are already host-resident,
            # so the OLD device table can be released before the new
            # allocation — the retry's peak is the new table alone
            # instead of old + new.  (Capacities are power-of-two
            # (ops/fpset.py masked indexing), so the "smaller factor"
            # here is a smaller allocation PEAK, not a non-pow2 table.)
            # A second failure propagates to _run_degradable, which
            # halves the batch — shrinking queues and trace buffers —
            # and resumes from the last intact snapshot.
            self._evlog.emit(
                "degraded", reason="oom_grow_retry", capacity=2 * C,
                error=f"{type(e).__name__}: {str(e)[:300]}",
                memory=device_memory_stats())
            self.metrics.counter("engine/degraded")
            for arr in (seen.hi, seen.lo):
                try:
                    arr.delete()
                except Exception:
                    pass
            return self._rebuilt(hi, lo, 2 * C)

    def _rebuilt(self, hi, lo, capacity):
        """The grown table, its rebuild's rounds and lane-rounds kept for
        the ``fpset_resize`` event."""
        seen, *self._rebuild_counts = fpset.from_host_keys(hi, lo, capacity)
        return seen

    @staticmethod
    def _record(trace, cols, lo: int, hi: int) -> None:
        """Entries ``[lo, hi)`` of five fetched trace columns into the
        host store.  The columns come whole, at a shape fixed when the
        engine was built, and are cut here: a device slice at every new
        length is a compile of its own."""
        sh, sl, ph, pl, ac = (np.asarray(x)[lo:hi] for x in cols)
        fps = (sh.astype(np.uint64) << np.uint64(32)) | sl.astype(np.uint64)
        parents = (ph.astype(np.uint64) << np.uint64(32)) \
            | pl.astype(np.uint64)
        trace.add_batch(fps, parents, ac)

    def _check_violation(self, res, vinfo) -> bool:
        viol_any, vinv, vrow, vhi, vlo = vinfo
        if not bool(viol_any):
            return False
        st = decode_state(unflatten_state(np.asarray(vrow), self.dims),
                          self.dims)
        fp = (int(vhi) << 32) | int(vlo)
        name = self.inv_names[int(vinv)]
        res.violation = Violation(invariant=name, state=st, fingerprint=fp)
        res.stop_reason = "violation"
        self._evlog.emit("violation", invariant=name, fingerprint=hex(fp),
                         level=res.diameter)
        return True
