"""Per-stage chunk profiler — the instrument behind ``--profile-chunks``.

Per-stage timings of the chunk pipeline (expand / fingerprint /
dedup-insert / enqueue) on whatever hardware a run lands on: every Nth
chunk call, the profiler re-runs the sampled batch through
separately-jitted stage programs with ``block_until_ready`` fencing
between stages, accumulates per-stage histograms into the
MetricsRegistry (``chunk_stage/<stage>``), and emits one
``chunk_profile`` run event plus a stderr stage table at run end.  (The
time of each stage inside the REAL fused chunk program is read from a
profiler capture instead: the program names its stages, engine/chunk.py
``STAGES``.)

The profiler is **observational**: the engine's real fused chunk program
still does all the work, and the sampled batch is re-expanded on the
side purely for measurement — so engine results are bit-identical with
profiling on or off (the acceptance contract), at the cost of roughly
``1/N`` extra compute.  The staged decomposition measures the v1
(classical) pipeline regardless of which pipeline the engine runs:
cross-pipeline comparability of the headings matters more than
mirroring v2's fused deltas.  The separately-timed ``total`` program (all four stages in one
jit, non-donating) is the fusion reference: ``sum(stages)`` vs
``total`` prices the inter-stage materialization XLA elides.

Stage -> pipeline mapping (engine/chunk.py):

    expand        unflatten + vmap(expand) over B*G lanes + compaction
    fingerprint   gather K candidate structs + two-lane hash
    dedup_insert  ops/fpset.py batched insert (in-batch dedup + probe)
    enqueue       materialize K uint8 rows + position scatter

``pipeline="swarm"`` profiles the walk-kernel decomposition of the
swarm tier's lockstep scan body (engine/swarm.py) instead of a
frontier chunk — same fencing discipline, swarm stage headings:

    expand        unflatten + enabled/overflow masks (v1 full expand
                  or v2 guards-only, matching the engine's pipeline)
    choose        counter-PRNG draws + family-diversified choice
    latch         chosen-successor materialization + fingerprint
    ring_probe    per-walk ring dedup probe -> push -> restart reset

jax is imported lazily (constructor), keeping ``obs`` importable in
device-less tooling like the rest of the package.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, Optional

STAGES = ("expand", "fingerprint", "dedup_insert", "enqueue")
STAGES_SWARM = ("expand", "choose", "latch", "ring_probe")

STAGE_PREFIX = "chunk_stage/"


def build_stage_programs(dims, B: int, K: int) -> dict:
    """The jitted stage programs, shared by :class:`ChunkProfiler` and
    ``scripts/profile_step.py`` (which used to hand-roll the same
    decomposition).  Returns ``{stage_name: fn, "total": fn,
    "queue_rows": int, "empty_seen": fn}``; see module docstring for the
    stage -> pipeline mapping."""
    import jax
    import jax.numpy as jnp

    from ..models.actions import build_expand
    from ..models.schema import flatten_state, unflatten_state
    from ..ops import fpset
    from ..ops.compact import build_compactor
    from ..ops.fingerprint import build_fingerprint

    _I32 = jnp.int32
    G = dims.n_instances
    BG = B * G
    expand = build_expand(dims)
    fingerprint = build_fingerprint(dims)
    compactor = build_compactor(B, G, K)
    # Profiler-local next-queue: K live rows + K per-lane trash slots
    # (the engine's trash-spread rule, ops/fpset.py design note 3).  The
    # scatter's cost scales with the rows written (K), not the target
    # size, so the small target keeps profiler memory bounded.
    QP = K

    def s_expand(rows, valid):
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        cands, en, _ovf = jax.vmap(expand)(states)
        en = en & valid[:, None]
        _P, _total, lane_id, kvalid = compactor(en)
        cflat = jax.tree.map(
            lambda a: a.reshape((BG,) + a.shape[2:]), cands)
        return cflat, lane_id, kvalid

    def s_fingerprint(cflat, lane_id):
        kstates = jax.tree.map(lambda a: a[lane_id], cflat)
        kh, kl = jax.vmap(fingerprint)(kstates)
        return kstates, kh, kl

    def s_insert(seen, kh, kl, kvalid):
        return fpset.insert(seen, kh, kl, kvalid)

    def s_enqueue(qnext, kstates, enq):
        krows = jax.vmap(flatten_state, (0, None))(kstates, dims)
        pos = jnp.cumsum(enq.astype(_I32)) - 1
        pos = jnp.where(enq, pos, QP + jnp.arange(K, dtype=_I32))
        return qnext.at[pos].set(krows, mode="drop")

    def s_total(rows, valid, seen, qnext):
        cflat, lane_id, kvalid = s_expand(rows, valid)
        kstates, kh, kl = s_fingerprint(cflat, lane_id)
        seen, new, _fail = s_insert(seen, kh, kl, kvalid)
        qnext = s_enqueue(qnext, kstates, new)
        return seen, qnext, jnp.sum(new, dtype=_I32)

    return {
        "expand": jax.jit(s_expand),
        "fingerprint": jax.jit(s_fingerprint),
        "dedup_insert": jax.jit(s_insert),
        "enqueue": jax.jit(s_enqueue),
        "total": jax.jit(s_total),
        "queue_rows": 2 * QP,
        "empty_seen": lambda cap: fpset.empty(cap),
    }


def build_stage_programs_swarm(dims, B: int, R: int,
                               pipeline: str = "v1") -> dict:
    """Stage programs at the swarm walk-kernel granularity
    (STAGES_SWARM), mirroring one lockstep step of
    ``engine/swarm.py``'s scan body for lane count ``B`` and ring
    capacity ``R``.  ``pipeline`` is the ENGINE'S resolved expand
    pipeline name ("v1" full expand or "v2" guards-only), so the
    profiled expand stage prices the masks the engine actually runs.

    The profiled step is the decision core only: invariant evaluation
    and the violation latch are not mirrored, and the PRNG is keyed on a
    synthetic ``(seed=0, walk=lane, step=sample)`` tuple — timings
    need representative control flow, not the engine's draws.  The
    per-sample rings persist in the :class:`ChunkProfiler`, so probe
    cost sees a realistically loaded ring, not a cold sentinel one.
    Returns ``{stage: fn, "total": fn, "ring_capacity": R}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.actions import build_expand
    from ..models.schema import (build_pack_guard, flatten_state,
                                 unflatten_state)
    from ..ops.fingerprint import build_fingerprint
    from ..ops.walk_kernels import (CHOICE_STREAM, FAMILY_STREAM,
                                    family_subset, preferred_choice,
                                    ring_probe, ring_push, ring_reset,
                                    walk_bits)

    _I32 = jnp.int32
    fingerprint = build_fingerprint(dims)
    fam = jnp.asarray(np.repeat(
        np.arange(len(dims.family_sizes), dtype=np.int32),
        dims.family_sizes))
    walk_ids = jnp.arange(B, dtype=jnp.int32)
    epoch = jnp.zeros((B,), jnp.int32)
    seed = jnp.uint32(0)
    lanes = jnp.arange(B)
    v2 = None
    if pipeline == "v2":
        from ..models.actions2 import build_v2
        v2 = build_v2(dims)
    expand = None if v2 is not None else build_expand(dims)
    pack_ok = None if v2 is not None else build_pack_guard(dims)

    def s_expand(rows, valid):
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        if v2 is None:
            cands, en, ovf = jax.vmap(expand)(states)
            ovf = ovf | (en & ~jax.vmap(jax.vmap(pack_ok))(cands))
            packed = cands
        else:
            en, ovf = jax.vmap(v2.masks)(states)
            packed = states
        return packed, en & valid[:, None], ovf

    def s_choose(en, k):
        bits = walk_bits(seed, walk_ids, k, CHOICE_STREAM)
        mbits = walk_bits(seed, walk_ids, epoch, FAMILY_STREAM)
        return preferred_choice(bits, en, family_subset(mbits, fam))

    def s_latch(packed, choice):
        if v2 is None:
            nxt = jax.tree.map(lambda a: a[lanes, choice], packed)
        else:
            ph = jax.vmap(v2.parent_hash)(packed)
            _h, _l, nxt = jax.vmap(v2.lane_out)(packed, ph,
                                                choice.astype(_I32))
        nrows = jax.vmap(flatten_state, (0, None))(nxt, dims)
        fp_hi, fp_lo = jax.vmap(fingerprint)(nxt)
        return nrows, fp_hi, fp_lo

    def s_ring(rh, rl, rp, fp_hi, fp_lo, en, ovf):
        seen = ring_probe(rh, rl, fp_hi, fp_lo)
        accept = (jnp.any(en, axis=1) & ~jnp.any(ovf, axis=1) & ~seen)
        rh, rl, rp = ring_push(rh, rl, rp, fp_hi, fp_lo, accept)
        rh, rl, rp = ring_reset(rh, rl, rp, ~accept)
        return rh, rl, rp, jnp.sum(accept, dtype=_I32)

    def s_total(rows, valid, rh, rl, rp, k):
        packed, en, ovf = s_expand(rows, valid)
        choice = s_choose(en, k)
        _nrows, fp_hi, fp_lo = s_latch(packed, choice)
        return s_ring(rh, rl, rp, fp_hi, fp_lo, en, ovf)

    return {
        "expand": jax.jit(s_expand),
        "choose": jax.jit(s_choose),
        "latch": jax.jit(s_latch),
        "ring_probe": jax.jit(s_ring),
        "total": jax.jit(s_total),
        "ring_capacity": R,
    }


class ChunkProfiler:
    """Samples every ``every``-th chunk call of one engine run.

    Owns two persistent FPSet tables (staged and fused paths receive
    every sample's keys, so both see the same load trajectory) and a
    small scatter target; everything else is rebuilt per sample from the
    engine's own frontier rows."""

    def __init__(self, dims, *, batch: int, lanes: int,
                 seen_capacity: int, pipeline: str = "v1", every: int = 1,
                 metrics=None, swarm_pipeline: str = "v1",
                 ring: int = 16):
        self.dims = dims
        self.B, self.K = int(batch), int(lanes)
        self.seen_capacity = int(seen_capacity)
        # "v1" = the classical four-stage decomposition (default);
        # "swarm" = the walk-kernel step of the swarm tier
        # (swarm_pipeline names the engine's resolved expand pipeline,
        # ring its dedup capacity).
        if pipeline not in ("v1", "swarm"):
            raise ValueError(f"profiler pipeline must be "
                             f"v1/swarm, got {pipeline!r}")
        self.pipeline = pipeline
        self.swarm_pipeline = swarm_pipeline
        self.ring_capacity = int(ring)
        self._swarm_k = 0
        self.stages = STAGES_SWARM if pipeline == "swarm" else STAGES
        self.every = max(1, int(every))
        self.metrics = metrics
        self.samples = 0
        self._calls = 0
        self._built = None
        self._stage_totals: Dict[str, float] = {s: 0.0
                                                for s in self.stages}
        self._total_total = 0.0

    def reset(self) -> None:
        """Zero the accumulators for a new run (warm/reused engines);
        compiled stage programs and the persistent tables are kept."""
        self.samples = 0
        self._calls = 0
        self._stage_totals = {s: 0.0 for s in self.stages}
        self._total_total = 0.0

    # -- sampling ------------------------------------------------------
    def want(self) -> bool:
        """Advance the chunk-call counter; True when this call should be
        sampled (first call always is, so short runs still profile)."""
        self._calls += 1
        return (self._calls - 1) % self.every == 0

    def _build(self, rows, valid):
        import jax
        import jax.numpy as jnp
        if self.pipeline == "swarm":
            from ..ops.walk_kernels import ring_init
            progs = build_stage_programs_swarm(
                self.dims, self.B, self.ring_capacity,
                pipeline=self.swarm_pipeline)
            # Two persistent ring sets, the swarm analogue of the
            # staged/fused FPSet pair below: both paths see the same
            # probe-load trajectory across samples.
            self._ring_s = ring_init(self.B, self.ring_capacity)
            self._ring_t = ring_init(self.B, self.ring_capacity)
            self._staged_chain(progs, rows, valid)
            rh, rl, rp, n = progs["total"](rows, valid, *self._ring_t,
                                           jnp.int32(0))
            self._ring_t = (rh, rl, rp)
            jax.block_until_ready((self._ring_s[0], rh, n))
            self._built = progs
            return progs
        progs = build_stage_programs(self.dims, self.B, self.K)
        from ..models.schema import state_width
        sw = state_width(self.dims)
        self._qnext = jnp.zeros((progs["queue_rows"], sw), jnp.uint8)
        self._seen_staged = progs["empty_seen"](self.seen_capacity)
        self._seen_total = progs["empty_seen"](self.seen_capacity)
        # One untimed pass compiles every program, so compile time never
        # lands in the first sample's histogram bucket.
        self._staged_chain(progs, rows, valid)
        self._seen_total, self._qnext, n = progs["total"](
            rows, valid, self._seen_total, self._qnext)
        jax.block_until_ready((self._seen_staged, self._qnext, n))
        self._built = progs
        return progs

    def _staged_chain(self, progs, rows, valid, fence=None):
        """Run the per-stage programs in pipeline order, fencing each
        when ``fence`` is given (the shared driver for warm-up and
        sampling; one sequence per stage granularity)."""
        fence = fence or (lambda stage, out: out)
        if self.pipeline == "swarm":
            import jax.numpy as jnp
            k = jnp.int32(self._swarm_k)
            packed, en, ovf = fence(
                "expand", progs["expand"](rows, valid))
            choice = fence("choose", progs["choose"](en, k))
            _nrows, fp_hi, fp_lo = fence(
                "latch", progs["latch"](packed, choice))
            rh, rl, rp, _n = fence(
                "ring_probe", progs["ring_probe"](
                    *self._ring_s, fp_hi, fp_lo, en, ovf))
            self._ring_s = (rh, rl, rp)
            return None
        cflat, lane_id, kvalid = fence(
            "expand", progs["expand"](rows, valid))
        kstates, kh, kl = fence(
            "fingerprint", progs["fingerprint"](cflat, lane_id))
        self._seen_staged, new, fail = fence("dedup_insert", progs[
            "dedup_insert"](self._seen_staged, kh, kl, kvalid))
        self._qnext = fence(
            "enqueue", progs["enqueue"](self._qnext, kstates, new))
        return fail

    def sample(self, rows, valid) -> None:
        """Profile one batch: ``rows`` [B, sw] device/host rows, ``valid``
        [B] bool parent-validity mask.  Fenced with block_until_ready
        before and between stages so each interval is one stage's device
        time (plus one dispatch — the fused ``total`` row prices that
        overhead)."""
        import jax
        import jax.numpy as jnp
        rows = jnp.asarray(rows)
        valid = jnp.asarray(valid)
        progs = self._built or self._build(rows, valid)
        mt = self.metrics
        timings = {}

        def fence(stage, out):
            jax.block_until_ready(out)
            t = time.perf_counter()
            dt = t - fence.t0
            fence.t0 = t
            timings[stage] = dt
            return out

        fence.t0 = time.perf_counter()
        fail = self._staged_chain(progs, rows, valid, fence=fence)
        if mt is not None and fail is not None and bool(fail):
            # The profiler's private table saturated: dedup_insert
            # timings from here on measure a pathologically full probe,
            # not the engine's.  Surfaced as a counter, never fatal.
            mt.counter("chunk_stage/insert_fail")
        if self.pipeline == "swarm":
            rh, rl, rp, _n = fence("total", progs["total"](
                rows, valid, *self._ring_t,
                jnp.int32(self._swarm_k)))
            self._ring_t = (rh, rl, rp)
            self._swarm_k += 1
        else:
            self._seen_total, self._qnext, _n = fence("total", progs[
                "total"](rows, valid, self._seen_total, self._qnext))

        self.samples += 1
        for s in self.stages:
            self._stage_totals[s] += timings[s]
            if mt is not None:
                mt.observe(STAGE_PREFIX + s, timings[s])
        self._total_total += timings["total"]
        if mt is not None:
            mt.observe(STAGE_PREFIX + "total", timings["total"])
        # Black-box mirror (obs/flight.py): recent per-stage samples ride
        # in the flight ring, so a postmortem dump carries the last
        # chunk-stage timings even when the run never reached its
        # chunk_profile run-end event.
        try:
            from .flight import RECORDER
            RECORDER.record(
                "chunk_stage", sample=self.samples,
                pipeline=self.pipeline, batch=self.B,
                stages={s: round(timings[s], 6) for s in self.stages},
                total=round(timings["total"], 6))
        except Exception:
            pass

    # -- reporting -----------------------------------------------------
    def stage_means(self) -> Dict[str, float]:
        """{stage: mean seconds/sampled batch} (+ ``total`` for the fused
        reference) — what bench JSON embeds as ``chunk_stages``."""
        if not self.samples:
            return {}
        out = {s: self._stage_totals[s] / self.samples
               for s in self.stages}
        out["total"] = self._total_total / self.samples
        return out

    def summary(self) -> dict:
        means = self.stage_means()
        staged_sum = sum(means.get(s, 0.0) for s in self.stages)
        return {
            "samples": self.samples,
            "every": self.every,
            "batch": self.B,
            "lanes": self.K,
            "pipeline": self.pipeline,
            "stages": {s: {"mean_seconds": round(means[s], 6),
                           "total_seconds":
                               round(self._stage_totals[s], 6)}
                       for s in self.stages} if self.samples else {},
            "fused_total_mean_seconds": round(means.get("total", 0.0), 6),
            "staged_sum_mean_seconds": round(staged_sum, 6),
        }

    def render_table(self) -> str:
        """Run-end stage table: measured mean ms per stage and its share
        of their sum."""
        means = self.stage_means()
        if not means:
            return "chunk profile: no samples"
        lines = [f"chunk profile ({self.samples} sampled batches, "
                 f"B={self.B}, K={self.K}, every {self.every}th call, "
                 f"{self.pipeline} stages):",
                 f"  {'stage':14s} {'mean ms':>10s} {'share':>7s}"]
        staged_sum = sum(means[s] for s in self.stages)
        for s in self.stages:
            ms = means[s] * 1e3
            share = means[s] / staged_sum if staged_sum else 0.0
            lines.append(f"  {s:14s} {ms:10.2f} {share:6.1%}")
        lines.append(f"  {'sum(stages)':14s} {staged_sum * 1e3:10.2f}")
        lines.append(f"  {'fused total':14s} {means['total'] * 1e3:10.2f}"
                     f"  (inter-stage materialization the fused program "
                     f"elides)")
        return "\n".join(lines)

    def finish(self, evlog, stream=None) -> None:
        """Run-end hook: emit the ``chunk_profile`` event and print the
        stage table.  No-op when nothing was sampled."""
        if not self.samples:
            return
        evlog.emit("chunk_profile", **self.summary())
        print(self.render_table(), file=stream or sys.stderr)


class XlaProfileCapture:
    """Opt-in ``jax.profiler`` trace window over N sampled chunk calls —
    the hardware-truth layer (``--xla-profile[=N]`` / ``XLA_PROFILE``
    directive).

    The host-side chunk profiler above times WHOLE stage programs with
    fences; it cannot see inside a program — which XLA kernels run,
    their launch count, or HBM traffic.
    ``jax.profiler.start_trace`` captures that (XPlane protos + a
    Perfetto-openable trace under ``<logdir>/plugins/profile/...``).

    Correlation: every engine span is in the capture itself, on the
    host's ``python`` line, as ``raft.<name>`` with its arguments as
    stats (obs/tracing.py) — ``raft.chunk`` with ``call=<i>`` is the
    dispatch this window counts — so the device timeline and the
    ``--trace-out`` Chrome trace line up by name and call index.

    Observational and fail-soft: the capture never changes what the
    engine computes, and a profiler that cannot start (unsupported
    backend, missing permissions) records its failure in
    the ``xla_profile`` event instead of killing the run.
    """

    def __init__(self, logdir: str, chunks: int):
        self.logdir = logdir
        self.chunks = max(1, int(chunks))
        self.steps = 0
        self.active = False
        self.done = False
        self.status: Optional[str] = None

    def _start(self) -> None:
        import jax
        try:
            import os
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self.active = True
            self.status = "ok"
        except Exception as e:
            self.done = True
            self.status = f"start failed: {type(e).__name__}: {e}"

    def step(self):
        """Context manager bracketing ONE chunk dispatch.  Starts the
        trace lazily on the first call (so warm-up compilation never
        pollutes the capture) and stops after ``chunks`` calls.  A no-op
        once done."""
        from contextlib import contextmanager

        @contextmanager
        def _cm():
            if self.done:
                yield
                return
            if not self.active:
                self._start()
                if self.done:           # start failed
                    yield
                    return
            self.steps += 1
            try:
                yield
            finally:
                if self.steps >= self.chunks:
                    self.stop()
        return _cm()

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        self.done = True
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self.status = f"stop failed: {type(e).__name__}: {e}"

    def summary(self) -> dict:
        """The ``xla_profile`` event's ``capture`` payload object."""
        return {"logdir": self.logdir, "chunks": self.chunks,
                "steps": self.steps,
                "status": self.status or "never started",
                "span_name": "chunk"}

    def finish(self, evlog) -> None:
        """Run-end hook: close an open window (early-exit runs) and emit
        the ``xla_profile`` event + flight record."""
        self.stop()
        evlog.emit("xla_profile", capture=self.summary())
        try:
            from .flight import RECORDER
            RECORDER.record("xla_profile", capture=self.summary())
        except Exception:
            pass


def profile_stages(dims, rows, valid=None, *, lanes: Optional[int] = None,
                   seen_capacity: int = 1 << 20,
                   n: int = 3) -> Dict[str, float]:
    """One-shot stage profile of a frontier batch — the
    ``scripts/profile_step.py`` entry point, now on the shared programs.
    Returns {stage: mean seconds} over ``n`` fenced repetitions (first
    repetition untimed: compile)."""
    import numpy as np

    from ..ops.compact import choose_k
    B = int(rows.shape[0])
    if valid is None:
        valid = np.ones((B,), bool)
    prof = ChunkProfiler(
        dims, batch=B,
        lanes=lanes or choose_k(B, dims.n_instances, None),
        seen_capacity=seen_capacity)
    for _ in range(n):
        prof.sample(rows, valid)
    return prof.stage_means()
