"""Launch accounting + the per-run performance block (``--perf``).

The engine's known bottleneck is kernel granularity — hundreds of small
launches per chunk against a ~0.1-0.3 ms bandwidth floor
— yet no telemetry leg could attribute time to launches.  This module
closes that gap with a **static launch model** plus a cheap dynamic
feed:

- *static*: walk the engine's REAL traced chunk program (the exact
  jaxpr ``engine/bfs.py`` / ``parallel/mesh.py`` compile, v1/v2,
  POR mask included) counting device ops — every equation except pure
  layout prims, loop bodies once.  The count is a deterministic
  PRE-FUSION upper bound on kernel launches (XLA fuses some
  neighbors), CI-pinnable.  The measured truth comes
  from the device profiler (``scripts/xplane_summary.py`` over the
  stage-5b XPlane artifacts) — the static model is the gate, the
  XPlane number is the evidence.
- *dynamic*: the host loop feeds (batches, seconds) per chunk call —
  two ints it already has — giving ``launches_per_chunk`` and the
  **launch tax**: ``launches x per-launch overhead`` priced against the
  measured chunk seconds (``launch_overhead_share``).

At run end the accounting joins the static roofline
(:mod:`obs.roofline`) with the ChunkProfiler's measured stage means
into achieved-bandwidth fractions, asks the fusion advisor for the top
candidate, and lands everything as the ``perf`` run event,
``EngineResult.perf``, ``perf/*`` gauges, and a stderr table.  Strictly
observational: the walk happens at build time on the traced jaxpr, the
dynamic feed is host arithmetic — engine results are bit-identical
with ``--perf`` on or off (tested).

Per-launch overhead defaults to 5 us (typical accelerator dispatch
floor); override with ``RAFT_LAUNCH_OVERHEAD_US``.  Because the launch
count is an upper bound, the share is too — it brackets, not measures,
the tax.  jax is imported lazily, keeping ``obs`` importable in
device-less tooling.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

#: Collective primitives (mesh chunk): counted separately so the
#: modeled collective share of the sharded path is explainable.
COLLECTIVE_PRIMS = frozenset((
    "psum", "pmin", "pmax", "all_to_all", "all_gather", "ppermute",
    "reduce_scatter", "psum_scatter", "axis_index"))

DEFAULT_LAUNCH_OVERHEAD_US = 5.0


def launch_overhead_seconds() -> float:
    """Per-launch overhead assumption (seconds); RAFT_LAUNCH_OVERHEAD_US
    overrides the 5 us default.  Malformed values warn and fall back:
    this runs inside the engines' fail-soft perf build and its fallback
    handler, so raising would fail the engine build."""
    env = os.environ.get("RAFT_LAUNCH_OVERHEAD_US")
    if env is not None:
        try:
            return float(env) * 1e-6
        except ValueError:
            print(f"perf: ignoring malformed RAFT_LAUNCH_OVERHEAD_US="
                  f"{env!r} (want microseconds as a number)",
                  file=sys.stderr)
    return DEFAULT_LAUNCH_OVERHEAD_US * 1e-6


def analyze_chunk_program(fn, *arg_avals) -> dict:
    """Trace ``fn`` (an engine's chunk program — jitted is fine, the
    walk recurses through pjit/shard_map) at the given avals and return
    the static launch model:

    - ``launches_per_batch``: device ops inside loop bodies — the batch
      while_loop is the chunk program's only top-level loop, so this is
      the per-batch cost (nested probe loops counted once, a floor);
    - ``launches_fixed``: ops outside any loop (stats packing, once per
      chunk call);
    - ``collectives_per_batch``: collective ops per batch (mesh).
    """
    import jax
    import jax.tree_util as jtu

    from .roofline import jaxpr_traffic
    closed = jax.make_jaxpr(fn)(*arg_avals)
    flat, _ = jtu.tree_flatten(arg_avals)
    t = jaxpr_traffic(closed, flat)
    return {
        "launches_per_batch": t["while_launches"],
        "launches_fixed": t["launches"] - t["while_launches"],
        "collectives_per_batch": t["collectives_in_loop"],
        "collectives_fixed": t["collectives"]
        - t["collectives_in_loop"],
        "model": "jaxpr device ops (pre-fusion upper bound; "
                 "loop bodies once)",
        "notes": t["notes"],
    }


class PerfAccounting:
    """One engine run's performance attribution: static models built at
    engine construction, dynamic (batches, seconds) fed per chunk call,
    the perf block assembled at run end.

    Everything here is host-side bookkeeping; the only non-trivial cost
    is the one-time jaxpr walk at build (sub-second, amortized across
    runs on a warm engine)."""

    def __init__(self, *, pipeline: str, launch_model: Optional[dict],
                 stage_traffic: Optional[Dict[str, dict]],
                 peak: Optional[dict] = None,
                 metrics=None):
        from . import roofline as roofline_mod
        self.pipeline = pipeline
        self.launch_model = launch_model
        self.traffic = stage_traffic
        self.peak = peak or roofline_mod.peak_bandwidth()
        self.metrics = metrics
        self.overhead_s = launch_overhead_seconds()
        self.reset()

    def reset(self) -> None:
        """Per-run accumulators (warm engines reuse the static halves)."""
        self.chunk_calls = 0
        self.batches = 0
        self.chunk_seconds = 0.0
        self._level_batches = 0
        self.level_launches: List[dict] = []
        self.collective_probe_seconds: Optional[float] = None

    # -- dynamic feed ---------------------------------------------------
    def add_chunk(self, batches: int, seconds: float) -> None:
        """One chunk call's measured (device batches, wall seconds) —
        fed from the packed-stats fetch the loop already does."""
        self.chunk_calls += 1
        self.batches += int(batches)
        self._level_batches += int(batches)
        self.chunk_seconds += float(seconds)

    def end_level(self, level: int) -> None:
        """Level boundary: snapshot the level's launch total so OOM /
        skew events can be correlated with launch pressure per level."""
        lm = self.launch_model
        if lm is not None:
            self.level_launches.append({
                "level": int(level), "batches": self._level_batches,
                "launches": self._level_batches
                * lm["launches_per_batch"]})
        self._level_batches = 0

    def note_collective_probe(self, seconds: float) -> None:
        """Mesh path: one timed psum round (sampled per level) — the
        latency term of the modeled collective share."""
        self.collective_probe_seconds = float(seconds)

    # -- assembly -------------------------------------------------------
    def launches_per_chunk(self) -> Optional[float]:
        lm = self.launch_model
        if lm is None or not self.chunk_calls:
            return None
        per_batch = lm["launches_per_batch"]
        return (per_batch * self.batches / self.chunk_calls
                + lm["launches_fixed"])

    def summary(self, chunk_stages: Optional[Dict[str, float]] = None
                ) -> dict:
        """The ``perf`` block: launch accounting + roofline rows +
        advisor verdict (+ the modeled collective share on the mesh)."""
        from . import roofline as roofline_mod
        lm = self.launch_model
        lpc = self.launches_per_chunk()
        launch: Dict[str, object] = {
            "model": (lm or {}).get("model"),
            "launches_per_batch": (lm or {}).get("launches_per_batch"),
            "launches_fixed_per_chunk": (lm or {}).get("launches_fixed"),
            "chunk_calls": self.chunk_calls,
            "batches": self.batches,
            "chunk_seconds": round(self.chunk_seconds, 6),
            "launches_per_chunk": (round(lpc, 1) if lpc is not None
                                   else None),
            "launch_overhead_us": round(self.overhead_s * 1e6, 3),
            "launch_overhead_share": None,
            "per_level": self.level_launches,
        }
        if lm is not None and self.chunk_seconds and self.batches:
            tax = (lm["launches_per_batch"] * self.batches
                   + lm["launches_fixed"] * self.chunk_calls) \
                * self.overhead_s
            launch["launch_tax_seconds"] = round(tax, 6)
            launch["launch_overhead_share"] = round(
                min(1.0, tax / self.chunk_seconds), 6)
        means = dict(chunk_stages or {})
        means.pop("total", None)
        rows = (roofline_mod.build_roofline(self.traffic, means, self.peak)
                if self.traffic else {})
        advisor = roofline_mod.advise(rows, self.overhead_s) if rows \
            else {"ranking": [], "top": None,
                  "verdict": ("launch accounting only (no per-stage "
                              "roofline on this engine)"
                              if self.traffic is None else
                              "no stage model (launch trace failed)")}
        out = {
            "pipeline": self.pipeline,
            "launch": launch,
            "roofline": {"peak_bytes_per_sec":
                         float(self.peak["bytes_per_sec"]),
                         "peak_source": self.peak["source"],
                         "stages": rows},
            "advisor": advisor,
        }
        if self.collective_probe_seconds is not None and lm is not None:
            probe = self.collective_probe_seconds
            coll = {"probe_seconds": round(probe, 6),
                    "collectives_per_batch": lm["collectives_per_batch"],
                    "share": None}
            if self.chunk_seconds and self.batches:
                coll["share"] = round(min(1.0, (
                    probe * lm["collectives_per_batch"] * self.batches)
                    / self.chunk_seconds), 6)
            out["collectives"] = coll
        return out

    def feed_metrics(self, mt, perf: dict) -> None:
        """Gauges from the assembled block — ONE tax formula lives in
        summary(), so the event payload and the gauges cannot drift."""
        launch = perf["launch"]
        if launch["launches_per_chunk"] is not None:
            mt.gauge("perf/launches_per_chunk",
                     launch["launches_per_chunk"])
        if launch["launch_overhead_share"] is not None:
            mt.gauge("perf/launch_overhead_share",
                     launch["launch_overhead_share"])

    def render_table(self, perf: dict) -> str:
        """Run-end stderr table: the launch tax priced against measured
        chunk time, roofline rows, and the advisor's one-line verdict —
        the replacement for hand-reading NORTHSTAR §c."""
        launch = perf["launch"]
        lines = [f"perf observatory ({self.pipeline} pipeline, "
                 f"{launch['chunk_calls']} chunk calls, "
                 f"{launch['batches']} batches):"]
        if launch["launches_per_batch"] is not None:
            share = launch["launch_overhead_share"]
            lines.append(
                f"  launches: {launch['launches_per_batch']} device ops/"
                f"batch (pre-fusion bound), "
                f"{launch['launches_per_chunk'] or 0:,.0f}/chunk; tax @ "
                f"{launch['launch_overhead_us']:g} us = "
                + (f"{share:.1%} of measured chunk time"
                   if share is not None else "n/a (no chunk time)"))
        rows = perf["roofline"]["stages"]
        if rows:
            lines.append(
                f"  roofline vs {perf['roofline']['peak_bytes_per_sec'] / 1e9:,.0f}"
                f" GB/s ({perf['roofline']['peak_source']}):")
            lines.append(f"    {'stage':14s} {'KB/batch':>10s} "
                         f"{'floor ms':>9s} {'meas ms':>9s} "
                         f"{'of peak':>8s} {'ops':>6s}")
            for stage, r in rows.items():
                meas = (f"{r['mean_seconds'] * 1e3:9.3f}"
                        if r["mean_seconds"] is not None else f"{'-':>9s}")
                frac = (f"{r['bandwidth_fraction']:8.1%}"
                        if r["bandwidth_fraction"] is not None
                        else f"{'-':>8s}")
                lines.append(
                    f"    {stage:14s} {r['bytes_total'] / 1024:10.1f} "
                    f"{(r['floor_seconds'] or 0) * 1e3:9.4f} {meas} "
                    f"{frac} {r['launches']:6d}")
        if perf.get("collectives"):
            c = perf["collectives"]
            share = c["share"]
            lines.append(
                f"  collectives: {c['collectives_per_batch']}/batch, "
                f"probe {c['probe_seconds'] * 1e3:.3f} ms"
                + (f", modeled share {share:.1%}" if share is not None
                   else ""))
        lines.append(f"  advisor: {perf['advisor']['verdict']}")
        return "\n".join(lines)

    def finish(self, evlog, chunk_stages=None, stream=None) -> dict:
        """Run-end hook (both engines): assemble the block, emit the
        ``perf`` event, push gauges, print the table.  Returns the block
        (what ``EngineResult.perf`` carries)."""
        perf = self.summary(chunk_stages)
        evlog.emit("perf", perf=perf)
        if self.metrics is not None:
            self.feed_metrics(self.metrics, perf)
        print(self.render_table(perf), file=stream or sys.stderr)
        return perf


def build_accounting(*, pipeline: str, chunk_fn, chunk_avals,
                     dims=None, B: Optional[int] = None,
                     K: Optional[int] = None,
                     with_stages: bool = True,
                     metrics=None, engine: str = "engine",
                     ring: int = 16, swarm_pipeline: str = "v1"
                     ) -> PerfAccounting:
    """Build one engine's PerfAccounting at construction time: trace the
    real chunk program for the launch model and (single-chip) the shared
    stage programs for the roofline traffic.  Fail-soft by construction:
    a model that cannot be built warns on stderr (named by ``engine``)
    and degrades to a perf block with nulls — same resolved ``pipeline``
    label either way — never a failed engine build.

    ``pipeline="swarm"`` prices the swarm tier instead: the traced
    chunk is the whole lockstep scan (launches_per_batch then counts
    device ops per scan STEP — the swarm's per-step pin next to the
    BFS per-batch ones), and the roofline rows come from the
    walk-kernel stage programs (``ring``/``swarm_pipeline`` mirror the
    engine's ring capacity and resolved expand pipeline)."""
    from . import roofline as roofline_mod
    launch_model = None
    traffic = None
    try:
        launch_model = analyze_chunk_program(chunk_fn, *chunk_avals)
        if with_stages and dims is not None:
            traffic = roofline_mod.stage_traffic(
                dims, B, K,
                pipeline="swarm" if pipeline == "swarm" else "v1",
                ring=ring, swarm_pipeline=swarm_pipeline)
    except Exception as e:
        print(f"perf: {engine} launch/roofline model unavailable "
              f"({type(e).__name__}: {e}); continuing without",
              file=sys.stderr)
    return PerfAccounting(pipeline=pipeline, launch_model=launch_model,
                          stage_traffic=traffic, metrics=metrics)


def timed_collective_probe(fn, *args, warm: bool = True) -> float:
    """Fence-timed single collective round (mesh skew telemetry): a
    warm-up call (compile) unless the caller already warmed ``fn``,
    then one timed call.  ``fn`` must block until the result is
    host-visible (multihost's agreement primitives do — they return
    host ints).  Callers probing every level should warm once at
    construction and pass ``warm=False`` so each level pays exactly
    one collective round."""
    if warm:
        fn(*args)                   # warm-up: compile off the sample
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0
