#!/usr/bin/env python3
"""Benchmark: distinct states/sec on the bounded 3-server MCraft model.

Runs the exhaustive BFS engine on ``configs/MCraft_bounded.cfg`` (MaxTerm=3,
MaxLogLen=2, MaxMsgCount=1 — BASELINE.json configs[1]) for a fixed wall
budget on the TPU, in this one process, then prints ONE JSON line.  A run
that finds no TPU exits non-zero before measuring, unless the CPU was
asked for explicitly with ``JAX_PLATFORMS=cpu`` (the JSON's ``platform``
and ``device_kind`` then say so).

Baseline note: this environment has no Java, so real CPU TLC cannot be
measured here (BASELINE.md §b).  The recorded ``vs_baseline`` is the ratio
against the pure-Python oracle checker measured in the same process — an
interpreted explicit-state checker on this host's single CPU core, i.e. a
*conservative stand-in* for TLC (TLC's compiled Java evaluator is roughly
an order of magnitude faster than the Python oracle; both numbers are
reported so the comparison can be re-based when a TLC measurement exists).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BENCH_SECONDS = float(os.environ.get("BENCH_SECONDS", "45"))
# Oracle window defaults to the engine's budget: comparable measurement
# windows (both all-fresh early levels first, duplicates later).
ORACLE_SECONDS = float(os.environ.get("BENCH_ORACLE_SECONDS",
                                      str(BENCH_SECONDS)))

_T0 = time.time()


def _mark(msg: str) -> None:
    """Timestamped stderr progress marker: localizes a stall without
    polluting the one-line stdout JSON contract."""
    print(f"bench[{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _swarm_bench(setup, platform: str) -> None:
    """BENCH_MODE=swarm: the randomized-walk tier's bench dialect.

    Same contract as the exhaustive bench — one JSON line on stdout,
    the run-event log validated as a hard gate, optional BENCH_HISTORY
    ledger entry — but the headline metric is lockstep walk steps/sec
    (``value``), with walks/sec, visited/sec and the time-to-first-
    counterexample (``violation_at_seconds``) riding along.  There is
    no oracle window: the swarm is not measuring exhaustive coverage,
    so ``vs_baseline`` has no meaning here (scripts/bench_diff.py
    folds gracefully when one side of a diff is swarm-dialect).
    Knobs: BENCH_WALKS / BENCH_MAX_DEPTH / BENCH_RING / BENCH_CHUNK /
    BENCH_SEED / BENCH_NUM_STEPS (unset = run the BENCH_SECONDS wall
    budget) on top of the shared BENCH_BATCH / BENCH_PIPELINE /
    BENCH_SECONDS / BENCH_EVENTS_OUT / BENCH_HISTORY."""
    import tempfile

    import jax

    from raft_tla_tpu.engine.check import (initial_states,
                                           make_swarm_engine)

    def env_int(name):
        return int(os.environ[name]) if os.environ.get(name) else None

    seed = int(os.environ.get("BENCH_SEED", "0"))
    num_steps = env_int("BENCH_NUM_STEPS")
    events_file = os.environ.get("BENCH_EVENTS_OUT")
    scratch_dir = None
    if events_file is None:
        scratch_dir = tempfile.mkdtemp(prefix="bench_obs_")
        events_file = os.path.join(scratch_dir, "events.jsonl")
    # Walks, depth, slice width and pipeline as ``check --mode swarm``
    # resolves them (engine/check.py make_swarm_engine); a knob left
    # unset is the cfg's or the engine's own default.
    shape = {k: v for k, v in (("ring", env_int("BENCH_RING")),
                               ("chunk", env_int("BENCH_CHUNK")))
             if v is not None}
    eng = make_swarm_engine(
        setup, walks=env_int("BENCH_WALKS"),
        max_depth=env_int("BENCH_MAX_DEPTH"),
        batch=env_int("BENCH_BATCH"),
        pipeline=os.environ.get("BENCH_PIPELINE"),
        events_out=events_file, **shape)
    walks, max_depth, ring = eng.walks, eng.max_depth, eng.ring
    _mark(f"swarm engine built (walks={walks}, depth={max_depth}, "
          f"ring={ring}); compiling + running "
          + (f"{num_steps} steps" if num_steps is not None
             else f"{BENCH_SECONDS:.0f}s budget"))
    res = eng.run(initial_states(setup, seed=seed), seed=seed,
                  num_steps=num_steps,
                  max_seconds=(None if num_steps is not None
                               else BENCH_SECONDS))
    _mark(f"swarm run done: {res.steps} steps / {res.visited} visited "
          f"in {res.wall_seconds:.1f}s")

    # Same telemetry-regression gate as the exhaustive bench: a swarm
    # run that leaves its event log missing/malformed fails loudly.
    from raft_tla_tpu.obs import validate_and_cleanup
    try:
        n_events = validate_and_cleanup(events_file, scratch_dir)
    except (OSError, ValueError) as e:
        print(f"bench: telemetry regression — run event log invalid: "
              f"{e}", file=sys.stderr)
        sys.exit(1)
    _mark(f"event log validated ({n_events} events)")

    from raft_tla_tpu.obs import host_fingerprint
    import secrets
    doc = {
        "run_id": secrets.token_hex(8),
        "metric": "swarm_steps_per_sec",
        "value": round(res.steps_per_second, 1),
        "unit": "steps/s",
        "mode": "swarm",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": len(jax.devices()),
        "host_fingerprint": host_fingerprint(),
        "walks": res.walks,
        "steps": res.steps,
        "visited": res.visited,
        "traces": res.traces,
        # Ledger-dialect aliases (entry_from_bench's column names):
        # distinct = ring-fresh visits, generated = lockstep steps.
        "distinct_states": res.distinct,
        "generated_states": res.generated,
        "generated_per_sec": round(res.steps_per_second, 1),
        "steps_per_sec": round(res.steps_per_second, 1),
        "walks_per_sec": round(res.walks_per_second, 1),
        "visited_per_sec": round(res.states_per_second, 1),
        "violation_at_seconds": res.violation_at_seconds,
        "max_depth": max_depth,
        "ring": ring,
        "seed": seed,
        "wall_s": round(res.wall_seconds, 2),
        "budget_s": BENCH_SECONDS,
        "diameter": res.diameter,
        "stop_reason": res.stop_reason,
        "phases": {k: round(v, 4) for k, v in res.phases.items()},
        "pipeline": res.pipeline,
        "report": res.report,
    }
    if res.report.get("hunt"):
        from raft_tla_tpu.obs import hunt as hunt_mod
        doc["hunt"] = hunt_mod.summarize(res.report["hunt"])
    print(json.dumps(doc))
    history_path = os.environ.get("BENCH_HISTORY")
    if history_path:
        from raft_tla_tpu.obs import history as history_mod
        history_mod.append_entry(
            history_path, history_mod.entry_from_bench(doc, kind="swarm"))
        _mark(f"history entry appended to {history_path}")


def main():
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"bench: first device is {dev} (platform {platform!r}), "
              f"not a TPU; set JAX_PLATFORMS=cpu to measure the CPU "
              f"on purpose", file=sys.stderr)
        sys.exit(1)
    _mark(f"backend up: {platform} ({dev.device_kind})")

    on_accel = platform not in ("cpu",)
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config

    here = os.path.dirname(os.path.abspath(__file__))
    setup = load_config(os.path.join(here, "configs/MCraft_bounded.cfg"))
    # Second product tier: BENCH_MODE=swarm benches the randomized-walk
    # engine (engine/swarm.py) on the same pinned model in its own
    # dialect (_swarm_bench); everything below is the exhaustive
    # headline measurement.
    bench_mode = os.environ.get("BENCH_MODE", "exhaustive")
    if bench_mode == "swarm":
        return _swarm_bench(setup, platform)
    if bench_mode != "exhaustive":
        print(f"bench: unknown BENCH_MODE {bench_mode!r} (expected "
              f"'exhaustive' or 'swarm')", file=sys.stderr)
        sys.exit(2)
    # Accelerator capacities are EXPLICIT and modest (~3.5 GB total), not
    # HBM-auto-sized.  A 45-60 s window generates < 2 M distinct
    # states — 2^21 queue rows and a 2^25-key table are ample, and the
    # spill path covers any overshoot.  Env overrides for experiments.
    qcap = int(os.environ.get("BENCH_QUEUE_CAP",
                              str(1 << 21 if on_accel else 1 << 19)))
    scap = int(os.environ.get("BENCH_SEEN_CAP",
                              str(1 << 25 if on_accel else 1 << 21)))
    # Run-event log (obs/): the bench is also the telemetry-regression
    # gate — after the run the file must exist and parse, else nonzero rc.
    # The default scratch dir is cleaned up after validation (repeated
    # CI runs must not accumulate orphans); an explicit BENCH_EVENTS_OUT
    # is the caller's to keep.
    import tempfile
    events_file = os.environ.get("BENCH_EVENTS_OUT")
    scratch_dir = None
    if events_file is None:
        scratch_dir = tempfile.mkdtemp(prefix="bench_obs_")
        events_file = os.path.join(scratch_dir, "events.jsonl")
    # Partial-order reduction (analysis/por.py): BENCH_POR=1 certifies
    # in-process at engine build, BENCH_POR_TABLE applies a pre-built
    # artifact.  The reduction (if any certificate proves) shows up in
    # the coverage object's "pruned" column and the generated/distinct
    # headline — bench_diff.py then reports generated-state reduction
    # alongside the distinct/s regression gate.
    # Successor pipeline: BENCH_PIPELINE takes what EngineConfig.pipeline
    # takes (auto/v1/v2); the resolved one is embedded in the JSON.
    # Device-profiler capture (obs/profile.py XlaProfileCapture;
    # BENCH_XLA_PROFILE=N traces the first N chunk calls): the
    # hardware-truth artifacts, landed under
    # BENCH_XLA_PROFILE_DIR (default artifacts/xla_profile).
    # Observational — the headline number is unaffected.
    xla_profile = int(os.environ.get("BENCH_XLA_PROFILE", "0"))
    cfg = EngineConfig(
        batch=int(os.environ.get("BENCH_BATCH",
                                 str(2048 if on_accel else 512))),
        queue_capacity=qcap,
        seen_capacity=scap,
        check_deadlock=False,
        record_trace=False,          # raw engine throughput (trace store is
        max_seconds=BENCH_SECONDS,   # host-side; C++ store tracked separately)
        events_out=events_file,
        trace_out=os.environ.get("BENCH_TRACE_OUT"),
        xla_profile_chunks=xla_profile or None,
        xla_profile_dir=os.environ.get("BENCH_XLA_PROFILE_DIR",
                                       "artifacts/xla_profile"),
        pipeline=os.environ.get("BENCH_PIPELINE", "auto"),
        por=bool(int(os.environ.get("BENCH_POR", "0"))),
        por_table=os.environ.get("BENCH_POR_TABLE"))
    # "auto": on a multi-accelerator slice (e.g. v5e-8) the run shards
    # over all devices — the mesh engine is the product's scaling path
    # and the north-star target is defined on the full slice.
    n_dev = len(jax.devices())
    engine = make_engine(setup, cfg, engine_cls="auto")
    is_mesh = type(engine).__name__ == "MeshBFSEngine"
    # Live introspection (obs/expose.py): BENCH_METRICS_PORT serves
    # /metrics (Prometheus) + /flight (the watch console's feed) for
    # the duration of the run.
    metrics_srv = None
    metrics_port = int(os.environ.get("BENCH_METRICS_PORT", "0"))
    if metrics_port:
        from raft_tla_tpu.obs import start_metrics_server
        from raft_tla_tpu.obs.flight import RECORDER
        try:
            metrics_srv, _t = start_metrics_server(metrics_port,
                                                   engine.metrics,
                                                   flight=RECORDER)
            _mark(f"metrics listener on 127.0.0.1:"
                  f"{metrics_srv.server_address[1]} (/metrics, /flight)")
        except OSError as e:
            # The listener is a nicety; the measurement is the point —
            # a busy port must not kill the bench.
            metrics_srv = None
            _mark(f"metrics listener unavailable on port "
                  f"{metrics_port} ({e}); continuing without it")
    _mark(f"engine built ({'mesh' if is_mesh else 'single'}, "
          f"batch={cfg.batch}); compiling + running "
          f"{BENCH_SECONDS:.0f}s budget")
    try:
        res = engine.run(initial_states(setup))
    finally:
        if metrics_srv is not None:
            metrics_srv.shutdown()
            # server_close too: shutdown() alone leaves the bound
            # socket accepting into the kernel backlog, which turns the
            # watcher's clean connection-refused "listener gone" exit
            # into per-poll read timeouts for the rest of the process.
            metrics_srv.server_close()
    rate = res.distinct / res.wall_seconds if res.wall_seconds else 0.0
    _mark(f"engine run done: {res.distinct} distinct in "
          f"{res.wall_seconds:.1f}s; starting oracle window")

    # Telemetry-regression gate: a run that leaves its event log missing
    # or malformed fails the WHOLE bench loudly — an unobservable engine
    # is a regression even when its states/sec number looks fine.  The
    # path is re-resolved through the engine (a process group rewrites
    # events_out to a per-controller piece name); cleanup happens on
    # both outcomes (obs.validate_and_cleanup).
    from raft_tla_tpu.obs import validate_and_cleanup
    try:
        n_events = validate_and_cleanup(engine._events_path(), scratch_dir)
    except (OSError, ValueError) as e:
        print(f"bench: telemetry regression — run event log invalid: {e}",
              file=sys.stderr)
        sys.exit(1)
    _mark(f"event log validated ({n_events} events)")
    # Same contract for the span trace when one was requested: a
    # BENCH_TRACE_OUT file Perfetto would reject fails the bench.
    if cfg.trace_out:
        from raft_tla_tpu.obs import validate_chrome_trace
        try:
            n_spans = len(validate_chrome_trace(cfg.trace_out))
        except (OSError, ValueError) as e:
            print(f"bench: telemetry regression — Chrome trace invalid: "
                  f"{e}", file=sys.stderr)
            sys.exit(1)
        _mark(f"chrome trace validated ({n_spans} events)")

    # Python-oracle baseline on the same model (CPU, single core), over
    # the SAME wall budget from the same root — comparable windows, so the
    # ratio measures engine speed, not space structure (round-2 verdict
    # weak #2).  The oracle level-loop can't stop mid-level; its own wall
    # clock is reported so the rate is exact for the work done.
    from raft_tla_tpu.models import oracle as orc
    from raft_tla_tpu.models.invariants import constraint_py
    from raft_tla_tpu.models.pystate import init_state

    t0 = time.time()
    ores = orc.bfs([init_state(setup.dims)], setup.dims,
                   constraint=constraint_py(setup.bounds),
                   check_deadlock=False,
                   stop_predicate=lambda r: time.time() - t0 > ORACLE_SECONDS)
    base_wall = time.time() - t0
    base_rate = ores.distinct_states / base_wall if base_wall else 1.0
    _mark("oracle window done; emitting JSON")

    # Host identity (obs/flight.py host_fingerprint): bench_diff.py
    # prints a cross-host warning when two diffed benches disagree here
    # — the PR 7 trap where BENCH_r05's absolute 38.4k/s was silently
    # compared against a ~4x slower container.
    from raft_tla_tpu.obs import host_fingerprint

    # Per-run identity shared by the printed JSON and the BENCH_HISTORY
    # ledger line: bench_diff --history excludes the candidate's OWN
    # entry by this id, so the record-then-gate workflow never
    # self-compares even when the captured file is later annotated or
    # reformatted (doc-equality alone would miss it then).
    import secrets
    run_id = secrets.token_hex(8)

    doc = {
        "run_id": run_id,
        "metric": "distinct_states_per_sec",
        "value": round(rate, 1),
        "unit": "states/s",
        "vs_baseline": round(rate / base_rate, 2) if base_rate else None,
        "platform": platform,
        "device_kind": dev.device_kind,
        "devices": n_dev,
        "host_fingerprint": host_fingerprint(),
        "engine": "mesh" if is_mesh else "single",
        "distinct_states": res.distinct,
        "generated_states": res.generated,
        "generated_per_sec": round(res.generated / res.wall_seconds, 1)
        if res.wall_seconds else 0.0,
        "wall_s": round(res.wall_seconds, 2),
        "budget_s": BENCH_SECONDS,
        "diameter": res.diameter,
        "levels": res.levels,
        "stop_reason": res.stop_reason,
        "generated_by_action": res.action_counts,
        # Seen-set doublings as (capacity-after, off-clock stall seconds):
        # the cost evidence for sizing SEEN_CAPACITY up front.
        "growth_stalls": res.growth_stalls,
        # Host-side per-phase wall-time breakdown (obs/ phase timers):
        # chunk dispatch vs stats fetch vs spill vs growth — the pipeline
        # accounting BENCH_r06+ carries so hot-path work can be targeted
        # at the phase that actually dominates.
        "phases": {k: round(v, 4) for k, v in res.phases.items()},
        "pipeline": res.pipeline,
        # The TLC-style coverage object — an axis scripts/bench_diff.py
        # gates BENCH_r* trajectories on.
        "coverage": res.coverage,
        # Certified ample instances the run's POR table carried (0 = POR
        # off or an all-conservative certificate).
        "por_instances": res.por_instances,
        # TLC-parity statespace report (obs/report.py): collision
        # probability, per-level table, out-degree, seen-set load —
        # the semantic half of the trajectory the run ledger records.
        "report": res.report,
        "baseline_states_per_sec": round(base_rate, 1),
        "baseline_distinct": ores.distinct_states,
        "baseline_wall_s": round(base_wall, 2),
        "baseline_kind": "python-oracle-1core (no TLC/java available)",
    }
    print(json.dumps(doc))

    # Run-history ledger (obs/history.py): BENCH_HISTORY names the
    # append-only JSONL trajectory file — one entry per bench run,
    # embedding the full bench object so scripts/bench_diff.py
    # --history can auto-resolve its baseline (newest same-host entry)
    # instead of a hand-picked file (the BENCH_r05 cross-host trap).
    history_path = os.environ.get("BENCH_HISTORY")
    if history_path:
        from raft_tla_tpu.obs import history as history_mod
        history_mod.append_entry(
            history_path, history_mod.entry_from_bench(doc))
        _mark(f"history entry appended to {history_path}")


if __name__ == "__main__":
    main()
