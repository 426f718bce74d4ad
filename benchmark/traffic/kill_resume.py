"""Traffic kind ``kill_resume``: a window of the supervised, checkpointed
exhaustive run: a snapshot at every level boundary under the foreground
load, a kill mid-level, and the run taken up again from the newest intact
snapshot on disk, all inside the clock.

The deployment is README "Resilience": ``check <cfg> --checkpoint-dir d
--checkpoint-interval .. --keep-checkpoints N --supervise`` (TLC's
``-checkpoint`` / ``-recover``).  ``benchmark/run.py``'s process holds the
chip, so a supervised CHILD cannot run under it: the kill is the fault
plan's own ``kill`` site in soft mode (``FaultInjected`` out of the chunk
loop, which leaves the same files behind as ``os._exit``), and the
recovery is what ``supervisor.run_supervised`` and ``cli.py`` do after a
crash exit, ``checkpoint.latest(dir)`` then ``run(resume=<that path>)``,
on the warm engine in this process.  What a real restart pays besides
(the child's start, ``import jax``, the chip's start-up, ``make_engine``,
the programs' cache loads) is ``setup_s`` in every cell and is not in this
window; the configuration lists it under ``reduced`` as ``restart``.

Set-up, outside the clock, is ``bfs_window``'s: the engine as ``cli.py
check`` builds it with the configuration's ``durability`` fields, the walk
root -> ``kill_level`` (every level held to the pin; it writes a snapshot
at every boundary into a directory of its own, so every program a save
uses is loaded), one warm resume FROM EACH OF THAT DIRECTORY'S TWO FILES,
the kill level's and then the start level's (the load from disk, the
restore and the chunk as a resumed run calls it), then the window's
directory made with the start level's file alone, and the fault plan
installed soft with a fresh state directory.  The walk goes one level
past the window's start because a restore uploads the frontier and
inserts the last piece of the keys AT THEIR OWN LENGTHS, a compile each
at every new length: the window's recovery restores the kill level's
snapshot, so the set-up restores one of the same lengths first, and the
window compiles nothing but the 4 ms slice of the one frontier no set-up
can hold, the level's after the kill level.

The window (``--seconds``), the profiler around all of it:
  1. ``eng.run(resume=<start-level file>)``: the start level expanded, the
     next level's snapshot written at the boundary, that level begun;
  2. the kill: ``kill@level=<kill_level>;chunk=<kill_chunk>`` fires at the
     dispatch of that call of the level;
  3. the recovery: ``checkpoint.latest(dir)``, ``eng.run(resume=<path>)``
     with what is left of the window: the load from disk, the restore, the
     killed level expanded again, whole, its boundary crossed and its
     snapshot written, retention applied, and the next level until the
     deadline.
Both runs get ``max_seconds`` = what is left of the window plus the
snapshot's own ``wall_seconds``: a resumed run back-dates its clock by
them, and the path goes in as the CLI passes it.

``distinct_per_s`` = (distinct when the window closes - the start level's)
/ the wall from the first ``run`` to the second's return: work done twice
counts once; a save, the load and the restore count as wall.

``correct`` (all exact but the kill's place, a range): see ``run``.  The
kind asks nothing of the program that a checkout from before the spans of
a save lacks; those are read by the per-layer readers only.

Mix parameters (``benchmark/traffic/<mix>.json``): ``bfs_window``'s
(``start_level``, ``sample``, ``forbidden_events``) and
  kill_level          level whose expansion the kill interrupts
  kill_chunk          which call of that level, counted from 1 (the plan's
                      ``chunk``: the fault grammar's one selector)
  kill_parents_share  the place aimed for, as a share of the level's
                      parents; ``kill_parents_held`` [lo, hi]: what the
                      parents expanded at the kill are held to
  kills               kills a window (1)
  replay_sample       states admitted after the recovery replayed to Init
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

import bench_lib as lib

SNAPSHOT = "level_{:05d}.npz"


def engine_config(config: dict, directory: str) -> object:
    """``EngineConfig`` as ``cli.py check`` builds it from its defaults,
    with the configuration's sizes and the flags of its ``durability``."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    d = config["durability"]
    return EngineConfig(
        batch=config["batch"], queue_capacity=config["queue_capacity"],
        seen_capacity=config["seen_capacity"],
        record_trace=d["record_trace"], pipeline="auto",
        progress_interval_seconds=60.0, checkpoint_dir=directory,
        checkpoint_every=d["checkpoint_every"],
        checkpoint_interval_seconds=d["checkpoint_interval_seconds"],
        keep_checkpoints=d["keep_checkpoints"])


def snapshots_in(directory: str) -> list:
    """Names of every snapshot file, whole or ``.tmp``, in a directory."""
    return sorted(n for n in os.listdir(directory)
                  if n.startswith("level_") and ".npz" in n)


def runs_of(events: list) -> list:
    """One list of events a run, cut at each ``run_start``."""
    runs = []
    for e in events:
        if e["event"] == "run_start":
            runs.append([])
        if runs:
            runs[-1].append(e)
    return runs


def one(events: list, name: str) -> list:
    return [e for e in events if e["event"] == name]


def run(ctx) -> dict:
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.schema import (decode_state, state_width,
                                            unflatten_state)
    from raft_tla_tpu.resilience import faults
    from raft_tla_tpu.utils.cfg import load_config
    from reference import snapshot as plain

    bw = lib.load_module("traffic", "bfs_window")
    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    start, kill_level = int(cell["start_level"]), int(cell["kill_level"])
    durability = config["durability"]
    pinned = lib.load_pinned(config["pinned"])
    setup = load_config(lib.write_cfg(config, ctx.tmp),
                        n_msg_slots=config["n_msg_slots"])
    row_bytes = state_width(setup.dims)
    ledger.exact("row width in bytes", row_bytes, config["shapes"]["row_bytes"])
    ledger.exact("action instances", setup.dims.n_instances,
                 config["shapes"]["action_instances"])
    ledger.exact("kills a window", int(cell["kills"]), 1)
    ledger.exact("the kill's level follows the start level", kill_level,
                 start + 1)

    setup_dir = os.path.join(ctx.tmp, "setup_states")
    window_dir = os.path.join(ctx.tmp, "states")
    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start
    eng = make_engine(setup, engine_config(config, setup_dir))
    make_engine_s = time.perf_counter() - t0
    ledger.exact("engine class", type(eng).__name__, "BFSEngine")
    ledger.exact("the engine's durability fields are the configuration's",
                 {"record_trace": eng.config.record_trace,
                  "checkpoint_every": eng.config.checkpoint_every,
                  "checkpoint_interval_seconds":
                      eng.config.checkpoint_interval_seconds,
                  "keep_checkpoints": eng.config.keep_checkpoints},
                 durability)
    ledger.exact("batch and pools are the configuration's",
                 (eng.config.batch, eng.config.queue_capacity,
                  eng.config.seen_capacity),
                 (config["batch"], config["queue_capacity"],
                  config["seen_capacity"]))

    # -- set-up: root -> kill level, a snapshot at every boundary --------
    walk_events = os.path.join(ctx.tmp, "walk.jsonl")
    eng.config.events_out = walk_events
    eng.config.max_diameter, eng.config.max_seconds = kill_level, None
    t0 = time.perf_counter()
    walk = eng.run(initial_states(setup))
    walk_s = time.perf_counter() - t0
    warmup_s = walk.phases.get("warmup", 0.0)
    ledger.exact("walk stop reason", walk.stop_reason, "diameter_budget")
    ledger.exact("pipeline", walk.pipeline, "v2")
    lib.compare_levels(ledger, lib.level_rows(lib.read_events(walk_events)),
                       pinned, range(kill_level + 1), "set-up")
    ledger.exact("the walk's directory holds its newest snapshots",
                 snapshots_in(setup_dir),
                 [SNAPSHOT.format(lv) for lv in range(
                     kill_level + 1 - durability["keep_checkpoints"],
                     kill_level + 1)])
    first = os.path.join(setup_dir, SNAPSHOT.format(start))
    ledger.exact("latest() of the walk's directory", ckpt_mod.latest(
        setup_dir), os.path.join(setup_dir, SNAPSHOT.format(kill_level)))
    # One resume from each path, as the CLI passes it, that stops after
    # its first one-batch call: the load from disk, every program the
    # restore uses AT THE LENGTHS OF THAT FILE'S frontier and keys, and
    # the chunk as a resumed run calls it compile or load here, not in
    # the window.  The kill level's first: the window's recovery restores
    # a file of its lengths; then the start level's, the window's first.
    eng.config.events_out = None
    eng.config.max_diameter, eng.config.max_seconds = None, 1e6
    eng.config.exit_conditions = (("distinct", 0),)
    t0 = time.perf_counter()
    warm_s = {}
    for level in (kill_level, start):
        warm = eng.run(resume=os.path.join(setup_dir,
                                           SNAPSHOT.format(level)))
        ledger.exact(f"warm resume of level {level}'s file: stop reason",
                     warm.stop_reason, "distinct_budget")
        warm_s[level] = (warm.phases.get("checkpoint_load", 0.0),
                         warm.phases.get("restore", 0.0))
    warm_resume_s = time.perf_counter() - t0
    eng.config.exit_conditions = ()
    # The window's directory starts with the start level's file alone.
    os.makedirs(window_dir)
    start_path = os.path.join(window_dir, SNAPSHOT.format(start))
    shutil.copyfile(first, start_path)
    eng.config.checkpoint_dir = window_dir
    start_meta = plain.read_meta(start_path)
    ledger.exact("start snapshot (level, frontier, distinct, generated)",
                 (start_meta["diameter"], start_meta["levels"][-1],
                  start_meta["distinct"], start_meta["generated"]),
                 (start,) + pinned[start])
    plan = f"kill@level={kill_level};chunk={int(cell['kill_chunk'])}"
    faults.install(plan, state_dir=os.path.join(ctx.tmp, "fault_state"),
                   hard=False)
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"walk to level {kill_level} {walk_s:.2f}s (warmup "
          f"{warmup_s:.2f}s, checkpoint "
          f"{walk.phases.get('checkpoint', 0.0):.2f}s over "
          f"{kill_level + 1} snapshots, trace_flush "
          f"{walk.phases.get('trace_flush', 0.0):.2f}s), {walk.distinct} "
          f"distinct, warm resumes from the files {warm_resume_s:.2f}s ("
          + ", ".join(f"level {lv}: load {ld:.2f}s, restore {rs:.2f}s"
                      for lv, (ld, rs) in warm_s.items())
          + f"); fault plan {plan}, soft", flush=True)

    # -- the window -------------------------------------------------------
    win_events = os.path.join(ctx.tmp, "window.jsonl")
    eng.config.events_out = win_events
    seconds = float(ctx.args.seconds)
    killed = res = latest = None
    try:
        with lib.traced(ctx):
            t_win0 = time.perf_counter()
            eng.config.max_seconds = seconds + start_meta["wall_seconds"]
            try:
                res = eng.run(resume=start_path)
            except faults.FaultInjected as exc:
                # As the supervisor sees a crash exit.
                killed = str(exc)
            t_kill = time.perf_counter()
            if killed is not None:
                latest = ckpt_mod.latest(window_dir)
                eng.config.max_seconds = (
                    seconds - (time.perf_counter() - t_win0)
                    + plain.read_meta(latest)["wall_seconds"])
                res = eng.run(resume=latest)
            # run() returns only after the last chunk's statistics are on
            # the host, so the clock stops on finished work.
            t_win1 = time.perf_counter()
    finally:
        faults.clear()
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    events = lib.read_events(win_events)
    runs = runs_of(events)
    new_distinct = res.distinct - pinned[start][1]
    ends = one(events, "run_end")
    parents = sum(int(e.get("parents_expanded") or 0) for e in ends)
    phases = {}
    for e in ends:
        for k, v in (e.get("phase_seconds") or {}).items():
            phases[k] = phases.get(k, 0.0) + v
    cur_rows = (lib.level_rows(events).get(res.diameter)
                or (pinned[res.diameter][0],))[0]
    next_rows = int(eng.metrics.gauge_value("engine/next_count"))
    fill = {"seen_load_pct": 100.0 * res.distinct / config["seen_capacity"],
            "queue_fill_pct": 100.0 * max(cur_rows, next_rows)
            / config["queue_capacity"]}
    print(f"fill: {res.distinct} keys of {config['seen_capacity']} "
          f"({fill['seen_load_pct']:.1f} %); level {res.diameter}'s "
          f"{cur_rows} rows and {next_rows} rows of level "
          f"{res.diameter + 1} in queues of {config['queue_capacity']} "
          f"({fill['queue_fill_pct']:.1f} %)", flush=True)
    print(f"window: {wall:.3f}s wall, {new_distinct} new distinct kept, "
          f"{parents} parents expanded in {len(runs)} runs, the kill at "
          f"{t_kill - t_win0:.3f}s ({killed}), levels {start}.."
          f"{res.diameter}, stop {res.stop_reason}", flush=True)
    for e in one(events, "checkpoint"):
        print(f"window snapshot: level {e['level']}, {e['distinct']} "
              f"distinct" + (f", {e['bytes_raw']} bytes in memory, "
                             f"{e['bytes_written']} on disk, "
                             f"{e['seconds']:.3f}s, parts {e['parts']}"
                             if "seconds" in e else ""), flush=True)
    print("window phases, s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(phases.items(),
                                          key=lambda kv: -kv[1])), flush=True)

    # -- correct: the two runs ---------------------------------------------
    ledger.exact("runs in the window", len(runs), 2)
    ledger.true("the first run was ended by the injected kill",
                killed is not None and plan in killed, str(killed))
    first_run, second_run = (runs + [[], []])[:2]
    end1 = (one(first_run, "run_end") or [{}])[-1]
    ledger.exact("the killed run's run_end (stop_reason, diameter)",
                 (end1.get("stop_reason"), end1.get("diameter")),
                 ("error", kill_level))
    ledger.true("the killed run's run_end names the fault",
                plan in str(end1.get("error")), str(end1.get("error")))
    ledger.exact("the killed run's level_complete and checkpoint events",
                 [(e["event"], e["level"]) for e in first_run
                  if e["event"] in ("level_complete", "checkpoint")],
                 [("level_complete", kill_level), ("checkpoint", kill_level)])
    lib.compare_levels(ledger, lib.level_rows(first_run), pinned,
                       [kill_level], "killed run")
    redo_parents = (int(end1.get("parents_expanded") or 0)
                    - pinned[start][0])
    share = redo_parents / pinned[kill_level][0]
    lo, hi = cell["kill_parents_held"]
    ledger.true(f"parents expanded at the kill, of level {kill_level}'s "
                f"{pinned[kill_level][0]}, within [{lo}, {hi}]",
                lo <= share <= hi, f"{redo_parents} = {share:.4f}; aimed "
                f"for {cell['kill_parents_share']}")
    # latest() after the kill, and the file it names.
    kill_path = os.path.join(window_dir, SNAPSHOT.format(kill_level))
    ledger.exact("latest() after the kill", latest, kill_path)
    acked = (one(first_run, "checkpoint") or [{}])[-1]
    start2 = (one(second_run, "run_start") or [{}])[-1]
    end2 = (one(second_run, "run_end") or [{}])[-1]
    ledger.exact("the second run is a resume", start2.get("resume"), True)
    # The one comparison a program may pass by silence: the parent of
    # PR 54 writes neither field, and the driver runs a new cell on the
    # parent's program too.  tests/test_kill_resume_deployment.py holds
    # the fields themselves.
    if "resume_path" in start2:
        ledger.exact("run_start's (resume_level, resume_path)",
                     (start2["resume_level"], start2["resume_path"]),
                     (kill_level, kill_path))
    ledger.exact("window stop reason", res.stop_reason, "duration_budget")
    ledger.exact("window pipeline", res.pipeline, "v2")
    crossed = sorted(lib.level_rows(second_run))
    ledger.true("the recovered run crossed the killed level's boundary",
                res.diameter > kill_level, str(res.diameter))
    ledger.exact("levels the recovered run crossed are consecutive from "
                 "the killed level", crossed,
                 list(range(kill_level + 1, res.diameter + 1)))
    lib.compare_levels(ledger, lib.level_rows(second_run), pinned, crossed,
                       "recovered run")
    ledger.exact("the recovered run's checkpoint events",
                 [e["level"] for e in one(second_run, "checkpoint")], crossed)
    ledger.exact("the recovered run's generated starts from the "
                 "snapshot's (nothing of the killed half level counts)",
                 res.generated
                 - sum((end2.get("generated_by_family") or {}).values()),
                 pinned[kill_level][2])
    ledger.exact("the recovered run's levels up to the snapshot's",
                 list(res.levels[:kill_level + 1]),
                 [pinned[lv][0] for lv in range(kill_level + 1)])
    comp = lib.check_window_log(ctx, events, t_win0, t_win1)

    # -- correct: what is on disk when the window closes -------------------
    on_disk = snapshots_in(window_dir)
    ledger.exact("the directory holds the two newest snapshots, no .tmp",
                 on_disk, [SNAPSHOT.format(lv) for lv in range(
                     res.diameter + 1 - durability["keep_checkpoints"],
                     res.diameter + 1)])
    older, _newer = snapshot_check(ctx, window_dir, on_disk, pinned)
    if older is not None and older.diameter == kill_level:
        ledger.exact("the kill-level file's metadata is its checkpoint "
                     "event's", (older.diameter, older.distinct),
                     (acked.get("level"), acked.get("distinct")))

    # -- correct: the replays (from the store the recovered run filled,
    # before another run replaces it), then the seeded sample --------------
    if older is not None:
        replay_check(ctx, eng, older, res)
        # The sample's run writes no snapshot into the window's directory.
        eng.config.checkpoint_dir = None
        bw.sample_check(ctx, eng, setup, older, decode_state,
                        unflatten_state)

    return {
        "end_to_end": {"setup_s": setup_s,
                       "distinct_per_s": new_distinct / wall},
        "window_wall_s": wall, "phases": phases,
        "events": events, "parents_expanded": parents, "counters": fill,
        # Kept, and generated: the second counts what both runs did
        # (the killed part level too), as ``parents_expanded`` does.
        "new_distinct": new_distinct,
        "new_generated": (int(end1.get("generated") or 0)
                          - pinned[start][2]
                          + res.generated - pinned[kill_level][2]),
        "batch": config["batch"], "row_bytes": row_bytes,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "walk": walk_s, "warm_resume": warm_resume_s},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
        "recovery": {"redo_parents": redo_parents,
                     "kill_at_s": t_kill - t_win0},
    }


def snapshot_check(ctx, directory, names, pinned):
    """Both files on disk through the plain reader and through
    ``checkpoint.load``: counts equal to the pin, keys unique and sorted,
    everything of the older file present in the newer, equal arrays.
    Returns the program's two loaded images (None, None where the
    directory does not hold two)."""
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from reference import snapshot as plain
    ledger = ctx.ledger
    if len(names) != 2 or any(n.endswith(".tmp") for n in names):
        return None, None
    t0 = time.perf_counter()
    loaded, snaps = [], []
    for name in names:
        path = os.path.join(directory, name)
        snap, ck = plain.read(path), ckpt_mod.load(path)
        meta, level = snap["meta"], snap["meta"]["diameter"]
        keys = plain.keys64(snap)
        ledger.exact(f"{name}: (level, rows, keys, records, distinct, "
                     f"generated) by the plain reader",
                     (level, len(snap["frontier"]), len(keys),
                      len(snap["trace_fps"]), meta["distinct"],
                      meta["generated"]),
                     (int(name[6:11]), pinned[level][0], pinned[level][1],
                      pinned[level][1], pinned[level][1], pinned[level][2]))
        ledger.true(f"{name}: keys unique and sorted",
                    plain.strictly_ascending(keys))
        ledger.exact(f"{name}: record keys that are no key of the seen-set",
                     plain.missing_from(keys, snap["trace_fps"]), 0)
        ledger.exact(f"{name}: arrays that differ from checkpoint.load's",
                     [a for a in plain.ARRAYS
                      if not np.array_equal(snap[a], getattr(ck, a))], [])
        ledger.exact(f"{name}: metadata that differs from "
                     f"checkpoint.load's",
                     (ck.diameter, ck.distinct, ck.generated,
                      list(ck.levels), ck.wall_seconds),
                     (meta["diameter"], meta["distinct"], meta["generated"],
                      meta["levels"], meta["wall_seconds"]))
        loaded.append(ck)
        snaps.append((keys, plain.records(snap)))
    (old_keys, old_records), (new_keys, new_records) = snaps
    ledger.exact(f"keys of {names[0]} missing from {names[1]}",
                 plain.missing_from(new_keys, old_keys), 0)
    ledger.exact(f"records of {names[0]} missing from {names[1]}",
                 plain.records_missing_from(new_records, old_records), 0)
    print(f"snapshots: {names} read twice and compared in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    return loaded


def replay_check(ctx, eng, ck, res) -> None:
    """``replay_sample`` states admitted after the recovery, drawn by the
    seed from the trace store's records, replayed from that store to
    ``Init``: their chains pass through records that only the snapshot
    carried across the kill."""
    ledger = ctx.ledger
    ref = lib.reference(ctx.config)
    want = int(ctx.cell["replay_sample"])
    ledger.exact("trace store", type(eng.trace).__name__,
                 "NativeTraceStore")
    fps = np.asarray(eng.trace.export()[0], np.uint64)
    rng = np.random.default_rng(ctx.args.seed)
    cand = fps[rng.integers(0, len(fps), 1 << 15)]
    # Admitted after the recovery: not among the snapshot's keys (sorted).
    old = ((ck.seen_hi.astype(np.uint64) << np.uint64(32))
           | ck.seen_lo.astype(np.uint64))
    at = np.minimum(np.searchsorted(old, cand), len(old) - 1)
    cand = rng.permutation(np.unique(cand[old[at] != cand]))
    picks = [int(fp) for fp in cand[:want]]
    ledger.exact("states admitted after the recovery drawn for the replay",
                 len(picks), want)
    init = ref.pystate.init_state(ref.dims)
    t0 = time.perf_counter()
    depths, rooted, legal = [], 0, 0
    for fp in picks:
        try:
            steps = eng.replay(fp)
        except (KeyError, RuntimeError) as exc:
            print(f"replay of {fp:#018x} failed: {type(exc).__name__}: "
                  f"{exc}", flush=True)
            continue
        states = [lib.to_reference_state(s, ref.pystate) for _a, s in steps]
        depths.append(len(steps) - 1)
        rooted += steps[0][0] == -1 and states[0] == init
        legal += all(t in ref.oracle.successor_set(s, ref.dims)
                     for s, t in zip(states, states[1:]))
    print(f"replay: {len(picks)} states admitted after the recovery in "
          f"{time.perf_counter() - t0:.2f}s, steps {sorted(set(depths))}",
          flush=True)
    ledger.true("replayed paths' steps are levels the recovered run built",
                len(depths) == len(picks)
                and all(ck.diameter < d <= res.diameter + 1 for d in depths),
                str(sorted(set(depths))))
    ledger.exact("replayed paths that start at Init", rooted, len(picks))
    ledger.exact("replayed paths legal under the reference, every step",
                 legal, len(picks))
