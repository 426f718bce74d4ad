"""Traffic kind ``bfs_window``: a time-bounded window of exhaustive
breadth-first search, deep in the space.

Set-up builds the engine the way ``cli.py check`` does, walks from the root
to ``start_level`` with it (every level held to the pinned profile), and
takes the level-boundary snapshot the engine itself writes there.  The
window is one ``engine.run(resume=snapshot)`` on that same warm engine with
``max_seconds = --seconds``.  Every level boundary the window crosses is
held to the pinned profile too.  After the window, outside the clock, a
seeded sample of start-level states goes through one level of the engine
and of the plain reference, and the three counts must agree.

The state space is defined by the spec, so this traffic has no random
part: ``--seed`` draws only the sample that is checked.

Mix parameters (``benchmark/traffic/<mix>.json``):
  start_level   level whose frontier the window starts expanding
  sample        states drawn from that frontier for the reference check
  forbidden_events  run events that may not occur inside the window
"""

from __future__ import annotations

import dataclasses
import os
import random
import time

import bench_lib as lib


def engine_config(config: dict, ctx) -> object:
    """``EngineConfig`` as ``cli.py check`` builds it from its defaults,
    with the sizes the configuration file states."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    return EngineConfig(
        batch=config["batch"], queue_capacity=config["queue_capacity"],
        seen_capacity=config["seen_capacity"], record_trace=True,
        pipeline="auto", progress_interval_seconds=60.0,
        # One snapshot at the start level (and the trivial one at the
        # root); the directory goes with the run.
        checkpoint_dir=os.path.join(ctx.tmp, "states"),
        checkpoint_every=ctx.cell["start_level"],
        checkpoint_interval_seconds=0.0)


def run(ctx) -> dict:
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.schema import (decode_state, state_width,
                                            unflatten_state)
    from raft_tla_tpu.utils.cfg import load_config

    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    start = int(cell["start_level"])
    pinned = lib.load_pinned(config["pinned"])
    setup = load_config(lib.write_cfg(config, ctx.tmp),
                        n_msg_slots=config["n_msg_slots"])
    row_bytes = state_width(setup.dims)
    ledger.exact("row width in bytes", row_bytes, config["shapes"]["row_bytes"])
    ledger.exact("action instances", setup.dims.n_instances,
                 config["shapes"]["action_instances"])

    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start     # imports, the look for a chip, the cfg
    eng = make_engine(setup, engine_config(config, ctx))
    make_engine_s = time.perf_counter() - t0

    # -- set-up: root -> start level, with the engine under test ---------
    walk_events = os.path.join(ctx.tmp, "walk.jsonl")
    eng.config.events_out = walk_events
    eng.config.max_diameter, eng.config.max_seconds = start, None
    t0 = time.perf_counter()
    walk = eng.run(initial_states(setup))
    walk_s = time.perf_counter() - t0
    warmup_s = walk.phases.get("warmup", 0.0)
    ledger.exact("walk stop reason", walk.stop_reason, "diameter_budget")
    ledger.exact("pipeline", walk.pipeline, "v2")
    lib.compare_levels(ledger, lib.level_rows(lib.read_events(walk_events)),
                       pinned, range(start + 1), "set-up")
    t0 = time.perf_counter()
    ck = ckpt_mod.load(ckpt_mod.latest(eng.config.checkpoint_dir))
    load_s = time.perf_counter() - t0
    ledger.exact("snapshot level", ck.diameter, start)
    # The snapshot carries the walk's seconds, which a resumed run counts
    # against its duration budget; the window's budget is its own.
    ck = dataclasses.replace(ck, wall_seconds=0.0)
    # One resume that stops after its first one-batch chunk call (a
    # distinct-states budget of 0 under a duration budget it cannot
    # reach): every program the restore uses (table rebuild, frontier
    # upload) and the chunk program as a resumed run calls it compile or
    # load here, not in the window.
    eng.config.events_out = None
    eng.config.max_diameter, eng.config.max_seconds = None, 1e6
    eng.config.exit_conditions = (("distinct", 0),)
    t0 = time.perf_counter()
    warm = eng.run(resume=ck)
    warm_resume_s = time.perf_counter() - t0
    eng.config.exit_conditions = ()
    ledger.exact("warm resume stop reason", warm.stop_reason,
                 "distinct_budget")
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"walk to level {start} {walk_s:.2f}s (warmup {warmup_s:.2f}s, "
          f"checkpoint {walk.phases.get('checkpoint', 0.0):.2f}s, "
          f"trace_flush {walk.phases.get('trace_flush', 0.0):.2f}s), "
          f"{walk.distinct} distinct, snapshot load {load_s:.2f}s, warm "
          f"resume {warm_resume_s:.2f}s", flush=True)

    # -- the window -------------------------------------------------------
    win_events = os.path.join(ctx.tmp, "window.jsonl")
    eng.config.events_out = win_events
    eng.config.max_seconds = float(ctx.args.seconds)
    with lib.traced(ctx):
        t_win0 = time.perf_counter()
        res = eng.run(resume=ck)
        # run() returns only after the last chunk's statistics are on the
        # host, so the clock stops on finished work.
        t_win1 = time.perf_counter()
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    new_distinct = res.distinct - ck.distinct
    new_generated = res.generated - ck.generated
    parents = int(eng.coverage.expanded)
    events = lib.read_events(win_events)
    # What of the device's pools the window really filled: the gauges are
    # the engine's own, set from the last chunk call's statistics.
    cur_rows = (lib.level_rows(events).get(res.diameter)
                or (len(ck.frontier),))[0]
    next_rows = int(eng.metrics.gauge_value("engine/next_count"))
    fill = {"seen_load_pct": 100.0 * res.distinct / config["seen_capacity"],
            "queue_fill_pct": 100.0 * max(cur_rows, next_rows)
            / config["queue_capacity"]}
    print(f"fill: {res.distinct} keys of {config['seen_capacity']} "
          f"({fill['seen_load_pct']:.1f} %); level {res.diameter}'s "
          f"{cur_rows} rows and {next_rows} rows of level "
          f"{res.diameter + 1} in queues of {config['queue_capacity']} "
          f"({fill['queue_fill_pct']:.1f} %)", flush=True)
    print(f"window: {wall:.3f}s wall, {new_distinct} new distinct, "
          f"{new_generated} generated, {parents} parents expanded, levels "
          f"{ck.diameter}..{res.diameter}, stop {res.stop_reason}",
          flush=True)

    # -- correct: the window itself --------------------------------------
    crossed = sorted(lv for lv in lib.level_rows(events) if lv > start)
    ledger.exact("window stop reason", res.stop_reason, "duration_budget")
    ledger.exact("window pipeline", res.pipeline, "v2")
    ledger.true("window admitted new states", new_distinct > 0,
                str(new_distinct))
    lib.compare_levels(ledger, lib.level_rows(events), pinned, crossed,
                       "window")
    ledger.exact("levels crossed are consecutive from the start level",
                 crossed, list(range(start + 1, res.diameter + 1)))
    comp = lib.check_window_log(ctx, events, t_win0, t_win1)

    # -- correct: the seeded sample, engine against reference -------------
    sample_check(ctx, eng, setup, ck, decode_state, unflatten_state)

    return {
        "end_to_end": {"setup_s": setup_s,
                       "distinct_per_s": new_distinct / wall},
        "window_wall_s": wall, "phases": dict(res.phases),
        "events": events, "parents_expanded": parents, "counters": fill,
        "new_distinct": new_distinct, "new_generated": new_generated,
        "batch": config["batch"], "row_bytes": row_bytes,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "walk": walk_s, "warm_resume": warm_resume_s},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
    }


def sample_check(ctx, eng, setup, ck, decode_state, unflatten_state):
    """``sample`` start-level states, drawn by the seed, as roots through
    one level of the engine and of the plain reference."""
    ledger = ctx.ledger
    n = min(int(ctx.cell["sample"]), len(ck.frontier))
    rows = random.Random(ctx.args.seed).sample(range(len(ck.frontier)), n)
    states = [decode_state(unflatten_state(ck.frontier[i], setup.dims),
                           setup.dims) for i in sorted(rows)]
    eng.config.events_out = None
    eng.config.max_seconds, eng.config.max_diameter = None, 1
    t0 = time.perf_counter()
    got = eng.run(states)
    t_eng = time.perf_counter() - t0

    ref = lib.reference(ctx.config)
    t0 = time.perf_counter()
    roots = [lib.to_reference_state(s, ref.pystate) for s in states]
    seen = set(roots)
    generated = frontier = 0
    for r in roots:
        succ = ref.oracle.successors(r, ref.dims)
        generated += len(succ)
        for _a, t in succ:
            if t not in seen:
                seen.add(t)
                frontier += bool(ref.constraint(t, ref.dims))
    t_ref = time.perf_counter() - t0
    print(f"sample: {n} states of level {ck.diameter}; engine {t_eng:.2f}s, "
          f"reference {t_ref:.2f}s", flush=True)
    ledger.exact("sample roots enqueued", got.levels[0], len(roots))
    ledger.exact("sample (frontier, distinct, generated) engine == reference",
                 (got.levels[-1], got.distinct, got.generated),
                 (frontier, len(seen), generated))
