"""Traffic kind ``verdict_loop``: whole checks, root to counterexample,
back to back on one warm engine.

Set-up builds the engine from the configuration's own cfg directives (the
calls ``cli.py check`` makes), and runs one untimed check, which compiles.
The window runs whole checks until ``--seconds`` have passed, at least
``min_verdicts``: ``run()`` from the root, the violation found, its trace
replayed from the trace store, and every step of it found legal by the
plain reference.  Every verdict is checked, so ``--seed`` orders nothing.

Mix parameters (``benchmark/traffic/<mix>.json``):
  min_verdicts  least number of whole checks in a window
  invariant     the invariant that must be reported violated
  depth         the counterexample's depth (a shortest one, by BFS)
  forbidden_events  run events that may not occur inside the window
"""

from __future__ import annotations

import os
import statistics
import time

import bench_lib as lib


def run(ctx) -> dict:
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.utils.cfg import load_config

    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    pinned = lib.load_pinned(config["pinned"])
    setup = load_config(lib.write_cfg(config, ctx.tmp))
    ledger.exact("row width in bytes", state_width(setup.dims),
                 config["shapes"]["row_bytes"])
    ledger.exact("cfg directives give the sizes the file states",
                 (setup.backend.get("BATCH"),
                  setup.backend.get("QUEUE_CAPACITY"),
                  setup.backend.get("SEEN_CAPACITY")),
                 (config["batch"], config["queue_capacity"],
                  config["seen_capacity"]))
    ref = lib.reference(config)

    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start     # imports, the look for a chip, the cfg
    eng = make_engine(setup)
    make_engine_s = time.perf_counter() - t0
    roots = initial_states(setup)

    # -- set-up: one untimed check (compiles every program) ---------------
    eng.config.events_out = os.path.join(ctx.tmp, "first.jsonl")
    t0 = time.perf_counter()
    first = eng.run(roots)
    steps = eng.replay(first.violation.fingerprint) if first.violation else []
    first_s = time.perf_counter() - t0
    warmup_s = first.phases.get("warmup", 0.0)
    verdict_ok(ledger, "set-up", cell, first, steps, trace_legal(steps, ref),
               eng, lib.level_rows(lib.read_events(eng.config.events_out)),
               pinned, ref)
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"first check "
          f"{first_s:.2f}s (warmup {warmup_s:.2f}s)", flush=True)

    # -- the window -------------------------------------------------------
    win_events = os.path.join(ctx.tmp, "window.jsonl")
    eng.config.events_out = win_events
    walls, results, phases = [], [], {}
    parents = 0
    with lib.traced(ctx):
        t_win0 = time.perf_counter()
        while (time.perf_counter() - t_win0 < ctx.args.seconds
               or len(walls) < int(cell["min_verdicts"])):
            t0 = time.perf_counter()
            res = eng.run(roots)
            steps = (eng.replay(res.violation.fingerprint)
                     if res.violation else [])
            legal = trace_legal(steps, ref)
            walls.append(time.perf_counter() - t0)
            results.append((res, steps, legal))
            parents += int(eng.coverage.expanded)
            for k, v in res.phases.items():
                phases[k] = phases.get(k, 0.0) + v
        t_win1 = time.perf_counter()
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    print(f"window: {wall:.3f}s wall, {len(walls)} verdicts, median "
          f"{statistics.median(walls):.4f}s, min {min(walls):.4f}s, max "
          f"{max(walls):.4f}s", flush=True)

    # -- correct: every verdict of the window ------------------------------
    events = lib.read_events(win_events)
    runs = split_runs(events)
    ledger.exact("runs in the window's event log", len(runs), len(results))
    for i, ((res, steps, legal), evs) in enumerate(zip(results, runs)):
        verdict_ok(ledger, f"verdict {i}", cell, res, steps, legal, eng,
                   lib.level_rows(evs), pinned, ref)
    comp = lib.check_window_log(ctx, events, t_win0, t_win1)

    return {
        # Over all the work and all the time of the window: its wall
        # divided by the whole checks it finished.
        "end_to_end": {"setup_s": setup_s, "verdict_s": wall / len(walls)},
        "window_wall_s": wall, "phases": phases, "events": events,
        "parents_expanded": parents, "batch": config["batch"],
        "row_bytes": state_width(setup.dims),
        "verdict_walls": walls,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "first_check": first_s},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
    }


def split_runs(events: list) -> list:
    runs = []
    for e in events:
        if e["event"] == "run_start":
            runs.append([])
        if runs:
            runs[-1].append(e)
    return runs


def trace_legal(steps, ref) -> bool:
    """The replayed trace starts at the reference's own initial state and
    every step is a transition the reference allows."""
    if not steps or steps[0][0] != -1:
        return False
    states = [lib.to_reference_state(s, ref.pystate) for _a, s in steps]
    if states[0] != ref.pystate.init_state(ref.dims):
        return False
    return all(nxt in ref.oracle.successor_set(prev, ref.dims)
               for prev, nxt in zip(states, states[1:]))


def verdict_ok(ledger, what, cell, res, steps, legal, eng, levels, pinned,
               ref) -> None:
    depth = int(cell["depth"])
    ledger.exact(f"{what}: invariant reported violated",
                 res.violation.invariant if res.violation else None,
                 cell["invariant"])
    ledger.exact(f"{what}: counterexample depth", len(steps) - 1, depth)
    ledger.true(f"{what}: trace legal under the reference, from its root",
                legal)
    ledger.true(f"{what}: last state violates the invariant",
                bool(steps) and not ref.rd.no_leader_py(
                    lib.to_reference_state(steps[-1][1], ref.pystate),
                    ref.dims))
    ledger.exact(f"{what}: trace store", type(eng.trace).__name__,
                 "NativeTraceStore")
    ledger.exact(f"{what}: pipeline", res.pipeline, "v2")
    # The violation sits in level `depth`, so levels below it are whole.
    lib.compare_levels(ledger, levels, pinned, range(depth), what)
