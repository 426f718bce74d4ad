"""Traffic kind ``smoke_loop``: whole budgeted smoke checks, back to back on
one warm engine, each from a fresh draw of ``SmokeInit`` roots.

Upstream's smoke test (``tlc -config Smokeraft.cfg Smokeraft.tla``) is all
start and stop: 512 random roots, breadth-first until the cfg's own
``StopAfter`` budget (one second) trips.  A check here is what ``python -m
raft_tla_tpu check configs/Smokeraft.cfg --seed s`` does after the cfg is
loaded: ``initial_states(setup, seed=s)`` and ``eng.run(roots)`` under the
cfg's own ``max_seconds`` and ``max_diameter``, nothing else passed.

Set-up writes the configuration's cfg AND its companion module (the
budget and ``k`` are read from the module) into the run's directory,
after holding both to the checkout's ``configs/`` files letter for letter
(a checkout without them does not have the deployment: exit 4 at once,
nothing run), then ``load_config``, ``make_engine`` (sizes from the cfg's
own directives), one untimed check of the first root seed (compiles), a
walk of the same roots to that check's last completed level for the
snapshot the sample is drawn from, and one warm check.  The window runs
whole checks for ``--seconds``, at least ``min_checks``, root seeds cycled
from the first in every window.  ``distinct_per_s`` is the distinct states
the window's checks admitted (their roots included) over the window's
wall: what a second of smoke testing covers, with the draw of the roots,
the ingest, the ramp, the overshoot of the budget and the run's end all
inside it.  ``--seed`` draws the samples, not the roots.

``correct``, every comparison exact and none on the clock but (c)'s two:
(a) TPU; row width, instances, families; the loaded setup reads smoke,
    k, the two budgets, no deadlock check, the invariants, no state
    constraint; ``BFSEngine``, v2, the batch and pools stated;
(b) for each root seed used, the program's roots pass the reference's
    ``is_smoke_init`` and equal, as a set, ``product`` of the draw pinned
    for that seed;
(c) every check: ``stop_reason`` ``duration_budget``, wall at least the
    budget, no violation, none of the forbidden events, no compile or
    cache load of ``chunk``/``ingest`` in the window; distinct reported =
    what the loop counted as new in its ingest and chunk calls, the roots
    all among them; the last check's trace store holds as many records;
(d) every level a check COMPLETED equals the pin for its root seed in
    frontier, distinct, generated and generated by family; a completed
    level the pin lacks fails; the level the budget cut stays under the
    pin's, where the pin has it;
(e) ``sample`` states drawn by ``--seed`` from the set-up check's last
    completed frontier through one level of the engine and of the
    reference: the counts agree and every value lies in the reference's
    ``TypeOK``;
(f) ``replayed`` states drawn from those the window's last check admitted
    (the store holds one check), replayed from the native store: each
    path starts at one of that check's roots, every step is in the
    reference's ``successor_set``.

Mix parameters (``benchmark/traffic/<mix>.json``):
  min_checks        least number of whole checks in a window
  sample            states of (e)
  replayed          states of (f)
  forbidden_events  run events that may not occur inside the window
Configuration keys beyond the README's: ``module_name``/``module_text``,
``repo_files``, ``smoke_k``, ``roots``, ``budget``, ``max_log``,
``root_seeds``.
"""

from __future__ import annotations

import json
import os
import random
import statistics
import sys
import time

import numpy as np

import bench_lib as lib

NO_DEPLOYMENT = 4       # exit code: this checkout lacks the deployment


def load_pinned(name: str) -> dict:
    """{root seed: {"draw": ..., level: (frontier, distinct, generated,
    {family: generated})}}."""
    out = {}
    with open(os.path.join(lib.BENCH_DIR, "pinned", name + ".jsonl"),
              encoding="utf-8") as f:
        for r in map(json.loads, filter(str.strip, f)):
            seed = out.setdefault(int(r["root_seed"]), {})
            seed[int(r["level"])] = (
                int(r["frontier"]), int(r["distinct"]), int(r["generated"]),
                {k: int(v) for k, v in r["generated_by_family"].items()})
            if "draw" in r:
                seed["draw"] = r["draw"]
    return out


def write_deployment(config: dict, directory: str) -> str:
    """The cfg and its companion module, as the configuration states them,
    where ``load_config`` reads both; each held to the checkout's own file
    first.  A checkout without them exits ``NO_DEPLOYMENT``."""
    for key, name, text in (
            ("cfg", config["cfg_name"], config["cfg_text"]),
            ("module", config["module_name"], config["module_text"])):
        body = "\n".join(text) + "\n"
        repo_file = os.path.join(lib.ROOT, config["repo_files"][key])
        if not os.path.isfile(repo_file):
            print(f"benchmark: this checkout has no "
                  f"{config['repo_files'][key]}: the deployment "
                  f"{config['name']!r} is not in it; nothing was run",
                  file=sys.stderr, flush=True)
            sys.exit(NO_DEPLOYMENT)
        with open(repo_file, encoding="utf-8") as f:
            if f.read() != body:
                print(f"benchmark: {config['repo_files'][key]} differs from "
                      f"the configuration's text; nothing was run",
                      file=sys.stderr, flush=True)
                sys.exit(NO_DEPLOYMENT)
        with open(os.path.join(directory, name), "w", encoding="utf-8") as f:
            f.write(body)
    return os.path.join(directory, config["cfg_name"])


def reference_of(config: dict):
    """``lib.reference`` at the configuration's own capacities, with the
    reference's ``smoke`` module beside the others."""
    ref = lib.reference(config)
    from reference import smoke
    ref.smoke = smoke
    ref.dims = ref.rd.RaftDims(
        n_servers=len(config["constants"]["Server"]),
        n_values=len(config["constants"]["Value"]),
        max_log=config["max_log"], n_msg_slots=config["n_msg_slots"])
    return ref


# A window's event log cut into its runs, and a run's ``level_complete``
# events as ``load_pinned`` rows: as the kinds that had them first.
split_runs = lib.load_module("traffic", "verdict_loop").split_runs


def level_rows(events: list) -> dict:
    return lib.load_module("traffic", "rooted_window").level_rows(events, {})


def one_check(eng, setup, seed: int, initial_states) -> dict:
    """A whole check: the draw of the roots and the run, with what the
    loop counted as new beside the result."""
    base = int(eng.metrics.counter_value("engine/distinct"))
    t0 = time.perf_counter()
    roots = initial_states(setup, seed=seed)
    t1 = time.perf_counter()
    res = eng.run(roots)
    t2 = time.perf_counter()
    return {"seed": seed, "roots": roots, "res": res, "roots_s": t1 - t0,
            "wall_s": t2 - t0, "parents": int(eng.coverage.expanded),
            "counted_new":
                int(eng.metrics.counter_value("engine/distinct")) - base}


def window(eng, setup, seeds: list, seconds: float, min_checks: int,
           initial_states):
    """Whole checks back to back for ``seconds``, at least ``min_checks``,
    the root seeds cycled from the first: (checks, start, end)."""
    checks = []
    t0 = time.perf_counter()
    while (time.perf_counter() - t0 < seconds or len(checks) < min_checks):
        checks.append(one_check(eng, setup, seeds[len(checks) % len(seeds)],
                                initial_states))
    return checks, t0, time.perf_counter()


def check_ok(ctx, what: str, check: dict, levels: dict, pins: dict) -> None:
    """(c) and (d) for one check."""
    ledger, config = ctx.ledger, ctx.config
    res, budget = check["res"], config["budget"]["max_seconds"]
    ledger.exact(f"{what}: stop reason", res.stop_reason, "duration_budget")
    ledger.true(f"{what}: wall on the engine's clock at least the budget",
                res.wall_seconds >= budget, f"{res.wall_seconds:.4f}s")
    ledger.exact(f"{what}: violation", res.violation, None)
    ledger.exact(f"{what}: pipeline", res.pipeline, "v2")
    ledger.exact(f"{what}: roots enqueued",
                 res.levels[0] if res.levels else None, config["roots"])
    ledger.exact(f"{what}: distinct reported = new counted in its ingest "
                 f"and chunk calls", res.distinct, check["counted_new"])
    pin = pins.get(check["seed"]) or {}
    done = sorted(levels)
    ledger.exact(f"{what}: completed levels are 0..diameter", done,
                 list(range(res.diameter + 1)))
    for lv in done:
        ledger.exact(f"{what}: root seed {check['seed']} level {lv} "
                     f"(frontier, distinct, generated, by family)",
                     levels[lv], pin.get(lv))
    cut = pin.get(res.diameter + 1)
    if cut is not None:
        # The level the budget cut: under the pin's, family by family.
        by_family = {k: int(res.action_counts.get(k, 0)) for k in cut[3]}
        ledger.true(
            f"{what}: level {res.diameter + 1}, cut by the budget, stays "
            f"under the pin's", res.distinct <= cut[1]
            and res.generated <= cut[2]
            and all(by_family[k] <= cut[3][k] for k in cut[3]),
            f"{res.distinct} <= {cut[1]}, {res.generated} <= {cut[2]}")


def run(ctx) -> dict:
    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    cfg_path = write_deployment(config, ctx.tmp)

    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.schema import (decode_state, state_width,
                                            unflatten_state)
    from raft_tla_tpu.utils.cfg import load_config

    pins = load_pinned(config["pinned"])
    seeds = [int(s) for s in config["root_seeds"]]
    setup = load_config(cfg_path)
    dims, shapes, budget = setup.dims, config["shapes"], config["budget"]
    row_bytes = state_width(dims)
    ledger.exact("row width in bytes", row_bytes, shapes["row_bytes"])
    ledger.exact("action instances", dims.n_instances,
                 shapes["action_instances"])
    ledger.exact("action families", dict(zip(dims.family_names,
                                             dims.family_sizes)),
                 shapes["families"])
    ledger.exact("the loaded setup: smoke, k, budgets, deadlock check, "
                 "invariants, state constraints",
                 (setup.smoke, setup.smoke_k, setup.max_seconds,
                  setup.max_diameter, setup.check_deadlock,
                  list(setup.invariants), list(setup.constraints),
                  tuple(setup.exit_conditions)),
                 (True, config["smoke_k"], budget["max_seconds"],
                  budget["max_diameter"], config["check_deadlock"],
                  config["invariants"], [], ()))
    ledger.exact("cfg directives give the sizes the file states",
                 tuple(setup.backend.get(k) for k in (
                     "BATCH", "QUEUE_CAPACITY", "SEEN_CAPACITY", "MAX_LOG",
                     "N_MSG_SLOTS")),
                 (config["batch"], config["queue_capacity"],
                  config["seen_capacity"], config["max_log"],
                  config["n_msg_slots"]))
    ref = reference_of(config)

    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start
    eng = make_engine(setup)
    make_engine_s = time.perf_counter() - t0
    ledger.exact("engine class", type(eng).__name__, "BFSEngine")
    ledger.exact("engine's budgets are the cfg's own",
                 (eng.config.max_seconds, eng.config.max_diameter),
                 (budget["max_seconds"], budget["max_diameter"]))

    # -- set-up: one untimed check (compiles), the sample's snapshot, one
    # warm check -----------------------------------------------------------
    eng.config.events_out = os.path.join(ctx.tmp, "first.jsonl")
    first = one_check(eng, setup, seeds[0], initial_states)
    first_s = first["wall_s"]
    warmup_s = first["res"].phases.get("warmup", 0.0)
    check_ok(ctx, "set-up check", first,
             level_rows(lib.read_events(eng.config.events_out)), pins)
    eng.config.events_out = None
    t0 = time.perf_counter()
    ck = frontier_snapshot(ctx, eng, first, ckpt_mod)
    snapshot_s = time.perf_counter() - t0
    eng.config.events_out = os.path.join(ctx.tmp, "warm.jsonl")
    t_warm0 = time.perf_counter()
    warm = one_check(eng, setup, seeds[0], initial_states)
    t_warm1 = time.perf_counter()
    check_ok(ctx, "warm check", warm,
             level_rows(lib.read_events(eng.config.events_out)), pins)
    ledger.exact("compiles of the engine's programs inside the warm check",
                 ctx.compiles.between(t_warm0, t_warm1,
                                      lib.ENGINE_PROGRAMS)["of_programs"],
                 [])
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"first check {first_s:.2f}s (warmup {warmup_s:.2f}s, "
          f"{first['res'].distinct} distinct, diameter "
          f"{first['res'].diameter}), the sample's snapshot "
          f"{snapshot_s:.2f}s, warm check {warm['wall_s']:.2f}s "
          f"({warm['res'].distinct} distinct)", flush=True)

    # -- the window ---------------------------------------------------------
    win_events = os.path.join(ctx.tmp, "window.jsonl")
    eng.config.events_out = win_events
    with lib.traced(ctx):
        checks, t_win0, t_win1 = window(
            eng, setup, seeds, ctx.args.seconds, int(cell["min_checks"]),
            initial_states)
    phases = {}
    for check in checks:
        for k, v in check["res"].phases.items():
            phases[k] = phases.get(k, 0.0) + v
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    # Read before the sample's run moves the gauge.
    next_rows = int(eng.metrics.gauge_value("engine/next_count"))
    distinct = sum(c["res"].distinct for c in checks)
    generated = sum(c["res"].generated for c in checks)
    parents = sum(c["parents"] for c in checks)
    walls = [c["wall_s"] for c in checks]
    print(f"window: {wall:.3f}s wall, {len(checks)} checks of root seeds "
          f"{[c['seed'] for c in checks]}, {distinct} distinct admitted, "
          f"{generated} generated, {parents} parents expanded; a check "
          f"median {statistics.median(walls):.4f}s (the draw of its roots "
          f"{statistics.median(c['roots_s'] for c in checks):.4f}s), min "
          f"{min(walls):.4f}s, max {max(walls):.4f}s", flush=True)
    print("checks (root seed: distinct, diameter, engine wall): "
          + ", ".join(f"{c['seed']}: {c['res'].distinct} "
                      f"{c['res'].diameter} {c['res'].wall_seconds:.3f}"
                      for c in checks), flush=True)

    # -- correct: the replays first (the store holds the last check), then
    # every check, the roots, the sample ------------------------------------
    last = checks[-1]
    replay_check(ctx, eng, last, ref)
    events = lib.read_events(win_events)
    runs = split_runs(events)
    ledger.exact("runs in the window's event log", len(runs), len(checks))
    for i, (check, evs) in enumerate(zip(checks, runs)):
        check_ok(ctx, f"check {i}", check, level_rows(evs), pins)
    comp = lib.check_window_log(ctx, events, t_win0, t_win1)
    ledger.exact("'violation' events in the window",
                 sum(e["event"] == "violation" for e in events), 0)
    roots_check(ctx, [first] + checks, pins, ref)
    sample_check(ctx, eng, setup, ck, ref, decode_state, unflatten_state)

    # The fill when the window closed: the last check's keys, and the
    # fuller of its two live queues (the level it was expanding, the level
    # it was building).
    res = last["res"]
    fill = {"seen_load_pct": 100.0 * res.distinct / config["seen_capacity"],
            "queue_fill_pct": 100.0 * max(res.levels[-1], next_rows)
            / config["queue_capacity"]}
    print(f"fill: {res.distinct} keys of {config['seen_capacity']} "
          f"({fill['seen_load_pct']:.1f} %); level {res.diameter}'s "
          f"{res.levels[-1]} rows and {next_rows} rows of level "
          f"{res.diameter + 1} in queues of {config['queue_capacity']} "
          f"({fill['queue_fill_pct']:.1f} %)", flush=True)

    roots_in = sum(c["res"].levels[0] for c in checks)
    return {
        "kind": "smoke_loop",
        "end_to_end": {"setup_s": setup_s, "distinct_per_s": distinct / wall},
        "window_wall_s": wall, "phases": phases, "events": events,
        "parents_expanded": parents, "counters": fill,
        # What the chunk calls admitted and generated: the roots came in
        # through ingest.
        "new_distinct": distinct - roots_in, "new_generated": generated,
        "checks": len(checks), "batch": config["batch"],
        "row_bytes": row_bytes,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "first_check": first_s},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
    }


def frontier_snapshot(ctx, eng, first: dict, ckpt_mod):
    """The set-up check's last completed level as the engine's own
    snapshot: the same roots walked to that level with no clock, the
    level-boundary image the engine writes there.  The engine's own
    settings are put back afterwards."""
    ledger = ctx.ledger
    level = first["res"].diameter
    keep = (eng.config.max_seconds, eng.config.max_diameter,
            eng.config.checkpoint_dir, eng.config.checkpoint_every,
            eng.config.checkpoint_interval_seconds)
    eng.config.max_seconds, eng.config.max_diameter = None, level
    eng.config.checkpoint_dir = os.path.join(ctx.tmp, "states")
    eng.config.checkpoint_every = max(level, 1)
    eng.config.checkpoint_interval_seconds = 0.0
    try:
        walk = eng.run(first["roots"])
    finally:
        (eng.config.max_seconds, eng.config.max_diameter,
         eng.config.checkpoint_dir, eng.config.checkpoint_every,
         eng.config.checkpoint_interval_seconds) = keep
    ledger.exact("the sample's walk: stop reason", walk.stop_reason,
                 "diameter_budget")
    snapshot = ckpt_mod.latest(os.path.join(ctx.tmp, "states"))
    ck = ckpt_mod.load(snapshot) if snapshot else None
    ledger.exact("the sample's snapshot: level, rows",
                 (ck.diameter, len(ck.frontier)) if ck else None,
                 (level, first["res"].levels[level]))
    return ck


def roots_check(ctx, checks: list, pins: dict, ref) -> None:
    """(b): each root seed used, once."""
    ledger, config = ctx.ledger, ctx.config
    done = set()
    for check in checks:
        seed = check["seed"]
        if seed in done:
            continue
        done.add(seed)
        states = [lib.to_reference_state(s, ref.pystate)
                  for s in check["roots"]]
        ledger.exact(f"root seed {seed}: what keeps its roots from being a "
                     f"SmokeInit set", ref.smoke.is_smoke_init(
                         states, config["smoke_k"], ref.dims), [])
        draw = (pins.get(seed) or {}).get("draw")
        ledger.true(f"root seed {seed}: roots equal, as a set, the product "
                    f"of the pinned draw", draw is not None and set(states)
                    == set(ref.smoke.product(ref.smoke.from_json(draw))))


def replay_check(ctx, eng, check: dict, ref) -> None:
    """(f): states the window's last check admitted, drawn by the seed
    from the trace store's records, replayed from that store to one of
    that check's roots."""
    ledger, res = ctx.ledger, check["res"]
    ledger.exact("trace store", type(eng.trace).__name__,
                 "NativeTraceStore")
    fps = np.asarray(eng.trace.export()[0], np.uint64)
    ledger.exact("last check: records in the trace store", len(fps),
                 res.distinct)
    rng = np.random.default_rng(ctx.args.seed)
    want = int(ctx.cell["replayed"])
    picks = [int(fp) for fp in rng.permutation(np.unique(fps))[:want]]
    ledger.exact("admitted states drawn for the replay", len(picks), want)
    root_states = {lib.to_reference_state(s, ref.pystate)
                   for s in check["roots"]}
    t0 = time.perf_counter()
    depths, rooted, legal = [], 0, 0
    for fp in picks:
        steps = eng.replay(fp)
        states = [lib.to_reference_state(s, ref.pystate) for _a, s in steps]
        depths.append(len(steps) - 1)
        rooted += steps[0][0] == -1 and states[0] in root_states
        legal += all(t in ref.oracle.successor_set(s, ref.dims)
                     for s, t in zip(states, states[1:]))
    print(f"replay: {len(picks)} states of root seed {check['seed']}'s "
          f"check in {time.perf_counter() - t0:.2f}s, steps "
          f"{sorted(set(depths))}", flush=True)
    ledger.true("replayed paths' steps are levels the check built",
                all(0 <= d <= res.diameter + 1 for d in depths),
                str(sorted(set(depths))))
    ledger.exact("replayed paths that start at one of the check's roots",
                 rooted, len(picks))
    ledger.exact("replayed paths legal under the reference, every step",
                 legal, len(picks))


def sample_check(ctx, eng, setup, ck, ref, decode_state, unflatten_state):
    """(e): ``sample`` states of the set-up check's last completed
    frontier, drawn by the seed, as roots through one level of the engine
    and of the plain reference."""
    ledger = ctx.ledger
    if ck is None:
        return
    n = min(int(ctx.cell["sample"]), len(ck.frontier))
    rows = random.Random(ctx.args.seed).sample(range(len(ck.frontier)), n)
    states = [decode_state(unflatten_state(ck.frontier[i], setup.dims),
                           setup.dims) for i in sorted(rows)]
    keep = (eng.config.max_seconds, eng.config.max_diameter,
            eng.config.events_out)
    eng.config.max_seconds, eng.config.max_diameter = None, 1
    eng.config.events_out = None
    t0 = time.perf_counter()
    try:
        got = eng.run(states)
    finally:
        (eng.config.max_seconds, eng.config.max_diameter,
         eng.config.events_out) = keep
    t_eng = time.perf_counter() - t0

    t0 = time.perf_counter()
    roots = [lib.to_reference_state(s, ref.pystate) for s in states]
    seen = set(roots)
    names = list(ctx.config["shapes"]["families"])
    by_family = dict.fromkeys(names, 0)
    frontier = 0
    bad_values = sum(not ref.smoke.type_ok(r, ref.dims) for r in roots)
    for r in roots:
        for (family, _params), t in ref.oracle.successors(r, ref.dims):
            by_family[names[family]] += 1
            if t not in seen:
                seen.add(t)
                frontier += 1
                bad_values += not ref.smoke.type_ok(t, ref.dims)
    t_ref = time.perf_counter() - t0
    print(f"sample: {len(states)} states of level {ck.diameter}; engine "
          f"{t_eng:.2f}s, reference {t_ref:.2f}s", flush=True)
    ledger.exact("sample roots enqueued", got.levels[0], len(roots))
    ledger.exact("sample (frontier, distinct, generated) engine == reference",
                 (got.levels[-1], got.distinct, got.generated),
                 (frontier, len(seen), sum(by_family.values())))
    ledger.exact("sample generated by family engine == reference",
                 {k: int(got.action_counts.get(k, 0)) for k in names},
                 by_family)
    ledger.exact("sampled states and successors outside the reference's "
                 "TypeOK", bad_values, 0)
    ledger.exact("sample run's violation", got.violation, None)
