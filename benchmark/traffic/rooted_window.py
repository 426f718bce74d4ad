"""Traffic kind ``rooted_window``: ``bfs_window``'s time-bounded window of
exhaustive breadth-first search, from roots the mix names and not from the
spec's ``Init``.

A mechanism that lives past the first election (a leader's log, a
membership change, a commit) is out of reach of a window that starts a
few levels from ``Init``: those levels hold almost no leader.  This kind
starts the search at states the plain reference shows reachable from
``Init``: the mix's ``roots`` names a function of ``benchmark/`` modules
(``<module>:<function>``) that makes them, each with its path from
``Init``, and levels are counted from them.  Beside the function its
module gives ``reference_dims(config)`` and ``reference_bounds(config)``
(the reference's own dims and constraint constants from the numbers the
configuration file states), ``FAMILY_NAMES`` (the action families in grid
order), ``path_is_legal(root, dims)`` and ``values_ok(state, dims)``.  A
new cell from other roots is a mix and such a module, no code here.

Set-up and window are ``bfs_window``'s call for call, with
``eng.run(roots)`` where that kind runs ``initial_states(setup)``: the
engine as ``cli.py check`` builds it (``bfs_window.engine_config``), the
walk roots -> ``start_level``, the engine's own snapshot, one warm resume,
then the window ``engine.run(resume=snapshot)`` for ``--seconds``.  The
pinned profile (``scripts/pin_rooted_profile.py``) is the reference's from
the same roots and carries generated by action family.

``correct``, all exact:
(a) TPU; row width, action instances, family names and sizes, dims class
    and engine class the configuration states; pipeline v2 in the walk
    and in the window;
(b) every root's path is legal under the reference from its ``Init``, the
    root lies inside the constraint, and the engine's packed row of it
    decodes to it;
(c) every level of the walk and every boundary the window crosses equals
    the pin in frontier, distinct, generated and generated by family (the
    engine's per-family statistics: ``level_complete.generated_by_family``
    on top of the snapshot's ``action_counts``; a program whose events do
    not carry them is held in the three counts level by level, and by
    family where the walk ends, through its result's ``action_counts``);
(d) the window's own generated by family (the result's ``action_counts``
    less the snapshot's, equal to ``run_end.generated_by_family`` where
    that is emitted) holds each family of the mix's ``families_in_window``
    above 0 and sums to its generated;
(e) the window stopped on its duration budget at the batch asked for,
    with none of the forbidden events and no compile of ``chunk`` or
    ``ingest``;
(f) ``sample`` seeded start-level states through one level of the engine
    and of the reference: frontier, distinct, generated and generated by
    family agree, and every value they and their successors carry is in
    the reference's ``TypeOK`` domain (the engine reported no violation);
(g) ``replayed`` seeded states admitted in the window, replayed from the
    native trace store: each path starts at one of the roots and every
    step is in the reference's ``successor_set``.

Mix parameters (``benchmark/traffic/<mix>.json``): ``bfs_window``'s
(``start_level``, ``sample``, ``forbidden_events``) and
  roots               ``<module>:<function>`` under ``benchmark/``
  replayed            admitted states replayed from the trace store (g)
  families_in_window  families the window itself must generate (d)
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time
import types

import numpy as np

import bench_lib as lib


def reference_of(ctx) -> types.SimpleNamespace:
    """``lib.reference`` with the dims, constraint and roots of the module
    the mix names: ``.mod`` too, and ``.roots`` (``Root`` triples)."""
    ref = lib.reference(ctx.config)
    modname, fn = ctx.cell["roots"].split(":")
    ref.mod = mod = importlib.import_module(modname)
    ref.dims = mod.reference_dims(ctx.config)
    ref.constraint = ref.rd.constraint_py(mod.reference_bounds(ctx.config))
    ref.roots = getattr(mod, fn)(ref.dims)
    return ref


def load_pinned(name: str) -> dict:
    """{level: (frontier, distinct, generated, {family: generated})}."""
    rows = {}
    with open(os.path.join(lib.BENCH_DIR, "pinned", name + ".jsonl"),
              encoding="utf-8") as f:
        for r in map(json.loads, filter(str.strip, f)):
            rows[int(r["level"])] = (
                int(r["frontier"]), int(r["distinct"]), int(r["generated"]),
                {k: int(v) for k, v in r["generated_by_family"].items()})
    return rows


def level_rows(events: list, base: dict) -> dict:
    """A run's ``level_complete`` events as ``load_pinned`` rows, its own
    generated by family on top of ``base`` (the resumed snapshot's); None
    in its place where the program's events do not carry it."""
    def families(e):
        own = e.get("generated_by_family")
        return own and {k: int(v) + int(base.get(k, 0))
                        for k, v in own.items()}
    return {int(e["level"]): (
        int(e["frontier_rows"]), int(e["distinct"]), int(e["generated"]),
        families(e)) for e in events if e["event"] == "level_complete"}


def compare_levels(ledger, got: dict, pinned: dict, levels, what) -> None:
    """Each of ``levels`` equal to the pin: in all four where the run's
    events carry the families, in the three counts where they do not (a
    program from before ``generated_by_family``: its ``action_counts``
    are held to the pin where the walk ends and over the window)."""
    for lv in levels:
        row, pin = got.get(lv), pinned.get(lv)
        if row is not None and row[3] is None and pin is not None:
            ledger.exact(f"{what} level {lv} (frontier, distinct, "
                         f"generated; the events carry no families)",
                         row[:3], pin[:3])
        else:
            ledger.exact(f"{what} level {lv} (frontier, distinct, "
                         f"generated, by family)", row, pin)


def run(ctx) -> dict:
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.models.pystate import PyState
    from raft_tla_tpu.models.schema import (decode_state, encode_state,
                                            flatten_state, state_width,
                                            unflatten_state)
    from raft_tla_tpu.utils.cfg import load_config

    bw = lib.load_module("traffic", "bfs_window")
    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    start = int(cell["start_level"])
    pinned = load_pinned(config["pinned"])
    setup = load_config(lib.write_cfg(config, ctx.tmp),
                        n_msg_slots=config["n_msg_slots"])
    dims, shapes = setup.dims, config["shapes"]
    row_bytes = state_width(dims)
    ledger.exact("row width in bytes", row_bytes, shapes["row_bytes"])
    ledger.exact("action instances", dims.n_instances,
                 shapes["action_instances"])
    ledger.exact("action families", dict(zip(dims.family_names,
                                             dims.family_sizes)),
                 shapes["families"])
    ledger.exact("dims class", type(dims).__name__, shapes["dims_class"])

    # -- the roots, by the reference, and their rows ---------------------
    t0 = time.perf_counter()
    ref = reference_of(ctx)
    ledger.exact("the reference's families are the engine's, in order",
                 list(ref.mod.FAMILY_NAMES), list(dims.family_names))
    ledger.exact("roots whose path from Init is legal under the reference",
                 sum(ref.mod.path_is_legal(r, ref.dims) for r in ref.roots),
                 len(ref.roots))
    ledger.exact("roots inside the constraint",
                 sum(bool(ref.constraint(r.state, ref.dims))
                     for r in ref.roots), len(ref.roots))
    roots = [PyState(**{f.name: getattr(r.state, f.name)
                        for f in dataclasses.fields(PyState)})
             for r in ref.roots]
    ledger.exact("roots whose packed row decodes to them", sum(
        decode_state(unflatten_state(np.asarray(flatten_state(
            encode_state(s, dims), dims)), dims), dims) == s
        for s in roots), len(roots))
    roots_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start
    eng = make_engine(setup, bw.engine_config(config, ctx))
    make_engine_s = time.perf_counter() - t0
    ledger.exact("engine class", type(eng).__name__, "BFSEngine")

    # -- set-up: roots -> start level, with the engine under test ---------
    walk_events = os.path.join(ctx.tmp, "walk.jsonl")
    eng.config.events_out = walk_events
    eng.config.max_diameter, eng.config.max_seconds = start, None
    t0 = time.perf_counter()
    walk = eng.run(roots)
    walk_s = time.perf_counter() - t0
    warmup_s = walk.phases.get("warmup", 0.0)
    ledger.exact("walk stop reason", walk.stop_reason, "diameter_budget")
    ledger.exact("pipeline", walk.pipeline, "v2")
    compare_levels(ledger, level_rows(lib.read_events(walk_events), {}),
                   pinned, range(start + 1), "set-up")
    ledger.exact("the walk's action_counts, by family",
                 {k: int(walk.action_counts.get(k, 0))
                  for k in dims.family_names},
                 (pinned.get(start) or (None,) * 4)[3])
    snapshot = ckpt_mod.latest(eng.config.checkpoint_dir)
    if walk.stop_reason != "diameter_budget" or snapshot is None:
        # No start level to open a window on (a control's program stops
        # at the roots): the line says so, with nothing measured.
        ledger.true("the walk left a snapshot of the start level", False,
                    f"stopped on {walk.stop_reason}: {walk.violation}")
        return {"end_to_end": {"setup_s": time.perf_counter() - ctx.t_start,
                               "distinct_per_s": 0.0},
                "window_wall_s": 0.0, "events": [], "trace_dir": None}
    t0 = time.perf_counter()
    ck = ckpt_mod.load(snapshot)
    load_s = time.perf_counter() - t0
    ledger.exact("snapshot level", ck.diameter, start)
    ledger.exact("snapshot dims class", type(ck.dims).__name__,
                 shapes["dims_class"])
    # The window's duration budget is its own (as ``bfs_window``), and so
    # is the warm resume that compiles or loads what a resume uses.
    ck = dataclasses.replace(ck, wall_seconds=0.0)
    eng.config.events_out = None
    eng.config.max_diameter, eng.config.max_seconds = None, 1e6
    eng.config.exit_conditions = (("distinct", 0),)
    t0 = time.perf_counter()
    warm = eng.run(resume=ck)
    warm_resume_s = time.perf_counter() - t0
    eng.config.exit_conditions = ()
    ledger.exact("warm resume stop reason", warm.stop_reason,
                 "distinct_budget")
    print(f"setup: ready {ready_s:.2f}s (of it the {len(roots)} roots by "
          f"the reference {roots_s:.2f}s), make_engine {make_engine_s:.2f}s,"
          f" walk to level {start} {walk_s:.2f}s (warmup {warmup_s:.2f}s, "
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
    got = level_rows(events, ck.action_counts)
    cur_rows = (got.get(res.diameter) or (len(ck.frontier),))[0]
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
    crossed = sorted(lv for lv in got if lv > start)
    ledger.exact("window stop reason", res.stop_reason, "duration_budget")
    ledger.exact("window pipeline", res.pipeline, "v2")
    ledger.true("window admitted new states", new_distinct > 0,
                str(new_distinct))
    compare_levels(ledger, got, pinned, crossed, "window")
    ledger.exact("levels crossed are consecutive from the start level",
                 crossed, list(range(start + 1, res.diameter + 1)))
    own = window_families(ctx, events, res, ck, new_generated)
    comp = lib.check_window_log(ctx, events, t_win0, t_win1)
    ledger.exact("'violation' events in the window",
                 sum(e["event"] == "violation" for e in events), 0)

    # -- correct: the replays (from the store the window filled, before
    # another run replaces it), then the seeded sample --------------------
    replay_check(ctx, eng, ck, res, ref)
    sample_check(ctx, eng, setup, ck, ref, decode_state, unflatten_state)

    return {
        "end_to_end": {"setup_s": setup_s,
                       "distinct_per_s": new_distinct / wall},
        "window_wall_s": wall, "phases": dict(res.phases),
        "events": events, "parents_expanded": parents, "counters": fill,
        "new_distinct": new_distinct, "new_generated": new_generated,
        "generated_by_family": own,
        "batch": config["batch"], "row_bytes": row_bytes,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "walk": walk_s, "warm_resume": warm_resume_s},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
    }


def window_families(ctx, events, res, ck, new_generated: int) -> dict:
    """(d): the window's own generated by family: the result's
    ``action_counts`` less the snapshot's, and its ``run_end``'s
    ``generated_by_family`` where the program emits it."""
    ledger = ctx.ledger
    own = {k: int(v) - int(ck.action_counts.get(k, 0))
           for k, v in res.action_counts.items()}
    print("window generated by family: " + ", ".join(
        f"{k} {v}" for k, v in own.items()), flush=True)
    ledger.exact("window's generated by family sums to its generated",
                 sum(own.values()), new_generated)
    for family in ctx.cell["families_in_window"]:
        ledger.true(f"window generated {family}", own.get(family, 0) > 0,
                    str(own.get(family)))
    ends = [e for e in events if e["event"] == "run_end"]
    said = ends[-1].get("generated_by_family") if ends else None
    if said is not None:
        ledger.exact("run_end.generated_by_family is the window's own",
                     said, own)
    return own


def replay_check(ctx, eng, ck, res, ref) -> None:
    """(g): states the window admitted, drawn by the seed from the trace
    store's records, replayed from that store to one of the roots."""
    ledger = ctx.ledger
    ledger.exact("trace store", type(eng.trace).__name__,
                 "NativeTraceStore")
    fps = np.asarray(eng.trace.export()[0], np.uint64)
    rng = np.random.default_rng(ctx.args.seed)
    cand = fps[rng.integers(0, len(fps), 1 << 15)]
    # Admitted in the window: not among the snapshot's keys (sorted).
    old = ((ck.seen_hi.astype(np.uint64) << np.uint64(32))
           | ck.seen_lo.astype(np.uint64))
    at = np.minimum(np.searchsorted(old, cand), len(old) - 1)
    cand = rng.permutation(np.unique(cand[old[at] != cand]))
    picks = [int(fp) for fp in cand[:int(ctx.cell["replayed"])]]
    ledger.exact("admitted states drawn for the replay", len(picks),
                 int(ctx.cell["replayed"]))
    root_states = {r.state for r in ref.roots}
    t0 = time.perf_counter()
    depths, rooted, legal = [], 0, 0
    for fp in picks:
        steps = eng.replay(fp)
        states = [lib.to_reference_state(s, ref.pystate) for _a, s in steps]
        depths.append(len(steps) - 1)
        rooted += steps[0][0] == -1 and states[0] in root_states
        legal += all(t in ref.oracle.successor_set(s, ref.dims)
                     for s, t in zip(states, states[1:]))
    print(f"replay: {len(picks)} states admitted in the window in "
          f"{time.perf_counter() - t0:.2f}s, steps {sorted(set(depths))}",
          flush=True)
    ledger.true("replayed paths' steps are levels the window built",
                all(ck.diameter < d <= res.diameter + 1 for d in depths),
                str(sorted(set(depths))))
    ledger.exact("replayed paths that start at one of the roots", rooted,
                 len(picks))
    ledger.exact("replayed paths legal under the reference, every step",
                 legal, len(picks))


def sample_check(ctx, eng, setup, ck, ref, decode_state, unflatten_state):
    """(f): ``sample`` start-level states, drawn by the seed as
    ``bfs_window`` draws them, as roots through one level of the engine
    and of the plain reference."""
    ledger = ctx.ledger
    states = lib.load_module("traffic", "safety_window").sampled_states(
        ctx, ck, setup, decode_state, unflatten_state)
    eng.config.events_out = None
    eng.config.max_seconds, eng.config.max_diameter = None, 1
    t0 = time.perf_counter()
    got = eng.run(states)
    t_eng = time.perf_counter() - t0

    t0 = time.perf_counter()
    roots = [lib.to_reference_state(s, ref.pystate) for s in states]
    seen = set(roots)
    names = ref.mod.FAMILY_NAMES
    by_family = dict.fromkeys(names, 0)
    frontier = bad_values = 0
    for r in roots:
        bad_values += not ref.mod.values_ok(r, ref.dims)
        for (family, _params), t in ref.oracle.successors(r, ref.dims):
            by_family[names[family]] += 1
            bad_values += not ref.mod.values_ok(t, ref.dims)
            if t not in seen:
                seen.add(t)
                frontier += bool(ref.constraint(t, ref.dims))
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
    ledger.exact("sampled states and successors with a value outside the "
                 "reference's TypeOK domain", bad_values, 0)
    ledger.exact("sample run's violation", got.violation, None)
