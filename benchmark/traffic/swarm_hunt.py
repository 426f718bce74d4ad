"""Traffic kind ``swarm_hunt``: whole hunts of the swarm tier, initial state
to replayed counterexample, back to back on one warm engine.

Set-up builds the engine through ``engine/check.py make_swarm_engine`` from
the configuration's own cfg text and its ``max_depth`` (the calls ``cli.py
check <cfg> --max-depth <d>`` makes), runs one untimed hunt, which
compiles, reads a sample of its walkers back, and runs the same hunt once
more on an engine of half the slice width.  The window runs whole hunts
until ``--seconds`` have passed, at least ``min_verdicts``:
``engine.run(roots, seed=s)`` until a violation is latched, its trace
reconstructed, and every step of it found legal by the plain reference.
The hunts' seeds are the mix's fixed list, cycled from its start in every
window; ``--seed`` draws only the sampled walkers.

Mix parameters (``benchmark/traffic/<mix>.json``):
  min_verdicts  least number of whole hunts in a window
  invariant     the invariant that must be reported violated
  seeds         the hunts' seeds, in order
  sample        walkers read back after the set-up hunt's chunk
  forbidden_events  run events that may not occur inside the window

Configuration keys beyond the README's: ``walks``, ``max_depth``, ``ring``,
``chunk``, ``hunt`` (what the engine must have been built with), ``depth``
(``shortest``: the pinned breadth-first depth of the violation), and
``pinned_hunts``: ``benchmark/pinned/<name>.jsonl``, THE PROGRAM'S OWN
record of each seed's verdict at ``walks`` (latch step, walk, fingerprint,
trace length), made on the CPU at one slice; equality with it across
devices and slice widths is the determinism the configuration guarantees,
not agreement with the reference.
"""

from __future__ import annotations

import json
import os
import random
import statistics
import time

import bench_lib as lib

WALK_CHUNK_PROGRAM = "chunk_fn"     # jax.jit's name for the walk chunk


def load_hunts(name: str) -> dict:
    """{seed: (latch step, walk, fingerprint, trace length)}."""
    out = {}
    with open(os.path.join(lib.BENCH_DIR, "pinned", name + ".jsonl"),
              encoding="utf-8") as f:
        for line in f:
            if line.strip():
                r = json.loads(line)
                out[int(r["seed"])] = (int(r["latch_step"]), int(r["walk"]),
                                       r["fingerprint"],
                                       int(r["trace_len"]))
    return out


def run(ctx) -> dict:
    from raft_tla_tpu.engine.check import initial_states, make_swarm_engine
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.obs.metrics import phase_delta
    from raft_tla_tpu.utils.cfg import load_config

    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    pinned = load_hunts(config["pinned_hunts"])
    seeds = [int(s) for s in cell["seeds"]]
    setup = load_config(lib.write_cfg(config, ctx.tmp))
    ledger.exact("row width in bytes", state_width(setup.dims),
                 config["shapes"]["row_bytes"])
    ledger.exact("action instances", setup.dims.n_instances,
                 config["shapes"]["action_instances"])
    ledger.exact("cfg directives give the mode and sizes the file states",
                 (setup.backend.get("MODE"), setup.backend.get("WALKS"),
                  setup.backend.get("BATCH")),
                 ("swarm", config["walks"], config["batch"]))
    ref = lib.reference(config)     # puts benchmark/ on sys.path
    from reference import walk
    ref.walk = walk

    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start
    eng = make_swarm_engine(setup, max_depth=config["max_depth"])
    make_engine_s = time.perf_counter() - t0
    ledger.exact("engine, pipeline, walks, depth, ring, chunk, slice, hunt",
                 (type(eng).__name__, eng.pipeline_name, eng.walks,
                  eng.max_depth, eng.ring, eng.chunk, eng.batch, eng.hunt),
                 ("SwarmEngine", "v2", config["walks"], config["max_depth"],
                  config["ring"], config["chunk"], config["batch"],
                  config["hunt"]))
    roots = initial_states(setup)

    # -- set-up: one untimed hunt (compiles), a sample of its walkers, ----
    # -- the same hunt at half the slice width ----------------------------
    first_log = os.path.join(ctx.tmp, "first.jsonl")
    eng.events_out = first_log
    t0 = time.perf_counter()
    first = hunt(eng, roots, seeds[0], ref)
    first_s = time.perf_counter() - t0
    warmup_s = ctx.compiles.between(t0, t0 + first_s)["seconds"]
    verdict_ok(ledger, "set-up", cell, config, first, ref,
               lib.read_events(first_log), pinned)
    t0 = time.perf_counter()
    sample_ok(ledger, cell, config, ctx.args.seed, eng, first, ref)
    sample_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    half = make_swarm_engine(setup, max_depth=config["max_depth"],
                             batch=config["batch"] // 2)
    ledger.exact("half-width hunt: (latch step, walk, fingerprint, trace "
                 "length) at slice width " f"{half.batch}",
                 record(hunt(half, roots, seeds[0], ref)),
                 pinned.get(seeds[0]))
    half_s = time.perf_counter() - t0
    del half
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"first hunt {first_s:.2f}s (compiles and cache loads "
          f"{warmup_s:.2f}s), sample of {cell['sample']} walkers "
          f"{sample_s:.2f}s, half-width hunt {half_s:.2f}s", flush=True)

    # -- the window -------------------------------------------------------
    win_events = os.path.join(ctx.tmp, "window.jsonl")
    eng.events_out = win_events
    walls, results = [], []
    mt = eng.metrics
    phase_base = mt.phase_seconds()
    scope_base = scope_seconds(mt)
    with lib.traced(ctx):
        t_win0 = time.perf_counter()
        while (time.perf_counter() - t_win0 < ctx.args.seconds
               or len(walls) < int(cell["min_verdicts"])):
            t0 = time.perf_counter()
            results.append(hunt(eng, roots, seeds[len(walls) % len(seeds)],
                                ref))
            walls.append(time.perf_counter() - t0)
        t_win1 = time.perf_counter()
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    phases = phase_delta(mt.phase_seconds(), phase_base)
    phases["reconstruct"] = scope_seconds(mt) - scope_base
    print(f"window: {wall:.3f}s wall, {len(walls)} verdicts, median "
          f"{statistics.median(walls):.4f}s, min {min(walls):.4f}s, max "
          f"{max(walls):.4f}s", flush=True)
    print("window phases, s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(phases.items(),
                                          key=lambda kv: -kv[1])),
        flush=True)

    # -- correct: every verdict of the window ------------------------------
    events = lib.read_events(win_events)
    runs = verdict_loop().split_runs(events)
    ledger.exact("runs in the window's event log", len(runs), len(results))
    for i, (res, evs) in enumerate(zip(results, runs)):
        verdict_ok(ledger, f"verdict {i}", cell, config, res, ref, evs,
                   pinned)
    lib.check_window_log(ctx, events, t_win0, t_win1)
    inside = sorted(n for (t, _d, n) in ctx.compiles.records
                    if t_win0 <= t <= t_win1 and WALK_CHUNK_PROGRAM in n)
    ledger.exact("compiles of the walk chunk inside the window", inside, [])
    ends = [e for e in events if e["event"] == "run_end"]
    counters = {k: sum(e.get(k, 0) for e in ends)
                for k in ("chunk_calls", "slices", "fetches", "steps",
                          "steps_past_latch", "restarts",
                          "reconstruct_steps")}
    print(f"window counters: {counters}", flush=True)

    return {
        "end_to_end": {"setup_s": setup_s, "verdict_s": wall / len(walls)},
        "window_wall_s": wall, "phases": phases, "events": events,
        "counters": counters, "verdicts": len(walls),
        "batch": config["batch"], "walks": config["walks"],
        "walk_chunk": config["chunk"], "ring": config["ring"],
        "row_bytes": state_width(setup.dims), "verdict_walls": walls,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "first_check": first_s},
        "trace_dir": ctx.trace_dir, "chunk_program": WALK_CHUNK_PROGRAM,
        "walk_kind": "swarm_hunt",
    }


def scope_seconds(metrics) -> float:
    """Seconds of the ``reconstruct`` scope so far (a scope holds phases,
    so ``phase_seconds`` leaves it out)."""
    return metrics.snapshot()["histograms"].get(
        "scope/reconstruct", {}).get("total", 0.0)


def verdict_loop():
    """The BFS verdict kind, for what the two share: ``trace_legal`` (the
    replayed trace starts at the reference's own initial state and every
    step is a transition the reference allows) and ``split_runs``."""
    return lib.load_module("traffic", "verdict_loop")


def hunt(eng, roots, seed: int, ref) -> dict:
    """One whole hunt: the run, the reconstructed trace, its legality."""
    res = eng.run(roots, seed=seed)
    steps = eng.replay(res.violation.fingerprint) if res.violation else []
    return {"seed": seed, "res": res, "steps": steps,
            "states": [lib.to_reference_state(s, ref.pystate)
                       for _a, s in steps],
            "legal": verdict_loop().trace_legal(steps, ref)}


def record(h: dict):
    """(latch step, walk, fingerprint, trace length) of one hunt."""
    res = h["res"]
    if res.violation is None:
        return None
    return (res.violation_step, res.violation_walk,
            f"{res.violation.fingerprint:#018x}", len(h["steps"]))


def one(events: list, name: str) -> dict:
    found = [e for e in events if e["event"] == name]
    return found[-1] if found else {}


def verdict_ok(ledger, what, cell, config, h, ref, events, pinned) -> None:
    res, states = h["res"], h["states"]
    ledger.exact(f"{what}: invariant reported violated",
                 res.violation.invariant if res.violation else None,
                 cell["invariant"])
    ledger.true(f"{what}: trace legal under the reference, from its root",
                h["legal"])
    ledger.true(f"{what}: depth of the trace within [shortest, max_depth]",
                config["depth"]["shortest"] <= len(states) - 1
                <= config["max_depth"], f"{len(states) - 1}")
    ledger.true(f"{what}: every state but the last holds no leader and "
                "passes the constraint, the last holds a leader",
                bool(states)
                and all(ref.rd.no_leader_py(s, ref.dims)
                        and ref.constraint(s, ref.dims)
                        for s in states[:-1])
                and not ref.rd.no_leader_py(states[-1], ref.dims))
    ledger.exact(f"{what}: pipeline", res.pipeline, "v2")
    # (c) the program's own record of this seed, on any device and slicing
    ledger.exact(f"{what}: seed {h['seed']} (latch step, walk, "
                 "fingerprint, trace length) equals the pinned record",
                 record(h), pinned.get(h["seed"]))
    # (d) conservation, from what the loop counted and what the device did
    end, W = one(events, "run_end"), config["walks"]
    rounds = end.get("chunk_calls", 0) // max(end.get("slices", 0), 1)
    ledger.exact(f"{what}: steps = walks x chunk x rounds ({rounds})",
                 end.get("steps"), W * config["chunk"] * rounds)
    ledger.exact(f"{what}: traces = walks + restarts",
                 (end.get("swarm") or {}).get("traces"),
                 W + end.get("restarts", -1))
    ledger.exact(f"{what}: the hunt's restart census sums to restarts",
                 ((one(events, "hunt").get("hunt") or {}).get("restarts")
                  or {}).get("total"), end.get("restarts"))
    ledger.true(f"{what}: latched inside its first chunk",
                0 <= end.get("latch_step", -1) < config["chunk"],
                f"step {end.get('latch_step')}")


def sample_ok(ledger, cell, config, seed: int, eng, h, ref) -> None:
    """(e) ``sample`` walkers drawn by ``--seed``, the latched one left
    out, as the hunt's last chunk left them: each one's current trace
    keeps the reference's rules, replays to the row the device holds, and
    holds no leader before the reported latch in (step, walk) order."""
    W, n = config["walks"], int(cell["sample"])
    res = h["res"]
    latched = res.violation_walk
    ids = random.Random(seed).sample([w for w in range(W) if w != latched],
                                     min(n, W - 1))
    from raft_tla_tpu.models.schema import decode_state, unflatten_state
    faults, rows_ok, early = [], 0, 0
    k_end = res.steps // W                 # lockstep steps the run made
    latch = (res.violation_step, latched)
    for w, (root, actions, row) in zip(ids, eng.walk_transcripts(ids)):
        steps = eng.replay_actions(root, actions)
        states = [lib.to_reference_state(s, ref.pystate) for _a, s in steps]
        families = [eng.dims.instance_info(g)[0] for g in actions]
        faults += [f"walk {w}: {f}" for f in ref.walk.check_transcript(
            states[0], families, states[1:], dims=ref.dims,
            depth=config["max_depth"], constraint=ref.constraint,
            ring=config["ring"])]
        held = decode_state(unflatten_state(row, eng.dims), eng.dims)
        rows_ok += (len(steps) == len(actions) + 1
                    and lib.to_reference_state(held, ref.pystate)
                    == states[-1])
        # Every step of the current trace was accepted, so state j was
        # reached at lockstep step k_end - len(actions) + j - 1.
        early += sum(
            1 for j, s in enumerate(states[1:], 1)
            if not ref.rd.no_leader_py(s, ref.dims)
            and (k_end - len(actions) + j - 1, w) < latch)
    for f in faults[:8]:
        print(f"sample: {f}", flush=True)
    ledger.exact(f"sample of {len(ids)} walkers: faults against the "
                 "reference's rules", len(faults), 0)
    ledger.exact(f"sample of {len(ids)} walkers: device rows that decode "
                 "to the replay's last state", rows_ok, len(ids))
    ledger.exact(f"sample of {len(ids)} walkers: leaders held before the "
                 "reported latch in (step, walk) order", early, 0)
