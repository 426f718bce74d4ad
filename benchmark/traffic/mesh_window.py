"""Traffic kind ``mesh_window``: a time-bounded window of exhaustive
breadth-first search on the mesh engine, from a level no single chip holds,
resumed from a level-boundary snapshot kept between a checkout's runs.

Set-up builds the engine the way ``cli.py check`` does on a multi-chip
host (``make_engine(setup, config, "auto")``), walks root -> ``walk_level``
with it (every level held to the pinned profile: this compiles or loads
every program and holds the cross-chip dedup to the pin in every run),
loads the kept snapshot of ``start_level``, makes one warm resume that
stops after its first call, and then times one
``engine.run(resume=snapshot)`` with ``max_seconds = --seconds``, restore
included, as ``bfs_window`` does on one chip.

The kept snapshot lives in ``<checkout>/.bench_kept/<config>.l<level>.
<digest>/`` (git-ignored, beside ``.jax_cache``): the engine's own
checkpoint, written by the engine, under a digest of the program's
sources and of the configuration's spec, so a snapshot made by other code
is never reused.  Beside it ``kept.json``: the making walk's level rows
and an order-independent digest of the frontier rows and of the key set.
A run that finds none makes it: the same engine walks on to
``start_level`` with every level held to the pin (those boundaries then
stand in for the walk to ``walk_level``), the snapshot the engine wrote
is moved into place, not written again.  Every run that reuses it
recomputes both digests and compares them and the counts; a failed
comparison deletes the copy.

``correct`` holds the window to: a clean event log, the stop reason, the
pipeline, no compile of the chunk inside it; (a) conservation, three
counts taken in three places: new distinct (the chunk's own count) =
growth of the shards' key counts (the tables' sizes on the chips) = trace
records the host's store gained; the rows the chips enqueued are held
only to lie within the pinned next level (what the constraint dropped is
counted nowhere in the program, so enqueue is held to the reference on
the sample alone); (b) ``sample`` start-level states, drawn by ``--seed``,
as roots through one level of the mesh engine and of the plain reference;
(c) ``replayed`` states admitted in the window, drawn by ``--seed`` from
the trace store's records in equal shares of the chips that own them,
replayed from the native trace store and held step by step to the
reference's successor sets; (d) every chip used,
the fullest chip's peak memory within ``memory_skew_limit`` of the
emptiest's.  It cannot see a lossy dedup that shows only between the
sample and a whole level: the window ends inside the level.

The state space is defined by the spec, so this traffic has no random
part: ``--seed`` draws only what is sampled and replayed.

Mix parameters (``benchmark/traffic/<mix>.json``):
  start_level   level whose frontier the window starts expanding
  walk_level    level every run walks to from the root, held to the pin
  sample        start-level states for the reference check (b)
  replayed      admitted states replayed from the trace store (c)
  kept_snapshot true: keep the start-level snapshot between runs
  memory_skew_limit  (fullest - emptiest) / emptiest peak bytes, at most
  forbidden_events   run events that may not occur inside the window
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np

import bench_lib as lib

KEPT_DIRNAME = ".bench_kept"
# The mesh engine's jitted programs, by the names jax reports compiles
# under: none may compile, or load from the cache, inside the window.
MESH_PROGRAMS = ("sharded_chunk", "sharded_ingest")
_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xC2B2AE3D27D4EB4F)


def engine_config(config: dict, checkpoint_dir, every) -> object:
    """``EngineConfig`` as ``cli.py check`` builds it from its defaults,
    with the sizes the configuration file states (the mesh divides both
    capacities by the number of chips)."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    return EngineConfig(
        batch=config["batch"], queue_capacity=config["queue_capacity"],
        seen_capacity=config["seen_capacity"], record_trace=True,
        pipeline="auto", progress_interval_seconds=60.0,
        checkpoint_dir=checkpoint_dir, checkpoint_every=every,
        checkpoint_interval_seconds=0.0)


# -- digests ------------------------------------------------------------------

def _mix(x):
    x = (x ^ (x >> np.uint64(31))) * _M1
    x = (x ^ (x >> np.uint64(29))) * _M2
    return x ^ (x >> np.uint64(32))


def rows_digest(rows: np.ndarray, block: int = 1 << 17) -> str:
    """An order-independent digest of a set of byte rows: every row is
    hashed (its bytes as 64-bit words, each times its column's own odd
    multiplier, summed and mixed), and the rows' hashes are summed and
    xor-ed.  Blocks go side by side (numpy releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor
    n, w = rows.shape
    words = -(-w // 8)
    mult = _mix(np.arange(1, words + 1, dtype=np.uint64) * _M2) | np.uint64(1)

    def one(lo):
        part = rows[lo:lo + block]
        buf = np.zeros((len(part), words * 8), np.uint8)
        buf[:, :w] = part
        h = _mix((buf.view(np.uint64) * mult).sum(axis=1, dtype=np.uint64))
        return (int(h.sum(dtype=np.uint64)),
                int(np.bitwise_xor.reduce(h)) if len(h) else 0)
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as ex:
        parts = list(ex.map(one, range(0, n, block)))
    total = sum(p[0] for p in parts) & (2 ** 64 - 1)
    x = functools.reduce(lambda a, b: a ^ b, (p[1] for p in parts), 0)
    return f"{n}:{total:016x}:{x:016x}"


def keys_digest(hi: np.ndarray, lo: np.ndarray) -> str:
    k = _mix((hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64))
    return (f"{len(k)}:{int(k.sum(dtype=np.uint64)):016x}:"
            f"{int(np.bitwise_xor.reduce(k)) if len(k) else 0:016x}")


def source_digest(config: dict) -> str:
    """sha256 over the program's sources (every ``.py`` of the package
    and the native trace store) and the configuration's spec."""
    import raft_tla_tpu
    pkg = os.path.dirname(os.path.abspath(raft_tla_tpu.__file__))
    h = hashlib.sha256()
    paths = []
    for d, _dirs, files in os.walk(pkg):
        paths += [os.path.join(d, f) for f in files
                  if f.endswith(".py") or f == "trace_store.cpp"]
    for p in sorted(paths):
        h.update(os.path.relpath(p, pkg).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(json.dumps([config["cfg_text"], config["constants"],
                         config["n_msg_slots"]], sort_keys=True).encode())
    return h.hexdigest()[:16]


# -- the kept snapshot --------------------------------------------------------

class Kept:
    """Where the start-level snapshot of this checkout lives."""

    def __init__(self, ctx, start: int):
        keep = bool(ctx.cell.get("kept_snapshot"))
        base = (os.path.join(lib.ROOT, KEPT_DIRNAME) if keep
                else os.path.join(ctx.tmp, KEPT_DIRNAME))
        name = f"{ctx.config['name']}.l{start}.{source_digest(ctx.config)}"
        self.dir = os.path.join(base, name)
        self.making = self.dir + ".making"
        self.snapshot = os.path.join(self.dir, f"level_{start:05d}.npz")
        self.record = os.path.join(self.dir, "kept.json")

    def present(self) -> bool:
        return os.path.isfile(self.snapshot) and os.path.isfile(self.record)

    def delete(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        shutil.rmtree(self.making, ignore_errors=True)

    def clear_others(self) -> None:
        """A copy of this configuration and level made by other sources
        is never reused: it goes."""
        base, mine = os.path.split(self.dir)
        prefix = mine.rsplit(".", 1)[0] + "."
        if os.path.isdir(base):
            for name in os.listdir(base):
                if name.startswith(prefix) and name != mine:
                    shutil.rmtree(os.path.join(base, name),
                                  ignore_errors=True)


def needs_of_the_program():
    """What this kind reads that the mesh engine has carried since the PR
    that added the cell; a program without it cannot run the cell."""
    try:
        from raft_tla_tpu.parallel import mesh
        return mesh.MESH_STAGES, mesh.MeshBFSEngine.shard_keys
    except (ImportError, AttributeError) as e:
        print(f"benchmark: the program in this checkout cannot run a "
              f"mesh_window cell (its mesh engine lacks the per-chip "
              f"counts, stage names and restore spans the cell reads: "
              f"{e}); nothing was run", file=sys.stderr, flush=True)
        sys.exit(2)


def run(ctx) -> dict:
    needs_of_the_program()
    import jax
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.schema import (decode_state, state_width,
                                            unflatten_state)
    from raft_tla_tpu.utils.cfg import load_config

    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    start, walk_to = int(cell["start_level"]), int(cell["walk_level"])
    chips = int(cell["chips"])
    pinned = lib.load_pinned(config["pinned"])
    setup = load_config(lib.write_cfg(config, ctx.tmp),
                        n_msg_slots=config["n_msg_slots"])
    row_bytes = state_width(setup.dims)
    ledger.exact("row width in bytes", row_bytes, config["shapes"]["row_bytes"])
    ledger.exact("action instances", setup.dims.n_instances,
                 config["shapes"]["action_instances"])
    kept = Kept(ctx, start)
    kept.clear_others()        # a run cut while making one included
    reuse = kept.present()

    # The engine, as cli.py's default --engine auto resolves it.  A
    # rehearsal has no accelerators for "auto" to count, and names the
    # class over the CPU's virtual devices.
    engine_cls = "auto"
    if getattr(ctx.args, "rehearsal", False):
        from raft_tla_tpu.parallel.mesh import MeshBFSEngine
        engine_cls = functools.partial(MeshBFSEngine,
                                       devices=jax.devices()[:chips])
    t0 = time.perf_counter()
    ready_s = t0 - ctx.t_start
    eng = make_engine(setup, engine_config(
        config, None if reuse else kept.making, start), engine_cls)
    make_engine_s = time.perf_counter() - t0

    def stamp(what):
        print(f"at {time.perf_counter() - ctx.t_start:.1f}s: {what}",
              flush=True)

    stamp(f"engine built; the walk to level "
          f"{walk_to if reuse else start} starts")
    ledger.exact("engine", type(eng).__name__, "MeshBFSEngine")
    ledger.exact("chips in the mesh", getattr(eng, "n_dev", 1), chips)

    # -- set-up: the walk, every level held to the pin --------------------
    walk_events = os.path.join(ctx.tmp, "walk.jsonl")
    depth = walk_to if reuse else start
    eng.config.events_out = walk_events
    eng.config.max_diameter, eng.config.max_seconds = depth, None
    t0 = time.perf_counter()
    walk = eng.run(initial_states(setup))
    walk_s = time.perf_counter() - t0
    stamp(f"walk done in {walk_s:.1f}s")
    warmup_s = walk.phases.get("warmup", 0.0)
    eng.config.checkpoint_dir = None
    walk_rows = lib.level_rows(lib.read_events(walk_events))
    ledger.exact("walk stop reason", walk.stop_reason, "diameter_budget")
    ledger.exact("pipeline", walk.pipeline, "v2")
    lib.compare_levels(ledger, walk_rows, pinned, range(depth + 1), "set-up")
    print(f"setup: compiles so far by program: "
          f"{compiles_by_name(ctx.compiles)}", flush=True)

    # -- set-up: the snapshot ---------------------------------------------
    t0 = time.perf_counter()
    if not reuse:
        made = ckpt_mod.latest(kept.making)
        if made is None:
            raise SystemExit("benchmark: the walk wrote no snapshot")
        sound = ledger.failed == 0
        if sound:
            os.makedirs(kept.dir, exist_ok=True)
            os.replace(made, kept.snapshot)     # moved, not written again
        ck = ckpt_mod.load(kept.snapshot if sound else made)
        shutil.rmtree(kept.making, ignore_errors=True)
        if sound:
            with open(kept.record, "w", encoding="utf-8") as f:
                json.dump({"levels": {str(k): list(v)
                                      for k, v in walk_rows.items()},
                           "frontier_digest": rows_digest(ck.frontier),
                           "keys_digest": keys_digest(ck.seen_hi,
                                                      ck.seen_lo)}, f)
        print(f"kept snapshot: made by this run's walk to level {start}"
              + (f" ({kept.dir})" if sound else
                 "; not kept, a comparison of the walk failed"), flush=True)
    else:
        ck = ckpt_mod.load(kept.snapshot)
        with open(kept.record, encoding="utf-8") as f:
            record = json.load(f)
        print(f"kept snapshot: reused ({kept.dir})", flush=True)
        ok = [
            ledger.exact("kept snapshot: the making walk's levels",
                         {int(k): tuple(v)
                          for k, v in record["levels"].items()},
                         {lv: pinned[lv] for lv in range(start + 1)}),
            ledger.exact("kept snapshot: digest of the frontier rows",
                         rows_digest(ck.frontier),
                         record["frontier_digest"]),
            ledger.exact("kept snapshot: digest of the key set",
                         keys_digest(ck.seen_hi, ck.seen_lo),
                         record["keys_digest"])]
        if not all(ok):
            kept.delete()
    snapshot_s = time.perf_counter() - t0
    stamp("snapshot loaded and held to its record; the warm resume starts")
    ok = [ledger.exact("snapshot level", ck.diameter, start),
          ledger.exact("snapshot (frontier rows, distinct, generated)",
                       (len(ck.frontier), ck.distinct, ck.generated),
                       pinned[start]),
          ledger.exact("snapshot keys and trace records",
                       (len(ck.seen_hi), len(ck.trace_fps)),
                       (pinned[start][1], pinned[start][1]))]
    if not all(ok):
        kept.delete()
    # The snapshot carries the walk's seconds, which a resumed run counts
    # against its duration budget; the window's budget is its own.
    ck = dataclasses.replace(ck, wall_seconds=0.0)

    # -- set-up: one warm resume that stops after its first call ----------
    eng.config.events_out = None
    eng.config.max_diameter, eng.config.max_seconds = None, 1e6
    eng.config.exit_conditions = (("distinct", 0),)
    t0 = time.perf_counter()
    warm = eng.run(resume=ck)
    warm_resume_s = time.perf_counter() - t0
    stamp("warm resume done; the window starts")
    eng.config.exit_conditions = ()
    ledger.exact("warm resume stop reason", warm.stop_reason,
                 "distinct_budget")
    restore = {k: warm.phases.get(k, 0.0) for k in
               ("restore_keys", "restore_frontier", "restore_trace")}
    print(f"setup: ready {ready_s:.2f}s, make_engine {make_engine_s:.2f}s, "
          f"walk to level {depth} {walk_s:.2f}s (warmup {warmup_s:.2f}s, "
          f"checkpoint {walk.phases.get('checkpoint', 0.0):.2f}s, "
          f"trace_flush {walk.phases.get('trace_flush', 0.0):.2f}s), "
          f"{walk.distinct} distinct, snapshot "
          f"{'load and digests' if reuse else 'move, load and digests'} "
          f"{snapshot_s:.2f}s, warm resume {warm_resume_s:.2f}s (restore: "
          + ", ".join(f"{k[8:]} {v:.2f}s" for k, v in restore.items())
          + ")", flush=True)

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
    stamp("window done; the comparisons start")
    setup_s = t_win0 - ctx.t_start
    new_distinct = res.distinct - ck.distinct
    new_generated = res.generated - ck.generated
    parents = int(eng.coverage.expanded)
    events = lib.read_events(win_events)
    end = next((e for e in reversed(events) if e["event"] == "run_end"), {})
    shard_keys = end.get("chip_shard_keys") or [0]
    next_rows = end.get("chip_next_count") or [0]
    crossed = sorted(lv for lv in lib.level_rows(events) if lv > start)
    cur_rows = ((lib.level_rows(events).get(res.diameter)
                 or (len(ck.frontier),))[0] + chips - 1) // chips
    per_chip_queue = -(-config["queue_capacity"] // chips)
    per_chip_seen = config["seen_capacity"] // chips
    fill = {"seen_load_pct": 100.0 * max(shard_keys) / per_chip_seen,
            "queue_fill_pct": 100.0 * max(cur_rows, max(next_rows))
            / per_chip_queue}
    print(f"fill: shards hold {shard_keys} keys of {per_chip_seen} each "
          f"(fullest {fill['seen_load_pct']:.1f} %); level "
          f"{res.diameter}'s rows at most {cur_rows} a chip and "
          f"{next_rows} rows of level {res.diameter + 1} in queues of "
          f"{per_chip_queue} a chip (fullest "
          f"{fill['queue_fill_pct']:.1f} %)", flush=True)
    print(f"window: {wall:.3f}s wall, {new_distinct} new distinct, "
          f"{new_generated} generated, {parents} parents expanded "
          f"({end.get('chip_parents_expanded')} by chip), levels "
          f"{ck.diameter}..{res.diameter}, stop {res.stop_reason}",
          flush=True)
    print("window phases, s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(res.phases.items(),
                                          key=lambda kv: -kv[1])),
          flush=True)

    # -- correct: the window itself --------------------------------------
    ledger.exact("window stop reason", res.stop_reason, "duration_budget")
    ledger.exact("window pipeline", res.pipeline, "v2")
    ledger.exact("window engine", sorted({e["engine"] for e in events
                                          if e["event"] == "run_start"}),
                 ["MeshBFSEngine"])
    ledger.exact("window recorded the trace",
                 sorted({e["record_trace"] for e in events
                         if e["event"] == "run_start"}), [True])
    ledger.true("window admitted new states", new_distinct > 0,
                str(new_distinct))
    lib.compare_levels(ledger, lib.level_rows(events), pinned, crossed,
                       "window")
    ledger.exact("levels crossed are consecutive from the start level",
                 crossed, list(range(start + 1, res.diameter + 1)))
    comp = check_window_log(ctx, events, t_win0, t_win1)
    conservation(ledger, ck, eng, end, crossed, pinned, new_distinct)
    memory_balance(ctx, jax, chips)

    # -- correct: the replays (from the store the window filled, before
    # another run replaces it) and the seeded sample ----------------------
    replay_check(ctx, eng, ck, res)
    # (b): as on one chip, through one level of the mesh engine.
    lib.load_module("traffic", "bfs_window").sample_check(
        ctx, eng, setup, ck, decode_state, unflatten_state)

    return {
        "end_to_end": {"setup_s": setup_s,
                       "distinct_per_s": new_distinct / wall},
        "window_wall_s": wall, "phases": dict(res.phases),
        "events": events, "parents_expanded": parents, "counters": fill,
        "new_distinct": new_distinct, "new_generated": new_generated,
        # A pass advances one batch on every chip.
        "batch": config["batch"] * chips, "row_bytes": row_bytes,
        "spans": {"make_engine": make_engine_s, "warmup": warmup_s,
                  "walk": walk_s, "warm_resume": warm_resume_s,
                  "snapshot": snapshot_s, **restore},
        "compiles": comp, "trace_dir": ctx.trace_dir,
        "chunk_program": "chunk",
        "mesh": {"chips": chips, "lanes": int(eng._K),
                 "kept_reused": reuse},
    }


def compiles_by_name(watch) -> dict:
    out = {}
    for _t, sec, name in watch.records:
        n, s = out.get(name, (0, 0.0))
        out[name] = (n + 1, round(s + sec, 2))
    return dict(sorted(out.items(), key=lambda kv: -kv[1][1])[:8])


def check_window_log(ctx, events: list, t0: float, t1: float) -> dict:
    """``bench_lib.check_window_log`` for the mesh engine's programs."""
    ledger = ctx.ledger
    ledger.exact("window ran at the batch asked for (a chip)",
                 sorted({e["batch"] for e in events
                         if e["event"] == "run_start"}),
                 [ctx.config["batch"]])
    for bad in ctx.cell["forbidden_events"]:
        ledger.exact(f"'{bad}' events in the window",
                     sum(e["event"] == bad for e in events), 0)
    # jax reports a compile under ``jit(<function>)``.
    inside = [(sec, name) for t, sec, name in ctx.compiles.records
              if t0 <= t <= t1]
    comp = {"count": len(inside), "seconds": sum(s for s, _n in inside),
            "of_programs": sorted(n for _s, n in inside
                                  if any(p in n for p in MESH_PROGRAMS))}
    by_name = {}
    for sec, name in inside:
        n, tot = by_name.get(name, (0, 0.0))
        by_name[name] = (n + 1, round(tot + sec, 3))
    print(f"window compiles: {comp['count']} taking {comp['seconds']:.3f}s "
          f"in all: {by_name}", flush=True)
    ledger.exact("compiles of the engine's programs inside the window",
                 comp["of_programs"], [])
    return comp


def conservation(ledger, ck, eng, end, crossed, pinned, new_distinct):
    """(a): what the window admitted, counted in three places."""
    shard_keys = end.get("chip_shard_keys")
    ledger.true("run_end carries the per-chip counts",
                shard_keys is not None and "chip_next_count" in end)
    if shard_keys is None:
        return
    ledger.exact("new distinct == growth of the shards' key counts",
                 sum(shard_keys) - len(ck.seen_hi), new_distinct)
    ledger.exact("new distinct == trace records the store gained",
                 len(eng.trace) - len(ck.trace_fps), new_distinct)
    if crossed:
        return          # whole levels were held to the pin instead
    enqueued = sum(end["chip_next_count"])
    nxt = pinned.get(ck.diameter + 1)
    print(f"enqueued: {enqueued} of the {new_distinct} new states lie in "
          f"the chips' next-level queues (the others are outside the "
          f"constraint: kept as keys, not expanded)", flush=True)
    if nxt:
        ledger.at_most("rows enqueued within the pinned next level",
                       enqueued, nxt[0])
        ledger.at_most("new distinct within the pinned next level",
                       new_distinct, nxt[1] - ck.distinct)


def memory_balance(ctx, jax, chips: int) -> None:
    """(d): every chip used, none much fuller than another."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    print(f"memory: peak bytes by chip {peaks}", flush=True)
    if not any(peaks):          # a backend that reports nothing (the CPU)
        ctx.ledger.true("memory peaks (none reported: rehearsal)",
                        bool(getattr(ctx.args, "rehearsal", False)))
        return
    ctx.ledger.true("every chip held memory", min(peaks) > 0, str(peaks))
    ctx.ledger.at_most("(fullest - emptiest) / emptiest chip's peak bytes",
                       (max(peaks) - min(peaks)) / max(min(peaks), 1),
                       float(ctx.cell["memory_skew_limit"]))


def replay_check(ctx, eng, ck, res) -> None:
    """(c): states the window admitted, drawn by the seed from the trace
    store's records in equal shares of the chips that own their keys,
    replayed from that store."""
    ledger, chips = ctx.ledger, int(ctx.cell["chips"])
    want = int(ctx.cell["replayed"])
    ledger.exact("trace store", type(eng.trace).__name__,
                 "NativeTraceStore")
    fps = np.asarray(eng.trace.export()[0], np.uint64)
    rng = np.random.default_rng(ctx.args.seed)
    cand = fps if len(fps) <= 1 << 20 else fps[
        rng.integers(0, len(fps), 1 << 15)]
    # Admitted in the window: not among the snapshot's keys (sorted).
    old = ((ck.seen_hi.astype(np.uint64) << np.uint64(32))
           | ck.seen_lo.astype(np.uint64))
    at = np.minimum(np.searchsorted(old, cand), len(old) - 1)
    cand = rng.permutation(np.unique(cand[old[at] != cand]))
    owner = (cand >> np.uint64(32)) % np.uint64(chips)
    picks = []
    for chip in range(chips):
        share = want // chips + (chip < want % chips)
        picks += [int(fp) for fp in cand[owner == chip][:share]]
    ledger.exact("chips owning a replayed state",
                 sorted({(fp >> 32) % chips for fp in picks}),
                 list(range(chips)))
    ref = lib.reference(ctx.config)
    trace_legal = lib.load_module("traffic", "verdict_loop").trace_legal
    t0 = time.perf_counter()
    depths, legal = [], 0
    for fp in picks:
        steps = eng.replay(fp)
        depths.append(len(steps) - 1)
        # From the reference's root, by its transitions.
        legal += bool(trace_legal(steps, ref))
    print(f"replay: {len(picks)} states admitted in the window in "
          f"{time.perf_counter() - t0:.2f}s, steps {sorted(set(depths))}",
          flush=True)
    ledger.true("replayed paths' steps are levels the window built",
                all(ck.diameter < d <= res.diameter + 1 for d in depths),
                str(sorted(set(depths))))
    ledger.exact("replayed paths legal under the reference from its root",
                 legal, len(picks))
