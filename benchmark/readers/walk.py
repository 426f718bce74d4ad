"""Reader ``walk``: the swarm tier's layers, from a run of traffic kind
``swarm_hunt`` (a run of any other kind gives None in every mode).

The walk chunk (``raft_tla_tpu/engine/swarm.py build_swarm_chunk``) is one
jitted ``lax.scan`` of ``chunk`` lockstep steps over the lanes of one
slice; its stages are named scopes (``WALK_STAGES``), so every device
operation's scope path says which it came from:
``jit(chunk_fn)/while/body/masks/...``.  One lockstep step of the swarm is
every one of its W walkers advanced once: ``slices`` calls' worth of one
scan iteration.  Device times are ratios over WHOLE calls of the walk
chunk, as ``readers/stages.py`` takes them for the BFS chunk: a call is
taken when its execution (the ``jit_chunk_fn`` event of ``XLA Modules``)
lies in the capture with operations over ``WHOLE`` of its length.  An
operation's self time goes to the first walk stage its scope path names,
to ``other`` where it names none.  Under ``NAMED_FLOOR`` of the time under
a name: nothing is reported.

Modes of ``read``:
  steps_per_s     lockstep walk-steps the window's runs made
                  (``run_end.steps``) / the window's wall
  step_ms         device self time of whole walk-chunk calls / their
                  lockstep steps of the whole swarm, in ms
  stage_ms        the same for the operations of ``stage``
  roofline        100 * least seconds a lockstep step
                  (``benchmark/roofline_walk.py``) / device seconds a step
  idle_share      ``readers/xplane.py``'s, over the hunts' steady span
  host_share      100 * (1 - ``swarm_fetch`` seconds / window wall): the
                  share of the window in which the host was not blocked
                  on a chunk's results
  per_call        ``run_end`` counter ``key`` / ``chunk_calls``
  share_of_steps  100 * ``run_end`` counter ``key`` / ``steps``
  per_verdict_ms  seconds of the window's ``spans`` (the engine's phase
                  histograms) * 1000 / verdicts
"""

from __future__ import annotations

import numpy as np

import bench_lib as lib

STAGES = ("masks", "choose", "lane_out", "fingerprint", "latch", "ring",
          "hunt")
NAMED_FLOOR = 0.9       # share of the calls' device time under a stage
WHOLE = 0.9             # operations must cover this much of a call


def stage_of(path: str):
    for part in path.split("/"):
        if part in STAGES:
            return part
    return None


def table(cap: dict, program: str):
    """{"calls", "device_ns", "named_ns", "leaves", "stage_ns": {stage or
    "other": ns}, "by_name": {stage: {op: ns}}} over the whole calls of
    the walk chunk in the capture, or None when there are none."""
    stages = lib.load_module("readers", "stages")
    stage = [stage_of(p) for p in cap["op_paths"]]
    ops = np.asarray(cap["ops"], np.int64).reshape(-1, 3)
    ops = ops[np.lexsort((-ops[:, 2], ops[:, 1]))]   # by start, outer first
    out = {"calls": 0, "device_ns": 0, "named_ns": 0, "leaves": 0,
           "stage_ns": {}, "by_name": {}}
    for name, lo, dur in cap["modules"]:
        if program not in name:
            continue
        inside = ops[np.searchsorted(ops[:, 1], lo):
                     np.searchsorted(ops[:, 1], lo + dur)].tolist()
        selfs, leaves = stages.self_times(inside)
        total = sum(ns for _i, ns in selfs)
        if total < WHOLE * dur:
            continue            # the capture lost part of this call
        out["calls"] += 1
        out["device_ns"] += total
        out["leaves"] += leaves
        for i, ns in selfs:
            st = stage[i]
            out["named_ns"] += ns if st else 0
            key = st or "other"
            out["stage_ns"][key] = out["stage_ns"].get(key, 0) + ns
            ops_of = out["by_name"].setdefault(key, {})
            op = cap["op_names"][i]
            ops_of[op] = ops_of.get(op, 0) + ns
    return out if out["calls"] else None


def stage_table(run: dict):
    """The table of this run's capture, computed and printed once, with
    ``steps``: the lockstep steps of the whole swarm its calls made."""
    if "_walk_table" in run:
        return run["_walk_table"]
    run["_walk_table"] = None
    cap = lib.load_module("readers", "spans").capture(run)
    tab = table(cap, run["chunk_program"]) if cap else None
    if tab is None:
        print("walk: no whole call of the walk chunk in the capture",
              flush=True)
        return None
    slices = -(-run["walks"] // run["batch"])
    tab["steps"] = tab["calls"] * run["walk_chunk"] / slices
    named = tab["named_ns"] / tab["device_ns"]
    per_step = lambda ns: ns / 1e6 / tab["steps"]  # noqa: E731
    print(f"walk: read {tab['calls']} whole calls of the walk chunk "
          f"({slices} a round), {tab['steps']:.0f} lockstep steps; device "
          f"time {per_step(tab['device_ns']):.3f} ms a step, "
          f"{100 * named:.1f} % of it under a stage name; "
          f"{tab['leaves'] / tab['steps']:.1f} operations a step",
          flush=True)
    for st, ops in sorted(tab["by_name"].items(),
                          key=lambda kv: -sum(kv[1].values())):
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:4]
        print(f"walk:   {st} {per_step(sum(ops.values())):.3f} ms a step"
              f" ({len(ops)} operations): "
              + ", ".join(f"{n} {per_step(ns):.3f}" for n, ns in top),
              flush=True)
    if named < NAMED_FLOOR:
        print(f"walk: under {100 * NAMED_FLOOR:.0f} % of the walk chunk's "
              f"device time carries a stage name; nothing reported",
              flush=True)
        return None
    run["_walk_table"] = tab
    return tab


def counter(run: dict, key: str):
    return (run.get("counters") or {}).get(key)


def read(run: dict, mode: str, stage: str = "", key: str = "", spans=()):
    if run.get("walk_kind") != "swarm_hunt":
        return None
    if mode == "steps_per_s":
        return counter(run, "steps") / run["window_wall_s"]
    if mode == "host_share":
        return 100.0 * (1.0 - run["phases"].get("swarm_fetch", 0.0)
                        / run["window_wall_s"])
    if mode == "per_call":
        calls = counter(run, "chunk_calls")
        return counter(run, key) / calls if calls else None
    if mode == "share_of_steps":
        steps = counter(run, "steps")
        return 100.0 * counter(run, key) / steps if steps else None
    if mode == "per_verdict_ms":
        return (1000.0 * sum(run["phases"].get(s, 0.0) for s in spans)
                / run["verdicts"])
    if mode == "idle_share":
        return lib.load_module("readers", "xplane").read(run, "idle_share")
    tab = stage_table(run)
    if tab is None:
        return None
    if mode == "step_ms":
        return tab["device_ns"] / 1e6 / tab["steps"]
    if mode == "stage_ms":
        return tab["stage_ns"].get(stage, 0) / 1e6 / tab["steps"]
    if mode == "roofline":
        import roofline
        import roofline_walk
        peak = roofline.peak_for(run["device_kind"],
                                 lib.load_json("peaks.json"))
        least = roofline_walk.least_step_seconds(
            run["walks"], run["row_bytes"], run["ring"],
            peak["hbm_bytes_per_s"])
        return 100.0 * least / (tab["device_ns"] / 1e9 / tab["steps"])
    raise ValueError(f"walk reader: unknown mode {mode!r}")
