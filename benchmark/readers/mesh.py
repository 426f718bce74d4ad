"""Reader ``mesh``: what the mesh engine adds to a pass, read from one
chip's plane of the profiler's capture and from the engine's own per-chip
counts.

The mesh chunk names its own stages inside the shared body's ``insert``
stage and around the loop (``raft_tla_tpu/parallel/mesh.py MESH_STAGES``):
``exchange`` (bucket by owner, the forward all_to_alls), ``owner_insert``,
``return`` (the reverse all_to_all, un-bucketing), ``agree`` (the psums of
the loop condition and of the statistics).  An operation belongs to the
innermost of them its scope path names.  ``run_end`` carries per-chip
lists (``chip_parents_expanded``, ``chip_next_count``,
``chip_shard_keys``), gathered from the chips by the chunk program.  A
run of a one-chip cell, or of a program without those names and counts,
leaves nothing to read and every mode returns None.

A capture of four chips holds four device planes.  Two are reduced: the
busiest chip's (most next-level rows admitted, by the engine's count:
every chip expands the same parents a pass, what differs is what it
enqueues) and the least busy one's, for skew; the metrics are the busiest chip's, and the printed line says which
planes were read.  Per-pass figures are ratios over WHOLE chunk calls
(``readers/stages.py``: the call's ``jit`` event with its ``raft.chunk``
and ``raft.account`` spans); where the capture was cut before the window
ended, the last call it holds is dropped, since its event ends with the
capture however many of its passes ran (PERF.md section 7, the flaw of
``stages.py``).  Shares of the steady span (first to last execution of the
chunk program on that chip) are read only where the capture reaches from
the start of the window's ``raft.run`` span to ``COVERS`` of the window.

Modes of ``read``:
  stage_ms   self time under ``stages`` (a list of mesh stage names) per
             pass, ms, busiest chip
  exposed    100 * self time of collective operations (time in which the
             chip ran a collective and nothing inside it) / steady span
  idle       100 * (1 - busy / steady span) of the busiest chip
  roofline   100 * least seconds a pass for the queries that must cross
             (benchmark/roofline_mesh.py over the window's own counts and
             benchmark/peaks_ici.json) / the ``exchange`` + ``return``
             seconds a pass
  chip_skew  100 * (max - mean) / mean of ``chip_next_count``, the rows of
             the level being built that each chip held when the window
             closed: how unevenly the next frontier lies on the chips.
             (Parents expanded are equal on every chip by construction:
             the compactor's ``pmin`` gives every chip the same prefix.)
  restore_share  100 * the window's own ``restore_keys`` +
             ``restore_frontier`` + ``restore_trace`` seconds / the
             window's wall: what of the window the resume takes before
             the first pass
"""

from __future__ import annotations

import array
import glob
import os
import re

import numpy as np

import bench_lib as lib

MESH_STAGES = ("exchange", "owner_insert", "return", "agree")
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|collective-permute|reduce-scatter)")
COVERS = 0.8
MIN_PASSES = 4
RESTORE_SPANS = ("restore_keys", "restore_frontier", "restore_trace")


def run_end(run: dict) -> dict:
    ends = [e for e in run.get("events") or [] if e.get("event") == "run_end"]
    return ends[-1] if ends else {}


def mesh_stage(path: str):
    """The innermost mesh stage a scope path names, or None."""
    found = None
    for part in path.split("/"):
        if part in MESH_STAGES:
            found = part
    return found


def load_plane(data, path: str, plane_name: str) -> dict:
    """{"modules", "ops", "op_names", "op_paths"} of one device plane of
    the parsed capture ``data``, as ``readers/spans.py load`` gives them
    for the first."""
    spans = lib.load_module("readers", "spans")
    metadata = spans.metadata_stats(path, plane_name)
    out = {"modules": [], "op_names": [], "op_paths": []}
    index = {}
    ids, starts, durs = array.array("q"), array.array("q"), array.array("q")
    for plane in data.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out["modules"] = [[ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)]
                                  for ev in line.events]
            elif line.name == "XLA Ops":
                for ev in line.events:
                    name = ev.name
                    i = index.get(name)
                    if i is None:
                        i = index[name] = len(out["op_names"])
                        out["op_names"].append(spans.short_name(name))
                        out["op_paths"].append(
                            spans.scope_path(metadata.get(name, {})))
                    ids.append(i)
                    starts.append(int(ev.start_ns))
                    durs.append(int(ev.duration_ns))
    out["ops"] = (np.column_stack([np.asarray(a, np.int64)
                                   for a in (ids, starts, durs)])
                  if len(ids) else np.zeros((0, 3), np.int64))
    return out


def reduce_plane(plane: dict, host: list, chunk_program: str,
                 window_ns: float) -> dict | None:
    """One chip's figures: per-pass stage times over whole calls, and the
    shares of its steady span."""
    stages = lib.load_module("readers", "stages")
    spans = lib.load_module("readers", "spans")
    cap = {"host": host, **plane}
    span = spans.steady_span(cap, chunk_program)
    ops = np.asarray(plane["ops"], np.int64).reshape(-1, 3)
    if span is None or not len(ops):
        return None
    ops = ops[np.lexsort((-ops[:, 2], ops[:, 1]))]
    runs = [e for e in host if e[0] == "run"]
    t_run = min(e[1] for e in runs) if runs else (
        host[0][1] if host else span[0])
    t_last = int((ops[:, 1] + ops[:, 2]).max())
    covered = t_last - t_run >= COVERS * window_ns
    calls = stages.whole_calls(cap, chunk_program)
    if calls and not covered:
        calls = calls[:-1]      # ends with the capture, not with its work
    out = {"covered": covered, "calls": 0, "passes": 0, "device_ns": 0,
           "stage_ns": {}, "span_ns": span[1] - span[0]}
    stage = [mesh_stage(p) for p in plane["op_paths"]]
    for lo, hi, n in calls:
        inside = ops[np.searchsorted(ops[:, 1], lo):
                     np.searchsorted(ops[:, 1], hi)].tolist()
        selfs, _leaves = stages.self_times(inside)
        total = sum(ns for _i, ns in selfs)
        if total < stages.WHOLE * (hi - lo):
            continue
        out["calls"] += 1
        out["passes"] += n
        out["device_ns"] += total
        for i, ns in selfs:
            if stage[i] is not None:
                out["stage_ns"][stage[i]] = (
                    out["stage_ns"].get(stage[i], 0) + ns)
    lo, hi = span
    inside = ops[(ops[:, 1] < hi) & (ops[:, 1] + ops[:, 2] > lo)].tolist()
    selfs, _leaves = stages.self_times(inside)
    out["collective_ns"] = sum(
        ns for i, ns in selfs if COLLECTIVE.match(plane["op_names"][i]))
    starts, ends = spans.busy_intervals(cap)
    edge = spans.busy_before(starts, ends, np.asarray([lo, hi], np.int64))
    out["busy_ns"] = int(edge[1] - edge[0])
    out["idle"] = spans.idle_by_span(cap, chunk_program)
    return out


def table(run: dict):
    """The reduction of this run's capture, computed and printed once."""
    if "_mesh_table" in run:
        return run["_mesh_table"]
    run["_mesh_table"] = None
    work = run_end(run).get("chip_next_count")
    if not run.get("mesh") or not work or not run.get("trace_dir"):
        return None
    try:
        run["_mesh_table"] = _table(run, work)
    except Exception as e:      # a reader never fails a run
        print(f"mesh: the capture could not be reduced "
              f"({type(e).__name__}: {e}); no trace metric", flush=True)
    return run["_mesh_table"]


def _table(run: dict, work: list):
    spans = lib.load_module("readers", "spans")
    paths = glob.glob(os.path.join(run["trace_dir"], "**", "*.xplane.pb"),
                      recursive=True)
    cap = spans.capture(run)
    if not paths or not cap or not cap["host"]:
        print("mesh: no capture with the program's spans", flush=True)
        return None
    from jax.profiler import ProfileData
    newest = max(paths, key=os.path.getmtime)
    data = ProfileData.from_file(newest)        # parsed once
    names = sorted((p.name for p in data.planes
                    if spans.DEVICE_PLANE.match(p.name)),
                   key=lambda name: int(name.rsplit(":", 1)[1]))
    if len(names) != len(work):
        print(f"mesh: {len(names)} device planes for {len(work)} chips; "
              f"no trace metric", flush=True)
        return None
    busiest = int(np.argmax(work))
    other = int(np.argmin(work))
    program = run.get("chunk_program", "chunk")
    window_ns = run["window_wall_s"] * 1e9
    red = {}
    for k in dict.fromkeys((busiest, other)):
        # readers/spans.py has loaded the first plane already.
        plane = cap if k == 0 else load_plane(data, newest, names[k])
        red[k] = reduce_plane(plane, cap["host"], program, window_ns)
    b = red[busiest]
    if b is None:
        print("mesh: no execution of the chunk program on the busiest "
              "chip's plane", flush=True)
        return None
    for k, r in red.items():
        if r is None:
            continue
        per = (lambda ns, r=r: ns / 1e6 / r["passes"] if r["passes"] else 0.0)
        print(f"mesh: chip {k} ({names[k]}, {work[k]} rows enqueued"
              f"{', the busiest' if k == busiest else ''}): "
              f"{r['calls']} whole calls, {r['passes']} passes, device "
              f"{per(r['device_ns']):.3f} ms a pass; "
              + ", ".join(f"{s} {per(r['stage_ns'].get(s, 0)):.3f}"
                          for s in MESH_STAGES)
              + f"; steady span {r['span_ns'] / 1e9:.2f}s, busy "
              f"{r['busy_ns'] / 1e9:.2f}s, collectives exposed "
              f"{r['collective_ns'] / 1e9:.3f}s; capture "
              f"{'covers' if r['covered'] else 'does NOT cover'} the "
              f"window", flush=True)
    if b["idle"]:
        total = b["idle"]["idle_ns"] or 1
        print("mesh: busiest chip's idle by innermost span, s: " + ", ".join(
            f"{k} {v / 1e9:.3f} ({100 * v / total:.1f} %)" for k, v in
            sorted(b["idle"]["innermost"].items(), key=lambda kv: -kv[1])),
            flush=True)
    return b


def read(run: dict, mode: str, stages=()):
    if mode == "chip_skew":
        rows = run_end(run).get("chip_next_count")
        if not rows or not sum(rows):
            return None
        mean = sum(rows) / len(rows)
        return 100.0 * (max(rows) - mean) / mean
    if mode == "restore_share":
        phases = run.get("phases") or {}
        if not run.get("window_wall_s") or not any(
                k in phases for k in RESTORE_SPANS):
            return None         # a program without the restore spans
        return 100.0 * sum(phases.get(k, 0.0) for k in RESTORE_SPANS) / run[
            "window_wall_s"]
    tab = table(run)
    if tab is None:
        return None
    if mode in ("exposed", "idle"):
        if not tab["covered"] or not tab["span_ns"]:
            return None
        if mode == "exposed":
            return 100.0 * tab["collective_ns"] / tab["span_ns"]
        return 100.0 * (1.0 - tab["busy_ns"] / tab["span_ns"])
    if tab["passes"] < MIN_PASSES:
        return None
    named = sum(tab["stage_ns"].get(s, 0) for s in stages)
    if mode == "stage_ms":
        if not any(s in tab["stage_ns"] for s in stages):
            return None         # an executable without the names
        return named / 1e6 / tab["passes"]
    if mode == "roofline":
        import roofline_mesh
        end = run_end(run)
        chips = run["mesh"]["chips"]
        # Every candidate generated in the window is one query.
        queries = run.get("new_generated")
        if not named or not queries or not end.get("passes"):
            return None
        peak = roofline_mesh.peak_for(run["device_kind"],
                                      lib.load_json("peaks_ici.json"))
        per_chip = queries / end["passes"] / chips
        least = roofline_mesh.least_exchange_seconds(
            per_chip, chips, peak["ici_bytes_per_s"])
        print(f"mesh: {per_chip:.0f} queries a pass a chip; least "
              f"{roofline_mesh.least_exchange_bytes(per_chip, chips):.0f} "
              f"bytes a pass a chip to cross, the program ships "
              f"{roofline_mesh.shipped_bytes(run['mesh']['lanes'], chips):.0f}",
              flush=True)
        return 100.0 * least / (named / 1e9 / tab["passes"])
    raise ValueError(f"mesh reader: unknown mode {mode!r}")
