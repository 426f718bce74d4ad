#!/usr/bin/env python3
"""Device operations per pass of the chunk loop, counted in a capture.

    python3 scripts/ops_per_pass.py --workload mcraft3-deep --seed <n>

Runs one traced window of a benchmark cell and counts, in the capture it
made, what the device executed for the chunk program: leaf operations inside the program's
outer ``while`` per iteration of it, and leaf operations outside the
loop per call.  It reads nothing but operation names and times, so it
counts the same way in a checkout whose program names no stage and
opens no span (copy it there, beside this PR's ``benchmark/`` files): the
number to compare between two commits that must run the same program.

Beside the counts it prints what the window's own ``run_end`` event
counted (chunk calls, passes, trace flushes, level closes and snapshots
overlapped and drained, the mesh's insert windows by chip and what its
resume overlapped, compiles by span; one ``run_end`` a run where the
window holds several, and its ``checkpoint`` events), the pools' fill,
the window's phases and the per-layer metrics as ``run.py --trace 1``
reduces them.

Iterations are counted as ``benchmark/readers/xplane.py`` counts them
(the most common number of times a direct child of the loop occurs);
where the capture carries the engine's ``raft.account`` spans the exact
count stands beside it.

It drives the cell as ``benchmark/run.py`` does (the cell's traffic kind
on a ``bench_lib.Context``), with a scratch directory of its own, so the
capture is still there to be counted when the window has closed.  Needs
a TPU, as the benchmark does; prints one JSON object as its last line.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)


def top_level_ns(events: list) -> int:
    """Nanoseconds under the events nested in no other."""
    total, end = 0, 0
    for _name, start, dur in events:
        if start >= end:
            total += dur
            end = start + dur
    return total


def count(planes: dict, xplane, stages,
          chunk_program: str = "chunk") -> dict:
    def leaves(events):
        return stages.self_times([(0, s, d) for _n, s, d in events])[1]

    dev = sorted(p for p in planes if xplane.DEVICE_PLANE.match(p))[0]
    ops = sorted(planes[dev].get(xplane.OPS_LINE, []),
                 key=lambda e: (e[1], -e[2]))
    starts = [e[1] for e in ops]
    out = {"calls": 0, "passes": 0, "ops_in_loop": 0, "ops_outside_loop": 0,
           "ops_by_call": []}
    for name, start, dur in planes[dev].get(xplane.MODULES_LINE, []):
        if chunk_program not in name:
            continue
        inside = ops[bisect.bisect_left(starts, start):
                     bisect.bisect_left(starts, start + dur)]
        passes = xplane.loop_iterations(inside)
        if not passes:
            continue                # a warm-up call: the loop ran no pass
        loop = max((e for e in inside if e[0].startswith("while")),
                   key=lambda e: e[2])
        if top_level_ns(inside) < 0.9 * dur:
            continue                # the capture lost part of this call
        body = [e for e in inside
                if e[1] >= loop[1] and e[1] + e[2] <= loop[1] + loop[2]
                and e is not loop]
        out["calls"] += 1
        out["passes"] += passes
        out["ops_in_loop"] += leaves(body)
        out["ops_outside_loop"] += leaves(inside) - leaves(body)
        # The same seed gives the first calls of a window the same work
        # (the engine sizes later ones from the clock), so these compare
        # one by one between two commits.
        out["ops_by_call"].append(leaves(inside))
    out["ops_by_call"] = out["ops_by_call"][:8]
    if out["passes"]:
        out["ops_per_pass"] = out["ops_in_loop"] / out["passes"]
        out["ops_outside_loop_per_call"] = (out["ops_outside_loop"]
                                            / out["calls"])
    return out


def traced_window(workload: str, seed: int, seconds: float, tmp: str):
    """One ``--trace 1`` window of the cell, set up as ``run.py`` sets it
    up; the traffic kind's result (``trace_dir``, ``chunk_program``)."""
    import run as bench_run
    import bench_lib as lib
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entry = bench_run.find(manifest["workloads"], workload, "workload")
    cfg_entry = bench_run.find(manifest["configs"], entry["config"],
                               "configuration")
    with open(os.path.join(ROOT, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    mix = lib.load_json("traffic", entry["traffic"] + ".json")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    bench_run.device_block(jax, entry["chips"], False)
    enable_persistent_cache()
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=1, rehearsal=False)
    ledger = lib.Ledger()
    run = lib.load_module("traffic", mix["kind"]).run(lib.Context(
        args=args, cell={**mix, **entry}, config=config, tmp=tmp,
        ledger=ledger, t_start=time.perf_counter(),
        compiles=lib.CompileWatch(),
        trace_dir=os.path.join(tmp, "xplane")))
    print(f"window: correct {ledger.correct} ({ledger.attempted} "
          f"comparisons, {ledger.failed} failed)", flush=True)
    return run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="ops_per_pass_")
    try:
        run = traced_window(args.workload, args.seed, args.seconds, tmp)
        import bench_lib as lib
        xplane = lib.load_module("readers", "xplane")
        stages = lib.load_module("readers", "stages")
        out = count(xplane.load(run["trace_dir"]), xplane, stages,
                    run.get("chunk_program", "chunk"))
        # The engine's own count of the same calls' passes (and the
        # stage table, printed), where the program writes them into the
        # capture.
        tab = stages.stage_table(run)
        if tab:
            out["passes_by_raft_account"] = tab["passes"]
        # What the window's own ``run_end`` counted (the loop's work
        # counters, its compiles by span; one a run where the window
        # holds several, as ``kill-resume``'s two), its snapshots as
        # acknowledged, the pools' fill, and the per-layer metrics as
        # ``run.py --trace 1`` reduces them.
        ends = [{k: e[k] for k in (
            "chunk_calls", "passes", "ingest_calls", "flush_overlapped",
            "flush_drained", "level_closes_overlapped",
            "level_closes_drained", "checkpoints_written",
            "checkpoints_overlapped", "checkpoints_drained",
            "checkpoint_wait_s", "parents_expanded", "chip_insert_windows",
            "restore_pieces", "restore_rounds", "restore_lane_rounds",
            "restore_host_s", "restore_wait_s", "compiles") if k in e}
            for e in run.get("events", []) if e["event"] == "run_end"]
        out["run_end"] = ends[-1] if ends else {}
        if len(ends) > 1:
            out["run_ends"] = ends
        saved = [{k: e[k] for k in ("level", "seconds", "stall_seconds",
                                    "bytes_raw", "parts") if k in e}
                 for e in run.get("events", []) if e["event"] == "checkpoint"]
        if saved:
            out["checkpoints"] = saved
        out["fill"] = run.get("counters")
        out["phases"] = {k: round(v, 4) for k, v in run["phases"].items()}
        import jax
        import run as bench_run
        run["device_kind"] = jax.devices()[0].device_kind
        with open(os.path.join(ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            metrics, _extra = bench_run.layer_metrics(json.load(f), run)
        out["layer_metrics"] = {k: v["value"] for k, v in metrics.items()}
        print(json.dumps({"workload": args.workload, **out}), flush=True)
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
