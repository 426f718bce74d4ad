#!/usr/bin/env python3
"""Sweep the swarm tier's walks W and slice width on the device jax finds.

    chiprun --chips 1 -- python3 scripts/swarm_sweep.py [--walks 4096,16384]
        [--batch 256,4096] [--seeds 1,2,3] [--no-hunt] [--within SECONDS]

For each (W, slice width): ``make_swarm_engine`` from
``configs/MCraft_swarm.cfg`` at depth 100, one hunt that compiles, then one
hunt a seed; a JSON line a combination (``chiprun_out/swarm_sweep.jsonl``
too): wall of each hunt, lockstep walk-steps a second over the warm ones,
the latch step, the host's phases, the loop's counters, peak memory.
PERF.md section 4 records what PR 33 read on a TPU v5e; a CPU's numbers
are no device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--walks", type=ints, default=[4096, 16384, 65536])
    ap.add_argument("--batch", type=ints, default=[256, 1024, 4096, 16384])
    ap.add_argument("--seeds", type=ints, default=[1, 2, 3])
    ap.add_argument("--no-hunt", action="store_true")
    ap.add_argument("--within", type=float, default=1500.0)
    ap.add_argument("--hunt-limit", type=float, default=90.0,
                    help="max_seconds of one hunt")
    args = ap.parse_args()
    t_start = time.time()

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    from raft_tla_tpu.engine.check import initial_states, make_swarm_engine
    from raft_tla_tpu.obs.metrics import phase_delta
    from raft_tla_tpu.utils.cfg import load_config
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind!r}", flush=True)
    setup = load_config(os.path.join(ROOT, "configs", "MCraft_swarm.cfg"))
    roots = initial_states(setup)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    for walks in args.walks:
        for batch in args.batch:
            if batch > walks:
                continue
            if time.time() - t_start > args.within:
                print(f"skipped W={walks} B={batch}: past --within",
                      flush=True)
                continue
            eng = make_swarm_engine(setup, walks=walks, batch=batch,
                                    max_depth=100,
                                    hunt=not args.no_hunt)
            t0 = time.perf_counter()
            first = eng.run(roots, seed=args.seeds[0],
                            max_seconds=args.hunt_limit)
            first_s = time.perf_counter() - t0
            base = eng.metrics.phase_seconds()
            walls, steps, latch, counts = [], 0, [], {}
            for seed in args.seeds:
                t0 = time.perf_counter()
                res = eng.run(roots, seed=seed,
                              max_seconds=args.hunt_limit)
                walls.append(time.perf_counter() - t0)
                steps += res.steps
                latch.append(eng._counts["latch_step"])
                for k, v in eng._counts.items():
                    counts[k] = counts.get(k, 0) + v
            phases = phase_delta(eng.metrics.phase_seconds(), base)
            hist = eng.metrics.snapshot()["histograms"]
            line = {
                "device": dev.device_kind, "walks": walks, "batch": batch,
                "hunt": not args.no_hunt, "first_s": round(first_s, 3),
                "first_stop": first.stop_reason,
                "walls": [round(w, 4) for w in walls],
                "median_s": round(statistics.median(walls), 4),
                "steps_per_s": round(steps / sum(walls), 1),
                "latch_steps": latch,
                "phases": {k: round(v, 4) for k, v in phases.items()},
                "reconstruct_s": round(hist.get(
                    "scope/reconstruct", {}).get("total", 0.0), 4),
                "counts": counts,
                "memory_peak_bytes": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)}
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "swarm_sweep.jsonl"), "a",
                      encoding="utf-8") as f:
                f.write(json.dumps(line) + "\n")
            del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
