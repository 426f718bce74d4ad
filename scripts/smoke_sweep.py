#!/usr/bin/env python3
"""The batch sweep of benchmark configuration ``smokeraft``: whole checks of
``configs/Smokeraft.cfg`` at each batch width, on the device jax finds.

    chiprun --chips 1 --timeout 1500 -- python3 scripts/smoke_sweep.py --batch 256 512 1024 2048

For each width, one engine as ``make_engine`` builds it from the cfg's own
directives with ``BATCH`` replaced, one untimed check (compiles), one warm
one, then ``--windows`` windows as the cell ``smoke-1s`` runs them: whole
checks (``benchmark/traffic/smoke_loop.py window``) for ``--seconds``,
the configuration's root seeds cycled from the first in every window.  One
JSON line a width: each window's ``distinct_per_s``, their spread (the
interquartile range over the median, ``statistics.quantiles(n=4)``), the
median distinct states a check over the root seeds (each seed's median
over the windows that held it), chunk calls and passes a check, and the
device's peak memory.  ``BATCH`` of the configuration is chosen from these
lines by the rule ``benchmark/configs/smokeraft.json`` states.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, nargs="+",
                    default=[256, 512, 1024, 2048])
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import bench_lib as lib
    import jax
    from raft_tla_tpu.engine.check import (engine_config_from_backend,
                                           initial_states, make_engine)
    from raft_tla_tpu.utils.cfg import load_config
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    kind = lib.load_module("traffic", "smoke_loop")
    config = lib.load_json("configs", "smokeraft.json")
    seeds = [int(s) for s in config["root_seeds"]]
    setup = load_config(os.path.join(ROOT, config["repo_files"]["cfg"]))
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind!r}", flush=True)

    for batch in args.batch:
        t0 = time.perf_counter()
        eng = make_engine(setup, dataclasses.replace(
            engine_config_from_backend(setup), batch=batch))
        first = kind.one_check(eng, setup, seeds[0], initial_states)
        build_s = time.perf_counter() - t0
        kind.one_check(eng, setup, seeds[0], initial_states)
        rates, by_seed, counts = [], {}, []
        calls0 = int(eng.metrics.counter_value("engine/chunk_calls"))
        passes0 = int(eng.metrics.counter_value("engine/passes"))
        stops = set()
        for _w in range(args.windows):
            checks, t_win0, t_win1 = kind.window(
                eng, setup, seeds, args.seconds, 3, initial_states)
            wall = t_win1 - t_win0
            rates.append(sum(c["res"].distinct for c in checks) / wall)
            counts.append(len(checks))
            for c in checks:
                by_seed.setdefault(c["seed"], []).append(c["res"].distinct)
                stops.add((c["res"].stop_reason, c["res"].diameter))
        n = sum(counts)
        print(json.dumps({
            "batch": batch, "platform": dev.platform,
            "build_and_first_check_s": round(build_s, 2),
            "first_check_distinct": first["res"].distinct,
            "windows_distinct_per_s": [round(r, 1) for r in rates],
            "distinct_per_s_median": round(statistics.median(rates), 1),
            "distinct_per_s_spread": round(spread(rates), 5),
            "checks_a_window": counts,
            "distinct_a_check_by_seed": {
                s: int(statistics.median(v)) for s, v in
                sorted(by_seed.items())},
            "distinct_a_check_median": statistics.median(
                statistics.median(v) for v in by_seed.values()),
            "stops": sorted(stops),
            "chunk_calls_a_check": round((int(eng.metrics.counter_value(
                "engine/chunk_calls")) - calls0) / n, 2),
            "passes_a_check": round((int(eng.metrics.counter_value(
                "engine/passes")) - passes0) / n, 2),
            "memory_peak_bytes": int((dev.memory_stats() or {}).get(
                "peak_bytes_in_use", 0))}), flush=True)
        del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
