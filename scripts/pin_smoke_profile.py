#!/usr/bin/env python3
"""Make ``benchmark/pinned/<pinned>.jsonl`` of a smoke configuration
(traffic kind ``smoke_loop``): for each root seed, the draw and the plain
reference's level profile from its ``SmokeInit`` product.

    JAX_PLATFORMS=cpu python3 scripts/pin_smoke_profile.py smokeraft --levels 2

The DRAW is the program's (``engine/check.py initial_states(setup, seed)``:
numpy's generator, since TLC's ``RandomSubset`` cannot be replayed); it is
read back from the roots by ``benchmark/reference/smoke.py draw_of`` and
kept as data on the seed's level-0 line.  Everything else is the
reference's alone: the roots must be a ``SmokeInit`` set
(``is_smoke_init``), the profile is breadth-first from ``product(draw)``
with no constraint (the cfg has none but the budget), and every root and
every admitted state must pass the reference's ``TypeOK``.  A seed that
fails either is reported and left out: it is no root seed, take the next.

One line a seed and level: ``root_seed``, ``level``, ``frontier``,
cumulative ``distinct`` and ``generated``, cumulative
``generated_by_family``.  Level 2 is 0.5-0.6 M successors a seed, a
minute of the reference, level 3 is 9 M and a quarter of an hour; the
seeds run in ``--jobs`` processes.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import multiprocessing
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pin_rooted_profile import digest  # noqa: E402


def profile(job):
    """The lines of one root seed, or a string saying why it is none."""
    config, seed, draw_json, levels = job
    from reference import dims as rd
    from reference import oracle, smoke
    dims = rd.RaftDims(n_servers=len(config["constants"]["Server"]),
                       n_values=len(config["constants"]["Value"]),
                       max_log=config["max_log"],
                       n_msg_slots=config["n_msg_slots"])
    names = list(config["shapes"]["families"])
    draw = smoke.from_json(draw_json)
    frontier = smoke.product(draw)
    wrong = smoke.is_smoke_init(frontier, config["smoke_k"], dims)
    if wrong:
        return f"seed {seed}: not a SmokeInit set: {wrong}"
    # The seen-set keeps a 16-byte digest of each state's canonical form
    # (as scripts/pin_rooted_profile.py does), not the state: level 3 is
    # 3 M states a seed.  The last level is counted, not kept.
    seen = {digest(s) for s in frontier}
    bad = sum(not smoke.type_ok(s, dims) for s in frontier)
    generated = 0
    by_family = dict.fromkeys(names, 0)
    lines = []
    t0 = time.time()
    width = len(frontier)
    for level in range(levels + 1):
        if bad:
            return (f"seed {seed}: {bad} states of level {level} fail the "
                    f"reference's TypeOK")
        line = {"config": config["name"], "root_seed": seed, "level": level,
                "frontier": width, "distinct": len(seen),
                "generated": generated,
                "generated_by_family": dict(by_family)}
        if level == 0:
            line["draw"] = draw_json
        lines.append(line)
        print(f"{time.time() - t0:7.1f}s seed {seed} level {level}: "
              f"{line['frontier']} {line['distinct']} {line['generated']}",
              flush=True)
        if level == levels:
            break
        nxt, width = [], 0
        for s in frontier:
            for (family, _params), t in oracle.successors(s, dims):
                generated += 1
                by_family[names[family]] += 1
                k = digest(t)
                if k not in seen:
                    seen.add(k)
                    bad += not smoke.type_ok(t, dims)
                    width += 1
                    if level + 1 < levels:
                        nxt.append(t)
        frontier = nxt
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--levels", type=int, default=2)
    ap.add_argument("--jobs", type=int, default=os.cpu_count() or 1)
    ap.add_argument("--seeds", type=int, nargs="*",
                    help="these root seeds, not the configuration's")
    ap.add_argument("--out", help="write here, not under benchmark/pinned")
    args = ap.parse_args(argv)

    with open(os.path.join(BENCH, "configs", args.config + ".json"),
              encoding="utf-8") as f:
        config = json.load(f)
    seeds = args.seeds or config["root_seeds"]

    # The program's part: the draws.
    from raft_tla_tpu.engine.check import initial_states
    from raft_tla_tpu.utils.cfg import load_config
    from reference import pystate, smoke
    with tempfile.TemporaryDirectory() as tmp:
        for name, text in ((config["cfg_name"], config["cfg_text"]),
                           (config["module_name"], config["module_text"])):
            with open(os.path.join(tmp, name), "w", encoding="utf-8") as f:
                f.write("\n".join(text) + "\n")
        setup = load_config(os.path.join(tmp, config["cfg_name"]))
    jobs = []
    for seed in seeds:
        roots = [pystate.PyState(**{f.name: getattr(s, f.name) for f in
                                    dataclasses.fields(pystate.PyState)})
                 for s in initial_states(setup, seed=int(seed))]
        jobs.append((config, int(seed), smoke.to_json(smoke.draw_of(roots)),
                     args.levels))

    out = args.out or os.path.join(BENCH, "pinned",
                                   config["pinned"] + ".jsonl")
    with multiprocessing.get_context("spawn").Pool(args.jobs) as pool:
        results = pool.map(profile, jobs, chunksize=1)
    kept = 0
    with open(out, "w", encoding="utf-8") as f:
        for res in results:
            if isinstance(res, str):
                print(res + ": NO ROOT SEED, take the next", flush=True)
                continue
            kept += 1
            for line in res:
                f.write(json.dumps(line) + "\n")
    print(f"{kept} of {len(seeds)} root seeds -> {out}")
    return 0 if kept == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
