#!/usr/bin/env python3
"""Make ``benchmark/pinned/<pinned_hunts>.jsonl``: the program's own record
of each hunt of a swarm configuration.

    JAX_PLATFORMS=cpu python3 scripts/pin_swarm_hunts.py mcraft3-swarm hunts-noleader

For each seed of the traffic mix, one hunt on the CPU with all of the
configuration's walks in ONE slice and the hunt observatory off (it feeds
nothing back into the walk: ``tests/test_swarm.py`` pins that, and a
(lanes x lanes) prior at 2^16 lanes is more than a CPU run wants): the
lockstep step and the walker of the first violation in (step, walk)
order, its fingerprint, the length of its trace.  The benchmark holds
every hunt on any device and at any slice width to this record: that is
the determinism the configuration guarantees, not agreement with the
plain reference.  A seed that does not latch inside its first chunk is
reported and left out: replace it in the mix by the next integer.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv) -> int:
    import tempfile

    import bench_lib as lib
    from raft_tla_tpu.engine.check import initial_states, make_swarm_engine
    from raft_tla_tpu.utils.cfg import load_config
    config = lib.load_json("configs", argv[0] + ".json")
    mix = lib.load_json("traffic", argv[1] + ".json")
    with tempfile.TemporaryDirectory() as tmp:
        setup = load_config(lib.write_cfg(config, tmp))
    eng = make_swarm_engine(setup, max_depth=config["max_depth"],
                            batch=config["walks"], hunt=False)
    roots = initial_states(setup)
    path = os.path.join(lib.BENCH_DIR, "pinned",
                        config["pinned_hunts"] + ".jsonl")
    lines = []
    for seed in mix["seeds"]:
        res = eng.run(roots, seed=int(seed))
        if res.violation is None or res.violation_step >= eng.chunk:
            print(f"seed {seed}: no latch inside the first chunk "
                  f"({res.stop_reason}, step {res.violation_step})")
            continue
        lines.append({
            "config": config["name"], "seed": int(seed),
            "walks": eng.walks, "max_depth": eng.max_depth,
            "latch_step": res.violation_step, "walk": res.violation_walk,
            "fingerprint": f"{res.violation.fingerprint:#018x}",
            "trace_len": len(eng.replay(res.violation.fingerprint)),
            "record": "the program's own, CPU, one slice, hunt off"})
        print(lines[-1], flush=True)
    with open(path, "w", encoding="utf-8") as f:
        for r in lines:
            f.write(json.dumps(r) + "\n")
    print(f"{len(lines)} of {len(mix['seeds'])} seeds -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
