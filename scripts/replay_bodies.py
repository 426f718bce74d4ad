#!/usr/bin/env python3
"""Time the fused replay's two bodies on the device jax finds.

    chiprun --chips 1 -- python3 scripts/replay_bodies.py [cfg]

For each body of ``engine/replay.py`` (v1: ``expand`` of every instance,
one selected, its ``fingerprint``; v2: ``masks`` and ``lane_out`` of the
one instance): the first call (trace, lower, compile or cache load), then
the wall of a call on the canary's nine-step election and on a 100-step
trace, so that the difference is what a step costs on the device and the
rest what a call costs whatever its length; the executable's instruction
count beside them.  One JSON line a body; PR 42 chose by it.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Timeout(0), Timeout(1), RequestVote(0,0), RequestVote(0,1), then the
# replies and BecomeLeader(0): an election of configs/MCraft_noleader.cfg
# in the kernel's own slot ids.
ELECTION = [3, 4, 6, 7, 36, 36, 37, 36, 15]


def main(argv) -> int:
    import jax
    import numpy as np
    from raft_tla_tpu.engine.replay import ReplayScan
    from raft_tla_tpu.models.pystate import init_state
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.obs import MetricsRegistry
    from raft_tla_tpu.utils.cfg import load_config
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    cfg = argv[0] if argv else os.path.join(ROOT, "configs",
                                            "MCraft_noleader.cfg")
    dims = load_config(cfg).dims
    root = init_state(dims)
    long = [3] * 100                       # Timeout(0), a hundred times
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "cfg": os.path.basename(cfg),
                      "row_bytes": state_width(dims),
                      "instances": dims.n_instances}), flush=True)
    for body in ("v1", "v2"):
        scan = ReplayScan(dims, MetricsRegistry(), body=body)
        t0 = time.perf_counter()
        rows, keys, calls = scan(root, ELECTION)
        first_s = time.perf_counter() - t0
        assert len(rows) == len(ELECTION) and calls == 1, (len(rows), calls)
        assert len(scan(root, long)[0]) == len(long)
        walls = {}
        for name, acts in (("election_9", ELECTION), ("timeouts_100", long)):
            ts = []
            for _ in range(100):
                t0 = time.perf_counter()
                scan(root, acts)
                ts.append(time.perf_counter() - t0)
            walls[name] = ts
        med = {k: statistics.median(v) * 1e3 for k, v in walls.items()}
        text = scan._program.lower(
            np.zeros(state_width(dims), np.uint8),
            np.zeros(scan.capacity, np.int32),
            np.int32(0)).compile().as_text()
        print(json.dumps({
            "body": body, "first_call_s": round(first_s, 3),
            "call_ms_median": {k: round(v, 3) for k, v in med.items()},
            "call_ms_min": {k: round(min(v) * 1e3, 3)
                            for k, v in walls.items()},
            "step_ms": round((med["timeouts_100"] - med["election_9"])
                             / (len(long) - len(ELECTION)), 4),
            "instructions": text.count(" = "),
            "fusions": text.count(" fusion("),
            "last_key": f"{int(keys[-1]):#018x}"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
