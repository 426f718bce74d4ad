#!/usr/bin/env python3
"""The three host jobs of a mesh resume in every order, on the devices
jax finds: what of them the chips' key inserts hide.

    python3 scripts/resume_orders.py [--keys 4945186] [--rows 1287967]
                                     [--records 19780743]

One chip's share of ``mcraft3-l12-x4``'s resume by default (the cell's
per-chip sizes: a 2^25-slot shard, a queue of 4,194,304 rows of 473
bytes), on a mesh of every device of the host, so one chip shows what
four cost to ask: ``MeshBFSEngine._shards_from_keys`` (dispatches the
inserts, ``keys`` a chip), ``_upload_segment`` (``rows`` a chip of random
bytes) and ``trace.add_batch`` (``records``, the host's alone), each
called as ``run()``'s resume branch calls it, in the orders

    keys upload trace     keys trace upload     upload keys trace

and then the one wait.  For each order one JSON line: the host seconds
of each job, when the host got to the wait, the wait, the whole, and the
seconds between the returns of the upload's ``_write_rows`` dispatches
(a host that a full queue of programs holds back returns one step an
insert; one that nothing holds returns a step every few milliseconds).
No chunk program is compiled.  Times are the host's clock around work
that ends in ``block_until_ready``: a device number only on a chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=4945186)
    ap.add_argument("--rows", type=int, default=1287967)
    ap.add_argument("--records", type=int, default=19780743)
    args = ap.parse_args()

    import jax
    import numpy as np
    from raft_tla_tpu.engine.bfs import EngineConfig, make_trace_store
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.models.schema import ROW_DTYPE
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    from raft_tla_tpu.utils.cfg import load_config
    from raft_tla_tpu.utils.platform import enable_persistent_cache

    enable_persistent_cache()
    n = len(jax.devices())
    setup = load_config(os.path.join(ROOT, "configs", "MCraft_bounded.cfg"),
                        n_msg_slots=32)
    eng = make_engine(
        setup, EngineConfig(batch=2048, queue_capacity=n << 22,
                            seen_capacity=n << 25, record_trace=True),
        engine_cls=functools.partial(MeshBFSEngine, devices=jax.devices()))
    rng = np.random.default_rng(45)
    hi = rng.integers(0, 1 << 32, n * args.keys, dtype=np.uint32)
    lo = rng.integers(0, 1 << 32, n * args.keys, dtype=np.uint32)
    most = max(int(np.count_nonzero(hi % np.uint32(n) == d))
               for d in range(n))
    rows = rng.integers(0, 255, (n * args.rows, eng._sw), dtype=ROW_DTYPE)
    fps = rng.integers(1, 1 << 63, args.records, dtype=np.uint64)
    parents = np.roll(fps, 1)
    actions = np.zeros(args.records, np.int32)
    print(json.dumps({"device": jax.devices()[0].device_kind, "chips": n,
                      "keys_a_chip": args.keys, "rows_a_chip": args.rows,
                      "row_bytes": eng._sw, "records": args.records}),
          flush=True)

    write_rows = eng._write_rows

    def one(order):
        qcur = eng._sharded_full((n, eng._QL + eng._PAD, eng._sw),
                                 ROW_DTYPE)
        qcur.block_until_ready()
        took, steps, out = {}, [], {}

        def stamped(*a):
            got = write_rows(*a)
            steps.append(time.time())
            return got

        eng._write_rows = stamped
        t0 = time.time()
        for job in order:
            t = time.time()
            if job == "keys":
                out["inserts"] = eng._shards_from_keys(hi, lo, most)[3]
            elif job == "upload":
                out["q"], out["counts"] = eng._upload_segment([rows], qcur)
            else:
                make_trace_store().add_batch(fps, parents, actions)
            took[job] = round(time.time() - t, 3)
        t_wait = time.time()
        out["inserts"].wait()
        out["counts"].block_until_ready()
        out["q"].block_until_ready()
        t1 = time.time()
        eng._write_rows = write_rows
        gaps = [round(b - a, 3) for a, b in zip(steps, steps[1:])]
        return {"order": " ".join(order), **took,
                "pieces": out["inserts"].pieces,
                "host_s": round(t_wait - t0, 3),
                "wait_s": round(t1 - t_wait, 3), "whole_s": round(t1 - t0, 3),
                "upload_step_gaps_s": gaps}

    orders = (("keys", "upload", "trace"), ("keys", "trace", "upload"),
              ("upload", "keys", "trace"))
    one(orders[0])                      # every program compiled
    for order in orders + orders:
        print(json.dumps(one(order)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
