#!/usr/bin/env python3
"""What a snapshot's capture moves from the device, timed piece by piece.

    python3 scripts/d2h_probe.py [--rows 548904] [--width 474] [--slots 24]

A save's capture (``engine/bfs.py _SnapshotSave.capture``) brings the
seen-set's two arrays (2^``slots`` ``uint32`` each) and the level's rows
(``rows`` x ``width`` ``uint8``, sliced off a queue) to the host while the
device waits.  This times those copies on the device jax finds, at
``mcraft3``'s level-10 sizes by default: each array alone, the three one
after another, the three started together (``copy_to_host_async``, as the
capture does), and the rows in four pieces started together.  One JSON
line a measurement; the first of each kind compiles its slice and is
printed apart (``first``).  A rate read here is the device link's and the
host's relayout together, as the capture pays them.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def timed(fn):
    t0 = time.perf_counter()
    nbytes = fn()
    return time.perf_counter() - t0, nbytes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=548904)
    ap.add_argument("--width", type=int, default=474)
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    queue_rows = max(args.rows, 1 << 21) if dev.platform != "cpu" \
        else args.rows
    fresh = jax.jit(lambda a, k: a + k)     # new buffers, nothing cached

    def arrays(k):
        q = fresh(jnp.zeros((queue_rows, args.width), jnp.uint8),
                  jnp.uint8(k))
        hi = fresh(jnp.zeros((1 << args.slots,), jnp.uint32), jnp.uint32(k))
        lo = fresh(jnp.zeros((1 << args.slots,), jnp.uint32),
                   jnp.uint32(k + 1))
        jax.block_until_ready((q, hi, lo))
        return q, hi, lo

    def keys_alone(q, hi, lo):
        return np.asarray(hi).nbytes + np.asarray(lo).nbytes

    def rows_alone(q, hi, lo):
        return np.asarray(q[:args.rows]).nbytes

    def one_after_another(q, hi, lo):
        return keys_alone(q, hi, lo) + rows_alone(q, hi, lo)

    def started_together(q, hi, lo):
        rows = q[:args.rows]
        for arr in (hi, lo, rows):
            arr.copy_to_host_async()
        return sum(np.asarray(a).nbytes for a in (hi, lo, rows))

    def rows_in_four(q, hi, lo):
        step = -(-args.rows // 4)
        pieces = [q[i:min(i + step, args.rows)]
                  for i in range(0, args.rows, step)]
        for arr in pieces:
            arr.copy_to_host_async()
        return sum(np.asarray(a).nbytes for a in pieces)

    def contiguous_copy(q, hi, lo):
        rows = np.asarray(q[:args.rows])
        t0 = time.perf_counter()
        out = np.ascontiguousarray(rows).astype(np.uint8, casting="safe",
                                                copy=False)
        print(json.dumps({
            "what": "ascontiguousarray_of_fetched_rows",
            "c_contiguous": bool(rows.flags.c_contiguous),
            "strides": list(rows.strides),
            "seconds": round(time.perf_counter() - t0, 6)}), flush=True)
        return out.nbytes

    for fn in (keys_alone, rows_alone, one_after_another, started_together,
               rows_in_four, contiguous_copy):
        for rep in range(args.repeats + 1):
            made = arrays(rep)              # outside the clock
            seconds, nbytes = timed(lambda: fn(*made))
            print(json.dumps({
                "what": fn.__name__, "first": rep == 0,
                "seconds": round(seconds, 6), "mb": round(nbytes / 1e6, 1),
                "mb_s": round(nbytes / 1e6 / max(seconds, 1e-9), 1)}),
                flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
