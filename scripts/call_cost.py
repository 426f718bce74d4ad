#!/usr/bin/env python3
"""What the host half of one device call costs, in microseconds.

    python3 scripts/call_cost.py [<checkout>] [--calls 10000]

A loop of ``--calls`` calls with the spans, counters and the ring record
a host loop of ``<checkout>``'s ``raft_tla_tpu`` writes a call
(``engine/bfs.py``: ``chunk``, ``trace_flush`` twice, ``stats_fetch``,
``account``, ``_count_chunk_call``'s four counters and six gauges).  The
host half is timed ALONE (``host_*``: no program, a host scalar "fetched":
a device round trip is 0.9 ms on a v5e and its jitter hides microseconds)
and, a fifth as often, against a zero-trip program (one jitted function of
a scalar, fetched each call) on the device jax finds (``bare_us``,
``spans_record_us``).  A checkout with ``obs/calls.py`` (PR 52 on) writes
one ``call`` row a call through ``CallLog``; an older one calls the
rate-limited ``FlightRecorder.progress``.  One JSON line, with what one
read of each clock costs on this machine, what making and dropping a
record of a row's size costs, and on a checkout that has it the loop with
the collector's hook taken off.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time


def main() -> int:
    argv = sys.argv[1:]
    n = 10000
    if "--calls" in argv:
        i = argv.index("--calls")
        n = int(argv[i + 1])
        del argv[i:i + 2]
    root = os.path.abspath(argv[0]) if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from raft_tla_tpu.obs import MetricsRegistry
    from raft_tla_tpu.obs import metrics as metrics_mod
    from raft_tla_tpu.obs.flight import RECORDER
    try:
        from raft_tla_tpu.obs.calls import CallLog
    except ImportError:
        CallLog = None
    metrics_mod.watch_compiles()

    program = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.int32(0))
    np.asarray(program(x))
    mt = MetricsRegistry()

    def bare():
        return np.asarray(program(x))

    def count(st):
        mt.counter("engine/chunk_calls")
        mt.counter("engine/passes", 1)
        mt.counter("engine/inv_lanes", 16)
        mt.counter("engine/parents_expanded", 1)
        mt.counter("engine/distinct", 1)
        mt.counter("engine/generated", 1)
        for g in ("seen_size", "seen_capacity", "next_count", "diameter"):
            mt.gauge("engine/" + g, 1)

    host_value = np.int32(0)

    def spans(record, program=lambda _x: host_value):
        call = int(mt.counter_value("engine/chunk_calls")) + 1
        with mt.phase_timer("chunk", call=call) as a:
            out = program(x)
        with mt.phase_timer("trace_flush") as f:
            pass
        with mt.phase_timer("stats_fetch") as b:
            st = np.asarray(out)
        acc = mt.open_span("account", call=call, passes=1)
        count(st)
        if record is not None:
            record(call, a, b, f, acc)
        acc.close()
        with mt.phase_timer("trace_flush"):
            pass
        return st

    def timed(fn, *args, calls=n):
        for _ in range(200):
            fn(*args)
        best = None
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(calls):
                fn(*args)
            us = (time.perf_counter() - t0) / calls * 1e6
            best = us if best is None else min(best, us)
        return round(best, 3)

    if CallLog is not None:
        log = CallLog(1)
        log.start()

        def record(call, a, b, f, acc):
            log.dispatch()
            log.row("chunk", "full", 1, a.seconds, b.seconds, f.seconds,
                    0.0, call, 1, 16, 1, 1, distinct=call, generated=call,
                    diameter=0, frontier=1, offset=1, next_count=1,
                    seen_size=call)
    else:
        def record(call, a, b, f, acc):
            RECORDER.progress(
                distinct=call, generated=call, diameter=0, frontier=1,
                offset=1, next_count=1, seen_size=call,
                elapsed=round(time.time(), 3))

    out = {"checkout": root, "device": str(jax.devices()[0]),
           "calls": n, "rows": CallLog is not None,
           "host_spans_us": timed(spans, None),
           "host_spans_record_us": timed(spans, record)}
    out["host_record_cost_us"] = round(
        out["host_spans_record_us"] - out["host_spans_us"], 3)
    on_gc = getattr(metrics_mod, "_on_gc", None)
    if on_gc is not None and on_gc in gc.callbacks:
        gc.callbacks.remove(on_gc)
        out["host_spans_record_no_gc_hook_us"] = timed(spans, record)
        gc.callbacks.append(on_gc)
    for clock in ("perf_counter", "thread_time", "process_time", "time",
                  "monotonic"):
        out[clock + "_us"] = timed(getattr(time, clock))
    # What a record of a row's size costs to make and drop: a dict of 27
    # keys is 832 bytes, over the 512 the interpreter's own allocator
    # serves, a dict of 8 or a tuple of 27 is under it.
    keys = [f"k{i}" for i in range(27)]
    out["dict27_us"] = timed(lambda: dict.fromkeys(keys, 0.5))
    out["dict8_us"] = timed(lambda: dict.fromkeys(keys[:8], 0.5))
    out["tuple27_us"] = timed(lambda: tuple(keys))
    out["bare_us"] = timed(bare, calls=n // 5)
    out["spans_record_us"] = timed(spans, record, program, calls=n // 5)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
