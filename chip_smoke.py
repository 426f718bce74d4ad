#!/usr/bin/env python3
"""The quickest proof that the checker still starts, runs and is right on
the chip.

    python chip_smoke.py             one TPU chip, five phases
    python chip_smoke.py --chips 4   four chips: the mesh engine and what
                                     it is compared with, nothing else

One process, which holds the chip(s) for its whole life; every phase is a
hard failure.  The last line of stdout is one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only after every phase ran on ``platform: tpu``.  A run
that finds no TPU exits non-zero and prints no result.  Timings printed on
the way are *smoke timings* on the named ``device_kind``, not benchmark
results.

Phases (one chip):
  1 device      jax.devices() is a TPU; versions, memory limit, cache dir
  2 exhaustive  ``python -m raft_tla_tpu check configs/MCraft_bounded.cfg``
                at the bench's device-resident sizes, through cli.main, to
                ``--max-diameter``; every level equals the Python-oracle
                record artifacts/mcraft_L14_oracle.jsonl
  3 counterexample  configs/MCraft_noleader.cfg: violation found, replayed
                trace legal under models/oracle.py, native trace store
  4 swarm       the randomized-walk tier finds the same violation
  5 server      raft_tla_tpu.server in a thread: ping, check, warm check,
                stats

The compile cache is wherever the program's own rule puts it
(utils/platform.py); this script sets none.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import socket
import sys
import tempfile
import threading
import time
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import raft_tla_tpu  # noqa: E402,F401  (fails here where the repo is absent)

BOUNDED_CFG = os.path.join(HERE, "configs", "MCraft_bounded.cfg")
NOLEADER_CFG = os.path.join(HERE, "configs", "MCraft_noleader.cfg")
ORACLE = os.path.join(HERE, "artifacts", "mcraft_L14_oracle.jsonl")



class Sizes(NamedTuple):
    batch: int
    queue_capacity: int
    seen_capacity: int
    server_depth: int


# The bench's device-resident sizes (bench.py): three ~1 GB queues and a
# 256 MB fingerprint table on the chip.  main() runs nothing else; the
# CPU rehearsal (tests/test_chip_smoke.py) calls the phases at a tiny one.
REAL = Sizes(batch=2048, queue_capacity=1 << 21, seen_capacity=1 << 25,
             server_depth=6)


def say(msg: str) -> None:
    print(msg, flush=True)


def oracle_levels(depth: int) -> list:
    """[(frontier, distinct, generated)] for levels 0..depth from the
    pinned Python-oracle record."""
    rows = {}
    with open(ORACLE) as f:
        for line in f:
            r = json.loads(line)
            rows[r["level"]] = (r["frontier"], r["distinct"], r["generated"])
    assert all(lv in rows for lv in range(depth + 1)), (
        f"oracle record covers levels {sorted(rows)}, need 0..{depth}")
    return [rows[lv] for lv in range(depth + 1)]


def read_events(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_cli(argv: list) -> tuple:
    """raft_tla_tpu.cli.main(argv) in this process — the entry point
    ``python -m raft_tla_tpu`` calls — returning (exit code, stdout)."""
    from raft_tla_tpu.cli import main
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    return rc, buf.getvalue()


def level_rows(events: list) -> list:
    return [(e["frontier_rows"], e["distinct"], e["generated"])
            for e in events if e["event"] == "level_complete"]


def assert_levels_equal_oracle(got: list, depth: int, what: str) -> None:
    want = oracle_levels(depth)
    assert len(got) == len(want), (
        f"{what}: ran {len(got)} levels, expected {len(want)}")
    for lv, (g, w) in enumerate(zip(got, want)):
        assert g == w, (f"{what}: level {lv} (frontier, distinct, "
                        f"generated) = {g}, oracle says {w}")


def assert_legal_trace(steps, dims, what: str) -> None:
    """Every step of a replayed counterexample is a transition the
    independent Python oracle allows."""
    from raft_tla_tpu.models import oracle as orc
    assert steps and steps[0][0] == -1, f"{what}: trace has no root"
    for (_a, prev), (act, nxt) in zip(steps, steps[1:]):
        assert nxt in orc.successor_set(prev, dims), (
            f"{what}: step {dims.describe_instance(act)} is not a legal "
            f"transition under models/oracle.py")


# -- phase 1 ---------------------------------------------------------------

def phase_device(chips: int) -> dict:
    import importlib.metadata as md

    import jax

    from raft_tla_tpu.utils.platform import (cache_dir,
                                             enable_persistent_cache)
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (first device: {d0}, platform "
              f"{d0.platform!r}); nothing was run", file=sys.stderr)
        sys.exit(3)
    assert len(devs) == chips, (
        f"asked for {chips} chip(s), jax.devices() has {len(devs)}")
    enable_persistent_cache()       # the program's rule, not this script's
    limit = d0.memory_stats()["bytes_limit"]
    say(f"[1 device] platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)} bytes_limit={limit}")
    say(f"[1 device] jax={jax.__version__} "
        f"jaxlib={md.version('jaxlib')} libtpu={md.version('libtpu')}")
    say(f"[1 device] compile cache: {cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f")")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "bytes_limit": int(limit)}


# -- phase 2 ---------------------------------------------------------------

def check_argv(sizes: Sizes, depth: int, events: str, *extra: str) -> list:
    return ["check", BOUNDED_CFG, "--batch", str(sizes.batch),
            "--queue-capacity", str(sizes.queue_capacity),
            "--seen-capacity", str(sizes.seen_capacity),
            "--max-diameter", str(depth), "--events-out", events, *extra]


def assert_clean_run(events: list, batch: int, what: str) -> dict:
    """From the run's own event log: it ended at the diameter budget with
    no error, never degraded, and ran at the batch that was asked for."""
    degraded = [e for e in events if e["event"] == "degraded"]
    assert not degraded, f"{what}: degraded events {degraded}"
    starts = [e for e in events if e["event"] == "run_start"]
    assert len(starts) == 1 and starts[0]["batch"] == batch, (
        f"{what}: run_start {starts}")
    end = [e for e in events if e["event"] == "run_end"][-1]
    assert end["error"] is None and end["stop_reason"] == "diameter_budget", (
        f"{what}: run_end stop_reason={end['stop_reason']!r} "
        f"error={end['error']!r}")
    return end


def phase_exhaustive(depth: int, device: dict, tmp: str,
                     sizes: Sizes = REAL) -> None:
    ev = os.path.join(tmp, "exhaustive.jsonl")
    t0 = time.perf_counter()
    rc, out = run_cli(check_argv(sizes, depth, ev))
    wall = time.perf_counter() - t0
    assert rc == 0, f"check exited {rc}:\n{out[-2000:]}"
    events = read_events(ev)
    assert_levels_equal_oracle(level_rows(events), depth, "exhaustive")
    end = assert_clean_run(events, sizes.batch, "exhaustive")
    assert "pipeline           v2" in out, (
        f"pipeline=auto did not resolve to v2:\n{out[-1500:]}")
    mem = end["memory"]
    if device["platform"] == "tpu":     # the CPU backend reports nothing
        # The memory block is the device's own report, not an assumed
        # limit: same bytes_limit as phase 1 read, and a peak that holds
        # at least the three queues and the table this run keeps there.
        assert mem.get("bytes_limit") == device["bytes_limit"], mem
        floor = (3 * sizes.queue_capacity * 473
                 + 8 * sizes.seen_capacity)
        assert mem.get("peak_bytes_in_use", 0) >= floor, (mem, floor)
    compile_s = end["phase_seconds"].get("warmup", 0.0)
    run_s = end["wall_seconds"]
    say(f"[2 exhaustive] depth {depth}: {end['distinct']:,} distinct, "
        f"{end['generated']:,} generated, every level == oracle; "
        f"pipeline v2, batch {sizes.batch}, 0 degraded")
    say(f"[2 exhaustive] smoke timings on {device['kind']}: "
        f"compile(warmup) {compile_s:.1f}s, run {run_s:.1f}s, "
        f"{end['distinct'] / run_s:,.0f} distinct/s, phase wall "
        f"{wall:.1f}s; peak bytes in use "
        f"{mem.get('peak_bytes_in_use')} of {mem.get('bytes_limit')}")
    phases = sorted(end["phase_seconds"].items(), key=lambda kv: -kv[1])
    say("[2 exhaustive] host phases (s, smoke timings): "
        + ", ".join(f"{k} {v:.1f}" for k, v in phases if v >= 0.05))


# -- phase 3 ---------------------------------------------------------------

def assert_native_trace_store(eng) -> str:
    """The store that served the trace is the C++ one, built in this
    checkout from the tracked source (content hash beside the .so)."""
    from raft_tla_tpu import native
    store = type(eng.trace).__name__
    assert store == "NativeTraceStore", (
        f"trace store is {store}: the native build failed or fell back")
    assert os.path.dirname(native._SO) == os.path.join(
        HERE, "raft_tla_tpu", "native"), native._SO
    with open(native._SO + ".sha256") as f:
        assert f.read().strip() == native.source_digest(native._SRC), (
            "libraftnative.so was not built from trace_store.cpp as tracked")
    return store


def phase_counterexample() -> None:
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.dims import LEADER
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(NOLEADER_CFG)
    t0 = time.perf_counter()
    eng = make_engine(setup)        # cfg directives, tracing on (default)
    res = eng.run(initial_states(setup))
    assert res.violation is not None, (
        f"no violation found ({res.stop_reason}, {res.distinct} distinct)")
    assert res.violation.invariant == "NoLeaderElected", res.violation
    steps = eng.replay(res.violation.fingerprint)
    assert_legal_trace(steps, setup.dims, "counterexample")
    assert LEADER in steps[-1][1].role, "last state elects no leader"
    store = assert_native_trace_store(eng)
    say(f"[3 counterexample] {res.violation.invariant} violated at depth "
        f"{len(steps) - 1}; replayed trace legal under the oracle; "
        f"trace store {store} (built here); "
        f"{time.perf_counter() - t0:.1f}s")


# -- phase 4 ---------------------------------------------------------------

def phase_swarm() -> None:
    from raft_tla_tpu.engine.check import (initial_states,
                                           make_swarm_engine)
    from raft_tla_tpu.models.dims import LEADER
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(NOLEADER_CFG)
    seed, walks = 7, 1024
    t0 = time.perf_counter()
    # check --mode swarm --walks 1024 --max-depth 64, as cli._run_swarm
    # builds it (the slice width: the cfg's BATCH directive).
    eng = make_swarm_engine(setup, walks=walks, max_depth=64)
    res = eng.run(initial_states(setup, seed=seed), seed=seed,
                  max_seconds=300.0)
    assert res.violation is not None, (
        f"swarm found no violation ({res.stop_reason}, {res.steps} steps)")
    steps = eng.replay(res.violation.fingerprint)
    assert_legal_trace(steps, setup.dims, "swarm")
    assert LEADER in steps[-1][1].role, "swarm: last state elects no leader"
    say(f"[4 swarm] {walks} walks, seed {seed}: {res.violation.invariant} "
        f"violated after {res.steps:,} steps at depth {len(steps) - 1}; "
        f"trace legal under the oracle; {time.perf_counter() - t0:.1f}s")


# -- phase 5 ---------------------------------------------------------------

def roundtrip(addr, req: dict) -> dict:
    with socket.create_connection(addr, timeout=900) as s:
        s.sendall((json.dumps(req) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)


def phase_server(platform: str, sizes: Sizes = REAL) -> None:
    from raft_tla_tpu import server as srv_mod
    depth = sizes.server_depth
    _f, distinct, generated = oracle_levels(depth)[-1]
    with open(BOUNDED_CFG) as f:
        cfg_text = f.read()
    # A thread, not a child: the chip belongs to this process.
    srv = srv_mod.serve(port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        addr = srv.server_address
        ping = roundtrip(addr, {"op": "ping"})
        assert ping == {"ok": True, "platform": platform,
                        "wait": True}, ping
        req = {"op": "check", "cfg_text": cfg_text, "batch": 512,
               "max_diameter": depth, "queue_capacity": 1 << 16,
               "seen_capacity": 1 << 19}

        def counters():
            st = roundtrip(addr, {"op": "stats"})
            assert st["ok"] is True, st
            c = st["metrics"]["counters"]
            return (c.get("server/engine_cache/hits", 0),
                    c.get("server/engine_cache/misses", 0)), st

        (h0, m0), _ = counters()
        t0 = time.perf_counter()
        cold = roundtrip(addr, req)
        t_cold = time.perf_counter() - t0
        (h1, m1), _ = counters()
        t0 = time.perf_counter()
        warm = roundtrip(addr, req)
        t_warm = time.perf_counter() - t0
        (h2, m2), st = counters()
        for name, resp in (("first", cold), ("repeat", warm)):
            assert resp["ok"] is True, resp
            assert (resp["distinct"], resp["generated"],
                    resp["diameter"]) == (distinct, generated, depth), (
                name, resp["distinct"], resp["generated"], resp["diameter"])
        # First request built the engine; the repeat was served by it.
        assert (h1 - h0, m1 - m0) == (0, 1), (h0, m0, h1, m1)
        assert (h2 - h1, m2 - m1) == (1, 0), (h1, m1, h2, m2)
        assert st["engine_cache"]["size"] >= 1, st["engine_cache"]
        say(f"[5 server] ping platform={ping['platform']}; check by "
            f"cfg_text to depth {depth}: {distinct:,} distinct == oracle, "
            f"twice; first {t_cold:.1f}s (engine build), repeat "
            f"{t_warm:.1f}s (engine-cache hit, no rebuild)")
    finally:
        srv.shutdown()
        srv.server_close()
        t.join(timeout=30)


# -- four chips --------------------------------------------------------------

def phase_mesh(depth: int, device: dict, tmp: str,
               sizes: Sizes = REAL) -> None:
    """MeshBFSEngine over the four chips against the oracle record and
    against BFSEngine on devices[0], same depth, same process; and the
    seen-set and frontier really spread over the chips."""
    ev_mesh = os.path.join(tmp, "mesh.jsonl")
    t0 = time.perf_counter()
    rc, out = run_cli(check_argv(sizes, depth, ev_mesh, "--no-trace",
                                 "--engine", "mesh"))
    t_mesh = time.perf_counter() - t0
    assert rc == 0, f"mesh check exited {rc}:\n{out[-2000:]}"
    mesh_events = read_events(ev_mesh)
    mesh_levels = level_rows(mesh_events)
    assert_levels_equal_oracle(mesh_levels, depth, "mesh")
    end = assert_clean_run(mesh_events, sizes.batch, "mesh")
    assert [e for e in mesh_events if e["event"] == "run_start"][0][
        "engine"] == "MeshBFSEngine"
    per_chip = end["devices_memory"]
    assert len(per_chip) == device["count"], per_chip
    say(f"[mesh] depth {depth}: {end['distinct']:,} distinct, every level "
        f"== oracle; {t_mesh:.1f}s phase wall, run {end['wall_seconds']:.1f}s"
        f" (smoke timing on {device['count']} x {device['kind']})")
    if device["platform"] == "tpu":     # the CPU backend reports nothing
        peaks = [m["peak_bytes_in_use"] for m in per_chip]
        say(f"[mesh] per-chip peak bytes in use: {peaks}; bytes in use "
            f"at run end: {[m['bytes_in_use'] for m in per_chip]}")
        # Sharded, not piled on the first chip: every chip's peak
        # within a small factor of every other's.
        assert min(peaks) > 0 and max(peaks) <= 1.5 * min(peaks), peaks

    ev_one = os.path.join(tmp, "one.jsonl")
    t0 = time.perf_counter()
    rc, out = run_cli(check_argv(sizes, depth, ev_one, "--no-trace",
                                 "--engine", "single"))
    t_one = time.perf_counter() - t0
    assert rc == 0, f"single-chip check exited {rc}:\n{out[-2000:]}"
    one_events = read_events(ev_one)
    one_end = assert_clean_run(one_events, sizes.batch, "single")
    assert level_rows(one_events) == mesh_levels, (
        "mesh and single-chip per-level counts differ")
    say(f"[mesh] BFSEngine on devices[0] to depth {depth}: same per-level "
        f"counts; {t_one:.1f}s phase wall, run "
        f"{one_end['wall_seconds']:.1f}s (smoke timing)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh engine over four chips "
                         "and what it is compared with")
    ap.add_argument("--max-diameter", type=int, default=11,
                    help="BFS depth of the exhaustive run (>= 10; the "
                         "oracle record reaches 13)")
    args = ap.parse_args(argv)
    assert args.max_diameter >= 10, "the smoke runs to diameter 10 at least"
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phase_mesh(args.max_diameter, device, tmp)
        else:
            phase_exhaustive(args.max_diameter, device, tmp)
            phase_counterexample()
            phase_swarm()
            phase_server(device["platform"])
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f}s "
        f"wall (depth {args.max_diameter})")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
