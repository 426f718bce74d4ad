"""Reader ``served``: what the checker service adds around its engines, over
the jobs of a ``served_loop`` window (``run["served"]``; a run of any other
kind gives None in every mode).

The program writes (``raft_tla_tpu/serving/manager.py``, ``server.py``):
one ``job_end`` event a job the executor finished (``queue_wait_s``,
``run_s``, ``engine_wall_s``, ``turnaround_s``, ``cached``,
``result_bytes``) in the service's own log, and inside a profiler capture
the spans ``raft.job`` (the executor, pick to terminal state, one a job),
within it ``raft.job_setup``, the engine's ``raft.run``,
``raft.job_respond`` and ``raft.journal``, and on the handler threads
``raft.request/<op>`` and ``raft.result_wait``.  A program without them
(the parent of the PR that added them) leaves nothing to read.

The executor is one thread, and the profiler keeps a line a thread: the
executor's is the host line that holds the ``raft.job`` spans
(``executor_line``).  ``lines(run)`` loads the newest capture's host
lines once, each ``[[span, start_ns, duration_ns], ...]``; the device's
operations and modules come from ``readers/spans.py capture``.  A job
counts as executed where a ``run`` span lies inside its ``job`` span (a
result-cache hit has none).  The steady span is ``readers/xplane.py``'s:
first to last execution of a program whose name holds ``chunk`` (the two
BFS engines' ``jit_chunk`` and the walk's ``jit_chunk_fn``); a capture
that does not cover the window (``xplane.COVERS``) is not read.

Modes of ``read``:
  overhead_ms     mean over the executed jobs of ``raft.job`` less the
                  ``raft.run`` inside it, in ms
  respond_ms      mean over the executed jobs of their ``raft.job_respond``
  turnaround_p95  95th percentile (nearest rank) of ``job_end.
                  turnaround_s`` over the window's jobs, hits included
  device_idle     ``readers/xplane.py``'s idle share of the steady span
  idle_between_jobs  100 * device-idle time of the steady span that lies
                  under no ``raft.run`` of the executor / the steady span
"""

from __future__ import annotations

import glob
import math
import os

import numpy as np

import bench_lib as lib

PREFIX = "raft."
PARTS = ("job_setup", "run", "job_respond", "journal")


def load_lines(trace_dir: str):
    """{line key: [[span, start_ns, duration_ns], ...]} of every host line
    of the newest capture that holds a ``raft.`` annotation."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    from jax.profiler import ProfileData
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            events = [[ev.name[len(PREFIX):], int(ev.start_ns),
                       int(ev.duration_ns)] for ev in line.events
                      if ev.name.startswith(PREFIX)]
            if events:
                out[f"{plane.name}#{n}:{line.name}"] = sorted(
                    events, key=lambda e: (e[1], -e[2]))
    return out


def lines(run: dict):
    if "_served_lines" not in run:
        run["_served_lines"] = (load_lines(run["trace_dir"])
                                if run.get("trace_dir") else None)
        for key, events in (run["_served_lines"] or {}).items():
            names = {}
            for e in events:
                names[e[0]] = names.get(e[0], 0) + 1
            print(f"host line {key}: " + ", ".join(
                f"{k} {v}" for k, v in sorted(names.items(),
                                              key=lambda kv: -kv[1])[:8]),
                flush=True)
    return run["_served_lines"]


def executor_line(host_lines: dict):
    """The events of the line that holds the ``job`` spans (the one with
    most, should a capture ever show two)."""
    best = max(host_lines.values(), default=None,
               key=lambda evs: sum(e[0] == "job" for e in evs))
    if not best or not any(e[0] == "job" for e in best):
        return None
    return best


def jobs_of(events: list) -> list:
    """[{"job": (start, dur), "class": .., <part>: ns, ...}] a ``job`` span
    of the executor's line, with the nanoseconds of each of ``PARTS``
    inside it (``run``: the outermost ones, an engine's replay opens
    none)."""
    out = []
    parts = [e for e in events if e[0] in PARTS]
    for name, start, dur in events:
        if name != "job":
            continue
        rec = {"job": (start, dur)}
        for part in PARTS:
            rec[part] = sum(
                d for n, s, d in parts
                if n == part and s >= start and s + d <= start + dur)
        out.append(rec)
    return out


def table(run: dict):
    """The executed jobs of the capture, printed once as where a served
    job's time goes."""
    if "_served_jobs" not in run:
        host = lines(run)
        events = executor_line(host) if host else None
        jobs = jobs_of(events) if events else []
        run["_served_jobs"] = jobs
        ran = [j for j in jobs if j["run"] > 0]
        if ran:
            ms = lambda key: sum(j[key] for j in ran) / len(ran) / 1e6  # noqa: E731
            whole = sum(j["job"][1] for j in ran) / len(ran) / 1e6
            print(f"served jobs in the capture: {len(jobs)}, of them "
                  f"{len(ran)} executed; a job executed, ms: job "
                  f"{whole:.2f} = " + ", ".join(
                      f"{p} {ms(p):.2f}" for p in PARTS)
                  + f", rest {whole - sum(ms(p) for p in PARTS):.2f}",
                  flush=True)
            hits = [j for j in jobs if j["run"] == 0]
            if hits:
                print(f"a hit, ms: job "
                      f"{sum(j['job'][1] for j in hits) / len(hits) / 1e6:.3f}"
                      f", journal "
                      f"{sum(j['journal'] for j in hits) / len(hits) / 1e6:.3f}",
                      flush=True)
    return run["_served_jobs"]


def idle_outside_runs(cap: dict, events: list, chunk_program: str):
    """(idle ns under no ``run`` span, steady span ns)."""
    spans = lib.load_module("readers", "spans")
    steady = spans.steady_span(cap, chunk_program)
    if steady is None:
        return None
    lo, hi = steady
    starts, ends = spans.busy_intervals(cap)
    edges, at = [], lo
    for name, start, dur in events:
        if name != "run" or start + dur <= at or start >= hi:
            continue
        if start > at:
            edges.append((at, start))
        at = max(at, min(start + dur, hi))
    if hi > at:
        edges.append((at, hi))
    idle = 0
    for g0, g1 in edges:
        b0, b1 = spans.busy_before(starts, ends, np.asarray([g0, g1]))
        idle += (g1 - g0) - int(b1 - b0)
    return idle, hi - lo


def read(run: dict, mode: str):
    served = run.get("served")
    if not served:
        return None
    if mode == "turnaround_p95":
        turn = sorted(e["turnaround_s"] for e in served["job_ends"]
                      if e.get("turnaround_s") is not None)
        if not turn:
            return None
        return turn[math.ceil(0.95 * len(turn)) - 1]
    xplane = lib.load_module("readers", "xplane")
    if mode == "device_idle":
        return xplane.read(run, "idle_share")
    jobs = table(run)
    ran = [j for j in jobs if j["run"] > 0]
    if mode in ("overhead_ms", "respond_ms"):
        if not ran:
            return None
        if mode == "respond_ms":
            return sum(j["job_respond"] for j in ran) / len(ran) / 1e6
        return sum(j["job"][1] - j["run"] for j in ran) / len(ran) / 1e6
    if mode == "idle_between_jobs":
        cap = lib.load_module("readers", "spans").capture(run)
        events = executor_line(lines(run) or {})
        if not cap or events is None:
            return None
        found = idle_outside_runs(cap, events,
                                  run.get("chunk_program", "chunk"))
        if found is None or (found[1] / 1e9
                             < xplane.COVERS * run["window_wall_s"]):
            return None
        return 100.0 * found[0] / found[1]
    raise ValueError(f"served reader: unknown mode {mode!r}")
