"""Reader ``spans``: the program's own spans, read from the profiler's
capture, and the device's idle time put down to them.

Inside a capture every span the engine opens (``MetricsRegistry.
phase_timer``/``scope`` in ``raft_tla_tpu/obs/metrics.py``, through
``SpanTracer.begin`` in ``obs/tracing.py``) is a
``jax.profiler.TraceAnnotation`` on the ``/host:CPU`` plane's ``python``
line, named ``raft.<span>``, its keyword arguments as event stats
(``run``, ``call``, ``passes``, ...), on the same clock as the device's
``XLA Ops``.  A program without those annotations (the parent of the PR
that added them) leaves nothing to read, and every mode returns None.

``capture(run)`` loads the newest ``.xplane.pb`` once into plain lists (so
a recorded capture kept beside the tests checks the arithmetic without a
chip; ``benchmark/tests/data/capture_small.json`` is one).  Names and
times come through ``jax.profiler.ProfileData``; an operation's scope
path is a stat of its event METADATA (``tf_op`` on a TPU), which that
class does not show, so the metadata table of the device's plane is
read from the file itself (``metadata_stats``: the few fields of
``xplane.proto`` it needs, decoded by hand):

  host     [[span, start_ns, duration_ns, {stat: value}], ...]  main thread
  modules  [[name, start_ns, duration_ns], ...]   first device, XLA Modules
  ops      [[op index, start_ns, duration_ns], ...]   first device, XLA Ops
           (an int64 array of that shape when loaded from a file)
  op_names, op_paths   per op index: the instruction's name, and the
           scope path jax gave it (``jit(chunk)/../while/body/masks/..:``)

Idle is what ``readers/xplane.py`` calls idle: the part of the steady
span (first to last execution of the chunk program) in which no device
operation ran.  Each instant of it is charged to the innermost span open
on the host at that instant — every gap, however short, cut at span
boundaries — or to ``outside`` where no span was open (between the runs of
a verdict window that is the harness's own legality check).

Modes of ``read``:
  idle        100 * idle under the ``spans`` named (``self_only``: only
              where that span was the innermost; else its whole subtree)
              / steady span.  Needs a capture that covers the window
              (``xplane.COVERS``).
  per_run_ms  the ``spans`` named, summed, in ms per ``raft.run`` span in
              the capture (a verdict).  Needs a covering capture too.
  per_call_ms seconds of the phases named (``EngineResult.phases``, the
              span's histogram: whole window, traced or not) * 1000 /
              chunk calls counted by the engine (``run_end.chunk_calls``)
"""

from __future__ import annotations

import array
import glob
import os
import re

import numpy as np

import bench_lib as lib

PREFIX = "raft."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_LINE = re.compile(r"^python")
COVERS = 0.8            # as readers/xplane.py: steady span / window wall
# Spans that hold other spans: idle under one of these and under no leaf
# is idle nobody has put a name to.
CONTAINERS = ("run", "level", "replay")


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wire}")


def metadata_stats(path: str, plane_name: str) -> dict:
    """{event name: {stat name: string value}} from the event metadata
    of one plane of an ``.xplane.pb``.  In ``xplane.proto``: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps:
    key = 1, value = 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (the id of a stat metadata whose name is the value)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == plane_name
                   for f, v in parts):
            continue
        stat_names, events = {}, []
        for f, v in parts:
            if f in (4, 5):
                entry = dict(_fields(v))
                value = dict((k, x) for k, x in _fields(entry.get(2, b""))
                             if k == 2)
                if f == 5:
                    stat_names[entry.get(1, 0)] = bytes(
                        value.get(2, b"")).decode(errors="replace")
                else:
                    events.append(entry.get(2, b""))
        out = {}
        for meta in events:
            name, stats = "", {}
            for f, v in _fields(meta):
                if f == 2:
                    name = bytes(v).decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1))
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
            out[name] = stats
        return out
    return {}


def scope_path(stats: dict) -> str:
    """The scope path among an operation's stats: ``tf_op`` on a TPU
    (jax 0.9.0, libtpu 0.0.34), ``<op_name>:<op type>``."""
    return stats.get("tf_op", "")


def load(trace_dir: str):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    from jax.profiler import ProfileData
    newest = max(paths, key=os.path.getmtime)
    data = ProfileData.from_file(newest)
    devices = sorted(p.name for p in data.planes
                     if DEVICE_PLANE.match(p.name))
    cap = {"host": [], "modules": [], "ops": [], "op_names": [],
           "op_paths": []}
    index = {}
    ids, starts, durs = array.array("q"), array.array("q"), array.array("q")
    metadata = metadata_stats(newest, devices[0]) if devices else {}
    for plane in data.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    cap["modules"] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        i = index.get(name)
                        if i is None:
                            # Looked up once per distinct operation.
                            i = index[name] = len(cap["op_names"])
                            cap["op_names"].append(short_name(name))
                            cap["op_paths"].append(
                                scope_path(metadata.get(name, {})))
                        ids.append(i)
                        starts.append(int(ev.start_ns))
                        durs.append(int(ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if HOST_LINE.match(line.name):
                    cap["host"].extend(
                        [ev.name[len(PREFIX):], int(ev.start_ns),
                         int(ev.duration_ns), dict(ev.stats)]
                        for ev in line.events
                        if ev.name.startswith(PREFIX))
    cap["host"].sort(key=lambda e: (e[1], -e[2]))
    cap["ops"] = np.column_stack([np.asarray(a, np.int64)
                                  for a in (ids, starts, durs)])
    return cap


def capture(run: dict):
    """This run's capture, loaded once (None without one)."""
    if "_capture" not in run:
        run["_capture"] = (load(run["trace_dir"])
                           if run.get("trace_dir") else None)
    return run["_capture"]


def steady_span(cap: dict, chunk_program: str = "chunk"):
    """(start, end) from the first to the last execution of the chunk
    program, as ``readers/xplane.py`` takes it."""
    mods = [m for m in cap["modules"] if chunk_program in m[0]]
    if not mods:
        return None
    return min(m[1] for m in mods), max(m[1] + m[2] for m in mods)


def busy_intervals(cap: dict):
    """Merged [start, end) intervals in which a device operation ran, as
    two sorted arrays."""
    ops = np.asarray(cap["ops"], np.int64).reshape(-1, 3)
    if not len(ops):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(ops[:, 1], kind="stable")
    starts = ops[order, 1]
    ends = np.maximum.accumulate(starts + ops[order, 2])
    first = np.ones(len(starts), bool)
    first[1:] = starts[1:] > ends[:-1]
    last = np.append(first[1:], True)
    return starts[first], ends[last]


def busy_before(starts, ends, t):
    """Device-busy nanoseconds before each instant of ``t``."""
    t = np.asarray(t, np.int64)
    if not len(starts):
        return np.zeros(t.shape, np.int64)
    cum = np.concatenate([[0], np.cumsum(ends - starts)])
    i = np.searchsorted(starts, t, side="right") - 1
    part = np.where(i >= 0, np.minimum(t, ends[np.maximum(i, 0)])
                    - starts[np.maximum(i, 0)], 0)
    return cum[np.maximum(i, 0)] + part


def innermost_segments(host: list, lo: int, hi: int) -> list:
    """[lo, hi) cut into [(start, end, path)] where ``path`` is the tuple
    of spans open on the host, outermost first (() where none is)."""
    points = []
    for name, start, dur, _stats in host:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            # Ends sort before starts at one instant; of two spans that
            # start together the longer (the outer) opens first.
            points.append((s, 1, -dur, name))
            points.append((e, 0, 0, name))
    points.sort()
    out, stack, at = [], [], lo
    for t, is_start, _d, name in points:
        if t > at:
            out.append((at, t, tuple(stack)))
            at = t
        if is_start:
            stack.append(name)
        elif name in stack:
            # The innermost of that name: spans of one thread nest.
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    if hi > at:
        out.append((at, hi, tuple(stack)))
    return out


def idle_by_span(cap: dict, chunk_program: str = "chunk"):
    """{"span_ns", "idle_ns", "innermost": {span: ns}, "under": {span:
    ns}}: the steady span's idle time by the innermost span open on the
    host (``outside`` where none), and by every span open at all."""
    span = steady_span(cap, chunk_program)
    if span is None:
        return None
    lo, hi = span
    starts, ends = busy_intervals(cap)
    segs = innermost_segments(cap["host"], lo, hi)
    edges = np.asarray([s for s, _e, _p in segs] + [hi], np.int64)
    busy = np.diff(busy_before(starts, ends, edges))
    innermost, under = {}, {}
    for (s, e, path), b in zip(segs, busy):
        idle = int(e - s - b)
        if idle <= 0:
            continue
        leaf = path[-1] if path else "outside"
        innermost[leaf] = innermost.get(leaf, 0) + idle
        for name in set(path):
            under[name] = under.get(name, 0) + idle
    return {"span_ns": hi - lo, "idle_ns": sum(innermost.values()),
            "innermost": innermost, "under": under}


def idle_table(run: dict):
    """The attribution of this run's capture, computed and printed
    once."""
    if "_idle_by_span" not in run:
        cap = capture(run)
        table = (idle_by_span(cap, run.get("chunk_program", "chunk"))
                 if cap and cap["host"] else None)
        run["_idle_by_span"] = table
        if table:
            total = table["idle_ns"] or 1
            rows = sorted(table["innermost"].items(), key=lambda kv: -kv[1])
            print("idle by innermost span, s (share of all idle): "
                  + ", ".join(f"{k} {v / 1e9:.3f} ({100 * v / total:.1f} %)"
                              for k, v in rows), flush=True)
            loose = sum(table["innermost"].get(c, 0) for c in CONTAINERS)
            print(f"idle in a run or a replay under no leaf span: "
                  f"{loose / 1e9:.3f}s = {100 * loose / total:.1f} % of "
                  f"{total / 1e9:.3f}s idle; outside every span (between "
                  f"runs): {table['innermost'].get('outside', 0) / 1e9:.3f}s",
                  flush=True)
    return run["_idle_by_span"]


def covers(run: dict, span_ns: int) -> bool:
    return span_ns / 1e9 >= COVERS * run["window_wall_s"]


def read(run: dict, mode: str, spans=(), self_only: bool = False):
    if mode == "per_call_ms":
        events = lib.load_module("readers", "events")
        calls = events.total(events.run_ends(run), "chunk_calls")
        phases = run.get("phases") or {}
        if not calls or not all(s in phases for s in spans):
            return None
        return 1000.0 * sum(phases[s] for s in spans) / calls
    cap = capture(run)
    if not cap or not cap["host"]:
        return None
    if mode == "idle":
        table = idle_table(run)
        if table is None or not covers(run, table["span_ns"]):
            return None
        by = table["innermost" if self_only else "under"]
        return 100.0 * sum(by.get(s, 0) for s in spans) / table["span_ns"]
    if mode == "per_run_ms":
        span = steady_span(cap, run.get("chunk_program", "chunk"))
        runs = sum(1 for e in cap["host"] if e[0] == "run")
        if span is None or not runs or not covers(run, span[1] - span[0]):
            return None
        return sum(e[2] for e in cap["host"] if e[0] in spans) / 1e6 / runs
    raise ValueError(f"spans reader: unknown mode {mode!r}")
