"""Reader ``xplane``: the reduction from the profiler's trace to device
busy and idle time, time per batch, the operations with most time and the
longest idle gaps.

The harness brackets a ``--trace 1`` window in ``jax.profiler.start_trace``
/ ``stop_trace``; the ``.xplane.pb`` that leaves behind is read with
``jax.profiler.ProfileData`` alone.  The reduction itself (``reduce``)
works on plain tuples, so that a recorded trace kept beside the tests
checks it without a chip.

What is what in a TPU capture:
  plane ``/device:TPU:<n>``   one per chip
    line ``XLA Modules``      one event per executed program (a chunk call)
    line ``XLA Ops``          one event per executed operation, nested
                              where an operation (a ``while``) runs others
  plane ``/host:CPU``         host threads, jax's own spans on them

Busy is the union of the intervals in which an operation ran on the device,
inside the steady span: from the start of the first execution of the
cell's chunk program to the end of the last.  Restore, upload and the
run-end report lie outside that span; they are host time that the
``host_share`` metrics see.

Modes of ``read``:
  idle_share    100 * (1 - busy / span), averaged over the chips used
  roofline      100 * least seconds per batch of parents (benchmark/
                roofline.py over the window's own counts) / device busy
                seconds per batch of parents
  batch_fill    100 * parents expanded / (passes of the chunk program's
                loop, counted in the trace, * batch): a pass takes the
                longest prefix of its batch whose successors fit the K
                compacted lanes, so under 100 % the loop runs more passes
                than the frontier has batches
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
GAP_FLOOR_NS = 50_000          # shorter gaps are launch latency, not idle
LABELLED_GAPS = 400            # the longest gaps, looked up on the host plane
# The host line that carries jax's own spans is the main thread's, named
# after the interpreter as it was started: "python", "python3", ...
HOST_LINE = re.compile(r"^python")
COVERS = 0.8                   # steady span / window wall of a whole capture


def load(trace_dir: str):
    """{plane: {line: [(name, start_ns, duration_ns), ...]}} of the newest
    capture under ``trace_dir`` (the lines ``reduce`` reads, no others),
    or None when there is none."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    from jax.profiler import ProfileData
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            wanted = lambda n: n in (OPS_LINE, MODULES_LINE)  # noqa: E731
        elif plane.name.startswith("/host:"):
            wanted = HOST_LINE.match
        else:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if not wanted(line.name):
                continue
            lines.setdefault(line.name, []).extend(
                (short_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return planes


def short_name(name: str) -> str:
    """The device lines name an operation by its whole HLO text
    (``%fusion.7 = (u32[...]...) fusion(...)``); the instruction's own
    name is the part before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> dict:
    """{name: ns} with each event's nested children taken out of it, so a
    ``while`` is charged only the time in which none of its body ran."""
    out = {}
    stack = []      # [name, end, child_ns]
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, 0, start])
    close(float("inf"))
    return out


def loop_iterations(ops: list) -> int:
    """How often the body of the outermost loop ran, given the operations
    of ONE execution of the chunk program: one iteration is one batch.
    The loop is the longest ``while`` not nested in another operation;
    each operation of its body shows once per iteration, so the count is
    the most common number of times a direct child's name occurs (an
    operation under a conditional may show less often, one shared by two
    call sites more)."""
    stack, top, children = [], None, {}
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if not stack and name.startswith("while") and (
                top is None or dur > top[2]):
            top, children = (name, start, dur), {}
        elif (len(stack) == 1 and top is not None
              and stack[0][0] == top[0] and stack[0][2] == top[1]):
            children[name] = children.get(name, 0) + 1
        stack.append((name, start + dur, start))
    if not children:
        return 0
    counts = {}
    for c in children.values():
        counts[c] = counts.get(c, 0) + 1
    return max(counts, key=lambda c: (counts[c], c))


def label_gaps(gaps: list, host_events: list) -> dict:
    """{label: ns}: each gap under the host span that covers most of it
    (the innermost on a tie), or under what the host shows nothing for.
    Only the ``LABELLED_GAPS`` longest are looked up; the rest are summed
    under one name."""
    import numpy as np
    out = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    rest = sum(g1 - g0 for g0, g1 in gaps[LABELLED_GAPS:])
    if rest:
        out["shorter gaps, not looked up"] = rest
    if host_events:
        names = [e[0] for e in host_events]
        starts = np.array([e[1] for e in host_events], np.int64)
        durs = np.array([e[2] for e in host_events], np.int64)
        ends = starts + durs
    for g0, g1 in gaps[:LABELLED_GAPS]:
        label = "no host span"
        if host_events:
            ov = np.minimum(g1, ends) - np.maximum(g0, starts)
            best = ov.max()
            if best > 0:
                cand = np.flatnonzero(ov == best)
                label = names[cand[np.argmin(durs[cand])]]
        out[label] = out.get(label, 0) + (g1 - g0)
    return out


def reduce(planes: dict, chunk_program: str = "chunk") -> dict | None:
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if not devices:
        return None
    # jax's own spans on the Python thread say what the host was doing.
    host_events = [ev for p, lines in planes.items() if p.startswith("/host:")
                   for ln, evs in lines.items() if HOST_LINE.match(ln)
                   for ev in evs]
    busy_ns = span_ns = 0
    steps = batches = 0
    ops_self, gaps = {}, {}
    for dev in devices:
        ops = planes[dev].get(OPS_LINE, [])
        mods = [m for m in planes[dev].get(MODULES_LINE, [])
                if chunk_program in m[0]]
        if not ops:
            continue
        lo = min(m[1] for m in mods) if mods else min(o[1] for o in ops)
        hi = (max(m[1] + m[2] for m in mods) if mods
              else max(o[1] + o[2] for o in ops))
        inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in ops if s < hi and s + d > lo]
        merged = union([(s, s + d) for _n, s, d in inside])
        busy_ns += sum(e - s for s, e in merged)
        span_ns += hi - lo
        steps = max(steps, len(mods))
        if dev == devices[0]:
            ordered = sorted(inside, key=lambda e: e[1])
            starts = [e[1] for e in ordered]
            for _n, ms, md in mods:
                i, j = (bisect.bisect_left(starts, ms),
                        bisect.bisect_left(starts, ms + md))
                batches += loop_iterations(ordered[i:j])
        for name, ns in self_times(inside).items():
            ops_self[name] = ops_self.get(name, 0) + ns
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        found = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 - g0 >= GAP_FLOOR_NS]
        for lab, ns in label_gaps(found, host_events).items():
            gaps[lab] = gaps.get(lab, 0) + ns
    if not span_ns:
        return None
    n = len(devices)
    top = lambda d: [[k, v / 1e9 / n] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": busy_ns / 1e9 / n, "window_s": span_ns / 1e9 / n,
            "chunk_calls": steps, "batches": batches,
            "device_ops": top(ops_self),
            "idle_gaps": top(gaps)}


def reduction(run: dict):
    """The reduction of this run's capture, computed once."""
    if "_xplane" not in run:
        planes = load(run["trace_dir"]) if run.get("trace_dir") else None
        run["_xplane"] = (reduce(planes, run.get("chunk_program", "chunk"))
                          if planes else None)
    return run["_xplane"]


def read(run: dict, mode: str):
    red = reduction(run)
    if red is None:
        return None
    # A capture is read only where it covers the window: the profiler
    # stops recording when its buffer is full, and what it kept is then a
    # prefix, which stands for nothing and holds an unknown share of the
    # parents the window expanded.
    if red["window_s"] < COVERS * run["window_wall_s"]:
        return None
    if mode == "idle_share":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    parents = run.get("parents_expanded")
    if not parents:
        return None
    if mode == "batch_fill":
        if not red["batches"]:
            return None
        return 100.0 * parents / (red["batches"] * run["batch"])
    if mode == "roofline":
        import bench_lib as lib
        import roofline
        peak = roofline.peak_for(run["device_kind"],
                                 lib.load_json("peaks.json"))
        least = roofline.least_batch_seconds(
            run["batch"], run["row_bytes"],
            run["new_generated"] / parents, run["new_distinct"] / parents,
            peak["hbm_bytes_per_s"])
        return 100.0 * least / (red["busy_s"] / (parents / run["batch"]))
    raise ValueError(f"xplane reader: unknown mode {mode!r}")
