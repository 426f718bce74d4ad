"""Reader ``stages``: device time per stage of the chunk program, and
device operations per pass, from the profiler's capture.

The chunk program names its stages (``raft_tla_tpu/engine/chunk.py
STAGES``, named scopes; ``prologue``/``epilogue`` around the loop in
``engine/bfs.py``), so every device operation's scope path says which
stage it came from: ``jit(chunk)/while/body/masks/...``.  The
engine's ``raft.account`` span carries how many passes each chunk call
ran (``readers/spans.py`` says where those spans are).

What is read is a ratio over WHOLE chunk calls, so a capture that holds
only the start of the window (the profiler stops recording when its
buffer is full) still gives it: a call is taken when its execution of
the chunk program (the ``jit_chunk`` event of ``XLA Modules``) lies in
the capture with operations over ``WHOLE`` of its length, and the
``raft.chunk`` span that dispatched it and the ``raft.account`` span of
the same ``run`` and ``call`` are there too.  The calls taken must hold ``MIN_PASSES`` passes
between them.  An operation's self time (its children taken out, so a
``while`` is charged only the time in which nothing of its body ran) goes
to the first stage its scope path names; to ``other`` where it names none
(``stats``, ``prologue``, ``epilogue``, the loop's own condition, copies
XLA put in).  Where less than ``NAMED_FLOOR`` of the time carries any
name of the program's the executable came from a compile cache filled
before the names existed, and nothing is reported.

Modes of ``read``:
  stage_ms   self time of ``stage``'s operations / passes, in ms
  launches   leaf operations (those that run no other) / passes
"""

from __future__ import annotations

import bisect

import numpy as np

import bench_lib as lib

# The stages reported one by one; every other name of the program's own
# (``NAMED`` less these) and every unnamed operation is ``other``.
STAGES = ("slice", "masks", "compact", "construct", "insert", "enqueue",
          "record")
NAMED = STAGES + ("stats", "prologue", "epilogue", "front",
                  "insert_enqueue")
MIN_PASSES = 4
NAMED_FLOOR = 0.9       # share of the calls' device time under a name
WHOLE = 0.9             # operations must cover this much of a call


def spans_reader():
    return lib.load_module("readers", "spans")


def stage_of(path: str):
    """The first stage a scope path names, or None.  A stage is a whole
    component; the operation's own name, last, ends in ``:`` and is none
    (``.../slice:`` is the primitive)."""
    for part in path.split("/"):
        if part in NAMED:
            return part
    return None


def whole_calls(cap: dict, chunk_program: str = "chunk") -> list:
    """[(start, end, passes)] of the chunk calls the capture holds whole
    and can count the passes of."""
    chunks = sorted((e[1], e[3].get("run"), e[3].get("call"))
                    for e in cap["host"] if e[0] == "chunk")
    passes = {(e[3].get("run"), e[3].get("call")): e[3].get("passes")
              for e in cap["host"] if e[0] == "account"}
    if not chunks:
        return []
    starts = [c[0] for c in chunks]
    out, taken = [], set()
    for name, start, dur in sorted(cap["modules"], key=lambda m: m[1]):
        if chunk_program not in name:
            continue
        # Dispatched by the last raft.chunk span opened before it ran;
        # of several that follow one span (a seen-set growth runs the
        # program once more, for no pass) the first is the call.
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or k in taken:
            continue
        taken.add(k)
        n = passes.get(chunks[k][1:])
        if n:
            out.append((start, start + dur, int(n)))
    return out


def table(cap: dict, chunk_program: str = "chunk"):
    """{"calls", "passes", "device_ns", "named_ns", "leaves",
    "stage_ns": {stage or "other": ns}, "by_name": {stage: {op: ns}}}
    over the whole calls of the capture, or None when there are none."""
    calls = whole_calls(cap, chunk_program)
    if not calls:
        return None
    stage = [stage_of(p) for p in cap["op_paths"]]
    ops = np.asarray(cap["ops"], np.int64).reshape(-1, 3)
    ops = ops[np.lexsort((-ops[:, 2], ops[:, 1]))]   # by start, outer first
    out = {"calls": 0, "passes": 0, "device_ns": 0, "named_ns": 0,
           "leaves": 0, "stage_ns": {}, "by_name": {}}
    for lo, hi, n in calls:
        inside = ops[np.searchsorted(ops[:, 1], lo):
                     np.searchsorted(ops[:, 1], hi)].tolist()
        selfs, leaves = self_times(inside)
        total = sum(ns for _i, ns in selfs)
        if total < WHOLE * (hi - lo):
            continue            # the capture lost part of this call
        out["calls"] += 1
        out["passes"] += n
        out["device_ns"] += total
        out["leaves"] += leaves
        for i, ns in selfs:
            st = stage[i]
            if st is not None:
                out["named_ns"] += ns
            key = st if st in STAGES else "other"
            out["stage_ns"][key] = out["stage_ns"].get(key, 0) + ns
            ops_of = out["by_name"].setdefault(st or "unnamed", {})
            name = cap["op_names"][i]
            ops_of[name] = ops_of.get(name, 0) + ns
    return out if out["calls"] else None


def self_times(events: list):
    """([(op index, self ns)], leaves) of events sorted by (start,
    -duration): each with its nested children taken out of it."""
    out, stack, leaves = [], [], 0       # stack: [index, end, child, start, kids]

    def close(upto):
        nonlocal leaves
        while stack and stack[-1][1] <= upto:
            i, end, child, start, kids = stack.pop()
            out.append((i, (end - start) - child))
            leaves += not kids
            if stack:
                stack[-1][2] += end - start
                stack[-1][4] += 1
    for i, start, dur in events:
        close(start)
        stack.append([i, start + dur, 0, start, 0])
    close(float("inf"))
    return out, leaves


def stage_table(run: dict):
    """The table of this run's capture, computed and printed once."""
    if "_stage_table" in run:
        return run["_stage_table"]
    run["_stage_table"] = None
    cap = spans_reader().capture(run)
    tab = (table(cap, run.get("chunk_program", "chunk"))
           if cap and cap["host"] else None)
    if tab is None:
        print("stages: no whole chunk call with its raft.chunk and "
              "raft.account spans in the capture", flush=True)
        return None
    named = tab["named_ns"] / tab["device_ns"]
    per_pass = lambda ns: ns / 1e6 / tab["passes"]  # noqa: E731
    print(f"stages: read {tab['calls']} whole chunk calls, "
          f"{tab['passes']} passes; device time "
          f"{per_pass(tab['device_ns']):.3f} ms a pass, "
          f"{100 * named:.1f} % of it under a stage name; "
          f"{tab['leaves'] / tab['passes']:.1f} operations a pass",
          flush=True)
    for st, ops in sorted(tab["by_name"].items(),
                          key=lambda kv: -sum(kv[1].values())):
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:4]
        print(f"stages:   {st} {per_pass(sum(ops.values())):.3f} ms a pass"
              f" ({len(ops)} operations): "
              + ", ".join(f"{n} {per_pass(ns):.3f}" for n, ns in top),
              flush=True)
    if tab["passes"] < MIN_PASSES:
        print(f"stages: fewer than {MIN_PASSES} passes in whole calls; "
              f"nothing reported", flush=True)
        return None
    if named < NAMED_FLOOR:
        print(f"stages: under {100 * NAMED_FLOOR:.0f} % of the chunk's "
              f"device time carries a stage name (an executable from a "
              f"compile cache filled before the names existed?); nothing "
              f"reported", flush=True)
        return None
    run["_stage_table"] = tab
    return tab


def read(run: dict, mode: str, stage: str = ""):
    tab = stage_table(run)
    if tab is None:
        return None
    if mode == "stage_ms":
        return tab["stage_ns"].get(stage, 0) / 1e6 / tab["passes"]
    if mode == "launches":
        return tab["leaves"] / tab["passes"]
    raise ValueError(f"stages reader: unknown mode {mode!r}")
