"""Reader ``construct``: the device time of stage ``construct`` by its
parts, and the lanes the invariants ran on for each state kept.

Inside ``construct`` the chunk program names its parts (``raft_tla_tpu/
engine/chunk.py CONSTRUCT_PARTS``, scopes nested in the stage's own):
``parents`` (the parents' hash sums and the K-lane gather of the parents),
``lane_out`` (the successors' construction and fingerprints),
``constraint``, ``flatten`` (the rows' packing) and ``invariants``, in
which each predicate runs under its cfg name (``engine/check.py
resolve_invariants``).  An operation of ``construct`` (``readers/
stages.py stage_of``: the FIRST stage its scope path names) goes to the
first part its path names after the stage; to ``rest`` where it names
none of those reported one by one (the constraint, the parents'
fingerprints for the trace records, copies XLA put in).  Self times, over
the whole chunk calls ``stages.whole_calls`` takes, per pass: the parts
sum to ``stage_ms.construct``.  The split is by the FUSED operation's own
path: a fusion XLA builds across two scopes carries one scope's name, so
a part can hold some of its neighbour's arithmetic; the difference of two
cells' passes (the same window with and without the suite) is the check.

A program without the nested scopes (the parent of the PR that added
them) names no part, and nothing is reported.

Modes of ``read``:
  part_ms            self time of ``part`` (one of ``PARTS`` or ``rest``)
                     in ms a pass
  inv_lanes_per_new  lanes the invariants were evaluated on
                     (``run_end.inv_lanes``, the engine's own count: K a
                     pass) per new distinct state of the window; TLC
                     evaluates an invariant once a new state
"""

from __future__ import annotations

import numpy as np

import bench_lib as lib

# The parts reported one by one; ``constraint`` and what names no part
# are ``rest``.
PARTS = ("parents", "lane_out", "flatten", "invariants")
NESTED = PARTS + ("constraint",)


def stages_reader():
    return lib.load_module("readers", "stages")


def part_of(path: str):
    """(part, predicate) of an operation whose first stage is
    ``construct``: the first nested scope its path names after the stage
    (None: the stage's own) and, under ``invariants``, the predicate's
    scope (None: the dispatch over the predicates' verdicts).  None for
    an operation of another stage or of none."""
    st = stages_reader()
    parts = path.split("/")
    for k, p in enumerate(parts):
        if p in st.NAMED:
            if p != "construct":
                return None
            rest = [unwrapped(c) for c in parts[k + 1:-1]]
            part = next((c for c in rest if c in NESTED), None)
            pred = None
            if part == "invariants":
                after = rest[rest.index(part) + 1:]
                pred = next((c for c in after if c.isidentifier()), None)
            return part, pred
    return None


def unwrapped(component: str) -> str:
    """``vmap(TypeOK)`` -> ``TypeOK``: a scope entered inside a ``vmap``
    is written inside the transform's brackets (and ``vmap(jit(f))``
    names no scope of the program's)."""
    while component.startswith("vmap("):
        component = component[5:]
    return component.rstrip(")")


def split(run: dict):
    """{"passes", "part_ns": {part or None: ns}, "pred_ns": {name or
    None: ns}} over the whole chunk calls of the capture, computed and
    printed once; None where ``stages`` reports nothing or the program
    names no part."""
    if "_construct_split" in run:
        return run["_construct_split"]
    run["_construct_split"] = None
    st = stages_reader()
    if st.stage_table(run) is None:
        return None
    cap = st.spans_reader().capture(run)
    of = [part_of(p) for p in cap["op_paths"]]
    ops = np.asarray(cap["ops"], np.int64).reshape(-1, 3)
    ops = ops[np.lexsort((-ops[:, 2], ops[:, 1]))]   # as stages.table
    passes, part_ns, pred_ns = 0, {}, {}
    for lo, hi, n in st.whole_calls(cap, run.get("chunk_program", "chunk")):
        inside = ops[np.searchsorted(ops[:, 1], lo):
                     np.searchsorted(ops[:, 1], hi)].tolist()
        selfs, _leaves = st.self_times(inside)
        if sum(ns for _i, ns in selfs) < st.WHOLE * (hi - lo):
            continue
        passes += n
        for i, ns in selfs:
            if of[i] is None:
                continue
            part, pred = of[i]
            part_ns[part] = part_ns.get(part, 0) + ns
            if part == "invariants":
                pred_ns[pred] = pred_ns.get(pred, 0) + ns
    if not passes or not any(p in part_ns for p in NESTED):
        print("construct: no operation of stage 'construct' names a part "
              "(a program from before the parts were named); nothing "
              "reported", flush=True)
        return None
    ms = lambda ns: ns / 1e6 / passes  # noqa: E731
    print("construct: " + ", ".join(
        f"{part or '(stage)'} {ms(ns):.3f}" for part, ns in sorted(
            part_ns.items(), key=lambda kv: -kv[1]))
        + f" ms a pass over {passes} passes", flush=True)
    if pred_ns:
        print("construct: invariants by predicate: " + ", ".join(
            f"{pred or '(dispatch)'} {ms(ns):.3f}" for pred, ns in sorted(
                pred_ns.items(), key=lambda kv: -kv[1])) + " ms a pass",
            flush=True)
    run["_construct_split"] = {"passes": passes, "part_ns": part_ns,
                               "pred_ns": pred_ns}
    return run["_construct_split"]


def read(run: dict, mode: str, part: str = ""):
    if mode == "inv_lanes_per_new":
        ends = [e for e in run.get("events") or []
                if e.get("event") == "run_end"]
        lanes = [e.get("inv_lanes") for e in ends]
        new = run.get("new_distinct")
        if not lanes or any(v is None for v in lanes) or not new:
            return None
        return sum(lanes) / new
    if mode != "part_ms":
        raise ValueError(f"construct reader: unknown mode {mode!r}")
    tab = split(run)
    if tab is None:
        return None
    if part == "rest":
        ns = sum(v for k, v in tab["part_ns"].items() if k not in PARTS)
    elif part in PARTS:
        ns = tab["part_ns"].get(part, 0)
    else:
        raise ValueError(f"construct reader: unknown part {part!r}")
    return ns / 1e6 / tab["passes"]
