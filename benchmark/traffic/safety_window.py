"""Traffic kind ``safety_window``: ``bfs_window``'s window under a
configuration that checks a whole suite of invariants, with the guarantee
"every invariant the cfg names is evaluated on every state that is
admitted" held by comparisons of its own.

Set-up and window ARE ``bfs_window``'s: this kind calls ``bfs_window.run``
and everything that kind holds (shapes, every level against the pin, v2,
no forbidden event, no compile in the window, the seeded sample through
one level of engine and reference) is held here by the same lines, so a
cell of this kind and one of that kind time the same calls in the same
order.  ``bfs_window.run`` ends, outside the clock, with its module's
``sample_check(ctx, eng, setup, ck, ..)``; this kind puts its own
comparisons behind that call, where the warm engine, the cfg's setup and
the start-level snapshot are at hand:

(a) the engine's invariants are the configuration's, in its order, and
    the window's own log reports no violation;
(b) the plain reference's predicates (``reference/safety.py``) hold on
    every sampled start-level state and on every member of its reference
    successor set: engine and reference both say "holds";
(c) witnesses through the timed program.  For each safety invariant,
    ``witnesses`` seeded witness parents (states mutated so that exactly
    that invariant is the first to fail, on them and on their successors)
    become the frontier of a snapshot beside the start level's, with a
    seen-set of their own keys, and the SAME warm engine resumes it: the
    chunk program that ran the window must stop with a violation, under
    the name the reference gives the reported state, that state a
    successor of a witness parent, the name the one the witnesses were
    made for, and the trace must replay to it from a witness parent.

The sample is drawn by ``--seed`` as ``bfs_window`` draws it; the witness
parents are made from the sampled states by ``--seed`` too.

Mix parameters (``benchmark/traffic/<mix>.json``): ``bfs_window``'s
(``start_level``, ``sample``, ``forbidden_events``) and
  witnesses     witness parents made for each safety invariant
"""

from __future__ import annotations

import dataclasses
import random
import time

import numpy as np

import bench_lib as lib


def run(ctx) -> dict:
    bw = lib.load_module("traffic", "bfs_window")
    inner = bw.sample_check

    def after_window(ctx, eng, setup, ck, decode_state, unflatten_state):
        inner(ctx, eng, setup, ck, decode_state, unflatten_state)
        suite_check(ctx, eng, setup, ck, decode_state, unflatten_state)

    bw.sample_check = after_window
    try:
        out = bw.run(ctx)
    finally:
        bw.sample_check = inner
    # (a), second half: what the window's own log says of violations.
    events = out["events"]
    ctx.ledger.exact("'violation' events in the window",
                     sum(e["event"] == "violation" for e in events), 0)
    ctx.ledger.exact("stop reasons of the window's run_end events",
                     [e.get("stop_reason") for e in events
                      if e["event"] == "run_end"], ["duration_budget"])
    return out


def safety_reference(config: dict):
    """``lib.reference`` with the suite: ``.safety`` (the module) and
    ``.names`` (the configuration's invariants, in its order)."""
    ref = lib.reference(config)
    from reference import safety
    ref.safety, ref.names = safety, list(config["invariants"])
    return ref


def sampled_states(ctx, ck, setup, decode_state, unflatten_state) -> list:
    """The start-level states ``bfs_window.sample_check`` draws."""
    n = min(int(ctx.cell["sample"]), len(ck.frontier))
    rows = random.Random(ctx.args.seed).sample(range(len(ck.frontier)), n)
    return [decode_state(unflatten_state(ck.frontier[i], setup.dims),
                         setup.dims) for i in sorted(rows)]


def suite_check(ctx, eng, setup, ck, decode_state, unflatten_state) -> None:
    ledger, config = ctx.ledger, ctx.config
    ref = safety_reference(config)
    ledger.exact("the engine's invariants, in order", list(eng.inv_names),
                 ref.names)

    # (b) the reference agrees that the suite holds where the engine
    # admitted states without a violation.
    t0 = time.perf_counter()
    pool = [lib.to_reference_state(s, ref.pystate) for s in sampled_states(
        ctx, ck, setup, decode_state, unflatten_state)]
    checked, failing = 0, []
    for s in pool:
        for t in [s, *ref.oracle.successor_set(s, ref.dims)]:
            checked += 1
            name = ref.safety.first_failing(t, ref.names, ref.dims)
            if name is not None:
                failing.append(name)
    print(f"suite: the reference's {len(ref.names)} predicates on "
          f"{len(pool)} sampled states of level {ck.diameter} and their "
          f"successors, {checked} states in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    ledger.exact("sampled states and successors failing a reference "
                 "invariant", failing, [])

    # (c) witnesses through the chunk program that ran the window.
    for name in ref.names:
        if name in ref.safety.WITNESS_MAKERS:
            witness_check(ctx, eng, setup, ck, ref, pool, name)


def witness_snapshot(ck, setup, parents):
    """The start level's snapshot with the witness parents as its
    frontier: a seen-set of their own keys, each a root of the trace."""
    import jax
    from raft_tla_tpu.models.pystate import PyState
    from raft_tla_tpu.models.schema import (check_packable, encode_state,
                                            flatten_state, stack_states)
    from raft_tla_tpu.ops.fingerprint import build_fingerprint
    states = [PyState(**{f.name: getattr(p, f.name)
                         for f in dataclasses.fields(PyState)})
              for p in parents]
    encoded = [encode_state(s, setup.dims) for s in states]
    for e in encoded:
        check_packable(e, setup.dims)
    rows = np.stack([flatten_state(e, setup.dims) for e in encoded])
    hi, lo = (np.asarray(x) for x in jax.vmap(
        build_fingerprint(setup.dims))(stack_states(encoded)))
    order = np.lexsort((lo, hi))
    fps = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    return dataclasses.replace(
        ck, frontier=rows, seen_hi=hi[order], seen_lo=lo[order],
        distinct=len(rows), generated=0, wall_seconds=0.0,
        levels=tuple(ck.levels[:-1]) + (len(rows),),
        trace_fps=fps, trace_parents=np.zeros(len(rows), np.uint64),
        trace_actions=np.full(len(rows), -1, np.int32),
        roots={int(fp): s for fp, s in zip(fps, states)})


def witness_check(ctx, eng, setup, ck, ref, pool, name: str) -> None:
    ledger = ctx.ledger
    want = int(ctx.cell["witnesses"])
    made = ref.safety.witness_parents(
        name, pool, want, ctx.args.seed, ref.dims, ref.names,
        ref.constraint)
    ledger.exact(f"{name}: witness parents made", len(made), want)
    if not made:
        return
    parents = [w for w, _failing in made]
    successors = set().union(*(ref.oracle.successor_set(w, ref.dims)
                               for w in parents))
    # One level and no more: a program that evaluates nothing stops at
    # the level's end, not at the budget's.
    eng.config.events_out = None
    eng.config.max_seconds = None
    eng.config.max_diameter = ck.diameter + 1
    t0 = time.perf_counter()
    res = eng.run(resume=witness_snapshot(ck, setup, parents))
    seconds = time.perf_counter() - t0
    v = res.violation
    got = lib.to_reference_state(v.state, ref.pystate) if v else None
    print(f"witness {name}: {len(parents)} parents, {len(successors)} "
          f"successors in the reference; resumed run {seconds:.2f}s, stop "
          f"{res.stop_reason}, reported "
          f"{v.invariant if v else None}", flush=True)
    ledger.exact(f"{name}: stop reason of the resumed witness frontier",
                 res.stop_reason, "violation")
    if v is None:
        return
    ledger.exact(f"{name}: invariant reported == first failing in the "
                 f"reference", v.invariant,
                 ref.safety.first_failing(got, ref.names, ref.dims))
    ledger.exact(f"{name}: invariant reported == the one the witnesses "
                 f"were made for", v.invariant, name)
    ledger.true(f"{name}: reported state is a reference successor of a "
                f"witness parent", got in successors)
    steps = [lib.to_reference_state(s, ref.pystate)
             for _g, s in eng.replay(v.fingerprint)]
    ledger.true(f"{name}: the trace replays from a witness parent to the "
                f"reported state",
                len(steps) == 2 and steps[0] in parents
                and steps[1] == got)
