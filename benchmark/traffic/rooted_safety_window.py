"""Traffic kind ``rooted_safety_window``: to ``rooted_window`` what
``safety_window`` is to ``bfs_window``: that kind's window from roots the
mix names, under a configuration that checks a whole suite of invariants,
with the guarantee "every invariant the cfg names is evaluated on every
state admitted" held by comparisons of its own.

Set-up and window ARE ``rooted_window``'s: this kind calls
``rooted_window.run``, so a cell of this kind and one of that kind time
the same calls in the same order and that kind's comparisons (a)-(g) are
held here by the same lines.  ``rooted_window.run`` ends, outside the
clock, with its module's ``sample_check(ctx, eng, setup, ck, ref, ..)``;
this kind puts its own comparisons behind that call, where the warm
engine, the cfg's setup, the start level's snapshot and the roots' side of
the reference are at hand:

(s1) ``eng.inv_names`` is the configuration's list in order, and the
     window's ``run_end`` stopped on the duration budget (that its log
     holds no ``violation`` event is ``rooted_window``'s (e));
(s2) the plain reference's predicates (the module the mix names under
     ``suite``, of ``benchmark/reference/``) hold on every sampled
     start-level state and on every member of its reference successor
     set: engine and reference both say "holds";
(s3) witnesses through the timed program.  For each safety invariant, and
     for each extra maker the mix lists, ``witnesses`` seeded witness
     parents (sampled states mutated so that exactly that invariant is the
     first to fail, on them and on their successors) become the frontier
     of a snapshot beside the start level's (``safety_window.
     witness_snapshot``: a seen-set of their own keys, each a trace root)
     that the SAME warm engine resumes: the chunk program that ran the
     window must stop with ``violation``, under the name the reference's
     ``first_failing`` gives the reported state, that state a reference
     successor of a witness parent, the name the one the witnesses were
     made for, and ``replay`` must lead from a witness parent to it.

Every comparison is exact and printed beside its limit.

Mix parameters (``benchmark/traffic/<mix>.json``): ``rooted_window``'s and
  suite            ``<module>`` under ``benchmark/reference/``: its
                   ``first_failing``, ``witness_parents``,
                   ``WITNESS_MAKERS`` and, for extra makers, ``made_for``
  witnesses        witness parents made for each invariant and maker
  extra_witnesses  makers of the suite beyond one an invariant (may be
                   empty)
  pinned           the pin's file name, where it is not the
                   configuration's own (a profile from these roots under a
                   configuration whose own pin is from ``Init``)
  shapes           what ``rooted_window`` asks of a configuration's
                   ``shapes`` and this configuration's file, written for
                   another kind, leaves out (``families``, ``dims_class``)
``rooted_window.run`` is handed a copy of the context whose configuration
carries those two; no file is edited.
"""

from __future__ import annotations

import dataclasses
import importlib
import time

import bench_lib as lib


def rooted_context(ctx):
    """``ctx`` with the mix's ``pinned`` and ``shapes`` laid over the
    configuration's own."""
    config = dict(ctx.config)
    config["pinned"] = ctx.cell.get("pinned", config["pinned"])
    config["shapes"] = {**config["shapes"], **ctx.cell.get("shapes", {})}
    return dataclasses.replace(ctx, config=config)


def run(ctx) -> dict:
    rw = lib.load_module("traffic", "rooted_window")
    inner = rw.sample_check

    def after_window(ctx, eng, setup, ck, ref, decode_state,
                     unflatten_state):
        inner(ctx, eng, setup, ck, ref, decode_state, unflatten_state)
        suite_check(ctx, eng, setup, ck, ref, decode_state, unflatten_state)

    rw.sample_check = after_window
    try:
        out = rw.run(rooted_context(ctx))
    finally:
        rw.sample_check = inner
    # (s1), second half: how the window's own log says it ended.
    ctx.ledger.exact("stop reasons of the window's run_end events",
                     [e.get("stop_reason") for e in out["events"]
                      if e["event"] == "run_end"], ["duration_budget"])
    return out


def suite_check(ctx, eng, setup, ck, ref, decode_state,
                unflatten_state) -> None:
    ledger, cell = ctx.ledger, ctx.cell
    sw = lib.load_module("traffic", "safety_window")
    suite = importlib.import_module("reference." + cell["suite"])
    names = list(ctx.config["invariants"])
    ledger.exact("the engine's invariants, in order", list(eng.inv_names),
                 names)

    # (s2) the reference agrees that the suite holds where the engine
    # admitted states without a violation.
    t0 = time.perf_counter()
    pool = [lib.to_reference_state(s, ref.pystate) for s in sw.sampled_states(
        ctx, ck, setup, decode_state, unflatten_state)]
    checked, failing = 0, []
    for s in pool:
        for t in [s, *ref.oracle.successor_set(s, ref.dims)]:
            checked += 1
            name = suite.first_failing(t, names, ref.dims)
            if name is not None:
                failing.append(name)
    print(f"suite: reference/{cell['suite']}.py's {len(names)} predicates "
          f"on {len(pool)} sampled states of level {ck.diameter} and their "
          f"successors, {checked} states in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    ledger.exact("sampled states and successors failing a reference "
                 "invariant", failing, [])

    # (s3) witnesses through the chunk program that ran the window.
    made_for = getattr(suite, "made_for", lambda maker: maker)
    makers = [n for n in names if n in suite.WITNESS_MAKERS]
    for maker in makers + list(cell["extra_witnesses"]):
        witness_check(ctx, eng, setup, ck, ref, suite, names, pool, maker,
                      made_for(maker))


def witness_check(ctx, eng, setup, ck, ref, suite, names, pool, maker: str,
                  name: str) -> None:
    """``safety_window.witness_check`` with the suite, the names and the
    maker given: ``maker``'s witnesses are made for invariant ``name``."""
    ledger = ctx.ledger
    witness_snapshot = lib.load_module(
        "traffic", "safety_window").witness_snapshot
    what = name if maker == name else f"{maker} ({name})"
    want = int(ctx.cell["witnesses"])
    made = suite.witness_parents(maker, pool, want, ctx.args.seed, ref.dims,
                                 names, ref.constraint)
    ledger.exact(f"{what}: witness parents made", len(made), want)
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
    holds = getattr(suite, "holds_config", None)
    print(f"witness {what}: {len(parents)} parents"
          + (f" ({sum(map(holds, parents))} hold a configuration entry)"
             if holds else "")
          + f", {len(successors)} successors in the reference; resumed run "
          f"{seconds:.2f}s, stop {res.stop_reason}, reported "
          f"{v.invariant if v else None}", flush=True)
    ledger.exact(f"{what}: stop reason of the resumed witness frontier",
                 res.stop_reason, "violation")
    if v is None:
        return
    ledger.exact(f"{what}: invariant reported == first failing in the "
                 f"reference", v.invariant,
                 suite.first_failing(got, names, ref.dims))
    ledger.exact(f"{what}: invariant reported == the one the witnesses "
                 f"were made for", v.invariant, name)
    ledger.true(f"{what}: reported state is a reference successor of a "
                f"witness parent", got in successors)
    steps = [lib.to_reference_state(s, ref.pystate)
             for _g, s in eng.replay(v.fingerprint)]
    ledger.true(f"{what}: the trace replays from a witness parent to the "
                f"reported state",
                len(steps) == 2 and steps[0] in parents
                and steps[1] == got)
