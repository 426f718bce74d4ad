"""Reader ``smoke``: what a budgeted smoke check costs around the engine's
own budget clock, over the checks of a ``smoke_loop`` window.

A check of ``configs/Smokeraft.cfg`` is all start and stop, so its cost is
in three places the deep cells hardly feel: the wall past the budget, the
calls the time left cuts short, and what the check does outside the
engine's clock.  The program records each (``raft_tla_tpu/engine/bfs.py``):
``run_end.budget_overshoot_s`` (wall less ``max_seconds`` of a run the
duration budget stopped), ``run_end.deadline_calls`` (chunk calls whose
size the time left, not the ramp or the level's end, set) and the spans
``raft.smoke_roots`` (``initial_states`` of a smoke setup: the draw, the
product), ``raft.root_check``, ``raft.run_init``, ``raft.frontier_fetch``
and ``raft.run_end``.

Every mode returns None outside a ``smoke_loop`` run (``run["kind"]``),
and where the program does not write the field or the span (the parent of
the PR that added them).

Modes of ``read`` (means over the window's checks):
  overshoot_ms    1000 * ``run_end.budget_overshoot_s``
  deadline_calls  ``run_end.deadline_calls`` (``probe_calls`` printed
                  beside them)
  fixed_ms        the ``spans`` named, summed over the capture, in ms per
                  ``raft.run`` span; None unless ``raft.smoke_roots`` is
                  among the capture's spans and the capture covers the
                  window
  idle_share      100 * (1 - device busy / steady span), first to last
                  execution of the chunk program of the window, the idle
                  between checks included (``readers/xplane.py``'s
                  reduction)
"""

from __future__ import annotations

import bench_lib as lib


def read(run: dict, mode: str, spans=()):
    if run.get("kind") != "smoke_loop":
        return None
    events = lib.load_module("readers", "events")
    ends = events.run_ends(run)
    if mode == "overshoot_ms":
        total = events.total(ends, "budget_overshoot_s")
        return None if total is None else 1000.0 * total / len(ends)
    if mode == "deadline_calls":
        total = events.total(ends, "deadline_calls")
        if total is None:
            return None
        probes, calls = (events.total(ends, k)
                         for k in ("probe_calls", "chunk_calls"))
        print(f"chunk calls a check: {calls / len(ends):.2f}, of them "
              f"{total / len(ends):.2f} sized by the time left and "
              f"{probes / len(ends):.2f} probes of one batch", flush=True)
        return total / len(ends)
    if mode == "fixed_ms":
        reader = lib.load_module("readers", "spans")
        cap = reader.capture(run)
        if not cap or not any(e[0] == "smoke_roots" for e in cap["host"]):
            return None
        runs = sum(1 for e in cap["host"] if e[0] == "run")
        if runs:
            by_span = {s: sum(e[2] for e in cap["host"] if e[0] == s)
                       / 1e6 / runs for s in spans}
            print("spans a check, ms: " + ", ".join(
                f"{s} {ms:.2f}" for s, ms in by_span.items()), flush=True)
        return reader.read(run, "per_run_ms", spans=spans)
    if mode == "idle_share":
        return lib.load_module("readers", "xplane").read(run, "idle_share")
    raise ValueError(f"smoke reader: unknown mode {mode!r}")
