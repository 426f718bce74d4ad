"""Reader ``events``: what the engine counted where it did the work, from
the window's own run-event log (``run["events"]``).

``run_end`` carries, since the engine counts them in its loop
(``raft_tla_tpu/engine/bfs.py WORK_COUNTERS``, ``compiles_by_span``):
``chunk_calls``, ``ingest_calls``, ``passes`` (iterations of the chunk
program's loop, the carry's own count), ``parents_expanded``, and
``compiles`` = ``{span:
[compiles and cache loads, seconds]}`` by the innermost span open when jax
reported each.  They exist in every run, traced or not.  A program that
does not write them (the parent of the PR that added them) leaves
nothing to read, and every mode returns None.

Modes of ``read`` (sums are over the window's runs):
  pass_fill         100 * parents_expanded / (passes * batch): parents
                    advanced per pass over the batch size; what
                    ``batch_fill`` estimates from the trace
  passes_per_call   passes / chunk_calls: the work one host round trip
                    buys
  calls_per_run     (chunk_calls + ingest_calls) / runs in the log
  compile_s         seconds of ``compiles``, every span's (and prints
                    them by span)
"""

from __future__ import annotations


def run_ends(run: dict) -> list:
    return [e for e in run.get("events") or []
            if e.get("event") == "run_end"]


def total(ends: list, key: str):
    """The sum of one field over the window's runs; None where a run
    does not carry it."""
    vals = [e.get(key) for e in ends]
    if not vals or any(v is None for v in vals):
        return None
    return sum(vals)


def compiles_by_span(ends: list):
    """{span: [compiles, seconds]} over the window's runs."""
    if not ends or any(e.get("compiles") is None for e in ends):
        return None
    out = {}
    for e in ends:
        for name, (n, sec) in e["compiles"].items():
            have = out.setdefault(name, [0, 0.0])
            have[0] += n
            have[1] += sec
    return out


def read(run: dict, mode: str):
    ends = run_ends(run)
    if mode == "compile_s":
        by = compiles_by_span(ends)
        if by is None:
            return None
        print("window compiles by span (count, seconds): " + (", ".join(
            f"{k} {n} {sec:.3f}" for k, (n, sec) in
            sorted(by.items(), key=lambda kv: -kv[1][1])) or "none"),
            flush=True)
        return sum(sec for _n, sec in by.values())
    calls, passes = total(ends, "chunk_calls"), total(ends, "passes")
    if calls is None or passes is None:
        return None
    if mode == "pass_fill":
        parents = total(ends, "parents_expanded")
        if not passes or parents is None or not run.get("batch"):
            return None
        return 100.0 * parents / (passes * run["batch"])
    if mode == "passes_per_call":
        return passes / calls if calls else None
    if mode == "calls_per_run":
        ingests = total(ends, "ingest_calls")
        return (calls + ingests) / len(ends) if ingests is not None else None
    raise ValueError(f"events reader: unknown mode {mode!r}")
