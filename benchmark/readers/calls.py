"""Reader ``calls``: what the host loops keep of their own calls, and what
one call costs beyond the device's own time.

Since the PR that added them every ``run_end`` carries, in every run,
traced or not (``raft_tla_tpu/obs/calls.py``, ``obs/metrics.py``,
``engine/bfs.py store_growth``):

  ``calls``            the run's own reduction of its rows, one row a
                       device call: ``n``, ``rows`` (how many of them the
                       ring still held), ``gap_s``, ``by_rule``,
                       ``stall_s`` / ``stall_calls`` (excesses of a call
                       over what its passes should cost, and of the gap
                       before it less what of it lay in spans of the
                       loop's own (``named_s``), that are over 50 ms and
                       over twice the expectation) and ``slowest``, the
                       row that exceeded most, with the ``phase`` its
                       excess lay in (``dispatch``, ``flush``, ``wait``,
                       ``host``, ``gap``)
  ``gc``               the process's garbage collections during the run:
                       ``collections`` by generation, ``seconds``,
                       ``by_span``
  ``trace_rehashes``,  what growing cost the run's native trace store: the
  ``trace_rehash_s``   doublings and the seconds spent in them

and inside a profiler capture ``raft.account`` carries ``rule`` (what
sized the call) beside ``run``, ``call`` and ``passes``.  A program
without the fields (the parent of that PR) leaves nothing to read, and
every mode returns None.

Modes of ``read`` (sums are over the window's runs):
  stall_ms        1000 * the sum of ``calls.stall_s``; prints each run's
                  ``slowest`` that is a stall with its phase split
  gc_ms           1000 * the sum of ``gc.seconds``; prints the
                  collections by generation and the seconds by span
  rehash_ms       1000 * the sum of ``trace_rehash_s``; prints the count
  round_trip_ms   per whole chunk call of the capture: the wall from the
                  open of its ``raft.chunk`` span to the open of its
                  ``raft.account`` span, less the execution of the chunk
                  program it dispatched (the ``XLA Modules`` event of the
                  first device, paired as ``readers/stages.py
                  whole_calls`` pairs them), in ms, the mean over the
                  calls; prints the dispatch half (span open to the
                  module's start) and the return half (its end to
                  ``account``) apart, and the mean by ``rule``
"""

from __future__ import annotations

import bisect

import bench_lib as lib

PHASES = ("gap", "dispatch", "flush", "wait", "host")


def run_ends(run: dict) -> list:
    return [e for e in run.get("events") or []
            if e.get("event") == "run_end"]


def with_field(run: dict, key: str):
    """The window's ``run_end`` events, or None where there is none or
    one lacks ``key`` (a program that does not write it)."""
    ends = run_ends(run)
    if not ends or any(e.get(key) is None for e in ends):
        return None
    return ends


def stall_ms(run: dict):
    ends = with_field(run, "calls")
    if ends is None:
        return None
    total = sum(e["calls"].get("stall_s") or 0.0 for e in ends)
    stalled = [e["calls"] for e in ends if e["calls"].get("stall_calls")]
    gaps = sum(e["calls"].get("gap_s", 0.0) for e in ends)
    print(f"calls: {sum(e['calls'].get('n', 0) for e in ends)} in "
          f"{len(ends)} runs ("
          f"{sum(e['calls'].get('rows', 0) for e in ends)} rows reduced), "
          f"gaps {gaps:.4f}s in all; "
          f"{sum(c['stall_calls'] for c in stalled)} stalls in "
          f"{len(stalled)} runs, {total:.4f}s", flush=True)
    for c in stalled:
        s = c.get("slowest") or {}
        print(f"calls: slowest of run {s.get('run')}: call {s.get('call')} "
              f"({s.get('kind')}, level {s.get('level')}, rule "
              f"{s.get('rule')}, {s.get('passes')} passes) "
              f"{s.get('excess_s', 0.0):.4f}s over "
              f"{s.get('expected_s', 0.0):.4f}s in {s.get('phase')}; "
              + " ".join(
                  f"{p} {s.get(p + '_s', 0.0):.4f}" for p in PHASES)
              + f" (of the gap in spans {s.get('named_s', 0.0):.4f})"
              + f"; cpu {s.get('cpu_s', 0.0):.4f} gc {s.get('gc_s', 0.0):.4f}",
              flush=True)
    by_rule = {}
    for e in ends:
        for rule, t in (e["calls"].get("by_rule") or {}).items():
            have = by_rule.setdefault(rule, [0, 0, 0.0])
            have[0] += t["calls"]
            have[1] += t["passes"]
            have[2] += t["seconds"]
    print("calls by rule (calls, passes, seconds): " + ", ".join(
        f"{k} {n} {p} {s:.3f}" for k, (n, p, s) in
        sorted(by_rule.items(), key=lambda kv: -kv[1][2])), flush=True)
    return 1000.0 * total


def gc_ms(run: dict):
    ends = with_field(run, "gc")
    if ends is None:
        return None
    gens, gen_s, by_span = [0, 0, 0], [0.0, 0.0, 0.0], {}
    for e in ends:
        gc = e["gc"]
        for g, n in enumerate(gc.get("collections") or ()):
            gens[g] += n
        for g, s in enumerate(gc.get("seconds_by_generation") or ()):
            gen_s[g] += s
        for span, s in (gc.get("by_span") or {}).items():
            by_span[span] = by_span.get(span, 0.0) + s
    print("gc: collections by generation " + " ".join(map(str, gens))
          + ", seconds " + " ".join(f"{s:.4f}" for s in gen_s)
          + "; seconds by span: " + (", ".join(
              f"{k} {s:.4f}" for k, s in
              sorted(by_span.items(), key=lambda kv: -kv[1])[:8]) or "none"),
          flush=True)
    return 1000.0 * sum(e["gc"].get("seconds") or 0.0 for e in ends)


def rehash_ms(run: dict):
    ends = with_field(run, "trace_rehash_s")
    if ends is None:
        return None
    print(f"rehash: {sum(e.get('trace_rehashes') or 0 for e in ends)} of "
          f"the trace store in {len(ends)} runs, "
          f"{sum(e['trace_rehash_s'] for e in ends):.4f}s; in a resume's "
          f"refill {sum(e.get('restore_rehashes') or 0 for e in ends)}, "
          f"{sum(e.get('restore_rehash_s') or 0.0 for e in ends):.4f}s",
          flush=True)
    return 1000.0 * sum(e["trace_rehash_s"] for e in ends)


# -- the round trip ---------------------------------------------------------

def round_trips(host: list, modules: list, chunk_program: str = "chunk"):
    """[(dispatch_ns, return_ns, rule)] of the chunk calls the capture
    holds whole: the module event follows the last ``raft.chunk`` span
    opened before it ran (the first that does, as ``stages.whole_calls``
    takes it), and the ``raft.account`` span is the next of the same
    ``run`` and ``call``, which must carry ``rule``."""
    chunks = sorted((e[1], e[3].get("run"), e[3].get("call"))
                    for e in host if e[0] == "chunk")
    accounts = {}
    for e in host:
        if e[0] == "account" and e[3].get("rule") is not None:
            accounts.setdefault((e[3].get("run"), e[3].get("call")),
                                []).append((e[1], e[3]["rule"]))
    if not chunks or not accounts:
        return []
    starts = [c[0] for c in chunks]
    out, taken = [], set()
    for name, start, dur in sorted(modules, key=lambda m: m[1]):
        if name.split("(", 1)[0] != "jit_" + chunk_program:
            continue
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or k in taken:
            continue
        taken.add(k)
        opened = chunks[k][0]
        after = [a for a in accounts.get(chunks[k][1:], ())
                 if a[0] >= start + dur]
        if not after:
            continue        # the capture ended inside the call
        at, rule = min(after)
        out.append((start - opened, at - (start + dur), str(rule)))
    return out


def round_trip_ms(run: dict):
    if "_round_trips" not in run:
        run["_round_trips"] = None
        cap = lib.load_module("readers", "spans").capture(run)
        if cap and cap["host"]:
            # The first device's modules, as ``readers/spans.py`` loads
            # them: on the mesh every chip runs the one program in step.
            try:
                trips = round_trips(cap["host"], cap["modules"],
                                    run.get("chunk_program", "chunk"))
            except Exception as e:      # a reader never fails a run
                print(f"round trip: the capture could not be read "
                      f"({type(e).__name__}: {e})", flush=True)
                trips = []
            if trips:
                run["_round_trips"] = trips
                n = len(trips)
                by_rule = {}
                for d, r, rule in trips:
                    by_rule.setdefault(rule, []).append(d + r)
                print(f"round trip: {n} whole chunk calls on chip 0; "
                      f"a call beyond its device time "
                      f"{sum(d + r for d, r, _ in trips) / n / 1e6:.3f} ms = "
                      f"dispatch {sum(d for d, _r, _ in trips) / n / 1e6:.3f} "
                      f"+ return {sum(r for _d, r, _ in trips) / n / 1e6:.3f}"
                      f"; by rule (calls, ms): " + ", ".join(
                          f"{k} {len(v)} {sum(v) / len(v) / 1e6:.3f}"
                          for k, v in sorted(by_rule.items())), flush=True)
            else:
                print("round trip: no whole chunk call whose raft.account "
                      "carries a rule in the capture", flush=True)
    trips = run["_round_trips"]
    if not trips:
        return None
    return sum(d + r for d, r, _ in trips) / len(trips) / 1e6


MODES = {"stall_ms": stall_ms, "gc_ms": gc_ms, "rehash_ms": rehash_ms,
         "round_trip_ms": round_trip_ms}


def read(run: dict, mode: str):
    if mode not in MODES:
        raise ValueError(f"calls reader: unknown mode {mode!r}")
    return MODES[mode](run)
