"""One row a device call, kept by the host loops themselves.

A host loop (``engine/bfs.py``, ``parallel/mesh.py``, ``engine/swarm.py``)
dispatches a program, does the host half of the last call's trace flush,
waits for the call's statistics and accounts for them.  Its
:class:`CallLog` writes ONE record of kind ``call`` a call into the
flight recorder's ring (obs/flight.py), in every run, traced or not:

  what the call was     ``run``, ``call`` (the argument its ``chunk`` and
                        ``account`` spans carry), ``kind`` (``chunk`` /
                        ``ingest`` / ``swarm_chunk``), ``level``, ``rule``
                        (what sized it: ``full``, ``probe``, ``ramp``,
                        ``deadline``, the level's end ``level_end``;
                        ``ingest``; ``steps``), ``allowed``, ``passes``
                        (swarm: lockstep steps), ``parents``, ``new``
  where its time lay    ``t`` (seconds since the run began, at dispatch),
                        ``gap_s`` (from the end of the call before to this
                        dispatch: the loop's time in no call) and
                        ``named_s`` (what of the gap lay in spans of the
                        loop's own: ``grow``, ``level_end``, ``spill``,
                        ``checkpoint``, a flush's device half),
                        ``dispatch_s`` (the ``chunk`` span), ``flush_s``
                        (the trace flush's host half that ran behind it),
                        ``wait_s`` (the ``stats_fetch``), ``host_s`` (the
                        ``account`` span); ``cpu_s`` and ``gc_s``: the
                        thread's CPU seconds and the process's collections
                        over the gap and the call
  the run's state       what ``FlightRecorder.progress()`` shows of it

so a watcher's current view, the postmortem's last seconds and the run's
own reduction read one record.  At the run's end :func:`reduce_calls`
turns the run's rows into ``run_end.calls``: how many, by rule, and the
``slowest`` call — the one that exceeded most what its passes should have
cost — with the phase its excess lay in.  Nothing per call reaches the
event log.
"""

from __future__ import annotations

import statistics
import sys
import time

from . import metrics as _metrics
from .flight import RECORDER

#: An excess (a call's seconds over what its passes should cost, or a
#: gap's seconds in no named span over the run's usual) counts as a stall
#: when it is over ``STALL_MIN_S`` and what was observed is over
#: ``STALL_FACTOR`` times what was expected.  One of more than
#: ``SLOW_CALL_S`` is a ``slow_call`` event of its own (at most
#: ``SLOW_CALLS`` a run).
STALL_MIN_S = 0.05
STALL_FACTOR = 2.0
SLOW_CALL_S = 1.0
SLOW_CALLS = 8
#: A level's own median stands for its calls once it has this many.
MIN_GROUP = 3
#: The parts of a call, in the order they run.
CALL_PHASES = ("dispatch", "flush", "wait", "host")


class CallLog:
    """A run's calls: the rows it writes, and the tallies that need no
    row (``n``, ``gap_s``, ``by_rule``), which hold for every call of a
    run however long, where the ring holds the newest ``CAPACITIES``."""

    def __init__(self, run: int, recorder=None):
        self.run = run
        self._rec = recorder if recorder is not None else RECORDER
        self._seq0 = self._rec.seq()
        # What ``run_end.gc`` is a run's share of: the process's
        # collections as they stand now.
        self._gc_base = _metrics.process_record().gc_reading()
        self.t0 = time.perf_counter()
        self._wall0 = time.time() - self.t0     # a row's ``ts`` less its clock
        self.n = 0
        self.gap_s = 0.0
        self.by_rule: dict = {}
        self._at = self.t0      # this call's dispatch
        # Where the last row ended: (wall, cpu, gc, the thread's seconds
        # in phase spans); that thread's record (``start``).
        self._end = None
        self._spans = None

    def start(self) -> None:
        """The loop begins, on this thread: the first call's gap counts
        from here (a resume's restore and the warm-up are spans of their
        own)."""
        self._spans = _metrics.open_spans()
        self._end = (time.perf_counter(), time.thread_time(),
                     _metrics.gc_seconds(), self._spans.phase_s)

    def dispatch(self) -> None:
        """Just before a call's dispatch span opens."""
        self._at = time.perf_counter()

    def row(self, kind: str, rule: str, passes: int, dispatch_s: float,
            wait_s: float, flush_s: float, host_s: float, call: int,
            level: int, allowed: int, parents: int, new: int,
            **state) -> None:
        """The call is accounted for: write its row, the call's own
        fields and ``state`` (the run's state as a watcher sees it)."""
        end = self._end
        if end is None:
            # A loop that never said where it began: no gap before its
            # first call.
            self.start()
            end = (self._at,) + self._end[1:]
        self._end = now = (time.perf_counter(), time.thread_time(),
                           _metrics.gc_seconds(), self._spans.phase_s)
        at = self._at
        gap = at - end[0] if at > end[0] else 0.0
        seconds = dispatch_s + flush_s + wait_s + host_s
        # The thread's seconds in phase spans since the last row, less
        # this call's own four: the spans of the gap.
        named = min(max(now[3] - end[3] - seconds, 0.0), gap)
        self.n += 1
        self.gap_s += gap
        tally = self.by_rule.get(rule)
        if tally is None:
            tally = self.by_rule[rule] = [0, 0, 0.0]
        tally[0] += 1
        tally[1] += passes
        tally[2] += seconds
        row = {"ts": self._wall0 + now[0], "run": self.run, "call": call,
               "kind": kind, "level": level, "rule": rule,
               "allowed": allowed, "passes": passes, "parents": parents,
               "new": new, "t": at - self.t0, "gap_s": gap,
               "named_s": named, "dispatch_s": dispatch_s,
               "flush_s": flush_s, "wait_s": wait_s, "host_s": host_s,
               "cpu_s": now[1] - end[1], "gc_s": now[2] - end[2]}
        row.update(state)
        self._rec.put("call", row)

    def rows(self) -> list:
        """This run's rows still in the ring, oldest first."""
        ring = self._rec.snapshot(kinds=("call",)).get("call", ())
        out = []
        for rec in reversed(ring):
            if rec["seq"] <= self._seq0:
                break
            if rec.get("run") == self.run:
                out.append(rec)
        out.reverse()
        return out

    def reduce(self) -> dict:
        """``run_end.calls``, with the rows of its slow calls still in it
        (``slow``)."""
        out = reduce_calls(self.rows())
        out["n"] = self.n
        out["gap_s"] = round(self.gap_s, 6)
        out["by_rule"] = {
            rule: {"calls": n, "passes": p, "seconds": round(s, 6)}
            for rule, (n, p, s) in self.by_rule.items()}
        return out

    def run_end_fields(self, evlog) -> dict:
        """What a run's ``run_end`` carries of this module's and of
        obs/metrics.py's: ``calls`` (``reduce``, its ``slow`` rows
        emitted as ``slow_call`` events and lines on stderr first, so
        that an untraced run's own log names its stall, and replaced by
        their count ``slow_calls``; a run whose stalls stay under the
        second gets one ``stall:`` line for its largest) and ``gc`` (the
        process's collections meanwhile)."""
        calls = self.reduce()
        slow = calls.pop("slow")
        for row in slow:
            evlog.emit("slow_call", **{k: v for k, v in row.items()
                                       if k not in ("seq", "ts")})
            print(slow_call_line(row), file=sys.stderr, flush=True)
        calls["slow_calls"] = len(slow)
        if calls["stall_calls"] and not slow:
            # Under a second: no event, one line a run for its largest.
            print(slow_call_line(calls["slowest"], "stall"),
                  file=sys.stderr, flush=True)
        return {"calls": calls,
                "gc": _metrics.process_record().gc_since(self._gc_base)}


def call_seconds(row: dict) -> float:
    """A call from its dispatch to the end of its accounting."""
    return row["dispatch_s"] + row["flush_s"] + row["wait_s"] + row["host_s"]


class _Groups:
    """What a call is held to: the calls of the same kind on the same
    level, or of the same kind in the run where the level has fewer than
    ``MIN_GROUP``; nothing where the kind has fewer in the whole run.
    ``typical(i, values)`` is row ``i``'s units (its passes; one, for a
    call that ran none: an ingest) x the median of ``values`` a unit over
    its group."""

    def __init__(self, rows: list):
        self.units = [max(int(r.get("passes") or 0), 1) for r in rows]
        self._key, members = [], {}
        for i, r in enumerate(rows):
            level, kind = (r["kind"], r.get("level")), (r["kind"],)
            members.setdefault(level, []).append(i)
            members.setdefault(kind, []).append(i)
            self._key.append((level, kind))
        self._members = {k: v for k, v in members.items()
                         if len(v) >= MIN_GROUP}
        self._medians = {}

    def typical(self, i: int, values: list, name: str):
        for key in self._key[i]:
            group = self._members.get(key)
            if group is not None:
                med = self._medians.get((name, key))
                if med is None:
                    med = self._medians[(name, key)] = statistics.median(
                        [values[j] / self.units[j] for j in group])
                return med * self.units[i]
        return None


def reduce_calls(rows: list) -> dict:
    """What a run's rows say of it, bounded whatever their number:
    ``rows`` (how many were read), ``slowest`` (the row whose call or
    whose gap exceeded its expectation most, with ``expected_s`` (what
    its passes should have cost: ``_Groups.typical``; for a gap the
    run's median), ``excess_s`` and the ``phase`` that holds most of the
    excess: one of ``CALL_PHASES`` or ``gap``), ``stall_s`` /
    ``stall_calls`` (the excesses that are stalls, module constants
    above) and ``slow`` (the rows of those past ``SLOW_CALL_S``, each as
    ``slowest`` is).  A gap is held to account for its seconds in no
    span (``gap_s`` less ``named_s``): a seen-set's growth or a snapshot
    between two calls is a span in ``phase_seconds``, not a stall."""
    out = {"rows": len(rows), "slowest": None, "stall_s": 0.0,
           "stall_calls": 0, "slow": []}
    if not rows:
        return out
    seconds = [call_seconds(r) for r in rows]
    groups = _Groups(rows)
    gaps = [r["gap_s"] - r["named_s"] for r in rows]
    usual_gap = statistics.median(gaps) if len(rows) >= MIN_GROUP else 0.0
    # (excess, row index, what was expected, in the gap?, a stall?)
    found = []
    for i, r in enumerate(rows):
        wanted = groups.typical(i, seconds, "call")
        if wanted is not None:
            found.append((seconds[i] - wanted, i, wanted, False,
                          seconds[i] > STALL_FACTOR * wanted))
        found.append((gaps[i] - usual_gap, i, usual_gap, True,
                      gaps[i] > STALL_FACTOR * usual_gap))
    stalls = [f for f in found if f[4] and f[0] > STALL_MIN_S]
    out["stall_s"] = round(sum((f[0] for f in stalls), 0.0), 6)
    out["stall_calls"] = len(stalls)
    slow = sorted((f for f in stalls if f[0] > SLOW_CALL_S),
                  key=lambda f: -f[0])[:SLOW_CALLS]

    def described(excess, i, wanted, in_gap, _stall):
        def over(phase):
            values = [r[phase + "_s"] for r in rows]
            return values[i] - (groups.typical(i, values, phase) or 0.0)
        return {**rows[i], "expected_s": round(wanted, 6),
                "excess_s": round(excess, 6),
                "phase": "gap" if in_gap else max(CALL_PHASES, key=over)}

    out["slowest"] = described(*max(found, key=lambda f: f[0]))
    out["slow"] = [described(*f) for f in slow]
    return out


def slow_call_line(row: dict, what: str = "slow call") -> str:
    """One line of a run's log for a ``slow_call``, or for the largest
    stall of a run that had none so large (``what`` = ``stall``)."""
    return (f"{what}: run {row.get('run')} call {row.get('call')} "
            f"({row.get('kind')}, level {row.get('level')}, rule "
            f"{row.get('rule')}, {row.get('passes')} passes) lost "
            f"{row['excess_s']:.3f}s in {row['phase']} (expected "
            f"{row['expected_s']:.4f}s; gap {row['gap_s']:.4f} of it in "
            f"spans {row['named_s']:.4f} dispatch "
            f"{row['dispatch_s']:.4f} flush {row['flush_s']:.4f} wait "
            f"{row['wait_s']:.4f} host {row['host_s']:.4f}; cpu "
            f"{row['cpu_s']:.4f} gc {row['gc_s']:.4f})")
