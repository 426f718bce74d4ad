"""Metrics registry — counters, gauges, histograms, and phase timing.

TLC's only live observability is a ~per-minute progress line; the engines
here replace their scattered prints and packed-stats side channels with
one registry every layer (engine, mesh, server, CLI, bench) writes into.
Zero-dependency and thread-safe: the checker service handles requests on
multiple threads against one process-global registry, and the engines'
host loops update theirs thousands of times per second — so every
operation is a few dict ops under one lock, and importing this module
never imports jax (the registry must be importable in tooling that never
touches a device; ``watch_compiles`` alone does, when it is called).

Metric name convention: ``<layer>/<what>`` with ``/`` separators, e.g.
``engine/generated``, ``server/requests/check``, ``phase/stats_fetch``.
Phase timers observe into histograms named ``phase/<name>`` whose
``total`` is the accumulated seconds — ``phase_seconds()`` projects just
that view, which is what run events and bench reports embed.

One span primitive: ``open_span`` (``phase_timer`` and ``scope`` are its
``with`` forms).  A span has a name, a start, an end and keyword
arguments; its parent is the span open on the same thread when it
started.  Closing it feeds three sinks: the histogram here, and through
the duck-typed ``registry.tracer`` hook (obs/tracing.py) the Chrome
trace file and a ``jax.profiler.TraceAnnotation`` on the device
profiler's own clock.  The seconds of the ``phase/`` spans a thread has
closed are summed for it (``open_spans().phase_s``): what a host loop
subtracts from the time between two device calls, so that only time in
no named span can count as a stall (obs/calls.py).  A garbage collection
that ran on a thread is charged to the innermost span open there, as a
compile is (``gc/<name>``, ``gc_seconds/<name>``; ``_on_gc``).

One process record (``ProcessRecord``, the module-level ``PROCESS``):
what the process did before its runs and between them, kept from the
package's import on.  Marks, every closed span's seconds by name
whichever registry opened it, and jax's trace / lower / compile-or-load
events by program, each counted whether or not a span is open.  Every
``run_start`` carries it (``ProcessRecord.run_start``).
"""

from __future__ import annotations

import gc
import math
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Optional

from .. import IMPORT_STAMP

# Histogram bucket upper bounds: geometric decades with a 1-2-5 ladder,
# 1 us .. 100 s — wide enough for both kernel dispatches and whole
# checkpoint writes.  Values are generic (a histogram may observe bytes
# or rows too); the ladder just has to be monotone.
_DEFAULT_BOUNDS = tuple(
    m * 10.0 ** e for e in range(-6, 3) for m in (1.0, 2.0, 5.0))

PHASE_PREFIX = "phase/"
# Spans that contain other spans (``run``, ``level``, ``replay``) observe
# under their own prefix: the ``phase/`` totals stay a partition of the
# wall (``phase_seconds``, ``level_complete.unattributed_seconds``).
SCOPE_PREFIX = "scope/"
# Spans of the serving layer (server.py, serving/manager.py) observe under
# a prefix of their own: they run on handler threads and on the executor
# around a run, on the registry the engines share, and seconds of theirs
# under ``phase/`` would land in whatever run was open meanwhile.
SERVE_PREFIX = "serve/"
# The parts of a piece of work that is no partition of the loop's wall
# (a snapshot's: those of its capture lie inside the ``checkpoint``
# phase, those of its commit on a thread of their own) observe under a
# prefix of their own, for the same reason turned round: their seconds
# are in a phase's already, or in no phase of the loop's at all.
PART_PREFIX = "part/"

# What an annotation's name starts with in a profiler capture.
ANNOTATION_PREFIX = "raft."

# The spans open on each thread, outermost first (``stack``), and the
# jit stages jax has begun on it and not ended (``jit``).  Process-wide
# because what reads it is: jax's monitoring listeners are registered
# once per process, and the compile listener below charges a compile to
# the innermost span open on the compiling thread.
_OPEN = threading.local()


class _OpenSpans(list):
    """The spans open on one thread, outermost first, and ``phase_s``:
    the seconds of the ``phase/`` spans that thread has closed."""

    __slots__ = ("phase_s",)

    def __init__(self):
        super().__init__()
        self.phase_s = 0.0


def open_spans() -> _OpenSpans:
    """The calling thread's record: a host loop takes it once and reads
    ``phase_s`` at every call (obs/calls.py)."""
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = _OpenSpans()
    return stack


def innermost_span():
    """The innermost span open on the calling thread, or None."""
    stack = getattr(_OPEN, "stack", None)
    return stack[-1] if stack else None


class Span:
    """One open span; ``close()`` ends it and feeds the sinks.  After
    the close ``seconds`` holds its duration."""

    __slots__ = ("registry", "name", "seconds", "jit", "_hist", "_t0",
                 "_token")

    def __init__(self, registry, hist: str, name: str, args: dict):
        self.registry, self.name, self._hist = registry, name, hist
        self.seconds = None
        # Seconds jax spent tracing, lowering, compiling or loading
        # inside this span (``_jit_end``): ``seconds - jit`` is its own.
        self.jit = 0.0
        open_spans().append(self)
        tracer = registry.tracer
        self._token = (tracer.begin(name, args) if tracer is not None
                       else None)
        self._t0 = time.perf_counter()

    def close(self) -> None:
        if self.seconds is not None:
            return
        self.seconds = time.perf_counter() - self._t0
        self.registry.observe(self._hist + self.name, self.seconds)
        stack = _OPEN.stack
        if self._hist == PHASE_PREFIX:
            stack.phase_s += self.seconds
        PROCESS.span_closed(self._hist + self.name, self.seconds, self.jit)
        if _GC_EVENTS:
            fold_collections()
        if self._token is not None:
            self.registry.tracer.end(self._token)
        # A loop-shaped span (``level``) may be closed after an
        # exception unwound past it: whatever was opened inside it and
        # never closed goes with it.
        if self in stack:
            del stack[stack.index(self):]


class Histogram:
    """Lock-free value container; the registry serializes access."""

    __slots__ = ("count", "total", "min", "max", "bounds", "buckets")

    def __init__(self, bounds=_DEFAULT_BOUNDS):
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.bounds = bounds
        self.buckets = [0] * (len(bounds) + 1)   # +1 overflow bucket

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        lo, hi = 0, len(self.bounds)
        while lo < hi:                    # first bound >= value
            mid = (lo + hi) // 2
            if self.bounds[mid] < value:
                lo = mid + 1
            else:
                hi = mid
        self.buckets[lo] += 1

    def summary(self) -> dict:
        out = {"count": self.count, "total": self.total}
        if self.count:
            out["min"] = self.min
            out["max"] = self.max
            out["mean"] = self.total / self.count
            # Only the occupied buckets, keyed by upper bound ("+inf" for
            # the overflow bucket) — compact in JSON snapshots.
            out["buckets"] = {
                ("+inf" if i == len(self.bounds)
                 else f"{self.bounds[i]:g}"): c
                for i, c in enumerate(self.buckets) if c}
        return out


class MetricsRegistry:
    """Named counters (monotone), gauges (last value wins), histograms."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._histograms: Dict[str, Histogram] = {}
        # Optional span tracer (obs/tracing.py SpanTracer, duck-typed —
        # this module stays import-free): when attached, every span is
        # handed to its ``begin``/``end``, so one attachment instruments
        # every phase site.
        self.tracer = None

    # -- writers -------------------------------------------------------
    def counter(self, name: str, inc: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram()
            h.observe(value)

    def open_span(self, name: str, prefix: str = PHASE_PREFIX,
                  **args) -> Span:
        """Open a span on this thread; the caller closes it.  For the
        loop-shaped scopes a ``with`` block cannot bracket."""
        return Span(self, prefix, name, args)

    @contextmanager
    def _spanning(self, prefix: str, name: str, args: dict):
        span = Span(self, prefix, name, args)
        try:
            yield span
        finally:
            span.close()

    def phase_timer(self, name: str, **args):
        """Accumulate wall seconds into the ``phase/<name>`` histogram.
        Phases are the host-side stages of an engine loop (chunk dispatch,
        stats fetch, spill drain, checkpoint, ...): non-overlapping by
        construction at the call sites, so their totals partition the
        loop's wall time.  The ``with`` block yields the :class:`Span`."""
        return self._spanning(PHASE_PREFIX, name, args)

    def scope(self, name: str, **args):
        """A span that contains phases (``run``, ``replay``): the same
        sinks, its seconds under ``scope/<name>``."""
        return self._spanning(SCOPE_PREFIX, name, args)

    def part_timer(self, name: str, **args):
        """A part of a snapshot (``ckpt_export`` ... inside the
        ``checkpoint`` phase, ``ckpt_sort`` ... on the save's own
        thread): the same sinks, its seconds under ``part/<name>``, so
        ``phase_seconds`` stays a partition."""
        return self._spanning(PART_PREFIX, name, args)

    def part_seconds(self) -> Dict[str, float]:
        """{part name: accumulated seconds}, as ``phase_seconds``."""
        with self._lock:
            return {name[len(PART_PREFIX):]: h.total
                    for name, h in self._histograms.items()
                    if name.startswith(PART_PREFIX)}

    def serve_timer(self, name: str, **args):
        """A span of the serving layer (``job``, ``job_setup``,
        ``job_respond``, ``journal``, ``result_wait``): the same sinks,
        its seconds under ``serve/<name>``."""
        return self._spanning(SERVE_PREFIX, name, args)

    # -- readers -------------------------------------------------------
    def counter_value(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def counters(self, prefix: str) -> Dict[str, float]:
        """{name less ``prefix``: value} of the counters that start with
        it."""
        with self._lock:
            return {k[len(prefix):]: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def gauge_value(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def phase_seconds(self) -> Dict[str, float]:
        """{phase name: accumulated seconds} — the per-phase breakdown
        run events and bench JSON embed."""
        with self._lock:
            return {name[len(PHASE_PREFIX):]: h.total
                    for name, h in self._histograms.items()
                    if name.startswith(PHASE_PREFIX)}

    def snapshot(self) -> dict:
        """One JSON-ready dict of everything — the supported interface
        for ``--metrics-out`` files and the server's ``stats`` op."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {n: h.summary()
                               for n, h in self._histograms.items()},
            }


def phase_delta(now: Dict[str, float],
                base: Optional[Dict[str, float]]) -> Dict[str, float]:
    """Per-phase seconds accumulated since ``base`` (an earlier
    ``phase_seconds()`` snapshot) — used to scope phase breakdowns to one
    run or one BFS level on a registry that outlives both."""
    if not base:
        return dict(now)
    return {k: v - base.get(k, 0.0) for k, v in now.items()
            if v - base.get(k, 0.0) > 0.0}


# -- the process record ----------------------------------------------------
# jax reports a jitted function's way to an executable in three timed
# stages, each through its monitoring hooks as a scalar when it begins
# and a duration when it ends, both with the function's name: the trace
# to a jaxpr, the lowering to a module, and the backend's compile, which
# is a load from the persistent cache when the cache's own ``cache_hits``
# event fell inside its bracket.  A program that compiles in under
# ``jax_persistent_cache_min_compile_time_secs`` is never stored, so it
# is a ``compile`` in every process.
JIT_STAGES = ("trace", "lower", "load", "compile")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_JIT_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
               _BACKEND_EVENT: "backend"}
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_STORED_EVENT = "/jax/compilation_cache/cache_misses"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _process_age() -> Optional[float]:
    """Seconds since the OS started this process, where ``/proc`` says
    (to a clock tick), else None."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return None


def _net(entry) -> float:
    """A span total's own seconds: all of them less the jit's."""
    return round(max(entry[1] - entry[2], 0.0), 4)


class _Program:
    """One jitted function's jit seconds in the process, by stage; its
    ``events`` (stages of its own, a trace inside a trace is none), what
    its last backend stage was (``cache``: "hit" or "miss"), the span
    its last stage fell in, and the backend stages that were compiles."""

    __slots__ = ("trace_s", "lower_s", "backend_s", "events", "cache",
                 "span", "compiles", "compile_s")

    def __init__(self):
        self.trace_s = self.lower_s = self.backend_s = self.compile_s = 0.0
        self.events = self.compiles = 0
        self.cache = self.span = None

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def row(self, name: str, base=(0.0, 0.0, 0.0, 0)) -> dict:
        return {"name": name, "trace_s": round(self.trace_s - base[0], 4),
                "lower_s": round(self.lower_s - base[1], 4),
                "backend_s": round(self.backend_s - base[2], 4),
                "cache": self.cache, "span": self.span}


class ProcessRecord:
    """What one process did before its runs and between them.

    - ``marks``: {name: seconds since the process started}, the first
      occurrence of each (``mark``).  The start is the OS's own where
      ``/proc/self/stat`` gives it, else the package's import.
    - every closed span's seconds by histogram name, whichever registry
      opened it, beside the jit seconds that fell inside it
      (``span_closed``, from ``Span.close``);
    - jax's jit stages (``JIT_STAGES``) as [events, seconds] and by
      program (``jit_event``, from the listeners ``watch_compiles``
      registers), in SELF time: a stage that ran inside another on the
      same thread is taken out of it, so the four never sum past the wall;
    - ``metrics``: a registry of its own for the spans no engine's
      registry exists for yet (``load_config``, ``make_engine``).  The
      record is that registry's tracer: such a span is ``raft.<name>`` in
      any profiler capture that is open, and those closed before the
      process's first run are kept for that run's ``--trace-out``
      (``early_spans``, obs/tracing.py ``SpanTracer.reset``).

    A few dict operations a call under one lock of its own, and nothing
    that grows with the events: names come from the code, and a program
    past ``MAX_PROGRAMS`` is counted under ``(others)``.  One instance a
    process (``PROCESS``); ``process_record()`` is how other modules
    reach it, so a test can put its own in its place."""

    PROGRAMS = 8            # ``run_start.process.programs`` holds so many
    PHASES = 12             # ... and ``runs.phases`` so many
    EARLY_SPANS = 32
    MAX_PROGRAMS = 512

    def __init__(self, package_stamp: Optional[float] = None):
        now = time.perf_counter()
        age = _process_age()
        if age is not None:
            self.t0 = now - age
        else:
            self.t0 = package_stamp if package_stamp is not None else now
        self._lock = threading.Lock()
        self.marks: Dict[str, float] = {}
        if package_stamp is not None:
            self.marks["package"] = max(package_stamp - self.t0, 0.0)
        self._jit = {stage: [0, 0.0] for stage in JIT_STAGES}
        self._retrieval_s = 0.0     # of ``load``: the cache's own read
        self._stored = 0            # compiles the persistent cache kept
        self._jit_before_engine = 0.0
        self._programs: Dict[str, _Program] = {}
        # histogram name -> [count, seconds, jit seconds inside]
        self._spans: Dict[str, list] = {}
        # Garbage collections (``fold_collections``): how many and their
        # seconds by generation, and the seconds by the span they fell in.
        self._gc_n = [0, 0, 0]
        self._gc_s = [0.0, 0.0, 0.0]
        self._gc_by_span: Dict[str, float] = {}
        self._early: list = []
        self.metrics = MetricsRegistry()
        self.metrics.tracer = self

    # -- writers -------------------------------------------------------
    def mark(self, name: str) -> None:
        """Stamp ``name`` now; the first stamp of a name stands."""
        t = time.perf_counter() - self.t0
        with self._lock:
            if name not in self.marks:
                self.marks[name] = t
                if name == "engine_begin":
                    self._jit_before_engine = sum(
                        s for _n, s in self._jit.values())

    def span_closed(self, hist_name: str, seconds: float,
                    jit_seconds: float) -> None:
        with self._lock:
            e = self._spans.get(hist_name)
            if e is None:
                e = self._spans[hist_name] = [0, 0.0, 0.0]
            e[0] += 1
            e[1] += seconds
            e[2] += jit_seconds

    def jit_event(self, stage: str, program: str, seconds: float,
                  own: bool, span: Optional[str]) -> None:
        """One ended stage: ``seconds`` of self time of ``program``;
        ``own`` is False for a trace inside a trace (its caller's
        tracing, no event of its own)."""
        with self._lock:
            total = self._jit[stage]
            total[0] += own
            total[1] += seconds
            p = self._programs.get(program)
            if p is None:
                if len(self._programs) >= self.MAX_PROGRAMS:
                    program = "(others)"
                p = self._programs.setdefault(program, _Program())
            p.events += own
            p.span = span
            if stage == "trace":
                p.trace_s += seconds
            elif stage == "lower":
                p.lower_s += seconds
            else:
                p.backend_s += seconds
                p.cache = "hit" if stage == "load" else "miss"
                if stage == "compile":
                    p.compiles += 1
                    p.compile_s += seconds

    def gc_event(self, generation: int, seconds: float,
                 span: Optional[str]) -> None:
        with self._lock:
            self._gc_n[generation] += 1
            self._gc_s[generation] += seconds
            key = span or "(none)"
            self._gc_by_span[key] = self._gc_by_span.get(key, 0.0) + seconds

    def cache_event(self, retrieval_s: float = 0.0, stored: int = 0) -> None:
        with self._lock:
            self._retrieval_s += retrieval_s
            self._stored += stored

    # -- the record's registry hands it its spans (``registry.tracer``) --
    def begin(self, name: str, args: dict):
        note = None
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            note = profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **args)
            note.__enter__()
        return name, time.perf_counter(), args, note

    def end(self, token) -> None:
        name, start, args, note = token
        if note is not None:
            note.__exit__(None, None, None)
        with self._lock:
            if ("first_run" not in self.marks
                    and len(self._early) < self.EARLY_SPANS):
                self._early.append((name, start, time.perf_counter(), args))

    # -- readers -------------------------------------------------------
    def early_spans(self) -> list:
        """``[(name, start, end, args)]`` (``perf_counter`` stamps) of
        the record's own spans closed so far, while no run of the
        process has started; ``[]`` from then on."""
        with self._lock:
            return [] if "first_run" in self.marks else list(self._early)

    def _top(self, rows: list) -> list:
        """The ``PROGRAMS`` rows (``_Program.row``) with most seconds."""
        rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"]
                                  + r["backend_s"]))
        return rows[:self.PROGRAMS]

    def run_start(self) -> dict:
        """The ``process`` field of a ``run_start`` event, and the stamp
        of the process's first run.  ``age_s`` is now; ``marks`` as they
        stand; ``jit`` {stage: [events, self seconds]} with, beside it,
        what of them fell before ``engine_begin`` and the cache's own
        counts; ``programs`` the ``PROGRAMS`` with most jit seconds and
        ``compiled`` [name, compiles, seconds] of those the backend
        compiled rather than loaded (in a warm process: the programs the
        persistent cache never keeps); ``runs`` the process's closed
        ``run`` scopes, its ``make_engine`` spans and the ``PHASES``
        largest phases of any engine so far, each net of the jit seconds
        inside it."""
        self.mark("first_run")
        with self._lock:
            age = time.perf_counter() - self.t0
            compiled = sorted(
                ([name, p.compiles, round(p.compile_s, 4)]
                 for name, p in self._programs.items() if p.compiles),
                key=lambda row: -row[2])[:self.PROGRAMS]
            spans = self._spans
            run = spans.get(SCOPE_PREFIX + "run", (0, 0.0, 0.0))
            phases = sorted(
                ((k[len(PHASE_PREFIX):], _net(e)) for k, e in spans.items()
                 if k.startswith(PHASE_PREFIX)), key=lambda kv: -kv[1])
            return {
                "age_s": round(age, 4),
                "marks": {k: round(v, 4) for k, v in self.marks.items()},
                "jit": {s: [n, round(sec, 4)]
                        for s, (n, sec) in self._jit.items()},
                "jit_before_engine_s": round(self._jit_before_engine, 4),
                "cache": {"retrieval_s": round(self._retrieval_s, 4),
                          "stored": self._stored},
                "programs": self._top(
                    [p.row(name) for name, p in self._programs.items()]),
                "compiled": compiled,
                "runs": {
                    "count": run[0], "run_s": _net(run),
                    "make_engine_s": _net(spans.get(
                        SCOPE_PREFIX + "make_engine", (0, 0.0, 0.0))),
                    "phases": dict(phases[:self.PHASES])},
            }

    def gc_reading(self):
        """The collections as they stand, for ``gc_since``."""
        fold_collections()
        with self._lock:
            return (tuple(self._gc_n), tuple(self._gc_s),
                    dict(self._gc_by_span))

    def gc_since(self, base) -> dict:
        """What ``run_end.gc`` carries: the process's collections since
        ``base`` (a ``gc_reading``), whichever thread ran them (a
        collection holds the interpreter, so every thread waits for it):
        ``collections`` and ``seconds_by_generation`` as [gen 0, 1, 2],
        ``seconds``, and ``by_span`` {innermost span open on the
        collecting thread, ``(none)`` where none was: seconds}."""
        n0, s0, by0 = base
        n1, s1, by1 = self.gc_reading()
        gen_s = [round(b - a, 6) for a, b in zip(s0, s1)]
        return {"collections": [b - a for a, b in zip(n0, n1)],
                "seconds": round(sum(s1) - sum(s0), 6),
                "seconds_by_generation": gen_s,
                "by_span": {k: round(v - by0.get(k, 0.0), 6)
                            for k, v in by1.items()
                            if v - by0.get(k, 0.0) > 0.0}}

    def jit_reading(self):
        """The jit totals as they stand, for ``jit_since``."""
        with self._lock:
            return ({s: tuple(v) for s, v in self._jit.items()},
                    {k: (p.trace_s, p.lower_s, p.backend_s, p.events)
                     for k, p in self._programs.items()})

    def jit_since(self, base) -> dict:
        """What ``run_end.jit`` carries: the stages that moved since
        ``base`` (a ``jit_reading``) as {stage: [events, seconds]} and,
        under ``programs``, the programs they belong to, each with the
        span its last stage fell in; ``{}`` when jax did nothing."""
        stages0, programs0 = base
        with self._lock:
            out = {}
            for s, (n, sec) in self._jit.items():
                n0, sec0 = stages0[s]
                if n > n0 or sec > sec0:
                    out[s] = [n - n0, round(sec - sec0, 4)]
            if not out:
                return out
            moved = []
            for name, p in self._programs.items():
                b = programs0.get(name, (0.0, 0.0, 0.0, 0))
                if p.events > b[3] or p.seconds > b[0] + b[1] + b[2]:
                    moved.append(p.row(name, b))
            out["programs"] = self._top(moved)
            return out


PROCESS = ProcessRecord(IMPORT_STAMP)


def process_record() -> ProcessRecord:
    """The process's record (looked up at every call)."""
    return PROCESS


@contextmanager
def process_span(name: str, begin: Optional[str] = None,
                 end: Optional[str] = None, **args):
    """A span on the process record's own registry, between two of its
    marks; a ``with`` block or a decorator.  For what runs before any
    engine's registry exists: ``load_config``, ``make_engine``."""
    record = process_record()
    if begin is not None:
        record.mark(begin)
    span = record.metrics.open_span(name, SCOPE_PREFIX, **args)
    try:
        yield span
    finally:
        span.close()
        if end is not None:
            record.mark(end)


def _program_name(fun_name) -> str:
    """``chunk`` of jax's ``jit(chunk)`` (the lowering and the backend
    name the module, the trace the function)."""
    name = str(fun_name or "?")
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


def _jit_begin(event: str, _value, fun_name=None, **_kw) -> None:
    stage = _JIT_EVENTS.get(event)
    if stage is None:
        return
    frames = getattr(_OPEN, "jit", None)
    if frames is None:
        frames = _OPEN.jit = []
    # A trace inside a trace is its caller's tracing: jax traces every
    # jitted function the chunk calls, thousands of them, inside the
    # chunk's own trace.
    if stage == "trace" and frames and frames[-1][0] == "trace":
        frames.append([stage, frames[-1][1], 0.0, False, False])
    else:
        frames.append([stage, _program_name(fun_name), 0.0, True, False])


def _jit_mark(event: str, **_kw) -> None:
    # Neither carries a name: a hit falls inside the backend bracket of
    # the program it belongs to, which is open on this thread and
    # reports after it.
    if event == _HIT_EVENT:
        frames = getattr(_OPEN, "jit", None)
        if frames:
            frames[-1][4] = True
    elif event == _STORED_EVENT:
        PROCESS.cache_event(stored=1)


def _jit_end(event: str, duration: float, fun_name=None, **_kw) -> None:
    """The listener for jax's duration events.  Every stage that ends is
    counted in the process record, in self time (its duration less the
    stages that ran inside it on this thread), and charged to every span
    open on the thread (``Span.jit``); the innermost one's registry
    counts it too: ``compile/<span>`` and ``compile_seconds/<span>`` a
    backend compile or cache load, as they always have,
    ``trace_seconds/<span>`` and ``lower_seconds/<span>`` the rest."""
    stage = _JIT_EVENTS.get(event)
    if stage is None:
        if event == _RETRIEVAL_EVENT:
            PROCESS.cache_event(retrieval_s=float(duration))
        return
    duration = float(duration)
    frames = getattr(_OPEN, "jit", None) or []
    frame = None
    while frames and frame is None:
        top = frames.pop()
        if top[0] == stage:
            frame = top
    if frame is None:       # begun before the listeners were registered
        frame = [stage, _program_name(fun_name), 0.0, True, False]
    _stage, program, inside, own, hit = frame
    seconds = max(duration - inside, 0.0)
    if frames:
        frames[-1][2] += duration
    if stage == "backend":
        stage = "load" if hit else "compile"
    stack = getattr(_OPEN, "stack", None)
    span = stack[-1] if stack else None
    if span is not None:
        for s in stack:
            s.jit += seconds
        if event == _BACKEND_EVENT:
            span.registry.counter("compile/" + span.name)
            span.registry.counter("compile_seconds/" + span.name, duration)
        else:
            span.registry.counter(f"{stage}_seconds/{span.name}", seconds)
    PROCESS.jit_event(stage, program, seconds, own,
                      span.name if span is not None else None)


# -- collections ------------------------------------------------------------
# The interpreter calls ``gc.callbacks`` at the start and the stop of
# every collection, on the thread whose allocation set it off, which may
# be inside one of this module's own locked sections (a histogram made
# under the registry's lock is such an allocation).  So the hook takes no
# lock: it appends what it saw to a deque and the next ``Span.close`` or
# reading folds that into the registries and the process record.
_GC_EVENTS: deque = deque()
_ANNOTATE = None            # jax.profiler.TraceAnnotation, once watching


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        note = None
        if _ANNOTATE is not None and _ANNOTATE.is_enabled():
            # Inside a capture a collection is ``raft.gc`` on the line of
            # the thread that ran it, on the device trace's own clock.
            note = _ANNOTATE(ANNOTATION_PREFIX + "gc",
                             generation=info["generation"])
            note.__enter__()
        _OPEN.gc = (time.perf_counter(), note)
        return
    begun = getattr(_OPEN, "gc", None)
    if begun is None:       # started before the hook was registered
        return
    _OPEN.gc = None
    seconds = time.perf_counter() - begun[0]
    if begun[1] is not None:
        begun[1].set_metadata(collected=info.get("collected", 0))
        begun[1].__exit__(None, None, None)
    stack = getattr(_OPEN, "stack", None)
    _GC_EVENTS.append((info["generation"], seconds,
                       stack[-1] if stack else None))


def fold_collections() -> None:
    """Charge the collections the hook has seen since the last call:
    each to the registry of the span it fell in (``gc/<span>``,
    ``gc_seconds/<span>``) and to the process record."""
    while _GC_EVENTS:
        try:
            generation, seconds, span = _GC_EVENTS.popleft()
        except IndexError:      # another thread folded it
            return
        if span is not None:
            span.registry.counter("gc/" + span.name)
            span.registry.counter("gc_seconds/" + span.name, seconds)
        PROCESS.gc_event(generation, seconds,
                         span.name if span is not None else None)


def gc_seconds() -> float:
    """Seconds of the process's collections so far (0.0 until
    ``watch_compiles`` has registered the hook): what a host loop reads
    at both ends of a call."""
    if _GC_EVENTS:
        fold_collections()
    return sum(PROCESS._gc_s)


_watching = False


def watch_compiles() -> None:
    """Register jax's listeners and the collector's hook, once per
    process (both keep them for the life of the process)."""
    global _watching, _ANNOTATE
    if not _watching:
        import jax.monitoring as monitoring
        import jax.profiler
        monitoring.register_scalar_listener(_jit_begin)
        monitoring.register_event_listener(_jit_mark)
        monitoring.register_event_duration_secs_listener(_jit_end)
        _ANNOTATE = jax.profiler.TraceAnnotation
        gc.callbacks.append(_on_gc)
        _watching = True
