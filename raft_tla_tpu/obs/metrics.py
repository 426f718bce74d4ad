"""Metrics registry — counters, gauges, histograms, and phase timing.

TLC's only live observability is a ~per-minute progress line; the engines
here replace their scattered prints and packed-stats side channels with
one registry every layer (engine, mesh, server, CLI, bench) writes into.
Zero-dependency and thread-safe: the checker service handles requests on
multiple threads against one process-global registry, and the engines'
host loops update theirs thousands of times per second — so every
operation is a few dict ops under one lock, and nothing here ever
imports jax (the registry must be importable in tooling that never
touches a device).

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
profiler's own clock.
"""

from __future__ import annotations

import math
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

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

# The spans open on each thread, outermost first.  Process-wide because
# what reads it is: jax's monitoring listeners are registered once per
# process, and the engines' compile listener (engine/bfs.py) charges a
# compile to the innermost span open on the compiling thread.
_OPEN = threading.local()


def innermost_span():
    """The innermost span open on the calling thread, or None."""
    stack = getattr(_OPEN, "stack", None)
    return stack[-1] if stack else None


class Span:
    """One open span; ``close()`` ends it and feeds the sinks.  After
    the close ``seconds`` holds its duration."""

    __slots__ = ("registry", "name", "seconds", "_hist", "_t0", "_token")

    def __init__(self, registry, hist: str, name: str, args: dict):
        self.registry, self.name, self._hist = registry, name, hist
        self.seconds = None
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        stack.append(self)
        tracer = registry.tracer
        self._token = (tracer.begin(name, args) if tracer is not None
                       else None)
        self._t0 = time.perf_counter()

    def close(self) -> None:
        if self.seconds is not None:
            return
        self.seconds = time.perf_counter() - self._t0
        self.registry.observe(self._hist + self.name, self.seconds)
        if self._token is not None:
            self.registry.tracer.end(self._token)
        # A loop-shaped span (``level``) may be closed after an
        # exception unwound past it: whatever was opened inside it and
        # never closed goes with it.
        stack = _OPEN.stack
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
