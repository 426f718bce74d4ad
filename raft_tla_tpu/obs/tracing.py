"""Span tracing — Chrome trace-event JSON for Perfetto / chrome://tracing.

The third leg of the telemetry spine (metrics = live numbers, events =
durable lifecycle JSONL, tracing = *time-structured* spans).  A
:class:`SpanTracer` records nested spans — context-managed ``with
tracer.span("chunk"): ...`` blocks, or explicit ``complete()`` stamps for
loop-shaped scopes — and serializes them to the Chrome trace-event array
format, so a ``check --trace-out run.json`` opens directly in Perfetto
(drag-and-drop) or ``chrome://tracing`` with per-thread nesting intact.

Zero-dependency and thread-safe, like the rest of ``obs/``: spans append
under one lock, thread ids come from the recording thread, and nothing
here imports jax.  A ``SpanTracer(None)`` is a no-op sink (the
``RunEventLog(None)`` pattern), so call sites never branch.

Wiring: the engines attach their tracer to the
:class:`~raft_tla_tpu.obs.metrics.MetricsRegistry` (``registry.tracer``),
which hands every span it opens (``phase_timer``, ``scope``,
``open_span``) to :meth:`SpanTracer.begin`/:meth:`SpanTracer.end` — one
attachment instruments every phase site (chunk dispatch, stats fetch,
spill, checkpoint, sim_chunk, ...) and the scopes around them (``run``,
one ``level`` per BFS level, ``replay``).  The supervisor adds one
``attempt`` span per child run plus ``restart`` instants.

The same ``begin``/``end`` hold an annotation open for the span when
the tracer was given an ``annotate`` factory: the engines pass
``jax.profiler.TraceAnnotation``, so inside any ``jax.profiler`` capture
(the benchmark's ``--trace 1`` window, ``--xla-profile``) every span is
on the host's ``python`` line of the same ``.xplane.pb`` as the device's
operations, as ``raft.<name>`` with its arguments as event stats.  With
no capture active an annotation is a flag check.  Every span also
carries ``run=<n>`` once the engine has set :attr:`SpanTracer.run`.

Format notes (the subset Perfetto accepts without complaint): a JSON
*array* of event objects; ``ph: "X"`` complete events carry ``ts`` and
``dur`` in microseconds; ``ph: "i"`` instants carry ``s: "t"`` (thread
scope); ``ph: "M"`` metadata names processes/threads.  ``ts`` is
relative to tracer creation — merge multi-process traces by the
``trace_start_unix`` metadata arg each file carries.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from . import metrics as _metrics


class SpanTracer:
    """Thread-safe span recorder; ``SpanTracer(None)`` discards.

    ``path`` is where :meth:`write` serializes to by default (the
    ``--trace-out`` file); recording is in-memory, flushed by the
    engines at every level boundary and at run end (atomic rewrite), so
    a crash loses at most the current level's spans and the hot loop
    never blocks on disk.
    """

    #: What an annotation's name starts with in a profiler capture.
    ANNOTATION_PREFIX = _metrics.ANNOTATION_PREFIX

    def __init__(self, path: Optional[str] = None,
                 process_name: str = "raft_tla_tpu", annotate=None):
        self.path = path
        # ``annotate(name, **args)`` -> context manager (the engines pass
        # jax.profiler.TraceAnnotation; this module imports no jax).
        self._annotate = annotate
        # The engine's count of run() calls, shared by the spans of one
        # verdict (its run and the replay of what it found).
        self.run: Optional[int] = None
        self._process_name = process_name
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._clear(time.perf_counter())

    def _clear(self, t0: float) -> None:
        """Drop everything recorded; ``ts`` counts from ``t0`` (a
        ``perf_counter`` stamp, now or earlier)."""
        with self._lock:
            self._events = []
            self._named_tids = set()
        self._t0 = t0
        if self.path is not None:
            # Process metadata + the epoch anchor for cross-process merge.
            self._append({"name": "process_name", "ph": "M",
                          "pid": self._pid, "tid": 0,
                          "args": {"name": self._process_name}})
            self._append({"name": "trace_start_unix", "ph": "M",
                          "pid": self._pid, "tid": 0,
                          "args": {"unix_seconds": round(
                              time.time() - (time.perf_counter() - t0), 6)}})

    def reset(self) -> None:
        """Drop everything recorded and restart the clock — one trace
        file describes ONE run, so warm/reused engines call this at
        every run start (``_telemetry_run``) instead of appending a
        second ``run`` span to the first run's events.  The FIRST run
        of a process keeps what the process recorded before it (the
        ``load_config`` and ``make_engine`` spans of obs/metrics.py
        ``ProcessRecord``), on a clock that starts with the process.
        The supervisor's own tracer is deliberately never reset: its
        attempt/restart timeline spans the whole supervision episode."""
        record = _metrics.process_record()
        early = record.early_spans() if self.path is not None else []
        self._clear(record.t0 if early else time.perf_counter())
        for name, start, end, args in early:
            self._span(name, start, end, args)

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- recording -----------------------------------------------------
    def _append(self, rec: dict) -> None:
        with self._lock:
            self._events.append(rec)

    def _tid(self) -> int:
        tid = threading.get_ident()
        if tid not in self._named_tids:
            self._named_tids.add(tid)
            self._append({"name": "thread_name", "ph": "M",
                          "pid": self._pid, "tid": tid,
                          "args": {"name": threading.current_thread().name}})
        return tid

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def begin(self, name: str, args: dict):
        """Start of a registry span (obs/metrics.py ``Span``): open its
        annotation; ``end`` takes what this returns."""
        if self.run is not None:
            args = {"run": self.run, **args}
        note = None
        if self._annotate is not None:
            note = self._annotate(self.ANNOTATION_PREFIX + name, **args)
            note.__enter__()
        return name, time.perf_counter(), args, note

    def end(self, token) -> None:
        name, start, args, note = token
        if note is not None:
            note.__exit__(None, None, None)
        self.complete(name, start, **args)

    @contextmanager
    def span(self, name: str, **args):
        """Record the block as one complete (``ph: "X"``) event.  Nesting
        is implicit: Chrome/Perfetto stack same-thread spans by ts/dur."""
        if self.path is None:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            self.complete(name, start, **args)

    def complete(self, name: str, start_perf_counter: float, **args) -> None:
        """Record a span from an earlier ``time.perf_counter()`` stamp to
        now — the loop-shaped-scope form (level boundaries, supervisor
        attempts), where a ``with`` block can't bracket the region."""
        if self.path is not None:
            self._span(name, start_perf_counter, time.perf_counter(), args)

    def _span(self, name: str, start: float, end: float, args: dict) -> None:
        rec = {"name": name, "ph": "X", "pid": self._pid,
               "tid": self._tid(),
               "ts": round((start - self._t0) * 1e6, 3),
               "dur": round((end - start) * 1e6, 3)}
        if args:
            rec["args"] = args
        self._append(rec)

    def instant(self, name: str, **args) -> None:
        """Point-in-time marker (``ph: "i"``, thread scope)."""
        if self.path is None:
            return
        rec = {"name": name, "ph": "i", "s": "t", "pid": self._pid,
               "tid": self._tid(), "ts": round(self._now_us(), 3)}
        if args:
            rec["args"] = args
        self._append(rec)

    # -- serialization -------------------------------------------------
    def write(self, path: Optional[str] = None) -> Optional[str]:
        """Serialize everything recorded so far as one Chrome trace JSON
        array (atomic tmp + rename; repeat calls rewrite — the engines
        call this at every run end, so the newest run always lands even
        if a later one crashes mid-write).  Returns the path written, or
        None when the tracer is disabled."""
        path = path or self.path
        if path is None:
            return None
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with self._lock:
            events = list(self._events)
        tmp = f"{path}.tmp{self._pid}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(events, f, default=str)
            f.write("\n")
        os.replace(tmp, path)
        return path


def validate_chrome_trace(path: str) -> list:
    """Validate a ``--trace-out`` file: it must parse as a JSON *array*
    of event objects each carrying ``name``/``ph`` (and ``ts`` for
    non-metadata phases) — the shape Perfetto accepts.  Returns the
    events; raises ``FileNotFoundError``/``ValueError`` otherwise.  The
    bench/CI tooling calls this next to ``validate_run_events`` so a
    trace regression fails as loudly as an event-log one."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"trace file missing: {path}")
    with open(path, encoding="utf-8") as f:
        try:
            events = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"{path}: not valid JSON ({e})")
    if not isinstance(events, list):
        raise ValueError(
            f"{path}: Chrome trace must be a JSON array of events, got "
            f"{type(events).__name__} (the object-with-traceEvents form "
            f"is not what this tracer emits)")
    for i, rec in enumerate(events):
        if not isinstance(rec, dict) or "name" not in rec \
                or "ph" not in rec:
            raise ValueError(
                f"{path}: event {i} is not an object with 'name'/'ph': "
                f"{str(rec)[:120]}")
        if rec["ph"] != "M" and "ts" not in rec:
            raise ValueError(
                f"{path}: event {i} ({rec['name']!r}, ph={rec['ph']!r}) "
                f"missing 'ts'")
    return events
