"""Telemetry subsystem shared by every engine and entry point.

One spine, several legs:

- :mod:`.metrics` — a zero-dep, thread-safe :class:`MetricsRegistry`
  (counters / gauges / histograms) with the one span primitive:
  :meth:`~MetricsRegistry.phase_timer` around the host-side phases of
  the BFS chunk loop and the simulate/mesh paths,
  :meth:`~MetricsRegistry.scope` around what holds them (``run``,
  ``level``, ``replay``); and the one **process record**
  (:class:`~.metrics.ProcessRecord`): marks, span totals and jax's
  trace / lower / load / compile stages by program since the package's
  import, carried by every ``run_start`` as ``process``;
- :mod:`.events` — the structured JSONL :class:`RunEventLog`
  (run_start, level_complete, fpset_resize, spill, checkpoint,
  violation, deadlock, coverage, run_end) written next
  to the checkpoint dir and per-host under ``parallel/mesh.py``;
- :mod:`.tracing` — :class:`SpanTracer`, nested spans serialized as
  Chrome trace-event JSON (``--trace-out``; opens in Perfetto).
  Attached to a registry it receives every span, and holds a
  ``jax.profiler.TraceAnnotation`` open for each (``raft.<name>`` in
  any profiler capture);
- :mod:`.profile` — :class:`XlaProfileCapture`, the ``jax.profiler``
  window over N of the real chunk dispatches behind ``--xla-profile``;
- :mod:`.coverage` — :class:`ActionCoverage`, TLC-style per-action
  generated/distinct/disabled counters and the run-end coverage table;
- :mod:`.calls` — one ``call`` row a device call, written by the host
  loops into the flight ring, and a run's own reduction of them
  (``run_end.calls``: by rule, the slowest call and the phase its
  excess lay in);
- :mod:`.flight` — the always-on :class:`FlightRecorder` black box
  (bounded ring of recent events and call rows) with the
  crash/SIGTERM/fault-kill **postmortem dump** and the process-global
  :data:`~.flight.RECORDER` the live-introspection consumers read;
- :mod:`.expose` — Prometheus text exposition of the registry
  (``render_prometheus``/``parse_prometheus``) and the standalone
  ``--metrics-port`` HTTP listener (``/metrics`` + ``/flight``) behind
  the ``watch`` run-attach console;
- :mod:`.report` — the TLC-parity **statespace run report** (collision
  probability, per-level frontier table, out-degree, seen-set load)
  assembled host-side at run end: the ``statespace`` event,
  ``EngineResult.report``, and the TLC-style stderr block;
- :mod:`.history` — the append-only JSONL **run-history ledger**
  (``check --history`` / ``HISTORY`` directive / ``BENCH_HISTORY``):
  per-run cfg/model/host fingerprints, verdict, rates, and report
  summary; ``scripts/bench_history.py`` renders the trajectory and
  ``scripts/bench_diff.py --history`` resolves baselines from it.

The CLI exposes them via ``--metrics-out`` / ``--events-out`` /
``--trace-out`` / ``--metrics-port`` / ``--xla-profile``, the checker
service via the ``stats`` / ``metrics`` / ``watch`` requests, and
``bench.py`` embeds the phase breakdown and coverage in its JSON
(``scripts/bench_diff.py`` gates on both).  See README.md
"Observability" for the schemas.

Nothing here models a performance number: how fast the program is
comes from the chip, through ``benchmark/`` (trace, spans, counters) or
``--xla-profile`` / ``--trace-out`` / ``--events-out``.  And ``obs`` is
a leaf: ``utils/`` imports it, so it imports none of its sibling
packages (tests/test_layers.py).
"""

from .metrics import (Histogram, MetricsRegistry, PHASE_PREFIX,  # noqa: F401
                      phase_delta)
from .events import (KNOWN_EVENTS, REQUIRED_EVENTS, RunEventLog,  # noqa: F401
                     all_device_memory_stats, device_memory_stats,
                     events_path, peak_host_rss_bytes,
                     validate_and_cleanup, validate_run_events)
from .tracing import SpanTracer, validate_chrome_trace           # noqa: F401
from .coverage import ActionCoverage                             # noqa: F401
from .flight import (FlightRecorder, RECORDER,                   # noqa: F401
                     host_fingerprint)
from .expose import (parse_prometheus, render_prometheus,        # noqa: F401
                     serve_metrics, start_metrics_server)
from .report import (build_report, collision_probability,        # noqa: F401
                     render_report)
from . import history                                            # noqa: F401
from .profile import XlaProfileCapture                           # noqa: F401
