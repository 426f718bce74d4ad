"""Structured run events — one JSONL line per engine lifecycle event.

The event log is the durable half of the telemetry subsystem (the
registry is the live half): engines append one JSON object per line for
``run_start``, ``level_complete``, ``fpset_resize``, ``spill``,
``checkpoint``, ``violation``, ``deadlock``, and ``run_end``.  Every
event carries ``ts`` (epoch seconds) and ``elapsed_seconds`` (since the
log was opened); level and end events add live counters, the per-phase
wall-time breakdown, and the device memory probe; a ``BFSEngine``'s
``run_start`` of a run from roots carries ``seen_capacity`` (slots of
the table the run starts at: what the last such run of the same engine
needed) beside ``seen_capacity_configured``, a resume's neither.  The
JSONL file is the supported interface for dashboards and regression
tooling — the bench harness fails loudly when a run leaves it missing
or malformed (``validate_run_events``).

Placement: ``EngineConfig.events_out`` names the file; when unset it
defaults to ``events.jsonl`` next to the checkpoint dir (TLC's states/
analog), and stays disabled when neither is set.  Multi-host runs write
one file per controller (``events_path`` suffixes the piece id), same
model as checkpoint/trace pieces.

A ``RunEventLog(None)`` is a no-op sink, so engines emit unconditionally.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

#: Event types a complete, healthy run always contains.
REQUIRED_EVENTS = ("run_start", "run_end")

#: Every event type the engines/tooling emit (documentation + the
#: validator's schema table).  Unknown types still validate — forward
#: compatibility — but known STRUCTURED types must carry their payload
#: field, so a half-written coverage emitter fails the bench
#: gate instead of shipping empty records.
KNOWN_EVENTS = (
    "run_start", "level_complete", "fpset_resize", "spill", "checkpoint",
    "violation", "deadlock", "run_end", "restart", "supervised_done",
    "supervise_giveup", "degraded", "analysis",
    # Deep-profiling layer (obs/coverage.py):
    "coverage",         # TLC-style per-action counters; payload: "actions"
    # Flight-recorder / live-introspection layer (obs/flight.py,
    # obs/expose.py):
    "postmortem",       # a black-box dump was written; payload: "dump"
    "watch_attach",     # a live watcher attached; payload: "client"
    "xla_profile",      # device-profiler capture window; payload: "capture"
    # Semantic-observability layer (obs/report.py): the TLC-parity
    # statespace report, one per completed run.  ``run_end`` also gains
    # ``counterexample_path`` when a traced violation was rendered
    # (engine/explain.py).
    "statespace",       # TLC-parity run report; payload: "report"
    # The mesh's per-shard balance warning (parallel/mesh.py skew
    # telemetry).
    "skew",             # shard imbalance warning; payload: "balance"
    "rebalance",        # the mesh dealt an uneven frontier out evenly
    # Swarm tier (engine/swarm.py): periodic walker progress.  Swarm
    # runs also attach the same ``swarm`` payload object to their
    # ``run_end`` (exhaustive run_ends carry none, so only the
    # progress event gets schema-table enforcement).
    "swarm_progress",   # walker-fleet progress; payload: "swarm"
    # Hunt observatory (obs/hunt.py): the run-end saturation /
    # walk-analytics report for swarm runs — the probabilistic sibling
    # of ``statespace``.
    "hunt",             # swarm coverage report; payload: "hunt"
    # Serving layer (serving/manager.py): one per job that reached a
    # terminal state through the executor, in the service's own log
    # ``<job-dir>/events.jsonl``: ``queue_wait_s``, ``run_s``,
    # ``engine_wall_s``, ``turnaround_s``, ``cached``, ``result_bytes``.
    "job_end",
    # Host loops (obs/calls.py): a call of the run just ended, or the gap
    # before it, exceeded what it should have cost by over a second; the
    # call's whole row, flat, with ``expected_s``, ``excess_s`` and the
    # ``phase`` the excess lay in.  Emitted before ``run_end``, which
    # counts them (``calls.slow_calls``).
    "slow_call",
)

#: Structured payload field each new event type must carry.
_EVENT_PAYLOAD_FIELDS = {"coverage": "actions",
                         "postmortem": "dump", "watch_attach": "client",
                         "xla_profile": "capture", "statespace": "report",
                         "skew": "balance",
                         "swarm_progress": "swarm", "hunt": "hunt"}


#: memory_stats() keys kept in event payloads (one extraction for the
#: single-device and per-device probes, so they can never desynchronize).
_MEMORY_KEEP = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size")


def _probe_device(device) -> dict:
    try:
        stats = device.memory_stats() or {}
    except Exception:
        return {}
    return {k: int(stats[k]) for k in _MEMORY_KEEP if k in stats}


def device_memory_stats() -> dict:
    """Compact view of the first device's ``memory_stats()`` probe (the
    same probe ``engine/bfs._auto_capacities`` sizes from); {} when the
    backend reports nothing (virtual CPU devices) or jax is unavailable."""
    try:
        import jax
        return _probe_device(jax.devices()[0])
    except Exception:
        return {}


def all_device_memory_stats() -> list:
    """Per-device memory probes for the run_end event, one dict per
    visible device IN ORDER.  Guarded the same way as the single-device
    probe: a platform whose devices report nothing (CPU, virtual
    devices) contributes ``{}`` per device — the field is always
    present, never silently absent — and a jax-less process returns
    ``[]``."""
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return []
    return [_probe_device(d) for d in devices]


def peak_host_rss_bytes():
    """Peak resident set size of this process in bytes (ru_maxrss is KB
    on Linux, bytes on macOS — normalize to bytes), or None where the
    resource module is unavailable (non-POSIX)."""
    try:
        import resource
        import sys
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(peak) if sys.platform == "darwin" else int(peak) * 1024
    except Exception:
        return None


def events_path(events_out: Optional[str], checkpoint_dir: Optional[str],
                process_index: int = 0,
                process_count: int = 1) -> Optional[str]:
    """Resolve the event-log path for one controller.  ``events_out``
    wins; otherwise the file lands next to the checkpoints; None/None
    disables.  Under a process group each controller writes its own
    piece file (suffix before the extension), mirroring checkpoint
    pieces — merge for dashboards by concatenation, order by ``ts``."""
    path = events_out
    if path is None and checkpoint_dir is not None:
        path = os.path.join(checkpoint_dir, "events.jsonl")
    if path is None or process_count <= 1:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.p{process_index}of{process_count}{ext or '.jsonl'}"


class RunEventLog:
    """Append-only JSONL event writer; ``RunEventLog(None)`` discards
    the FILE half only — every emit is also mirrored into the
    process-global flight recorder ring (obs/flight.py), which is how
    a run with no event log configured still shows up in the ``watch``
    console and the postmortem dump.  Thread-safe: the run's engine
    thread and a watch attach (server handler thread) may emit into
    one log concurrently, and interleaved partial lines would corrupt
    the JSONL contract."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._f = None
        self._t0 = time.time()
        self._lock = threading.Lock()
        if path is not None:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._f = open(path, "a", encoding="utf-8")

    @property
    def enabled(self) -> bool:
        return self._f is not None

    def elapsed(self) -> float:
        """Seconds since the log was opened (the run's true wall clock —
        unlike the engines' budget clock ``t0`` it never shifts for
        off-clock stalls, so phase sums can be audited against it)."""
        return time.time() - self._t0

    def emit(self, event: str, **fields) -> None:
        now = time.time()
        rec = {"event": event, "ts": round(now, 6),
               "elapsed_seconds": round(now - self._t0, 6)}
        rec.update(fields)
        # Flight-recorder mirror FIRST (before the file check): the ring
        # is the always-on black box, fed even by file-less RunEventLog
        # instances — a crash during a run with no --events-out still
        # postmortems its recent events.  Lazy import avoids an import
        # cycle at package init (flight is a sibling leg).
        try:
            from .flight import RECORDER
            RECORDER.record("event", **rec)
        except Exception:
            pass
        if self._f is None:
            return
        # One line per event, flushed immediately: a crashed run's log
        # stays readable up to the crash (append-only, no buffering).
        # Under the lock: concurrent emitters (engine thread + a watch
        # attach) must never interleave partial lines.
        with self._lock:
            f = self._f
            if f is None:
                return
            f.write(json.dumps(rec, default=str) + "\n")
            f.flush()

    def close(self) -> None:
        with self._lock:
            f, self._f = self._f, None
        if f is not None:
            f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def validate_and_cleanup(events_file: str, scratch_dir: Optional[str],
                         required=REQUIRED_EVENTS) -> int:
    """Bench-harness gate: validate a run's event log, removing
    ``scratch_dir`` whether validation succeeds or raises (a failing CI
    run must not orphan its scratch directory either).  Returns the
    event count; raises like :func:`validate_run_events`."""
    import shutil
    try:
        return len(validate_run_events(events_file, required=required))
    finally:
        if scratch_dir is not None:
            shutil.rmtree(scratch_dir, ignore_errors=True)


def validate_run_events(path: str,
                        required=REQUIRED_EVENTS) -> list:
    """Parse a run event log and verify it is healthy: the file exists,
    every line is a JSON object with ``event`` and ``ts``, and every
    ``required`` event type appears.  Returns the parsed events; raises
    ``FileNotFoundError``/``ValueError`` otherwise.  This is the bench
    harness's telemetry-regression gate (nonzero rc on failure)."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"run event log missing: {path}")
    events = []
    with open(path, encoding="utf-8") as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{ln}: malformed event line ({e})")
            if not isinstance(rec, dict) or "event" not in rec \
                    or "ts" not in rec:
                raise ValueError(
                    f"{path}:{ln}: event record missing 'event'/'ts': "
                    f"{line[:120]}")
            payload = _EVENT_PAYLOAD_FIELDS.get(rec["event"])
            if payload is not None and not isinstance(
                    rec.get(payload), dict):
                raise ValueError(
                    f"{path}:{ln}: {rec['event']!r} event missing its "
                    f"{payload!r} payload object: {line[:120]}")
            events.append(rec)
    have = {e["event"] for e in events}
    missing = [r for r in required if r not in have]
    if missing:
        raise ValueError(
            f"{path}: incomplete run event log — missing {missing} "
            f"(saw {sorted(have)})")
    return events
