"""Flight recorder — always-on black-box telemetry + postmortem dumps.

Every other observability leg (JSONL events, Chrome traces,
coverage) is post-hoc and file-based: a run that dies with a
hung device, a SIGTERM'd supervised child, or a fault-injected
``os._exit`` leaves nothing but whatever already hit disk.  This module
is the black box: a bounded in-memory ring of recent telemetry records
— run events (mirrored automatically from every :class:`RunEventLog`,
file-backed or not), one ``call`` row a device call of a host loop
(obs/calls.py: what the call was, and where its time lay), and
run-context/registry deltas — always
on at near-zero overhead (a deque append under a lock per record),
plus a **postmortem dump**: when the
recorder is armed for a run and the process dies abnormally, the ring
(and a final metrics-registry snapshot) is written to
``<workdir>/postmortem.json`` so the last N seconds of telemetry
survive the crash.

Dump triggers, covering every way a run has actually died in this repo:

- an exception escaping ``engine.run()`` (the engines' shared
  ``_telemetry_run`` dumps in its error path and stamps
  ``postmortem_path`` into the ``run_end`` event);
- ``SIGTERM`` (handler installed while armed; dumps, then re-delivers
  the signal with the previous disposition restored);
- a fault-injected hard kill (``resilience/faults.py`` ``_die`` dumps
  best-effort before ``os._exit`` — atexit hooks never run there);
- any other interpreter exit while armed (``atexit`` backstop).

A clean run end (exhausted / violation / deadlock / budget stop)
disarms without dumping — a postmortem file always means a run that did
NOT complete.

The ring is also the live half of **run attach**: the server's ``watch``
op and the standalone ``--metrics-port`` HTTP listener
(:mod:`.expose`) read their snapshots from here, never from the event
file — so a plain ``check``/bench run is watchable with no event log
configured at all.

Zero-dependency and jax-free at import, like the rest of ``obs/``
(:func:`host_fingerprint` imports jax lazily and degrades to nulls).
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import threading
import time
from collections import deque
from typing import Dict, Optional

#: Records kept per kind.  Per-kind rings (not one shared ring) so a
#: high-rate kind (call) can never evict the rare, precious ones
#: (run events, run context) out of the black box.
DEFAULT_CAPACITY = 256

#: Kinds that keep more: one ``call`` row a device call, not
#: rate-limited, and a run's own reduction at its end (obs/calls.py
#: ``reduce_calls``) reads them back, so the ring holds a run.
CAPACITIES = {"call": 4096}

#: The kind ``watch``, ``--metrics-port`` and the postmortem read as a
#: run's current state.  No loop writes it: it is a VIEW of the newest
#: ``call`` row, these fields of it (a BFS loop's and the swarm's) and
#: ``elapsed``, the row's ``t``.
PROGRESS = "progress"
PROGRESS_FIELDS = ("mode", "distinct", "generated", "diameter", "frontier",
                   "offset", "next_count", "seen_size", "steps", "visited",
                   "traces", "saturation")


def host_fingerprint() -> dict:
    """Identity of the host + accelerator stack a measurement ran on:
    CPU model, jax/jaxlib versions, device kind and count, platform.
    Embedded in bench JSON (``scripts/bench_diff.py`` warns when two
    diffed benches disagree — absolute numbers off a different host are
    not comparable, the PR 7 BENCH_r05 trap) and in every postmortem
    dump.  Best-effort: a jax-less or /proc-less environment yields
    nulls, never a raise."""
    out = {"cpu_model": None, "jax": None, "jaxlib": None,
           "device_kind": None, "device_count": None, "platform": None,
           "hostname": None}
    try:
        import platform as _platform
        out["hostname"] = _platform.node() or None
        out["cpu_model"] = _platform.processor() or None
    except Exception:
        pass
    try:                       # Linux: the processor() string is often ""
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    out["cpu_model"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    try:
        import jax
        out["jax"] = jax.__version__
        try:
            import jaxlib
            out["jaxlib"] = getattr(jaxlib, "__version__", None)
        except Exception:
            pass
        devs = jax.devices()
        out["device_count"] = len(devs)
        out["platform"] = devs[0].platform
        out["device_kind"] = getattr(devs[0], "device_kind", None)
    except Exception:
        pass
    return out


class FlightRecorder:
    """Bounded per-kind ring of recent telemetry records.

    Thread-safe: the engine's host loop, the server's handler threads,
    and the HTTP listener all touch one process-global instance
    (:data:`RECORDER`).  Each record is a small dict stamped with a
    process-monotone ``seq`` (so consumers can order across kinds and
    detect new data) and ``ts``.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        # RLock, not Lock: the SIGTERM/atexit dump path runs snapshot()
        # in the MAIN thread, and the signal handler can interrupt the
        # main thread INSIDE a record() that already holds the lock — a
        # plain Lock would deadlock the dying process right where it is
        # supposed to write its black box.  (CPython guarantees the
        # interrupted critical section resumes after the handler; a
        # same-thread re-entrant read sees a consistent-enough ring —
        # at worst the in-flight record is absent.)
        self._lock = threading.RLock()
        self._rings: Dict[str, deque] = {}
        self._seq = 0
        # -- postmortem arming (one run at a time, like the device) ----
        self._live = False            # a run is in flight (watch liveness)
        self._armed_path: Optional[str] = None   # where a dump would land
        self._armed_context: Optional[dict] = None
        self._metrics = None           # registry to snapshot into dumps
        self._live_evlog = None        # run's RunEventLog for watch_attach
        self._hooks_installed = False
        self._prev_sigterm = None

    # -- recording -----------------------------------------------------
    def record(self, kind: str, /, **fields) -> int:
        """Append one record; returns its ``seq``."""
        rec = {"seq": 0, "ts": round(time.time(), 6)}
        rec.update(fields)
        return self.put(kind, rec)

    def put(self, kind: str, rec: dict) -> int:
        """``record`` for a caller that has built its record already,
        ``ts`` included (a host loop's row a call): ``rec`` itself goes
        into the ring, numbered."""
        with self._lock:
            self._seq += 1
            rec["seq"] = seq = self._seq
            ring = self._rings.get(kind)
            if ring is None:
                ring = self._rings[kind] = deque(maxlen=max(
                    self.capacity, CAPACITIES.get(kind, 0)))
            ring.append(rec)
        return seq

    def progress(self) -> Optional[dict]:
        """The run's current state, as its newest ``call`` row has it
        (``PROGRESS_FIELDS`` and ``elapsed``, under the row's ``seq`` and
        ``ts``); None before a loop's first call."""
        with self._lock:
            ring = self._rings.get("call")
            row = ring[-1] if ring else None
        if row is None:
            return None
        view = {"seq": row["seq"], "ts": row["ts"],
                "elapsed": round(row.get("t", 0.0), 3)}
        view.update((k, row[k]) for k in PROGRESS_FIELDS if k in row)
        return view

    # -- reading -------------------------------------------------------
    def seq(self) -> int:
        with self._lock:
            return self._seq

    def snapshot(self, kinds=None, last: Optional[int] = None) -> dict:
        """{kind: [records oldest->newest]}; ``last`` trims each kind to
        its newest N records.  ``progress`` is the one view of
        :meth:`progress`, where there is one."""
        with self._lock:
            out = {}
            for kind, ring in self._rings.items():
                if kinds is not None and kind not in kinds:
                    continue
                recs = list(ring)
                if last is not None:
                    recs = recs[-last:]
                out[kind] = recs
        view = self.progress()
        if view is not None and (kinds is None or PROGRESS in kinds):
            out[PROGRESS] = [view]
        return out

    def last_record(self, kind: str) -> Optional[dict]:
        if kind == PROGRESS:
            return self.progress()
        with self._lock:
            ring = self._rings.get(kind)
            return ring[-1] if ring else None

    def last_event(self, event_type: str) -> Optional[dict]:
        """Newest mirrored run event of one type (the ``event`` ring
        holds every RunEventLog emit) — how the watch op finds the last
        ``level_complete`` / ``coverage`` / ``run_end``."""
        with self._lock:
            ring = self._rings.get("event")
            if not ring:
                return None
            for rec in reversed(ring):
                if rec.get("event") == event_type:
                    return rec
        return None

    def clear(self) -> None:
        """Testing hook: drop every ring (the seq counter keeps
        advancing — consumers rely on it being process-monotone)."""
        with self._lock:
            self._rings.clear()

    # -- run attach ----------------------------------------------------
    def set_live_evlog(self, evlog) -> None:
        """Register the current run's event log (engines'
        ``_telemetry_run``) so a watcher attaching mid-run can leave a
        ``watch_attach`` event in the run's durable record."""
        self._live_evlog = evlog

    def note_attach(self, **client) -> int:
        """A watcher attached (server ``watch`` op / HTTP ``/flight``
        consumer): record it in the ring and, when a run is live, in its
        JSONL event log (payload object ``client`` — see
        ``obs/events.py`` KNOWN_EVENTS)."""
        seq = self.record("watch_attach", client=dict(client))
        evlog = self._live_evlog
        if evlog is not None:
            try:
                evlog.emit("watch_attach", client=dict(client))
            except Exception:
                pass               # attach bookkeeping must never kill a run
        return seq

    # -- postmortem ----------------------------------------------------
    def arm(self, path: Optional[str], metrics=None,
            context: Optional[dict] = None) -> None:
        """Arm for one run: liveness on (watchers see a run in flight)
        and the postmortem dump targeted at ``path``.  ``path`` None
        arms the bookkeeping (context/metrics still feed watch
        snapshots, ``armed`` still reports the live run) but disables
        the dump — there is nowhere to write it."""
        self._live = True
        self._armed_path = path
        self._armed_context = dict(context or {})
        self._metrics = metrics
        if context:
            self.record("run_context", **dict(context))
        self._install_hooks()

    def disarm(self) -> None:
        """The run completed (any stop_reason) — no dump on exit."""
        self._live = False
        self._armed_path = None
        self._armed_context = None
        self._metrics = None

    def context(self) -> dict:
        """The armed run's context snapshot (engine/pipeline plus any
        ``run_context_extra`` tags — job id / tenant under the serving
        layer); {} when no run is live.  The server's per-job watch
        reads this to attribute the ring's progress records to the job
        that owns the device right now."""
        with self._lock:
            return dict(self._armed_context or {})

    @property
    def armed(self) -> bool:
        """A run is in flight.  Liveness, NOT dump-path-configured: a
        run without a checkpoint/postmortem dir is still live for the
        watch consumers (its dump is simply disabled — ``dump()``
        no-ops on the missing path)."""
        return self._live

    def dump(self, reason: str, path: Optional[str] = None
             ) -> Optional[str]:
        """Write the postmortem JSON (atomic tmp + rename) and return
        its path, or None when there is nowhere to write (not armed and
        no explicit path).  Never raises — this runs from signal
        handlers, ``atexit``, and the fault-injection death path, where
        a secondary failure must not mask the primary one."""
        path = path or self._armed_path
        if path is None:
            return None
        try:
            doc = {
                "postmortem": True,
                "reason": reason,
                "written_ts": round(time.time(), 6),
                "pid": os.getpid(),
                "context": dict(self._armed_context or {}),
                "host": host_fingerprint(),
                "records": self.snapshot(),
            }
            mt = self._metrics
            if mt is not None:
                try:
                    doc["metrics"] = mt.snapshot()
                except Exception:
                    pass
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f, default=str)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            return path
        except Exception:
            return None

    # -- process hooks -------------------------------------------------
    def _install_hooks(self) -> None:
        """atexit backstop + SIGTERM handler, installed once per
        process.  The SIGTERM handler dumps, restores the previous
        disposition, and re-delivers — so supervisors/timeouts that
        expect SIGTERM to kill still see it kill."""
        if self._hooks_installed:
            return
        self._hooks_installed = True
        atexit.register(self._atexit_dump)
        try:
            self._prev_sigterm = signal.signal(
                signal.SIGTERM, self._on_sigterm)
        except (ValueError, OSError):
            # Not the main thread (server-embedded engines) or an
            # environment without signals: the atexit/error paths still
            # cover everything except a hard external kill.
            self._prev_sigterm = None

    def _atexit_dump(self) -> None:
        if self.armed:
            self.dump("atexit_while_armed")

    def _on_sigterm(self, signum, frame) -> None:
        self.dump("sigterm")
        # Restore the EXACT previous disposition (SIG_IGN / SIG_DFL /
        # handler — signal.signal accepts all three) and re-deliver:
        # the host's choice is respected, including a deliberate
        # SIG_IGN, which the recorder must not convert into a death.
        prev = self._prev_sigterm
        try:
            signal.signal(signum, prev if prev is not None
                          else signal.SIG_DFL)
        except (ValueError, OSError, TypeError):
            pass
        try:
            os.kill(os.getpid(), signum)    # re-deliver
        except OSError:
            os._exit(143)


#: The process-global black box every layer feeds (engines, event logs,
#: profiler, server) and every consumer reads (watch op, HTTP listener,
#: postmortem dumps).  One per process, like the server's _METRICS.
RECORDER = FlightRecorder()
