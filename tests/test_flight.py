"""Flight recorder, Prometheus exposition, and run-attach tests
(ISSUE 9: always-on black-box telemetry + live introspection).

The ring/exposition halves are tested standalone (zero-dep, jax-free);
the integration tests then pin the acceptance contract: a crashing run
leaves a postmortem dump holding its last progress snapshots and
chunk-stage samples (the hard-kill variant is exercised end-to-end by
``scripts/chaos_check.py`` in CI — here the in-process error path,
which shares the dump machinery), engine results are bit-identical
with ``--xla-profile`` / ``--metrics-port`` on vs off, and the
``watch`` HTTP transport serves live snapshots.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models.dims import RaftDims
from raft_tla_tpu.models.invariants import Bounds, build_constraint
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.obs import (MetricsRegistry, parse_prometheus,
                              render_prometheus, validate_run_events)
from raft_tla_tpu.obs.expose import counter_sample, start_metrics_server
from raft_tla_tpu.obs import flight as flight_mod
from raft_tla_tpu.obs.calls import CallLog
from raft_tla_tpu.obs.flight import RECORDER, FlightRecorder

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def small_config(**kw):
    base = dict(batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, record_trace=False)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# FlightRecorder ring semantics

def test_ring_eviction_keeps_newest_per_kind():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("hunt", i=i)
    fr.record("event", event="run_start")
    snap = fr.snapshot()
    assert len(snap["hunt"]) == 8
    assert [r["i"] for r in snap["hunt"]] == list(range(12, 20))
    # A high-rate kind never evicts a rare one: per-kind rings.
    assert len(snap["event"]) == 1
    # seq is process-monotone across kinds.
    seqs = [r["seq"] for recs in snap.values() for r in recs]
    assert len(set(seqs)) == len(seqs)
    assert fr.last_record("hunt")["i"] == 19
    assert fr.last_event("run_start")["event"] == "run_start"
    assert fr.last_event("run_end") is None


def test_ring_thread_safety():
    fr = FlightRecorder(capacity=4096)
    barrier = threading.Barrier(8)

    def work(k):
        barrier.wait()
        for i in range(200):
            fr.record(f"kind{k % 2}", worker=k, i=i)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = fr.snapshot()
    total = sum(len(v) for v in snap.values())
    assert total == 8 * 200
    assert fr.seq() == 8 * 200


def test_progress_is_a_view_of_the_newest_call_row():
    """No loop writes a ``progress`` record any more: what ``watch``,
    ``--metrics-port`` and a postmortem read under that name is the
    newest ``call`` row's state fields, under that row's ``seq``."""
    fr = FlightRecorder()
    fr.arm(None)
    assert fr.progress() is None and fr.last_record("progress") is None
    assert "progress" not in fr.snapshot()
    log = CallLog(1, recorder=fr)
    log.start()
    for call in (1, 2):
        log.dispatch()
        log.row("chunk", "full", 4, 0.001, 0.002, 0.0, 0.0005, call, 3, 4,
                7, 2, distinct=10 * call, generated=30 * call, diameter=2,
                frontier=7, offset=4, next_count=5, seen_size=10 * call)
    view = fr.progress()
    row = fr.last_record("call")
    assert view["seq"] == row["seq"] and view["distinct"] == 20
    assert set(view) == {"seq", "ts", "distinct", "generated", "diameter",
                         "frontier", "offset", "next_count", "seen_size",
                         "elapsed"}          # and no other clock of the row
    assert view["elapsed"] == round(row["t"], 3)
    assert abs(row["ts"] - time.time()) < 5.0    # the wall's, as any record's
    assert fr.last_record("progress") == view
    snap = fr.snapshot()
    assert snap["progress"] == [view] and len(snap["call"]) == 2
    assert "progress" not in fr.snapshot(kinds=("call",))
    # One record a call is all the loop wrote.
    assert fr.seq() == row["seq"] and [r["call"] for r in snap["call"]] \
        == [1, 2]
    fr.disarm()
    assert not fr.armed


def test_the_call_ring_holds_a_run():
    """Kinds keep ``DEFAULT_CAPACITY`` records but ``call``, which keeps
    ``CAPACITIES['call']``: a run's own reduction reads its rows back."""
    fr = FlightRecorder()
    log = CallLog(7, recorder=fr)
    n = flight_mod.CAPACITIES["call"] + 10
    for call in range(n):
        log.dispatch()
        log.row("chunk", "ramp" if call % 2 else "full", 2, 0.0, 0.001,
                0.0, 0.0, call, 1, 2, 1, 1)
        fr.record("hunt", i=call)
    snap = fr.snapshot()
    assert len(snap["call"]) == flight_mod.CAPACITIES["call"] == 4096
    assert len(snap["hunt"]) == flight_mod.DEFAULT_CAPACITY
    assert snap["call"][-1]["call"] == n - 1
    # The tallies need no row: they hold for every call of the run.
    calls = log.reduce()
    assert calls["n"] == n and calls["rows"] == 4096
    assert sum(r["calls"] for r in calls["by_rule"].values()) == n
    assert sum(r["passes"] for r in calls["by_rule"].values()) == 2 * n
    # Another run's rows, and rows from before the log, are not its own.
    other = CallLog(8, recorder=fr)
    assert other.rows() == [] and other.reduce()["slowest"] is None


def test_dump_and_disarm(tmp_path):
    fr = FlightRecorder()
    path = str(tmp_path / "postmortem.json")
    mt = MetricsRegistry()
    mt.counter("engine/distinct", 7)
    fr.arm(path, metrics=mt, context={"engine": "T", "batch": 4})
    fr.record("progress", distinct=7)
    out = fr.dump("test_reason")
    assert out == path
    doc = json.loads(open(path).read())
    assert doc["postmortem"] is True and doc["reason"] == "test_reason"
    assert doc["context"]["engine"] == "T"
    assert doc["records"]["progress"][-1]["distinct"] == 7
    assert doc["records"]["run_context"][-1]["batch"] == 4
    assert doc["metrics"]["counters"]["engine/distinct"] == 7
    assert "cpu_model" in doc["host"]
    fr.disarm()
    # Disarmed: no implicit path, dump is a no-op.
    assert fr.dump("again") is None


# ---------------------------------------------------------------------------
# Prometheus exposition

def test_prometheus_render_parse_roundtrip():
    mt = MetricsRegistry()
    mt.counter("server/requests/check", 5)
    mt.gauge("engine/seen_size", 1234)
    for v in (0.001, 0.003, 0.004, 7.5):
        mt.observe("phase/chunk", v)
    text = render_prometheus(mt.snapshot(), labels={"host": "2"})
    samples = parse_prometheus(text)
    assert counter_sample(samples, "server/requests/check") == 5
    g = samples["raft_engine_seen_size"]
    assert g[0] == ({"host": "2"}, 1234.0)
    # Histogram: cumulative monotone buckets closing at +Inf == _count.
    buckets = samples["raft_phase_chunk_bucket"]
    inf = [v for l, v in buckets if l["le"] == "+Inf"]
    assert inf == [4.0]
    assert samples["raft_phase_chunk_count"][0][1] == 4.0
    assert abs(samples["raft_phase_chunk_sum"][0][1] - 7.508) < 1e-9
    counts = [v for _l, v in buckets]
    assert counts == sorted(counts)


def test_prometheus_parser_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all!\n")
    with pytest.raises(ValueError):                 # bad value
        parse_prometheus("raft_x{a=\"b\"} notanumber\n")
    with pytest.raises(ValueError):                 # duplicate TYPE
        parse_prometheus("# TYPE raft_x counter\n# TYPE raft_x counter\n"
                         "raft_x 1\n")
    # Histogram without +Inf bucket.
    with pytest.raises(ValueError):
        parse_prometheus("# TYPE raft_h histogram\n"
                         "raft_h_bucket{le=\"1\"} 1\n"
                         "raft_h_sum 1\nraft_h_count 1\n")
    # Non-monotone buckets.
    with pytest.raises(ValueError):
        parse_prometheus("# TYPE raft_h histogram\n"
                         "raft_h_bucket{le=\"1\"} 5\n"
                         "raft_h_bucket{le=\"2\"} 3\n"
                         "raft_h_bucket{le=\"+Inf\"} 5\n"
                         "raft_h_sum 1\nraft_h_count 5\n")


def test_metrics_http_listener_serves_metrics_and_flight():
    mt = MetricsRegistry()
    mt.counter("engine/distinct", 42)
    srv, _t = start_metrics_server(0, mt, flight=RECORDER)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            text = r.read().decode()
            assert "version=0.0.4" in r.headers["Content-Type"]
        samples = parse_prometheus(text)
        assert counter_sample(samples, "engine/distinct") == 42
        seq_before = RECORDER.seq()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/flight", timeout=30) as r:
            doc = json.loads(r.read().decode())
        assert doc["ok"] and "records" in doc
        # The poll itself leaves a watch_attach record in the ring.
        att = RECORDER.last_record("watch_attach")
        assert att is not None and att["seq"] > seq_before
        assert att["client"]["transport"] == "http"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=30)
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Event-log schema: the new event types enforce their payload objects

def test_validate_events_new_payloads(tmp_path):
    def write(recs):
        p = tmp_path / "e.jsonl"
        p.write_text("".join(json.dumps(r) + "\n" for r in recs))
        return str(p)

    base = [{"event": "run_start", "ts": 1.0},
            {"event": "run_end", "ts": 2.0}]
    good = base + [
        {"event": "postmortem", "ts": 1.5, "dump": {"path": "x"}},
        {"event": "watch_attach", "ts": 1.6,
         "client": {"transport": "server"}},
        {"event": "xla_profile", "ts": 1.7,
         "capture": {"logdir": "d", "status": "ok"}}]
    assert len(validate_run_events(write(good))) == 5
    for bad in ({"event": "postmortem", "ts": 1.5},
                {"event": "watch_attach", "ts": 1.5, "client": "peer"},
                {"event": "xla_profile", "ts": 1.5, "capture": None}):
        with pytest.raises(ValueError):
            validate_run_events(write(base + [bad]))


def test_file_less_evlog_mirrors_into_flight():
    from raft_tla_tpu.obs import RunEventLog
    seq0 = RECORDER.seq()
    log = RunEventLog(None)
    assert not log.enabled
    log.emit("coverage", actions={"A": {}})
    rec = RECORDER.last_event("coverage")
    assert rec is not None and rec["seq"] > seq0
    assert rec["actions"] == {"A": {}}


# ---------------------------------------------------------------------------
# Engine integration

def test_error_exit_writes_postmortem_with_progress(tmp_path):
    """The in-process half of the crash contract (the hard-kill half is
    scripts/chaos_check.py in CI, via the same dump machinery in
    faults._die): a run dying on an exception leaves postmortem.json
    with the last progress snapshots, and its run_end event carries
    postmortem_path."""
    from raft_tla_tpu.resilience import faults
    ck = tmp_path / "states"
    ev = tmp_path / "e.jsonl"
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(
                        checkpoint_dir=str(ck), events_out=str(ev),
                        checkpoint_interval_seconds=0.0,
                        degrade_on_oom=False, max_diameter=6))
    faults.install("oom@level=2", hard=False)
    try:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    pm_path = os.path.join(str(ck), "postmortem.json")
    assert os.path.exists(pm_path)
    doc = json.loads(open(pm_path).read())
    assert doc["reason"].startswith("run error:")
    assert doc["records"]["progress"], "no progress snapshots in dump"
    assert doc["context"]["engine"] == "BFSEngine"
    # run_end points at the dump; a postmortem event precedes it.
    events = validate_run_events(str(ev))
    end = [e for e in events if e["event"] == "run_end"][-1]
    assert end["stop_reason"] == "error"
    assert end["postmortem_path"] == pm_path
    assert any(e["event"] == "postmortem"
               and e["dump"]["path"] == pm_path for e in events)
    assert not RECORDER.armed          # error path still disarms


def test_clean_run_leaves_no_postmortem(tmp_path):
    ck = tmp_path / "states"
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(checkpoint_dir=str(ck),
                                        max_diameter=2))
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "diameter_budget"
    assert not os.path.exists(os.path.join(str(ck), "postmortem.json"))
    assert not RECORDER.armed


def test_xla_profile_and_metrics_port_are_observational(tmp_path):
    """Acceptance: bit-identical verdict/counts/levels with the device
    profiler window and the exposition listener on vs off."""
    plain = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(max_diameter=3))
    base = plain.run([init_state(DIMS)])

    ev = tmp_path / "e.jsonl"
    instr = BFSEngine(
        DIMS, constraint=build_constraint(DIMS, BOUNDS),
        config=small_config(
            max_diameter=3, events_out=str(ev),
            xla_profile_chunks=2,
            xla_profile_dir=str(tmp_path / "xp")))
    srv, _t = start_metrics_server(0, instr.metrics, flight=RECORDER)
    try:
        port = srv.server_address[1]
        res = instr.run([init_state(DIMS)])
        # The exposition is live and valid right after the run.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            samples = parse_prometheus(r.read().decode())
        assert counter_sample(samples, "engine/distinct") is not None
    finally:
        srv.shutdown()
    assert (res.distinct, res.generated, res.levels, res.stop_reason) \
        == (base.distinct, base.generated, base.levels, base.stop_reason)
    # The capture landed its event; ok or a recorded failure, never
    # silence.
    events = validate_run_events(str(ev))
    caps = [e for e in events if e["event"] == "xla_profile"]
    assert len(caps) == 1
    cap = caps[0]["capture"]
    assert cap["chunks"] == 2 and cap["span_name"] == "chunk"
    if cap["status"] == "ok":        # CPU backend supports the profiler
        assert cap["steps"] >= 1
        assert os.path.isdir(str(tmp_path / "xp"))


def test_mesh_engine_has_flight_hooks():
    """MeshBFSEngine duck-types BFSEngine (no inheritance): every hook
    the shared _telemetry_run calls must exist on it explicitly — a
    missing one only explodes at run start on a multi-device box, which
    tier-1's budget may never reach (caught live: _xla_profile_dir)."""
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    for hook in ("_postmortem_path", "_xla_profile_dir", "_events_path",
                 "_emit_level_event"):
        assert callable(getattr(MeshBFSEngine, hook, None)), hook


def test_watch_http_console_renders(tmp_path, capsys):
    """The watch CLI's HTTP transport against a live listener: at least
    one rendered line, clean exit on --count."""
    from raft_tla_tpu.cli import _watch_http
    mt = MetricsRegistry()
    RECORDER.record("call", distinct=11, generated=22, diameter=1,
                    frontier=3, next_count=4, elapsed=1.0)
    srv, _t = start_metrics_server(0, mt, flight=RECORDER)
    try:
        port = srv.server_address[1]
        rc = _watch_http(f"http://127.0.0.1:{port}", interval=0.05,
                         count=2, timeout=30, as_json=False)
    finally:
        srv.shutdown()
    assert rc == 0
    out = capsys.readouterr().out
    assert "watch[" in out and "distinct 11" in out


# ---------------------------------------------------------------------------
# One row a device call: every host loop writes them (obs/calls.py)

def test_the_mesh_loop_writes_a_row_a_call(tmp_path):
    """Two of the suite's virtual devices: the mesh's own loop feeds the
    same rows, the same ``run_end.calls`` and through them the same
    watch view as the one-chip loop."""
    import jax
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    ev = tmp_path / "e.jsonl"
    eng = MeshBFSEngine(
        DIMS, constraint=build_constraint(DIMS, BOUNDS),
        devices=jax.devices()[:2],
        config=small_config(batch=8, sync_every=2, max_diameter=5,
                            events_out=str(ev)))
    res = eng.run([init_state(DIMS)])
    rows = eng._calls.rows()
    end = validate_run_events(str(ev))[-1]
    assert {r["kind"] for r in rows} == {"ingest", "chunk"}
    assert len(rows) == end["chunk_calls"] + end["ingest_calls"] \
        == end["calls"]["n"]
    assert sum(r["passes"] for r in rows) == end["passes"]
    assert sum(r["new"] for r in rows) == res.distinct
    by_rule = end["calls"]["by_rule"]
    assert sum(r["passes"] for r in by_rule.values()) == end["passes"]
    assert {"ingest", "level_end"} <= set(by_rule) <= {"ingest", "full",
                                                       "level_end"}
    last = [r for r in rows if r["kind"] == "chunk"][-1]
    assert (last["distinct"], last["level"]) == (res.distinct, 5)
    view = RECORDER.last_record("progress")
    assert view["seq"] == last["seq"] and view["distinct"] == res.distinct
    assert {"gc", "trace_rehashes"} <= set(end)


def test_the_swarm_loop_writes_a_row_a_chunk(tmp_path):
    """One slice: a row a chunk of lockstep steps (``passes`` are the
    steps), and the watch view of a hunt read from the newest."""
    from raft_tla_tpu.engine.swarm import SwarmEngine
    ev = tmp_path / "e.jsonl"
    eng = SwarmEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      walks=32, max_depth=12, batch=32, chunk=8, ring=8,
                      events_out=str(ev))
    res = eng.run([init_state(DIMS)], seed=3, num_steps=24)
    rows = eng._calls.rows()
    end = validate_run_events(str(ev))[-1]
    assert [r["kind"] for r in rows] == ["swarm_chunk"] * 3
    assert [r["passes"] for r in rows] == [8, 8, 8]
    assert [r["level"] for r in rows] == [0, 8, 16]      # the start step
    assert len(rows) == end["chunk_calls"]          # one slice
    assert sum(r["new"] for r in rows) == res.visited
    assert end["calls"]["by_rule"]["steps"]["passes"] == 24
    assert end["calls"]["n"] == 3 and "gc" in end
    assert rows[-1]["steps"] == res.steps == 32 * 24
    view = RECORDER.last_record("progress")
    assert view["mode"] == "swarm" and view["steps"] == res.steps
    assert view["visited"] == res.visited


def test_a_dump_holds_the_last_calls_and_the_view(tmp_path):
    """A postmortem's ``records`` carry the run's last ``call`` rows (where
    each call's time lay) and, under ``progress``, the view of the newest:
    what the supervisor and ``scripts/chaos_check.py`` read."""
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=4))
    eng.run([init_state(DIMS)])
    RECORDER.arm(None)
    try:
        path = RECORDER.dump("test", path=str(tmp_path / "pm.json"))
    finally:
        RECORDER.disarm()
    doc = json.loads(open(path).read())
    rows = [r for r in doc["records"]["call"]
            if r.get("run") == eng._run_id]
    assert rows and {"dispatch_s", "wait_s", "flush_s", "host_s", "gap_s",
                     "cpu_s", "gc_s", "rule", "passes"} <= set(rows[-1])
    (view,) = doc["records"]["progress"]
    assert view["seq"] == doc["records"]["call"][-1]["seq"]
    assert view["distinct"] == doc["records"]["call"][-1]["distinct"]
