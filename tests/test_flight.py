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
        fr.record("progress", i=i)
    fr.record("event", event="run_start")
    snap = fr.snapshot()
    assert len(snap["progress"]) == 8
    assert [r["i"] for r in snap["progress"]] == list(range(12, 20))
    # A high-rate kind never evicts a rare one: per-kind rings.
    assert len(snap["event"]) == 1
    # seq is process-monotone across kinds.
    seqs = [r["seq"] for recs in snap.values() for r in recs]
    assert len(set(seqs)) == len(seqs)
    assert fr.last_record("progress")["i"] == 19
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


def test_progress_rate_limit_first_always_lands():
    fr = FlightRecorder()
    fr.arm(None)                      # resets the limiter, armed bookkeeping
    assert fr.progress(distinct=1) is not None
    # Immediately after: suppressed by the rate limiter.
    assert fr.progress(distinct=2) is None
    assert fr.last_record("progress")["distinct"] == 1
    fr.disarm()
    assert not fr.armed


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
    RECORDER.record("progress", distinct=11, generated=22, diameter=1,
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
