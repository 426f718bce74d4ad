"""Telemetry subsystem tests (obs/): registry semantics, event-log
schema, and the engine/CLI integrations.

The registry/event-log halves are tested standalone (they are zero-dep
and must stay importable without jax); the integration tests then assert
the ISSUE acceptance contract end-to-end: a run's JSONL log contains
run_start, level_complete events whose per-phase timings account for the
wall clock, and run_end — through both the BFSEngine API and the CLI.
"""

import json
import os
import threading

import jax.numpy as jnp
import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import Bounds, build_constraint
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.obs import (MetricsRegistry, RunEventLog,
                              events_path, phase_delta,
                              validate_run_events)

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def small_config(**kw):
    base = dict(batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# MetricsRegistry semantics

def test_counters_accumulate_and_gauges_overwrite():
    mt = MetricsRegistry()
    mt.counter("a")
    mt.counter("a", 4)
    mt.gauge("g", 7)
    mt.gauge("g", 3)
    snap = mt.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 3
    assert mt.counter_value("a") == 5
    assert mt.counter_value("missing") == 0


def test_histogram_summary_and_buckets():
    mt = MetricsRegistry()
    for v in (0.001, 0.002, 0.004, 10.0):
        mt.observe("h", v)
    h = mt.snapshot()["histograms"]["h"]
    assert h["count"] == 4
    assert h["min"] == 0.001 and h["max"] == 10.0
    assert abs(h["total"] - 10.007) < 1e-9
    assert abs(h["mean"] - 10.007 / 4) < 1e-9
    # 1-2-5 ladder: 0.001 -> "0.001" bucket, 0.002 -> "0.002",
    # 0.004 -> "0.005", 10.0 -> "10"; counts sum to the observation count.
    assert sum(h["buckets"].values()) == 4
    assert h["buckets"]["0.001"] == 1 and h["buckets"]["0.005"] == 1


def test_phase_timer_accumulates_into_phase_seconds():
    mt = MetricsRegistry()
    for _ in range(3):
        with mt.phase_timer("stage"):
            pass
    ph = mt.phase_seconds()
    assert set(ph) == {"stage"}
    assert ph["stage"] >= 0.0
    assert mt.snapshot()["histograms"]["phase/stage"]["count"] == 3
    # phase_timer records even when the body raises (finally-path).
    with pytest.raises(RuntimeError):
        with mt.phase_timer("stage"):
            raise RuntimeError("boom")
    assert mt.snapshot()["histograms"]["phase/stage"]["count"] == 4


def test_phase_delta_scopes_to_a_baseline():
    mt = MetricsRegistry()
    with mt.phase_timer("a"):
        pass
    base = mt.phase_seconds()
    with mt.phase_timer("b"):
        pass
    d = phase_delta(mt.phase_seconds(), base)
    assert "b" in d and "a" not in d     # a advanced by zero since base
    assert phase_delta({"x": 1.0}, None) == {"x": 1.0}


def test_registry_is_thread_safe():
    mt = MetricsRegistry()

    def work():
        for _ in range(1000):
            mt.counter("n")
            mt.observe("h", 0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert mt.counter_value("n") == 8000
    assert mt.snapshot()["histograms"]["h"]["count"] == 8000


# ---------------------------------------------------------------------------
# RunEventLog + validation

def test_event_log_writes_schema_lines(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    with RunEventLog(p) as log:
        assert log.enabled
        log.emit("run_start", foo=1)
        log.emit("run_end", bar="x")
    recs = [json.loads(l) for l in open(p)]
    assert [r["event"] for r in recs] == ["run_start", "run_end"]
    for r in recs:
        assert "ts" in r and "elapsed_seconds" in r
    assert recs[0]["foo"] == 1 and recs[1]["bar"] == "x"
    assert validate_run_events(p)[0]["event"] == "run_start"


def test_event_log_null_sink_noops():
    log = RunEventLog(None)
    assert not log.enabled
    log.emit("run_start")           # must not raise
    log.close()


def test_validate_rejects_missing_malformed_and_incomplete(tmp_path):
    with pytest.raises(FileNotFoundError):
        validate_run_events(str(tmp_path / "nope.jsonl"))
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "run_start", "ts": 1}\nnot json\n')
    with pytest.raises(ValueError, match="malformed"):
        validate_run_events(str(bad))
    partial = tmp_path / "partial.jsonl"
    partial.write_text('{"event": "run_start", "ts": 1}\n')
    with pytest.raises(ValueError, match="run_end"):
        validate_run_events(str(partial))


def test_events_path_resolution(tmp_path):
    assert events_path(None, None) is None
    assert events_path("/x/e.jsonl", "/ck") == "/x/e.jsonl"
    assert events_path(None, "/ck") == os.path.join("/ck", "events.jsonl")
    # Per-controller piece suffix under a process group.
    assert events_path("/x/e.jsonl", None, 1, 4) == "/x/e.p1of4.jsonl"


# ---------------------------------------------------------------------------
# Engine integration (the acceptance contract)

def run_and_load_events(tmp_path, engine_cls=BFSEngine, **cfg_kw):
    ev = str(tmp_path / "events.jsonl")
    eng = engine_cls(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(max_diameter=3, events_out=ev,
                                         **cfg_kw))
    res = eng.run([init_state(DIMS)])
    return res, validate_run_events(ev)


def test_engine_run_emits_complete_event_log(tmp_path):
    res, events = run_and_load_events(tmp_path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    levels = [e for e in events if e["event"] == "level_complete"]
    # Root-ingest level 0 plus the three expanded levels.
    assert len(levels) == len(res.levels) == 4
    assert [e["level"] for e in levels] == [0, 1, 2, 3]
    assert [e["frontier_rows"] for e in levels] == res.levels
    assert levels[-1]["distinct"] == res.distinct
    # Phase accounting: cumulative per-phase seconds + the unattributed
    # remainder == elapsed (exact by construction), AND the named phases
    # cover most of the wall — the breakdown is real, not rounding dust.
    last = levels[-1]
    ph = last["phase_seconds"]
    covered = sum(ph.values())
    assert abs(covered + last["unattributed_seconds"]
               - last["elapsed_seconds"]) < 0.05
    assert covered >= 0.5 * last["elapsed_seconds"]
    assert {"warmup", "chunk", "stats_fetch"} <= set(ph)
    # run_end carries the final snapshot, mirrored on the result object.
    end = events[-1]
    assert end["stop_reason"] == "diameter_budget" == res.stop_reason
    assert end["distinct"] == res.distinct
    assert res.phases and set(ph) <= set(res.phases)


def test_engine_metrics_registry_feeds_counters(tmp_path):
    ev = str(tmp_path / "e.jsonl")
    mt = MetricsRegistry()
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=2, events_out=ev,
                                        metrics=mt))
    res = eng.run([init_state(DIMS)])
    assert eng.metrics is mt     # shared registry honored
    assert mt.counter_value("engine/distinct") == res.distinct
    assert mt.counter_value("engine/generated") == res.generated
    assert mt.snapshot()["gauges"]["engine/seen_size"] > 0


def test_violation_event_and_depth0_replay(tmp_path):
    # Mid-run violation -> a violation event in the log.
    ev = str(tmp_path / "v.jsonl")
    inv = {"NoLeader": lambda st: jnp.all(st.role != LEADER)}
    eng = BFSEngine(DIMS, invariants=inv,
                    constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(events_out=ev))
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    ev_kinds = [e["event"] for e in validate_run_events(ev)]
    assert "violation" in ev_kinds

    # Depth-0 violation (a root violates): replay() must return the
    # one-state trace instead of raising KeyError (ADVICE r5 /
    # mesh root-violation fix; same contract single-chip).
    viol_root = init_state(DIMS).replace(role=(2, 0, 0))
    eng2 = BFSEngine(DIMS, invariants=inv,
                     constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config())
    res2 = eng2.run([viol_root])
    assert res2.stop_reason == "violation"
    steps = eng2.replay(res2.violation.fingerprint)
    assert steps == [(-1, viol_root)]


def test_mesh_engine_emits_events_too(tmp_path):
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    res, events = run_and_load_events(tmp_path, engine_cls=MeshBFSEngine,
                                      batch=16)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    levels = [e for e in events if e["event"] == "level_complete"]
    assert [e["frontier_rows"] for e in levels] == res.levels
    assert res.phases and "stats_fetch" in res.phases


def test_mesh_depth0_root_violation_replayable():
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    inv = {"NoLeader": lambda st: jnp.all(st.role != LEADER)}
    viol_root = init_state(DIMS).replace(role=(2, 0, 0))
    eng = MeshBFSEngine(DIMS, invariants=inv,
                        constraint=build_constraint(DIMS, BOUNDS),
                        config=small_config(batch=16))
    res = eng.run([viol_root])
    assert res.stop_reason == "violation"
    assert eng.replay(res.violation.fingerprint) == [(-1, viol_root)]


# ---------------------------------------------------------------------------
# CLI integration (--events-out / --metrics-out / --progress-interval)

def test_cli_check_writes_events_and_metrics(tmp_path, capsys):
    from raft_tla_tpu.cli import main as cli_main
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ev = str(tmp_path / "cli_events.jsonl")
    mo = str(tmp_path / "cli_metrics.json")
    rc = cli_main([
        "check", os.path.join(here, "configs/MCraft_bounded.cfg"),
        "--engine", "single", "--batch", "64",
        "--queue-capacity", str(1 << 12), "--seen-capacity", str(1 << 15),
        "--max-diameter", "2", "--events-out", ev, "--metrics-out", mo,
        "--progress-interval", "0"])
    assert rc == 0
    events = validate_run_events(ev)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and "level_complete" in kinds \
        and kinds[-1] == "run_end"
    snap = json.load(open(mo))
    assert snap["counters"]["engine/distinct"] == 22   # pinned L2 prefix
    assert any(k.startswith("phase/") for k in snap["histograms"])
    out = capsys.readouterr().out
    assert "distinct states    22" in out


# ---------------------------------------------------------------------------
# Span tracing (obs/tracing.py): recorder semantics, Chrome-trace shape,
# thread safety, and the phase_timer mirror.

def test_span_tracer_nesting_roundtrip(tmp_path):
    from raft_tla_tpu.obs import SpanTracer, validate_chrome_trace
    path = str(tmp_path / "t.json")
    tr = SpanTracer(path)
    with tr.span("outer", level=1):
        with tr.span("inner"):
            pass
    tr.instant("mark", n=3)
    assert tr.write() == path
    events = validate_chrome_trace(path)
    by_name = {e["name"]: e for e in events}
    # Metadata anchors for Perfetto + cross-process merge.
    assert by_name["process_name"]["ph"] == "M"
    assert "unix_seconds" in by_name["trace_start_unix"]["args"]
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"] == {"level": 1}
    # Nesting is by ts/dur containment on one tid — inner inside outer.
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert by_name["mark"]["ph"] == "i"


def test_span_tracer_disabled_is_noop():
    from raft_tla_tpu.obs import SpanTracer
    tr = SpanTracer(None)
    with tr.span("x"):
        tr.instant("y")
    assert len(tr) == 0 and tr.write() is None and not tr.enabled


def test_span_tracer_thread_safety(tmp_path):
    from raft_tla_tpu.obs import SpanTracer, validate_chrome_trace
    path = str(tmp_path / "mt.json")
    tr = SpanTracer(path)
    N_THREADS, N_SPANS = 8, 50
    # All threads alive simultaneously (distinct idents — the OS reuses
    # an exited thread's ident) and recording concurrently.
    gate = threading.Barrier(N_THREADS)

    def work(i):
        gate.wait()
        for j in range(N_SPANS):
            with tr.span(f"w{i}", j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,), name=f"worker-{i}")
               for i in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.write()
    events = validate_chrome_trace(path)
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == N_THREADS * N_SPANS      # none lost to races
    # Each thread got its own lane + exactly one thread_name metadata.
    tids = {e["tid"] for e in spans}
    assert len(tids) == N_THREADS
    names = [e for e in events if e["name"] == "thread_name"]
    assert len({e["tid"] for e in names}) == len(names)


def test_phase_timer_mirrors_into_tracer(tmp_path):
    from raft_tla_tpu.obs import SpanTracer, validate_chrome_trace
    mt = MetricsRegistry()
    mt.tracer = SpanTracer(str(tmp_path / "p.json"))
    with mt.phase_timer("roundtrip"):
        pass
    mt.tracer.write()
    events = validate_chrome_trace(str(tmp_path / "p.json"))
    assert any(e["name"] == "roundtrip" and e["ph"] == "X"
               for e in events)
    # Registry histogram and span agree it happened once.
    assert mt.snapshot()["histograms"]["phase/roundtrip"]["count"] == 1


def test_validate_chrome_trace_rejects(tmp_path):
    from raft_tla_tpu.obs import validate_chrome_trace
    p = tmp_path / "bad.json"
    with pytest.raises(FileNotFoundError):
        validate_chrome_trace(str(tmp_path / "missing.json"))
    p.write_text("{not json")
    with pytest.raises(ValueError, match="not valid JSON"):
        validate_chrome_trace(str(p))
    p.write_text('{"traceEvents": []}')        # object form: rejected
    with pytest.raises(ValueError, match="JSON array"):
        validate_chrome_trace(str(p))
    p.write_text('[{"ph": "X"}]')              # event without name
    with pytest.raises(ValueError, match="name"):
        validate_chrome_trace(str(p))
    p.write_text('[{"name": "a", "ph": "X"}]')  # non-metadata needs ts
    with pytest.raises(ValueError, match="ts"):
        validate_chrome_trace(str(p))
    p.write_text('[{"name": "m", "ph": "M"}]')  # metadata needs no ts
    assert validate_chrome_trace(str(p))


def test_validate_run_events_new_event_payloads(tmp_path):
    from raft_tla_tpu.obs import KNOWN_EVENTS
    assert {"xla_profile", "coverage"} <= set(KNOWN_EVENTS)
    p = tmp_path / "ev.jsonl"
    ok = [{"event": "run_start", "ts": 0.0},
          {"event": "coverage", "ts": 1.0, "actions": {"Timeout": {}}},
          {"event": "xla_profile", "ts": 2.0, "capture": {}},
          {"event": "run_end", "ts": 3.0}]
    p.write_text("".join(json.dumps(e) + "\n" for e in ok))
    assert len(validate_run_events(str(p))) == 4
    # A half-written emitter (payload missing) must fail the gate.
    bad = list(ok)
    bad[1] = {"event": "coverage", "ts": 1.0}
    p.write_text("".join(json.dumps(e) + "\n" for e in bad))
    with pytest.raises(ValueError, match="actions"):
        validate_run_events(str(p))
    bad = list(ok)
    bad[2] = {"event": "xla_profile", "ts": 2.0, "capture": 7}
    p.write_text("".join(json.dumps(e) + "\n" for e in bad))
    with pytest.raises(ValueError, match="capture"):
        validate_run_events(str(p))


def test_skew_event_requires_its_payload(tmp_path):
    """The validator's schema table knows the mesh's ``skew`` warning: a
    record without its ``balance`` object is a malformed log."""
    def log(skew):
        p = tmp_path / "ev.jsonl"
        p.write_text("".join(json.dumps(e) + "\n" for e in (
            {"event": "run_start", "ts": 1.0}, skew,
            {"event": "run_end", "ts": 3.0})))
        return str(p)
    with pytest.raises(ValueError, match="balance"):
        validate_run_events(log({"event": "skew", "ts": 2.0}))
    assert len(validate_run_events(log(
        {"event": "skew", "ts": 2.0,
         "balance": {"frontier_skew": 3.0}}))) == 3


# ---------------------------------------------------------------------------
# Deep-profiling integration: --trace-out spans + coverage, through a
# real (small) engine run.

def test_engine_trace_profile_coverage_end_to_end(tmp_path):
    from raft_tla_tpu.obs import validate_chrome_trace
    ev = str(tmp_path / "e.jsonl")
    trace = str(tmp_path / "trace.json")
    mt = MetricsRegistry()
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(
                        max_diameter=3, events_out=ev, trace_out=trace,
                        metrics=mt))
    res = eng.run([init_state(DIMS)])

    # -- Chrome trace: valid array, a span per level, >=1 chunk span,
    #    one run span bracketing everything.
    events = validate_chrome_trace(trace)
    levels = [e for e in events if e["name"] == "level"]
    assert len(levels) == len(res.levels)
    assert sum(1 for e in events if e["name"] == "chunk") >= 1
    runs = [e for e in events if e["name"] == "run"]
    assert len(runs) == 1 and runs[0]["ph"] == "X"

    # -- The spans' phases land in the shared registry too.
    assert mt.snapshot()["histograms"]["phase/chunk"]["count"] >= 1
    recs = validate_run_events(ev)

    # -- Coverage: per-family generated matches action_counts EXACTLY
    #    (one packed-stats source), distinct partitions distinct minus
    #    the root, disabled = expanded*size - generated.
    cov = res.coverage
    assert {a: v["generated"] for a, v in cov.items()} == res.action_counts
    assert sum(v["generated"] for v in cov.values()) == res.generated
    assert sum(v["distinct"] for v in cov.values()) == res.distinct - 1

    # -- run_end memory satellites: peak RSS + per-device stats list
    #    (CPU devices contribute {} but the field is present).
    end = recs[-1]
    assert end["event"] == "run_end"
    assert end["host_rss_peak_bytes"] is None \
        or end["host_rss_peak_bytes"] > 0
    assert isinstance(end["devices_memory"], list)
    assert len(end["devices_memory"]) >= 1


def test_every_level_row_carries_the_hbm_watermark():
    """The per-level device-HBM watermark needs no option: the field is
    on every ``level_stats`` row (None on CPU devices, which report no
    memory stats — present either way)."""
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=2))
    res = eng.run([init_state(DIMS)])
    assert res.level_stats
    assert all("hbm_peak_bytes" in row for row in res.level_stats)


def test_coverage_events_on_progress_interval(tmp_path, capsys):
    """A tiny progress interval fires a coverage event at every chunk
    boundary and prints the run-end coverage table on stderr."""
    ev = str(tmp_path / "e.jsonl")
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=2, events_out=ev,
                                        progress_interval_seconds=1e-9))
    res = eng.run([init_state(DIMS)])
    recs = validate_run_events(ev)
    cov_evs = [e for e in recs if e["event"] == "coverage"]
    assert len(cov_evs) >= 2                  # interval events + final
    assert cov_evs[-1].get("final") is True
    total_gen = sum(v["generated"]
                    for v in cov_evs[-1]["actions"].values())
    assert total_gen == res.generated
    err = capsys.readouterr().err
    assert "coverage (actions:" in err
    assert "fpset load" in err                # enriched progress line


def test_warm_engine_trace_resets_per_run(tmp_path):
    """A reused engine's second run rewrites the trace as ONE run —
    tracer.reset() at run start, not append (one trace file = one run)."""
    from raft_tla_tpu.obs import validate_chrome_trace
    trace = str(tmp_path / "t.json")
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=1, trace_out=trace))
    eng.run([init_state(DIMS)])
    eng.run([init_state(DIMS)])
    events = validate_chrome_trace(trace)
    assert sum(1 for e in events if e["name"] == "run") == 1
    assert sum(1 for e in events if e["name"] == "trace_start_unix") == 1


# ---------------------------------------------------------------------------
# The observational options do not reach the program: the static form of
# "bit-identical with X on or off".  A run-twice test holds it for the
# states one small run visits; the lowered text holds it for the program.

def chunk_text(**option) -> str:
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(**option))
    return eng._chunk.lower(*eng.chunk_avals()).as_text()


@pytest.fixture(scope="module")
def default_chunk_text():
    return chunk_text()


@pytest.mark.parametrize("option", [
    lambda tmp: {"events_out": str(tmp / "e.jsonl")},
    lambda tmp: {"trace_out": str(tmp / "t.json")},
    lambda tmp: {"metrics": MetricsRegistry()},
    lambda tmp: {"xla_profile_chunks": 2,
                 "xla_profile_dir": str(tmp / "xla")},
    lambda tmp: {"statespace_report": False},
    lambda tmp: {"postmortem_dir": str(tmp)},
    lambda tmp: {"run_context_extra": {"job_id": "j1", "tenant": "t"}},
    lambda tmp: {"progress_interval_seconds": 1e-6},
    lambda tmp: {"counterexample_dir": str(tmp)},
    lambda tmp: {"keep_checkpoints": 2},
    lambda tmp: {"skew_warn_ratio": 1.0},
    lambda tmp: {"trace_merge_timeout_seconds": 5.0},
], ids=["events_out", "trace_out", "metrics", "xla_profile",
        "statespace_report_off", "postmortem_dir", "run_context_extra",
        "progress_interval_seconds", "counterexample_dir",
        "keep_checkpoints", "skew_warn_ratio",
        "trace_merge_timeout_seconds"])
def test_an_observational_option_leaves_the_chunk_program_as_it_is(
        option, tmp_path, default_chunk_text):
    assert chunk_text(**option(tmp_path)) == default_chunk_text
