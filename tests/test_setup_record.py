"""The process record (obs/metrics.py ``ProcessRecord``): marks, span
totals net of jit, and jax's trace / lower / compile-or-load events in
self time by program, counted whether or not a span is open; the spans a
process closes before its first run land in that run's ``--trace-out``.

Each test puts a record of its own where the listeners and ``Span.close``
look for the process's, so none depends on what ran before it in the
worker.  What an engine's ``run_start`` / ``run_end`` carry is held in
tests/test_spans.py (``BFSEngine``), tests/test_mesh.py and
tests/test_swarm.py.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from raft_tla_tpu import IMPORT_STAMP
from raft_tla_tpu.obs import MetricsRegistry, SpanTracer
from raft_tla_tpu.obs import metrics as metrics_mod
from raft_tla_tpu.obs.metrics import (JIT_STAGES, ProcessRecord,
                                      process_span, watch_compiles)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture
def record(monkeypatch):
    rec = ProcessRecord()
    monkeypatch.setattr(metrics_mod, "PROCESS", rec)
    return rec


def setup_reader():
    """benchmark/readers/setup.py: what reads the record into the eight
    ``setup.*`` metrics."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_setup",
        os.path.join(REPO, "benchmark", "readers", "setup.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_process(process: dict) -> dict:
    """What every ``run_start.process`` holds, whichever engine emitted
    it; returns the eight parts the benchmark's reader makes of it."""
    marks = process["marks"]
    chain = [marks[k] for k in ("cfg_loaded", "engine_begin", "engine_built",
                                "first_run") if k in marks]
    assert chain == sorted(chain) and chain[-1] <= process["age_s"], marks
    assert all(t >= 0 for t in marks.values())
    assert set(process["jit"]) == set(JIT_STAGES)
    assert len(process["programs"]) <= ProcessRecord.PROGRAMS
    assert len(json.dumps(process)) < 4096
    reader = setup_reader()
    parts = reader.partition(process)
    assert tuple(parts) == reader.PARTS and len(parts) == 8
    assert sum(parts.values()) == pytest.approx(process["age_s"], rel=0.01)
    assert all(parts[k] >= 0 for k in reader.PARTS[:-1]), parts
    # Self time: the four stages never pass the wall they fell in.
    assert sum(sec for _n, sec in process["jit"].values()) <= process["age_s"]
    return parts


def begin(event, name):
    metrics_mod._jit_begin(event, time.time(), fun_name=name)


def end(event, name, seconds):
    metrics_mod._jit_end(event, seconds, fun_name=name)


def stage_seconds(process):
    return sum(sec for _n, sec in process["jit"].values())


# -- (a) the listener, fed by hand -------------------------------------------

def test_a_trace_inside_a_trace_is_counted_once(record):
    """jax reports ``inner``'s trace inside ``chunk``'s, each with its
    own duration: 3.0 s of wall hold 3.0 s of tracing, not 4.5, all of
    it ``chunk``'s, in one event."""
    begin(TRACE, "chunk")
    begin(TRACE, "inner")
    begin(TRACE, "_where")
    end(TRACE, "_where", 0.5)
    end(TRACE, "inner", 1.0)
    begin(TRACE, "inner")
    end(TRACE, "inner", 0.5)
    end(TRACE, "chunk", 3.0)
    begin(LOWER, "jit(chunk)")
    end(LOWER, "jit(chunk)", 0.25)
    process = record.run_start()
    assert process["jit"]["trace"] == [1, 3.0]
    assert process["jit"]["lower"] == [1, 0.25]
    assert [(p["name"], p["trace_s"], p["lower_s"])
            for p in process["programs"]] == [("chunk", 3.0, 0.25)]
    assert stage_seconds(process) == pytest.approx(3.25)


def test_stages_inside_a_stage_never_pass_the_enclosing_wall(record):
    """An eager operation inside a trace is traced, lowered and compiled
    while the outer trace's clock runs: the outer keeps what is left, so
    the four stages sum to the 2.0 s of wall they fell in."""
    begin(TRACE, "outer")
    begin(TRACE, "iota")
    end(TRACE, "iota", 0.125)
    begin(LOWER, "jit(iota)")
    end(LOWER, "jit(iota)", 0.25)
    begin(BACKEND, "jit(iota)")
    end(BACKEND, "jit(iota)", 0.5)
    end(TRACE, "outer", 2.0)
    process = record.run_start()
    assert process["jit"]["trace"] == [1, 2.0 - 0.25 - 0.5]
    assert process["jit"]["lower"] == [1, 0.25]
    assert process["jit"]["compile"] == [1, 0.5]
    assert stage_seconds(process) == pytest.approx(2.0)
    by = {p["name"]: p for p in process["programs"]}
    assert by["iota"]["backend_s"] == 0.5 and by["iota"]["trace_s"] == 0.0
    assert by["outer"]["trace_s"] == 1.25


def test_a_hit_is_a_load_and_a_miss_a_compile(record):
    """``cache_hits`` carries no name: it falls inside the backend
    bracket of the program it belongs to, which reports after it."""
    begin(BACKEND, "jit(chunk)")
    metrics_mod._jit_mark("/jax/compilation_cache/compile_requests_use_cache")
    metrics_mod._jit_mark(HIT)
    metrics_mod._jit_end(RETRIEVAL, 0.75)
    end(BACKEND, "jit(chunk)", 1.0)
    begin(BACKEND, "jit(_fetch_shard)")
    metrics_mod._jit_mark("/jax/compilation_cache/compile_requests_use_cache")
    end(BACKEND, "jit(_fetch_shard)", 0.125)
    begin(BACKEND, "jit(_fetch_shard)")
    end(BACKEND, "jit(_fetch_shard)", 0.125)
    begin(BACKEND, "jit(ingest)")
    metrics_mod._jit_mark("/jax/compilation_cache/cache_misses")  # stored
    end(BACKEND, "jit(ingest)", 2.0)
    process = record.run_start()
    assert process["jit"]["load"] == [1, 1.0]
    assert process["jit"]["compile"] == [3, 2.25]
    assert process["cache"] == {"retrieval_s": 0.75, "stored": 1}
    assert {p["name"]: p["cache"] for p in process["programs"]} == {
        "chunk": "hit", "_fetch_shard": "miss", "ingest": "miss"}
    assert process["compiled"] == [["ingest", 1, 2.0],
                                   ["_fetch_shard", 2, 0.25]]


def test_nesting_is_judged_on_the_thread_the_events_came_from(record):
    """Two threads trace at once (the server runs engines on several):
    neither's trace is taken out of the other's."""
    begin(TRACE, "chunk")

    def other():
        begin(TRACE, "ingest")
        end(TRACE, "ingest", 1.0)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    end(TRACE, "chunk", 2.0)
    process = record.run_start()
    assert process["jit"]["trace"] == [2, 3.0]
    assert {p["name"]: p["trace_s"] for p in process["programs"]} == {
        "chunk": 2.0, "ingest": 1.0}


# -- (b) real compiles, with and without a span ------------------------------

def test_a_compile_with_no_span_open_is_counted_and_one_under_a_span_twice(
        record):
    watch_compiles()
    assert metrics_mod.innermost_span() is None

    def setup_record_alone(x):
        return x * 3 + 1

    x = np.arange(7)
    jax.jit(setup_record_alone)(x).block_until_ready()
    process = record.run_start()
    assert [p["name"] for p in process["programs"]] == ["setup_record_alone"]
    (row,) = process["programs"]
    assert row["span"] is None
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert process["jit"]["trace"][0] == process["jit"]["lower"][0] == 1
    assert process["jit"]["load"][0] + process["jit"]["compile"][0] == 1

    def setup_record_spanned(x):
        return x * 5 + 2

    mt = MetricsRegistry()
    base = record.jit_reading()
    with mt.scope("run"):
        with mt.phase_timer("warmup") as span:
            jax.jit(setup_record_spanned)(x).block_until_ready()
    # ... in the span's registry, as ever, with the trace and the
    # lowering beside the backend's seconds,
    assert mt.counter_value("compile/warmup") == 1
    backend_s = mt.counter_value("compile_seconds/warmup")
    assert backend_s > 0
    assert mt.counter_value("trace_seconds/warmup") > 0
    assert mt.counter_value("lower_seconds/warmup") > 0
    # ... in the span itself and in what holds it,
    assert 0 < span.jit <= span.seconds
    # ... and in the record, under its program's name and its span's.
    delta = record.jit_since(base)
    assert [(p["name"], p["span"]) for p in delta["programs"]] == [
        ("setup_record_spanned", "warmup")]
    assert delta["programs"][0]["backend_s"] == pytest.approx(backend_s,
                                                              abs=1e-4)
    assert sum(delta[s][1] for s in JIT_STAGES if s in delta) == \
        pytest.approx(span.jit, abs=1e-3)
    runs = record.run_start()["runs"]
    assert runs["count"] == 1
    assert runs["phases"]["warmup"] == pytest.approx(
        span.seconds - span.jit, abs=1e-3)
    # The same call again compiles nothing: the delta of a warm step.
    base = record.jit_reading()
    jax.jit(setup_record_spanned)(x).block_until_ready()
    assert record.jit_since(base) == {}


# -- (c) the persistent cache: a load is told from a compile -----------------

CACHE_SCRIPT = """
    import json, sys, tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from raft_tla_tpu.obs.metrics import process_record, watch_compiles
    jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    watch_compiles()

    def status(name):
        rows = process_record().run_start()["programs"]
        return next(p["cache"] for p in rows if p["name"] == name)

    def kept(x):
        return jnp.cumsum(x * 3) + 1

    def floored(x):
        return jnp.cumsum(x * 7) + 2

    out = {}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(kept)(np.arange(9)).block_until_ready()
    out["first"] = status("kept")
    jax.clear_caches()
    jax.jit(kept)(np.arange(9)).block_until_ready()
    out["again"] = status("kept")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
    jax.jit(floored)(np.arange(9)).block_until_ready()
    out["floored_first"] = status("floored")
    jax.clear_caches()
    jax.jit(floored)(np.arange(9)).block_until_ready()
    out["floored_again"] = status("floored")
    out["process"] = process_record().run_start()
    print("RESULT " + json.dumps(out))
"""


def test_a_cache_load_is_told_from_a_compile_and_the_floor_from_both():
    """A process of its own (the cache's directory is fixed when jax
    first uses it): with the floor at 0 a program is compiled and kept,
    then loaded; with the floor above its compile time it is a miss in
    every round, which is what a warm process's ``compile`` is."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CACHE_SCRIPT)], env=env,
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(next(line for line in done.stdout.splitlines()
                          if line.startswith("RESULT "))[7:])
    assert (out["first"], out["again"]) == ("miss", "hit")
    assert (out["floored_first"], out["floored_again"]) == ("miss", "miss")
    process = out["process"]
    assert process["jit"]["load"][0] >= 1
    assert process["cache"]["stored"] >= 1
    assert process["cache"]["retrieval_s"] <= process["jit"]["load"][1]
    assert "floored" in [name for name, _n, _s in process["compiled"]]
    # No program code ran before the package's import in that process.
    assert 0 < process["marks"]["package"] < process["marks"]["first_run"]


# -- (f) bounded -------------------------------------------------------------

def test_the_record_stays_small_whatever_the_process_compiled(record):
    for i in range(700):
        begin(TRACE, f"program_with_a_rather_long_name_{i}")
        end(TRACE, f"program_with_a_rather_long_name_{i}", 1.0 + i)
        begin(BACKEND, f"jit(program_with_a_rather_long_name_{i})")
        end(BACKEND, f"jit(program_with_a_rather_long_name_{i})", 0.5)
    mt = MetricsRegistry()
    for i in range(40):
        mt.open_span(f"a_phase_with_a_long_name_{i}").close()
    for name in ("package", "jax_imported", "backend_ready", "cache_enabled",
                 "cfg_loaded", "engine_begin", "engine_built"):
        record.mark(name)
    process = record.run_start()
    assert len(process["programs"]) == record.PROGRAMS == 8
    assert len(process["compiled"]) == 8
    assert len(process["runs"]["phases"]) == record.PHASES
    assert len(json.dumps(process)) < 4096
    assert len(record._programs) == record.MAX_PROGRAMS + 1
    assert process["jit"]["trace"] == [700, sum(1.0 + i for i in range(700))]
    # Nothing is left of the stages that ended.
    assert metrics_mod._OPEN.jit == []


# -- marks and span totals ---------------------------------------------------

def test_the_first_stamp_of_a_mark_stands(record):
    record.mark("engine_begin")
    first = record.marks["engine_begin"]
    time.sleep(0.01)
    record.mark("engine_begin")
    record.mark("engine_built")
    assert record.marks["engine_begin"] == first
    since_import = time.perf_counter() - IMPORT_STAMP
    a = record.run_start()
    time.sleep(0.01)
    b = record.run_start()
    assert a["marks"]["first_run"] == b["marks"]["first_run"] <= a["age_s"]
    assert b["age_s"] > a["age_s"] >= a["marks"]["engine_built"] >= first > 0
    # The clock started with the process, before the package's import,
    # not with the record.
    assert a["age_s"] > since_import


def test_every_registrys_spans_reach_the_record_net_of_jit(record):
    """The hunt cell builds two engines with a registry each: the
    record's totals are by name, whichever registry closed the span."""
    a, b = MetricsRegistry(), MetricsRegistry()
    for mt, seconds in ((a, 0.5), (b, 0.25)):
        with mt.scope("run"):
            with mt.phase_timer("swarm_fetch"):
                begin(TRACE, "chunk_fn")
                end(TRACE, "chunk_fn", seconds)
    with process_span("make_engine", "engine_begin", "engine_built"):
        begin(LOWER, "jit(chunk_fn)")
        end(LOWER, "jit(chunk_fn)", 0.125)
    count, seconds, jit_s = record._spans["scope/run"]
    assert (count, jit_s) == (2, 0.75) and seconds >= 0
    assert record._spans["phase/swarm_fetch"][2] == 0.75
    assert record._spans["scope/make_engine"][2] == 0.125
    runs = record.run_start()["runs"]
    # Spans of microseconds that "held" 0.75 s of jit: net, nothing.
    assert runs["count"] == 2 and runs["run_s"] == 0.0
    assert runs["make_engine_s"] == 0.0
    assert runs["phases"] == {"swarm_fetch": 0.0}


# -- (g) the spans before the first run, in its trace file -------------------

def test_the_first_runs_trace_file_keeps_what_came_before_it(record, tmp_path):
    with process_span("load_config", end="cfg_loaded"):
        pass
    with process_span("make_engine", "engine_begin", "engine_built",
                      kind="bfs"):
        tracer = SpanTracer(str(tmp_path / "t.json"))
    mt = MetricsRegistry()
    mt.tracer = tracer

    def one_run():
        tracer.reset()
        with mt.scope("run"):
            record.run_start()
        tracer.write()
        with open(tmp_path / "t.json", encoding="utf-8") as f:
            events = json.load(f)
        start = next(e for e in events if e["name"] == "trace_start_unix")
        return ([e for e in events if e["ph"] == "X"],
                start["args"]["unix_seconds"])

    spans, started = one_run()
    assert [e["name"] for e in spans] == ["load_config", "make_engine", "run"]
    assert spans[1]["args"] == {"kind": "bfs"}
    # On a clock that starts with the process: the marks' own.
    assert spans[1]["ts"] == pytest.approx(
        record.marks["engine_begin"] * 1e6, abs=2e3)
    assert started == pytest.approx(time.time() - (
        time.perf_counter() - record.t0), abs=0.05)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1
               for a, b in zip(spans, spans[1:]))
    # A later run's file describes that run alone, from its own start.
    spans, started = one_run()
    assert [e["name"] for e in spans] == ["run"]
    assert started == pytest.approx(time.time(), abs=0.5)
    assert record.early_spans() == []
