"""The span primitive and what rides on it (obs/metrics.py, obs/tracing.py,
engine/bfs.py, engine/chunk.py): one span reaches three sinks, the tree
closes over a run, the loop's counters equal a hand count, compiles are
charged to the span they fell in, and the chunk and ingest programs name
their stages and carry the names' tag in what is hashed for the compile
cache.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine import bfs as bfs_mod
from raft_tla_tpu.engine import chunk as chunk_mod
from raft_tla_tpu.engine.bfs import EngineConfig
from raft_tla_tpu.engine.check import initial_states, make_engine
from raft_tla_tpu.obs import MetricsRegistry, SpanTracer
from raft_tla_tpu.obs import metrics as metrics_mod
from raft_tla_tpu.obs.metrics import innermost_span
from raft_tla_tpu.utils.cfg import load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTAINERS = ("run", "level", "replay")


@pytest.fixture(scope="module")
def noleader():
    return load_config(os.path.join(REPO, "configs/MCraft_noleader.cfg"))


@pytest.fixture(scope="module")
def verdict(noleader, tmp_path_factory):
    """One whole check of the canary cfg and the replay of what it found,
    inside a profiler capture: (engine, result, steps, run events, Chrome
    events, raft.* events of the capture's python line)."""
    d = tmp_path_factory.mktemp("verdict")
    eng = make_engine(noleader, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17,
        events_out=str(d / "ev.jsonl"), trace_out=str(d / "tr.json")))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d / "xplane"), profiler_options=opts)
    try:
        res = eng.run(initial_states(noleader))
        steps = eng.replay(res.violation.fingerprint)
    finally:
        jax.profiler.stop_trace()
    with open(d / "ev.jsonl", encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    eng.tracer.write()          # the replay's spans came after run end
    with open(d / "tr.json", encoding="utf-8") as f:
        # Less what the process recorded before its first run, which
        # this may be (``load_config``, ``make_engine``: they carry no
        # ``run``; tests/test_setup_record.py holds them).
        chrome = [e for e in json.load(f) if e["ph"] == "X"
                  and "run" in e.get("args", {})]
    from jax.profiler import ProfileData
    pb = glob.glob(str(d / "xplane" / "**" / "*.xplane.pb"),
                   recursive=True)[0]
    noted = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:")
             for line in plane.lines if line.name.startswith("python")
             for ev in line.events if ev.name.startswith("raft.")]
    return eng, res, steps, events, chrome, noted


# -- the primitive ------------------------------------------------------------

def test_one_span_three_sinks_and_the_yielded_seconds(tmp_path):
    notes = []

    class Note:
        def __init__(self, name, **args):
            self.rec = [name, args, "new"]
            notes.append(self.rec)

        def __enter__(self):
            self.rec[2] = "open"

        def __exit__(self, *exc):
            self.rec[2] = "closed"

    mt = MetricsRegistry()
    mt.tracer = SpanTracer(str(tmp_path / "t.json"), annotate=Note)
    mt.tracer.run = 7
    with mt.scope("run", resume=False):
        with mt.phase_timer("chunk", call=3) as span:
            assert innermost_span() is span and span.name == "chunk"
            assert notes[-1] == ["raft.chunk", {"run": 7, "call": 3}, "open"]
    assert innermost_span() is None
    assert [n[2] for n in notes] == ["closed", "closed"]
    hist = mt.snapshot()["histograms"]
    assert hist["phase/chunk"]["count"] == hist["scope/run"]["count"] == 1
    assert span.seconds == pytest.approx(hist["phase/chunk"]["total"])
    # A container is not a phase: the phases stay a partition of the wall.
    assert set(mt.phase_seconds()) == {"chunk"}
    mt.tracer.write()
    with open(tmp_path / "t.json", encoding="utf-8") as f:
        chrome = {e["name"]: e for e in json.load(f) if e["ph"] == "X"}
    assert chrome["chunk"]["args"] == {"run": 7, "call": 3}
    assert chrome["run"]["args"] == {"run": 7, "resume": False}


def test_a_loop_shaped_span_closed_late_takes_what_was_left_open():
    mt = MetricsRegistry()
    level = mt.open_span("level", level=1)
    leaked = mt.open_span("account")       # an exception unwound past it
    assert innermost_span() is leaked
    level.close()
    assert innermost_span() is None
    level.close()                          # closing twice observes once
    assert mt.snapshot()["histograms"]["phase/level"]["count"] == 1


# -- one verdict: run + replay -------------------------------------------------

def test_run_level_replay_reach_all_three_sinks(verdict):
    eng, res, steps, events, chrome, noted = verdict
    hist = eng.metrics.snapshot()["histograms"]
    n_levels = len(res.levels)          # the violation's level is unfinished
    want = {"run": 1, "replay": 1, "level": n_levels + 1}
    for name, n in want.items():
        assert hist[f"scope/{name}"]["count"] == n, name
        assert sum(e["name"] == name for e in chrome) == n, name
        assert sum(nm == f"raft.{name}" for nm, _s in noted) == n, name
    # ... and so does every leaf, under the same name in each.
    for name in ("roots_encode", "run_init", "warmup", "ingest", "chunk",
                 "stats_fetch", "account", "trace_flush", "grow", "level_end",
                 "run_end", "trace_chain", "replay_scan"):
        assert hist[f"phase/{name}"]["count"] == sum(
            e["name"] == name for e in chrome) == sum(
            nm == f"raft.{name}" for nm, _s in noted) > 0, name
    # The whole trace in one call of the fused program, no step through
    # the per-step matcher (tests/test_replay_scan.py holds its spans).
    assert hist["phase/replay_scan"]["count"] == 1 and len(steps) - 1 == 9
    assert "phase/replay_step" not in hist


def test_spans_nest_and_share_the_run_id(verdict):
    eng, res, steps, events, chrome, noted = verdict
    assert {e["args"]["run"] for e in chrome} == {eng._run_id}
    assert {s["run"] for _n, s in noted} == {eng._run_id}
    by = {}
    for e in chrome:
        by.setdefault(e["name"], []).append(e)
    run, replay = by["run"][0], by["replay"][0]
    inside = lambda a, b: (b["ts"] <= a["ts"] + 1e-3 and  # noqa: E731
                           a["ts"] + a["dur"] <= b["ts"] + b["dur"] + 1e-3)
    for e in chrome:
        if e["name"] in ("trace_chain", "replay_scan"):
            assert inside(e, replay), e
        elif e["name"] not in ("run", "replay"):
            assert inside(e, run), e
    for name in ("chunk", "stats_fetch", "account", "trace_flush",
                 "level_end"):
        for e in by[name]:
            assert sum(inside(e, lv) for lv in by["level"]) == 1, e
    # The arguments the readers match on.
    calls = [e["args"]["call"] for e in by["chunk"]]
    assert calls == list(range(1, len(calls) + 1))
    assert [e["args"]["call"] for e in by["account"]] == calls
    assert [e["args"]["level"] for e in by["level"]] == list(
        range(len(by["level"])))
    # A span carries what identifies it and what a reader matches on,
    # nothing the events already say.
    stats = {n: set(s) for n, s in noted}
    assert stats["raft.account"] == {"run", "call", "passes"}
    assert stats["raft.chunk"] == {"run", "call"}
    assert stats["raft.trace_flush"] == {"run"}
    assert stats["raft.replay_scan"] == {"run", "steps"}


def test_the_span_tree_closes_over_the_run(verdict):
    eng, res, steps, events, chrome, noted = verdict
    run = next(e for e in chrome if e["name"] == "run")
    lo, hi = run["ts"], run["ts"] + run["dur"]
    leaves = [e for e in chrome if e["name"] not in CONTAINERS
              and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-3]
    # Leaves do not overlap one another ...
    leaves.sort(key=lambda e: e["ts"])
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a, b)
    covered = sum(e["dur"] for e in leaves)
    # ... so what no leaf covers is the containers' own time, measured on
    # its own: the gaps between consecutive leaves and at both ends.
    edges = [lo] + [x for e in leaves
                    for x in (e["ts"], e["ts"] + e["dur"])] + [hi]
    unattributed = sum(b - a for a, b in zip(edges[0::2], edges[1::2]))
    assert covered + unattributed == pytest.approx(run["dur"], rel=0.02)
    assert unattributed < 0.15 * run["dur"], (unattributed, run["dur"])
    # The histograms say the same as the Chrome file.
    phases = eng.metrics.phase_seconds()
    in_run = sum(v for k, v in phases.items()
                 if k not in ("trace_chain", "replay_scan"))
    assert in_run * 1e6 == pytest.approx(covered, rel=0.02)


def test_loop_counters_equal_a_hand_count(noleader, tmp_path):
    """Levels 0-3 of the canary hold 1, 3, 15 and 52 states: under a batch
    of 64 each is one chunk call of one pass."""
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(noleader, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17,
        max_diameter=4, events_out=ev))
    res = eng.run(initial_states(noleader))
    assert res.levels == [1, 3, 15, 52, 162]
    with open(ev, encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    end = events[-1]
    assert end["event"] == "run_end"
    assert (end["chunk_calls"], end["passes"], end["ingest_calls"],
            end["parents_expanded"]) == (4, 4, 1, 1 + 3 + 15 + 52)
    assert end["passes"] * eng.config.batch >= end["parents_expanded"]
    per_level = [(e["level"], e["chunk_calls"], e["passes"],
                  e["ingest_calls"], e["parents_expanded"])
                 for e in events if e["event"] == "level_complete"]
    assert per_level == [(0, 0, 0, 1, 0), (1, 1, 1, 0, 1), (2, 1, 1, 0, 3),
                         (3, 1, 1, 0, 15), (4, 1, 1, 0, 52)]
    # The events read the registry's counters, which run on: a second
    # run on the warm engine reports its own share.
    eng.run(initial_states(noleader))
    with open(ev, encoding="utf-8") as f:
        again = [json.loads(line) for line in f][-1]
    assert (again["chunk_calls"], again["passes"], again["ingest_calls"],
            again["parents_expanded"]) == (4, 4, 1, 71)
    # One call a level: each flush is drained at its level's end.
    assert bfs_mod.work_counts(eng.metrics) == {
        "chunk_calls": 8, "passes": 8, "inv_lanes": 8 * eng._K,
        "ingest_calls": 2,
        "parents_expanded": 142, "flush_overlapped": 0,
        "flush_drained": 8,
        # No duration budget: no call is a probe or sized by a deadline.
        "deadline_calls": 0, "probe_calls": 0}


def test_deeper_levels_take_the_passes_their_frontier_needs(verdict):
    eng, res, steps, events, chrome, noted = verdict
    end = events[-1]
    assert end["passes"] * eng.config.batch >= end["parents_expanded"]
    assert end["parents_expanded"] == eng.coverage.expanded
    for e in events:
        if e["event"] == "level_complete" and e["level"] >= 1:
            frontier = res.levels[e["level"] - 1]
            assert e["parents_expanded"] == frontier
            assert e["passes"] >= -(-frontier // eng.config.batch)
    accounted = [s for n, s in noted if n == "raft.account"]
    assert sum(s["passes"] for s in accounted) == end["passes"]


def test_compiles_are_charged_to_the_span_they_fell_in(noleader, tmp_path):
    """Every program the loop runs is compiled in ``warmup``: ingest,
    chunk, and the trace flush's fetch programs, whose shapes are fixed
    when the engine is built, so that a flush compiles nothing at
    whatever length.  Sizes no other test uses, so that none of them is
    in this process's jit cache."""
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(noleader, EngineConfig(
        batch=48, queue_capacity=48 * 211, seen_capacity=1 << 17,
        max_diameter=5, events_out=ev))
    eng.run(initial_states(noleader))
    with open(ev, encoding="utf-8") as f:
        end = [json.loads(line) for line in f][-1]
    compiles = end["compiles"]
    n, seconds = compiles["warmup"]
    # ingest, chunk and one fetch program a length
    assert n >= 2 + len(eng._fetch_lens) and seconds > 0
    assert end["flush_drained"] == 5            # five flushes, five lengths
    for span in ("trace_flush", "stats_fetch", "account", "chunk"):
        assert span not in compiles, compiles
    # The same sizes again: everything is in the jit cache, and a run
    # reports its own compiles, not the registry's running total.
    eng.run(initial_states(noleader))
    with open(ev, encoding="utf-8") as f:
        again = [json.loads(line) for line in f][-1]["compiles"]
    assert "trace_flush" not in again and "warmup" not in again


# -- the process record on run_start and run_end --------------------------------

@pytest.fixture(scope="module")
def three_runs(tmp_path_factory):
    """The first three runs of a process as its record (obs/metrics.py,
    one of this fixture's own) saw them: the canary cfg loaded, an engine
    made (``test_loop_counters``' sizes), run to level 4 twice, then once
    more after a fetch length no warm-up has seen was put on the engine.
    (record, the events of each run, the Chrome events of each run)"""
    d = tmp_path_factory.mktemp("record")
    events, chrome = [], []
    with pytest.MonkeyPatch.context() as mp:
        record = metrics_mod.ProcessRecord()
        mp.setattr(metrics_mod, "PROCESS", record)
        setup = load_config(os.path.join(REPO, "configs/MCraft_noleader.cfg"))
        eng = make_engine(setup, EngineConfig(
            batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17,
            max_diameter=4, trace_out=str(d / "tr.json")))
        for i in range(3):
            if i == 2:
                eng._fetch_lens = [7] + eng._fetch_lens
            eng.config.events_out = str(d / f"ev{i}.jsonl")
            eng.run(initial_states(setup))
            with open(eng.config.events_out, encoding="utf-8") as f:
                events.append([json.loads(line) for line in f])
            with open(d / "tr.json", encoding="utf-8") as f:
                chrome.append([e for e in json.load(f) if e["ph"] == "X"])
    return record, events, chrome


def test_run_start_carries_the_process_record(three_runs):
    from tests.test_setup_record import check_process
    record, events, _chrome = three_runs
    starts = [evs[0]["process"] for evs in events]
    assert [evs[0]["event"] for evs in events] == ["run_start"] * 3
    parts = [check_process(p) for p in starts]
    # One first run a process; every later run_start says when it was.
    assert len({p["marks"]["first_run"] for p in starts}) == 1
    assert set(starts[0]["marks"]) == {"cfg_loaded", "engine_begin",
                                       "engine_built", "first_run"}
    # Before the first run: nothing has run, and what make_engine took is
    # its own seconds and the jit's (constants made eagerly as it builds).
    first = starts[0]
    assert first["runs"]["count"] == 0 and first["runs"]["phases"] == {}
    built = first["marks"]["engine_built"] - first["marks"]["engine_begin"]
    jit_s = sum(sec for _n, sec in first["jit"].values())
    assert parts[0]["make_engine_s"] + jit_s == pytest.approx(
        built + first["jit_before_engine_s"], abs=0.01)
    assert parts[0]["runs_s"] == 0.0 and abs(parts[0]["outside_s"]) < 0.05
    # Before the second: the first run, its phases and what it compiled.
    second = starts[1]
    assert second["runs"]["count"] == 1 and parts[1]["runs_s"] > 0
    assert {"warmup", "stats_fetch"} <= set(second["runs"]["phases"])
    assert "chunk" in [p["name"] for p in second["programs"]]
    assert parts[1]["trace_s"] > parts[0]["trace_s"]
    assert starts[2]["runs"]["count"] == 2


def test_a_warm_run_compiles_nothing_and_a_new_shape_is_named(three_runs):
    _record, events, _chrome = three_runs
    first, warm, reshaped = (evs[-1] for evs in events)
    assert first["event"] == "run_end"
    assert first["jit"]["trace"][0] >= 2 and "warmup" in first["compiles"]
    assert "chunk" in [p["name"] for p in first["jit"]["programs"]]
    assert warm["jit"] == {} and warm["compiles"] == {}
    # The third run's warm-up met a fetch length it had not compiled:
    # one program, named, under the span it fell in, in ``compiles`` too.
    assert reshaped["compiles"] == {"warmup": [1, pytest.approx(
        reshaped["compiles"]["warmup"][1])]}
    assert [(p["name"], p["span"]) for p in reshaped["jit"]["programs"]] == [
        ("<lambda>", "warmup")]
    assert reshaped["jit"]["trace"][0] == reshaped["jit"]["lower"][0] == 1
    assert (reshaped["jit"].get("load", [0])[0]
            + reshaped["jit"].get("compile", [0])[0]) == 1


def test_the_first_runs_trace_file_holds_make_engine_and_load_config(
        three_runs):
    record, _events, chrome = three_runs
    first = [e["name"] for e in chrome[0]]
    assert first[:2] == ["load_config", "make_engine"]
    make = chrome[0][1]
    assert make["args"] == {"kind": "bfs"}
    assert make["ts"] == pytest.approx(
        record.marks["engine_begin"] * 1e6, abs=5e3)
    run = next(e for e in chrome[0] if e["name"] == "run")
    assert make["ts"] + make["dur"] <= run["ts"]
    for later in chrome[1:]:
        names = {e["name"] for e in later}
        assert "run" in names and not names & {"make_engine", "load_config"}
        # ... on its own clock again, from the run's start.
        assert next(e for e in later if e["name"] == "run")["ts"] < 1e5


# -- stage names in the programs -----------------------------------------------

def ingest_avals(eng):
    qav, i32, _i, _q, _n, seen_av, _t, _tc, _ms = eng.chunk_avals()
    return (jax.ShapeDtypeStruct((eng._B, eng._sw), jnp.uint8),
            jax.ShapeDtypeStruct((eng._B,), jnp.bool_), qav, i32, seen_av)


@pytest.mark.parametrize("pipeline", ["v1", "v2"])
def test_every_stage_is_named_in_the_lowered_programs(noleader, pipeline):
    eng = make_engine(noleader, EngineConfig(
        batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
        pipeline=pipeline))
    tag = f'mhlo.frontend_attributes = {{stages_tag = "{chunk_mod.STAGES_TAG}"}}'
    text = eng._chunk.lower(*eng.chunk_avals()).as_text(debug_info=True)
    # The program keeps its name, the tag is an attribute of one of its
    # operations (what the cache key hashes) ...
    assert "module @jit_chunk " in text and text.count(tag) == 1
    # ... and every stage is in the debug info (what a capture shows).
    for stage in chunk_mod.STAGES:
        assert f'"jit(chunk)/while/body/{stage}/' in text, stage
    for stage in ("prologue", "epilogue"):
        assert f'"jit(chunk)/{stage}/' in text, stage
    text = eng._ingest.lower(*ingest_avals(eng)).as_text(debug_info=True)
    assert "module @jit_ingest " in text and text.count(tag) == 1
    for stage in ("construct", "insert", "enqueue", "record", "stats"):
        assert f'"jit(ingest)/{stage}/' in text, stage


def cache_key_of(jitted, avals) -> str:
    from jax._src import cache_key, compiler
    module = jitted.lower(*avals).compiler_ir()
    options = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    devices = np.array(jax.devices()[:1])
    return cache_key.get(module, devices, options, devices[0].client)


def test_the_stage_tag_is_in_the_compile_cache_key(noleader, monkeypatch):
    """jax strips debug info before it hashes a module, so the stage
    names alone would take an executable without them, or with older
    ones, from a cache filled earlier.  The tag, an attribute of one
    operation of each program, is what changes the key."""
    cfg = EngineConfig(batch=32, queue_capacity=1 << 12,
                       seen_capacity=1 << 15)

    def keys():
        eng = make_engine(noleader, cfg)
        return (cache_key_of(eng._chunk, eng.chunk_avals()),
                cache_key_of(eng._ingest, ingest_avals(eng)))

    tagged = keys()
    for mod in (chunk_mod, bfs_mod):
        monkeypatch.setattr(mod, "named_stage", lambda name, fn: fn)
    assert keys() == tagged         # the trap: names do not reach the key
    monkeypatch.setattr(chunk_mod, "STAGES_TAG", "s0")
    other = keys()
    assert other[0] != tagged[0] and other[1] != tagged[1]


# -- what the programs compute is what they computed ----------------------------

@pytest.mark.parametrize("pipeline", ["v1", "v2"])
def test_level_counts_equal_the_pinned_profile(pipeline):
    setup = load_config(os.path.join(REPO, "configs/MCraft_bounded.cfg"))
    with open(os.path.join(REPO, "artifacts/mcraft_L14_oracle.jsonl"),
              encoding="utf-8") as f:
        pinned = [json.loads(line) for line in f][:7]
    eng = make_engine(setup, EngineConfig(
        batch=256, queue_capacity=1 << 16, seen_capacity=1 << 18,
        max_diameter=6, pipeline=pipeline))
    res = eng.run(initial_states(setup))
    assert res.levels == [r["frontier"] for r in pinned]
    assert (res.distinct, res.generated) == (
        pinned[-1]["distinct"], pinned[-1]["generated"]) == (9457, 24429)
    assert [(r["distinct"], r["generated"]) for r in res.level_stats] == [
        (r["distinct"], r["generated"]) for r in pinned]
