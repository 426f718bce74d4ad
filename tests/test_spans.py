"""The span primitive and what rides on it (obs/metrics.py, obs/tracing.py,
engine/bfs.py, engine/chunk.py): one span reaches three sinks, the tree
closes over a run, the loop's counters equal a hand count, compiles are
charged to the span they fell in, and the chunk and ingest programs name
their stages and carry the names' tag in what is hashed for the compile
cache.
"""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine import bfs as bfs_mod
from raft_tla_tpu.engine import chunk as chunk_mod
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.engine.check import initial_states, make_engine
from raft_tla_tpu.models.dims import RaftDims
from raft_tla_tpu.models.invariants import Bounds, build_constraint
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.obs import MetricsRegistry, RunEventLog, SpanTracer
from raft_tla_tpu.obs import calls as calls_mod
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
    # (A collection's annotation comes from the collector's hook, which
    # knows no run: ``test_a_collection_is_charged_to_the_span_it_fell_in``.)
    assert {s["run"] for n, s in noted if n != "raft.gc"} == {eng._run_id}
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
    assert stats["raft.account"] == {"run", "call", "passes", "rule",
                                     "parents", "new"}
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
    # One call a level: each flush rides behind the next level's first
    # call, with the close of its level (the root level's too); the one
    # before ``max_diameter`` stops the run is drained.
    assert bfs_mod.work_counts(eng.metrics) == {
        "chunk_calls": 8, "passes": 8, "inv_lanes": 8 * eng._K,
        "ingest_calls": 2,
        "parents_expanded": 142, "flush_overlapped": 6,
        "flush_drained": 2,
        "level_closes_overlapped": 8, "level_closes_drained": 2,
        # No duration budget: no call is a probe or sized by a deadline.
        "deadline_calls": 0, "probe_calls": 0,
        # No checkpoint directory: no snapshot.
        "checkpoints_written": 0, "checkpoint_bytes_raw": 0,
        "checkpoint_bytes_written": 0, "checkpoints_overlapped": 0,
        "checkpoints_drained": 0, "checkpoint_wait_s": 0}


def test_a_levels_close_lies_behind_the_next_levels_first_call(verdict):
    """Level L+1's ``level`` span opens before its first ``chunk``; level
    L's last flush and its ``level_end`` lie inside it, between that
    ``chunk`` and the ``stats_fetch`` that follows; level L's own span
    ends where L+1's begins.  (The benchmark's readers charge idle time
    to the innermost span open: ``benchmark/readers/spans.py``.)"""
    eng, res, steps, events, chrome, noted = verdict
    by = {}
    for e in sorted(chrome, key=lambda e: e["ts"]):
        by.setdefault(e["name"], []).append(e)
    levels, closes = by["level"], by["level_end"]
    end = lambda e: e["ts"] + e["dur"]          # noqa: E731
    # Every level but the violation's was closed, each behind a call.
    assert len(closes) == len(levels) - 1 == len(res.levels)
    leaves = [e for e in sorted(chrome, key=lambda e: e["ts"])
              if e["name"] not in CONTAINERS]
    for closed, (lv, nxt) in enumerate(zip(levels, levels[1:])):
        assert end(lv) <= nxt["ts"] + 1e-3
        inside = [e["name"] for e in leaves
                  if nxt["ts"] <= e["ts"] and end(e) <= end(nxt) + 1e-3]
        # The root level's records went through ``ingest`` itself.
        flush = ["trace_flush"] if closed else []
        assert inside[:len(flush) + 3] == (
            ["chunk"] + flush + ["level_end", "stats_fetch"]), (closed,
                                                                inside)
    run_end = events[-1]
    assert (run_end["level_closes_overlapped"],
            run_end["level_closes_drained"]) == (len(closes), 0)
    # Nothing but the violating call's own flush met an empty device.
    assert run_end["flush_drained"] == 1


@pytest.mark.parametrize("max_diameter", [None, 6],
                         ids=["to_the_violation", "to_a_diameter"])
def test_level_complete_says_the_same_behind_a_call_as_with_the_device_empty(
        noleader, max_diameter, tmp_path):
    """The canary's check twice: as it runs (every close but the last
    behind the next level's first call) and with every boundary settled
    at once.  The events agree field by field, but for the clocks and
    for the four counters that say WHERE a flush and a close ran (a
    level's last flush counts in the next level's row when it rides
    behind that level's first call)."""
    cfg = EngineConfig(batch=64, queue_capacity=1 << 14,
                       seen_capacity=1 << 17, max_diameter=max_diameter)
    logs = []
    # The drained order, through a snapshot due at every level.
    for name, ck in (("riding", None), ("settled", str(tmp_path / "ck"))):
        ev = str(tmp_path / (name + ".jsonl"))
        eng = make_engine(noleader, dataclasses.replace(
            cfg, events_out=ev, checkpoint_dir=ck))
        res = eng.run(initial_states(noleader))
        with open(ev, encoding="utf-8") as f:
            logs.append((res, [json.loads(line) for line in f]))
    (res, riding), (_res, settled) = logs
    end = riding[-1]
    n_levels = len(res.levels)
    assert n_levels == (9 if max_diameter is None else 7)
    assert end["flush_drained"] <= 2
    assert end["level_closes_overlapped"] >= n_levels - 2
    assert end["level_closes_overlapped"] + end["level_closes_drained"] \
        == n_levels
    assert (settled[-1]["level_closes_overlapped"],
            settled[-1]["level_closes_drained"]) == (0, n_levels)
    clocks = {"ts", "elapsed_seconds", "phase_seconds",
              "unattributed_seconds", "memory"}
    where = {"flush_overlapped", "flush_drained", "level_closes_overlapped",
             "level_closes_drained"}
    # ... and for the snapshots, which only the settled run writes.
    saves = {"checkpoints_written", "checkpoint_bytes_raw",
             "checkpoint_bytes_written", "checkpoints_overlapped",
             "checkpoints_drained", "checkpoint_wait_s"}

    def closes(events):
        return [{k: v for k, v in e.items()
                 if k not in clocks | where | saves}
                for e in events if e["event"] == "level_complete"]

    assert closes(riding) == closes(settled)
    assert len(closes(riding)) == n_levels
    assert (riding[-1]["checkpoints_written"],
            settled[-1]["checkpoints_written"]) == (0, n_levels)
    assert {"level", "frontier_rows", "distinct", "generated",
            "generated_by_family", "chunk_calls", "passes",
            "parents_expanded", "ingest_calls"} <= set(closes(riding)[0])
    # Every flush and every close is in some row, or in the run's end.
    for events in (riding, settled):
        for k in where:
            assert sum(e[k] for e in events
                       if e["event"] == "level_complete") <= events[-1][k]
    # ... and a level's close precedes every event of the next level.
    names = [(e["event"], e.get("level")) for e in riding]
    assert names.index(("level_complete", n_levels - 1)) \
        < names.index(("run_end", None))


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
    # five flushes, five lengths
    assert end["flush_overlapped"] + end["flush_drained"] == 5
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


# -- one row a device call (obs/calls.py) -----------------------------------------

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def small_engine(tmp_path, **kw):
    """Levels 0-5 hold 1, 3, 15, 52, 162 and 486 states: at one batch of
    16 a call, level 4 is built in 4 calls (4-7) and level 5 in 11
    (8-18)."""
    base = dict(batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, sync_every=1, max_diameter=5,
                record_trace=False,
                events_out=str(tmp_path / "ev.jsonl"))
    base.update(kw)
    return BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=EngineConfig(**base))


def events_of(eng):
    with open(eng.config.events_out, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def test_the_rows_of_a_run_sum_to_its_counters(tmp_path):
    eng = small_engine(tmp_path, record_trace=True, sync_every=4)
    eng.run([init_state(DIMS)])
    rows = eng._calls.rows()
    events = events_of(eng)
    end = events[-1]
    assert end["event"] == "run_end"
    assert len(rows) == end["chunk_calls"] + end["ingest_calls"]
    assert sum(r["passes"] for r in rows) == end["passes"]
    assert sum(r["parents"] for r in rows if r["kind"] == "chunk") \
        == end["parents_expanded"]
    assert [r["call"] for r in rows if r["kind"] == "chunk"] == list(
        range(1, end["chunk_calls"] + 1))
    calls = end["calls"]
    assert calls["n"] == calls["rows"] == len(rows)
    by_rule = calls["by_rule"]
    assert sum(r["calls"] for r in by_rule.values()) == len(rows)
    assert sum(r["passes"] for r in by_rule.values()) == end["passes"]
    for rule, tally in by_rule.items():
        mine = [r for r in rows if r["rule"] == rule]
        assert (tally["calls"], tally["passes"]) == (
            len(mine), sum(r["passes"] for r in mine))
    # No budget: a call takes what it is allowed (``full``) but the last
    # of a level, and the first levels are under a batch.
    assert set(by_rule) == {"ingest", "full", "level_end"}
    assert by_rule["ingest"] == {"calls": 1, "passes": 0,
                                 "seconds": by_rule["ingest"]["seconds"]}
    # A row's clocks: the gaps and the calls lie inside the run, in order.
    assert all(b["t"] >= a["t"] + a["dispatch_s"] for a, b in zip(rows,
                                                                  rows[1:]))
    assert abs(calls["gap_s"] - sum(r["gap_s"] for r in rows)) < 1e-4
    # What of a gap lay in spans of the loop's own: a level's end is one.
    assert all(0.0 <= r["named_s"] <= r["gap_s"] for r in rows)
    first_of_level = [b for a, b in zip(rows, rows[1:])
                      if b["level"] > a["level"] > 0]
    assert first_of_level and all(r["named_s"] > 0 for r in first_of_level)
    # The event log holds no line a call.
    assert len(events) < 20 and "call" not in {e["event"] for e in events}
    assert {"gc", "trace_rehashes", "trace_rehash_s"} <= set(end)
    # The spans keep the wall alone; the rows carry the thread's CPU.
    assert "phases_cpu" not in end and sum(r["cpu_s"] for r in rows) >= 0


def test_a_close_behind_a_call_is_in_that_calls_row(tmp_path):
    """With no trace to flush, ``flush_s`` is the close alone: the first
    call of every level carries the ``level_end`` span that ran behind
    its dispatch, no other call carries anything, and the one close
    ``max_diameter`` settled is in no row."""
    eng = small_engine(tmp_path)
    res = eng.run([init_state(DIMS)])
    rows = eng._calls.rows()
    first = [b["call"] for a, b in zip(rows, rows[1:])
             if b["level"] > a["level"]]
    assert first == [1, 2, 3, 4, 8]
    assert [r["call"] for r in rows
            if r["kind"] == "chunk" and r["flush_s"] > 0] == first
    riding = sum(r["flush_s"] for r in rows)
    assert 0 < riding < res.phases["level_end"]
    end = events_of(eng)[-1]
    assert (end["level_closes_overlapped"],
            end["level_closes_drained"]) == (5, 1)


def test_a_budgeted_run_names_the_rule_that_sized_each_call(tmp_path):
    eng = small_engine(tmp_path, max_seconds=60.0, sync_every=8,
                       max_diameter=5)
    eng.run([init_state(DIMS)])
    end = events_of(eng)[-1]
    by_rule = end["calls"]["by_rule"]
    assert sum(r["calls"] for r in by_rule.values()) \
        == end["chunk_calls"] + end["ingest_calls"]
    assert sum(r["passes"] for r in by_rule.values()) == end["passes"]
    # One probe (no estimate yet), the ramp from 2, and the calls a level's
    # end cut short; the minute left never sizes one.
    assert by_rule["probe"]["calls"] == end["probe_calls"] == 1
    assert by_rule["ramp"]["calls"] >= 1 and "deadline" not in by_rule
    assert by_rule["level_end"]["calls"] >= 3
    rows = eng._calls.rows()
    assert all(r["passes"] == r["allowed"] for r in rows
               if r["rule"] == "ramp")
    assert all(r["passes"] < r["allowed"] for r in rows
               if r["rule"] == "level_end")


@pytest.mark.parametrize("phase", ["wait", "host", "gap"])
def test_a_stall_is_named_by_call_and_phase(phase, tmp_path, capfd):
    """A ``stall`` fault of 0.3 s in one call of level 5: ``slowest`` is
    that call, its phase the one the time went to, ``stall_s`` the 0.3 s;
    the thread's CPU clock follows the wall where the loop spun
    (``host``) and not where it slept."""
    from raft_tla_tpu.resilience import faults
    eng = small_engine(tmp_path)
    faults.install(f"stall@phase={phase};call=12;seconds=0.3", hard=False)
    try:
        eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    events = events_of(eng)
    calls = events[-1]["calls"]
    slowest = calls["slowest"]
    assert (slowest["call"], slowest["kind"], slowest["level"]) == (
        12, "chunk", 5)
    assert slowest["phase"] == phase
    assert abs(slowest["excess_s"] - 0.3) < 0.05
    assert abs(calls["stall_s"] - 0.3) < 0.05 and calls["stall_calls"] == 1
    assert slowest[phase + "_s"] >= 0.3
    if phase == "host":
        # (Not the whole 0.3 s where the suite's other workers take the
        # core meanwhile: that difference is what the clock is for.)
        assert slowest["cpu_s"] > 0.1
    else:
        assert slowest["cpu_s"] < 0.05
    assert slowest["expected_s"] < 0.05
    # Under a second: no event of its own, one line of the run's log.
    assert calls["slow_calls"] == 0
    assert "slow_call" not in {e["event"] for e in events}
    err = capfd.readouterr().err
    assert "stall: run 1 call 12 (chunk, level 5" in err
    assert f"in {phase} (" in err and "slow call" not in err


def test_a_stall_of_over_a_second_is_an_event_of_its_own(tmp_path, capfd):
    from raft_tla_tpu.resilience import faults
    eng = small_engine(tmp_path)
    faults.install("stall@phase=wait;call=10;seconds=1.2", hard=False)
    try:
        eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    events = events_of(eng)
    slow = [e for e in events if e["event"] == "slow_call"]
    assert len(slow) == 1 and events[-1]["calls"]["slow_calls"] == 1
    (e,) = slow
    assert (e["call"], e["phase"], e["rule"]) == (10, "wait", "full")
    assert 1.15 < e["excess_s"] < 1.3 and e["wait_s"] > 1.2
    assert e["cpu_s"] < 0.1 and e["expected_s"] < 0.05
    assert events.index(e) == len(events) - 2       # just before run_end
    # And the run's own log names it.
    assert "slow call: run 1 call 10 (chunk, level 5" in capfd.readouterr().err
    # Without a plan the sites cost a module flag: nothing fires again.
    eng.run([init_state(DIMS)])
    assert events_of(eng)[-1]["calls"]["stall_calls"] == 0


def row(call, level, passes, wait_s, **kw):
    base = dict(call=call, kind="chunk", level=level, passes=passes,
                gap_s=0.0001, named_s=0.0, dispatch_s=0.0005, flush_s=0.0,
                wait_s=wait_s, host_s=0.0002)
    base.update(kw)
    return base


@pytest.mark.parametrize("case", ["level_median", "run_median", "too_few",
                                  "gap", "named_gap", "under_the_floor"])
def test_the_reduction_of_a_runs_rows(case):
    reduce_calls = calls_mod.reduce_calls
    if case == "level_median":
        # Level 2 costs ten times level 1 a pass: each is held to its own.
        rows = ([row(i, 1, 4, 0.004) for i in range(4)]
                + [row(4 + i, 2, 4, 0.040) for i in range(3)]
                + [row(7, 2, 8, 0.300)])
        out = reduce_calls(rows)
        assert out["slowest"]["call"] == 7 and out["slowest"]["phase"] == "wait"
        # 8 passes at level 2's median 10.2 ms a pass (0.7 ms a call of
        # dispatch and accounting included).
        assert abs(out["slowest"]["expected_s"] - 8 * 0.0407 / 4) < 1e-3
        assert out["stall_calls"] == 1
        assert abs(out["stall_s"] - (0.3007 - 0.0814)) < 1e-3
    elif case == "run_median":
        # Two calls a level: the run's median a pass stands for all.
        rows = [row(i, i // 2, 2, 0.002) for i in range(8)]
        rows[5]["dispatch_s"] = 0.2
        out = reduce_calls(rows)
        assert (out["slowest"]["call"], out["slowest"]["phase"]) == (
            5, "dispatch")
        assert out["stall_calls"] == 1
    elif case == "too_few":
        # Two calls in all: nothing to hold either to, but its gap.
        out = reduce_calls([row(0, 1, 2, 0.002), row(1, 1, 2, 2.0)])
        assert out["stall_calls"] == 0 and out["slowest"]["phase"] == "gap"
        assert reduce_calls([])["slowest"] is None
    elif case == "gap":
        rows = [row(i, 1, 4, 0.004) for i in range(6)]
        rows[3]["gap_s"] = 1.5
        out = reduce_calls(rows)
        assert (out["slowest"]["call"], out["slowest"]["phase"]) == (3, "gap")
        assert len(out["slow"]) == 1 and out["slow"][0]["call"] == 3
        assert abs(out["stall_s"] - 1.4999) < 1e-3
    elif case == "named_gap":
        # 3.6 s between two calls, all but 20 ms of it a seen-set's growth
        # (a span of the loop's own): named work, no stall.  With 200 ms
        # of it in no span, those are the stall.
        rows = [row(i, 1, 4, 0.004) for i in range(6)]
        rows[3].update(gap_s=3.6, named_s=3.58)
        out = reduce_calls(rows)
        assert out["stall_calls"] == 0 and out["slow"] == []
        rows[3]["named_s"] = 3.4
        out = reduce_calls(rows)
        assert (out["slowest"]["call"], out["slowest"]["phase"]) == (3, "gap")
        assert out["stall_calls"] == 1 and abs(out["stall_s"] - 0.1999) < 1e-3
    else:
        # 40 ms over is under STALL_MIN_S; 60 ms over a 100 ms call is
        # under STALL_FACTOR: neither is a stall, the larger is slowest.
        rows = ([row(i, 1, 1, 0.004) for i in range(5)]
                + [row(5, 1, 1, 0.044)]
                + [row(6 + i, 2, 1, 0.100) for i in range(5)]
                + [row(11, 2, 1, 0.160)])
        out = reduce_calls(rows)
        assert out["stall_calls"] == 0 and out["stall_s"] == 0.0
        assert out["slowest"]["call"] == 11


# -- CPU beside wall, and collections, on every span --------------------------------

def test_a_gap_is_split_into_named_spans_and_the_rest():
    """Between two calls the loop slept 0.15 s inside a span of its own
    and 0.1 s in none: the row's ``gap_s`` holds both, ``named_s`` the
    first, and only the second is a stall.  Spans under another prefix,
    and another thread's, are not the loop's."""
    import threading
    import time
    from raft_tla_tpu.obs.flight import FlightRecorder
    mt = MetricsRegistry()
    log = calls_mod.CallLog(7, recorder=FlightRecorder())
    log.start()

    def call(i, before=lambda: None):
        before()
        log.dispatch()
        with mt.phase_timer("chunk") as c:
            pass
        with mt.phase_timer("stats_fetch") as f:
            time.sleep(0.002)
        acc = mt.open_span("account")
        acc.close()
        log.row("chunk", "full", 1, c.seconds, f.seconds, 0.0, acc.seconds,
                i, 1, 1, 1, 1, distinct=i)

    def between():
        with mt.phase_timer("grow"):
            time.sleep(0.15)
        with mt.scope("replay"):
            time.sleep(0.05)

        def elsewhere():
            with mt.phase_timer("grow"):
                time.sleep(0.05)

        other = threading.Thread(target=elsewhere)
        other.start()
        other.join()

    for i in range(1, 7):
        call(i, between if i == 4 else (lambda: None))
    rows = log.rows()
    assert [r["call"] for r in rows] == [1, 2, 3, 4, 5, 6]
    # (Sleeps overshoot on a loaded host: the bounds leave room above.)
    assert rows[3]["gap_s"] >= 0.25 and 0.15 <= rows[3]["named_s"] < 0.3
    assert rows[3]["gap_s"] - rows[3]["named_s"] >= 0.1
    assert all(r["named_s"] < 0.001 for r in rows if r["call"] != 4)
    out = log.reduce()
    assert (out["slowest"]["call"], out["slowest"]["phase"]) == (4, "gap")
    assert out["stall_calls"] == 1 and 0.09 < out["stall_s"] < 0.4
    assert metrics_mod.open_spans().phase_s >= 0.15


def test_a_collection_is_charged_to_the_span_it_fell_in(tmp_path):
    import gc
    metrics_mod.watch_compiles()        # registers the collector's hook
    mt = MetricsRegistry()
    record = metrics_mod.process_record()
    base = record.gc_reading()
    total = metrics_mod.gc_seconds()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "xplane"), profiler_options=opts)
    try:
        with mt.scope("outer"):
            with mt.phase_timer("inner"):
                cycles = []
                for _ in range(1000):
                    a, b = [], []
                    a.append(b), b.append(a)
                    cycles.append(a)
                del cycles
                gc.collect()
            with mt.phase_timer("after"):
                pass
    finally:
        jax.profiler.stop_trace()
    since = record.gc_since(base)
    assert since["collections"][2] >= 1
    assert since["by_span"].get("inner", 0) > 0
    assert "after" not in since["by_span"] or since["by_span"]["after"] < \
        since["by_span"]["inner"]
    assert abs(sum(since["by_span"].values()) - since["seconds"]) < 1e-5
    assert abs(sum(since["seconds_by_generation"]) - since["seconds"]) < 1e-5
    assert mt.counter_value("gc/inner") >= 1
    assert abs(mt.counter_value("gc_seconds/inner")
               - since["by_span"]["inner"]) < 1e-5
    assert metrics_mod.gc_seconds() - total >= since["by_span"]["inner"] - 1e-5
    # Inside the capture the collection is ``raft.gc`` on the host's line.
    from jax.profiler import ProfileData
    pb = glob.glob(str(tmp_path / "xplane" / "**" / "*.xplane.pb"),
                   recursive=True)[0]
    noted = [dict(ev.stats)
             for plane in ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:")
             for line in plane.lines
             for ev in line.events if ev.name == "raft.gc"]
    full = [s for s in noted if str(s.get("generation")) == "2"]
    assert full and all("collected" in s for s in noted)
    assert max(int(s["collected"]) for s in full) >= 1000


def test_run_end_carries_the_runs_collections(tmp_path, monkeypatch):
    import gc
    eng = small_engine(tmp_path, max_diameter=3)
    emit = RunEventLog.emit

    def collecting(self, event, **fields):
        if event == "level_complete":       # inside the ``level_end`` span
            gc.collect()
        emit(self, event, **fields)

    monkeypatch.setattr(RunEventLog, "emit", collecting)
    eng.run([init_state(DIMS)])
    end = events_of(eng)[-1]
    assert end["gc"]["collections"][2] >= 4         # levels 0-3
    assert end["gc"]["by_span"]["level_end"] > 0
    assert end["gc"]["seconds"] >= end["gc"]["by_span"]["level_end"]
    # A call's row holds the collections of its gap and itself: behind
    # the first call of a level runs the close of the level before it.
    rows = eng._calls.rows()
    first = [r for r in rows if r["kind"] == "chunk"
             and r["level"] >= 2][0]
    assert first["gc_s"] > 0
