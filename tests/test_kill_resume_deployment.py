"""The supervised, checkpointed run as a deployment (README "Resilience",
benchmark configuration ``mcraft3-supervised``): killed and recovered
equals uninterrupted equals the plain reference, wherever in a level the
kill falls; what a save owes the disk and in which order; the spans,
counters and event fields of a save and of a load; and that a run ended
by an exception leaves its record and lets go of its device pools.

CPU, small sizes, through ``make_engine`` and the benchmark kind's own
sequence (``benchmark/traffic/kill_resume.py``): the run resumed from a
snapshot's path, the fault plan's soft kill, ``checkpoint.latest`` and the
resume of that path on the same warm engine.
"""

import dataclasses
import gc
import json
import os
import stat
import sys
import types

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import checkpoint as ckpt_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import WORK_COUNTERS  # noqa: E402
from raft_tla_tpu.engine.check import (initial_states,  # noqa: E402
                                       make_engine)
from raft_tla_tpu.models.schema import (decode_state,  # noqa: E402
                                        unflatten_state)
from raft_tla_tpu.resilience import faults  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

kind = lib.load_module("traffic", "kill_resume")
CFG = os.path.join(REPO, "configs", "MCraft_bounded.cfg")
CONFIG = dict(lib.load_json("configs", "mcraft3-supervised.json"),
              batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17)
PINNED = lib.load_pinned(CONFIG["pinned"])
START, KILL, DEPTH = 4, 5, 6
SEED = 3000000054
PARTS = ("ckpt_export", "ckpt_keys", "ckpt_frontier", "ckpt_deflate",
         "ckpt_write", "ckpt_gc")


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def setup():
    return load_config(CFG, n_msg_slots=CONFIG["n_msg_slots"])


def read_events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def spans_of(path):
    """[(name, start, end)] of one run's Chrome trace."""
    with open(path, encoding="utf-8") as f:
        return [(e["name"], e["ts"], e["ts"] + e["dur"])
                for e in json.load(f) if e["ph"] == "X"]


def keys_of(ck):
    return ((ck.seen_hi.astype(np.uint64) << np.uint64(32))
            | ck.seen_lo.astype(np.uint64))


def records_of(ck):
    order = np.argsort(ck.trace_fps, kind="stable")
    return (ck.trace_fps[order], ck.trace_parents[order],
            ck.trace_actions[order])


@pytest.fixture(scope="module")
def whole(setup, tmp_path_factory):
    """The uninterrupted run, root to ``DEPTH`` with a snapshot at every
    boundary: (engine, result, its directory, its events, the spans of
    its Chrome trace).  Calls of two batches, so that a level holds
    several; the engine is the one every kill below is recovered on."""
    d = tmp_path_factory.mktemp("whole")
    cfg = dataclasses.replace(
        kind.engine_config(CONFIG, str(d / "states")), sync_every=2,
        max_diameter=DEPTH, events_out=str(d / "ev.jsonl"),
        trace_out=str(d / "tr.json"), progress_interval_seconds=0.0)
    eng = make_engine(setup, cfg)
    res = eng.run(initial_states(setup))
    return (eng, res, str(d / "states"), read_events(d / "ev.jsonl"),
            spans_of(d / "tr.json"))


@pytest.fixture(scope="module")
def reference_frontier():
    """The states of level ``DEPTH``, by the plain reference."""
    ref = lib.reference(CONFIG)
    res = ref.oracle.bfs([ref.pystate.init_state(ref.dims)], ref.dims,
                         constraint=ref.constraint, max_levels=DEPTH)
    depth = {}      # ``parent`` is in the order the states were admitted
    for s, (parent, _action) in res.parent.items():
        depth[s] = 0 if parent is None else depth[parent] + 1
    return {s for s, d in depth.items()
            if d == DEPTH and ref.constraint(s, ref.dims)}


# -- the uninterrupted run: the pin, retention, the record of a save --------

def test_the_uninterrupted_run_is_the_pin_and_keeps_two(whole):
    _eng, res, states, events, _spans = whole
    assert res.stop_reason == "diameter_budget" and res.pipeline == "v2"
    assert lib.level_rows(events) == {lv: PINNED[lv]
                                      for lv in range(DEPTH + 1)}
    assert kind.snapshots_in(states) == [
        kind.SNAPSHOT.format(lv) for lv in (DEPTH - 1, DEPTH)]
    saved = [e for e in events if e["event"] == "checkpoint"]
    assert [e["level"] for e in saved] == list(range(DEPTH + 1))


def test_a_checkpoint_event_says_path_bytes_seconds_and_parts(whole):
    _eng, _res, states, events, _spans = whole
    saved = [e for e in events if e["event"] == "checkpoint"]
    end = [e for e in events if e["event"] == "run_end"][-1]
    for e in saved:
        assert e["path"] == os.path.join(
            states, kind.SNAPSHOT.format(e["level"]))
        assert e["distinct"] == PINNED[e["level"]][1]
        # Rows, two key halves, and a record (8 + 8 + 4 bytes) a state.
        assert e["bytes_raw"] == (
            PINNED[e["level"]][0] * CONFIG["shapes"]["row_bytes"]
            + PINNED[e["level"]][1] * (8 + 20))
        assert set(e["parts"]) == set(PARTS)
        assert 0 < sum(e["parts"].values()) <= e["seconds"]
    last = saved[-1]
    assert last["bytes_written"] == os.path.getsize(last["path"])
    assert end["checkpoints_written"] == len(saved)
    assert end["checkpoint_bytes_raw"] == sum(e["bytes_raw"] for e in saved)
    assert end["checkpoint_bytes_written"] == sum(e["bytes_written"]
                                                  for e in saved)
    assert {"checkpoints_written", "checkpoint_bytes_raw",
            "checkpoint_bytes_written"} <= set(WORK_COUNTERS)
    # The parts are no phases: the phases stay a partition of the wall.
    assert not set(PARTS) & set(end["phase_seconds"])
    assert end["phase_seconds"]["checkpoint"] == pytest.approx(
        sum(e["seconds"] for e in saved), abs=1e-4)


def test_the_parts_of_a_save_are_spans_inside_its_phase(whole):
    _eng, _res, _states, _events, spans = whole
    saves = sorted((s, e) for name, s, e in spans if name == "checkpoint")
    assert len(saves) == DEPTH + 1
    for part in PARTS:
        inside = sorted((s, e) for name, s, e in spans if name == part)
        assert len(inside) == len(saves), part
        for (s, e), (s0, e0) in zip(inside, saves):
            assert s0 <= s <= e <= e0, part
    # In the order the save makes them.
    first = {part: min(s for name, s, _e in spans if name == part)
             for part in PARTS}
    assert sorted(PARTS, key=first.get) == list(PARTS)


# -- killed and recovered == uninterrupted == the reference ------------------

def level_calls(events, level):
    """Chunk calls the uninterrupted run made to build ``level``."""
    return next(e["chunk_calls"] for e in events
                if e["event"] == "level_complete" and e["level"] == level)


@pytest.mark.parametrize("where", ["first", "mid", "last", "last_torn"])
def test_killed_and_recovered_equals_uninterrupted(whole, setup, where,
                                                   reference_frontier,
                                                   tmp_path):
    eng, want, whole_dir, whole_events, _spans = whole
    calls = level_calls(whole_events, KILL + 1)
    assert calls >= 4
    chunk = {"first": 1, "mid": calls // 2}.get(where, calls)
    # The kind's sequence: a directory that holds the start level's file
    # alone (the walk's engine wrote it), the plan installed soft.
    d = tmp_path / "states"
    d.mkdir()
    walk = tmp_path / "walk"
    eng.config.checkpoint_dir, eng.config.max_diameter = str(walk), START
    eng.config.events_out = eng.config.trace_out = None
    eng.run(initial_states(setup))
    start_path = str(d / kind.SNAPSHOT.format(START))
    os.replace(walk / kind.SNAPSHOT.format(START), start_path)
    eng.config.checkpoint_dir, eng.config.max_diameter = str(d), DEPTH
    eng.config.events_out = str(tmp_path / "ev.jsonl")
    faults.install(f"kill@level={KILL};chunk={chunk}",
                   state_dir=str(tmp_path / "fault_state"), hard=False)
    with pytest.raises(faults.FaultInjected):
        eng.run(resume=start_path)
    kill_path = str(d / kind.SNAPSHOT.format(KILL))
    if where == "last_torn":
        # A newer file that a crash tore lies beside the intact one.
        with open(kill_path, "rb") as f:
            torn = f.read()[:1000]
        (d / kind.SNAPSHOT.format(KILL + 1)).write_bytes(torn)
    latest = ckpt_mod.latest(str(d))
    assert latest == kill_path
    got = eng.run(resume=latest)

    assert (got.stop_reason, got.distinct, got.generated, got.diameter,
            list(got.levels)) == (
        "diameter_budget", want.distinct, want.generated, DEPTH,
        list(want.levels))
    assert got.action_counts == want.action_counts
    first_run, second_run = kind.runs_of(read_events(tmp_path / "ev.jsonl"))
    assert lib.level_rows(first_run) == {KILL: PINNED[KILL]}
    assert lib.level_rows(second_run) == {DEPTH: PINNED[DEPTH]}
    # The killed run's record: where it died, and what it had done.
    end = first_run[-1]
    assert (end["event"], end["stop_reason"], end["diameter"]) == (
        "run_end", "error", KILL)
    assert f"kill@level={KILL};chunk={chunk}" in end["error"]
    assert end["chunk_calls"] == (level_calls(whole_events, KILL)
                                  + chunk - 1)
    assert end["checkpoints_written"] == 1
    assert end["postmortem_path"] == str(d / "postmortem.json")
    start2 = second_run[0]
    assert (start2["resume"], start2["resume_level"],
            start2["resume_path"]) == (True, KILL, kill_path)
    # Retention 2, nothing torn left behind, and the SET of keys, the
    # records and the frontier of the last snapshot are the
    # uninterrupted run's.
    assert kind.snapshots_in(str(d)) == [
        kind.SNAPSHOT.format(lv) for lv in (DEPTH - 1, DEPTH)]
    a = ckpt_mod.load(os.path.join(whole_dir, kind.SNAPSHOT.format(DEPTH)))
    b = ckpt_mod.load(str(d / kind.SNAPSHOT.format(DEPTH)))
    assert np.array_equal(keys_of(a), keys_of(b))
    assert len(keys_of(b)) == PINNED[DEPTH][1]
    for x, y in zip(records_of(a), records_of(b)):
        assert np.array_equal(x, y)
    rows = lambda ck: {bytes(r) for r in ck.frontier}  # noqa: E731
    assert rows(a) == rows(b) and len(rows(b)) == PINNED[DEPTH][0]
    # ... and the reference's: the states of the last level, decoded.
    ref = lib.reference(CONFIG)
    assert {lib.to_reference_state(
        decode_state(unflatten_state(r, setup.dims), setup.dims),
        ref.pystate) for r in b.frontier} == reference_frontier
    # States admitted after the recovery replay to Init through records
    # that only the snapshot carried across the kill.
    ctx = types.SimpleNamespace(
        ledger=lib.Ledger(), config=CONFIG, cell={"replay_sample": 6},
        args=types.SimpleNamespace(seed=SEED))
    kind.replay_check(ctx, eng, ckpt_mod.load(kill_path), got)
    assert ctx.ledger.correct and ctx.ledger.attempted == 5


def test_a_resume_from_a_path_loads_under_a_span_of_its_own(whole, tmp_path):
    eng, _want, whole_dir, _events, _spans = whole
    eng.config.checkpoint_dir = str(tmp_path / "states")
    eng.config.events_out = str(tmp_path / "ev.jsonl")
    eng.config.trace_out = None
    eng.config.max_diameter = DEPTH
    eng.tracer.path = str(tmp_path / "tr.json")
    try:
        res = eng.run(resume=os.path.join(
            whole_dir, kind.SNAPSHOT.format(DEPTH - 1)))
    finally:
        eng.tracer.path = None
    assert res.phases["checkpoint_load"] > 0
    names = [name for name, _s, _e in sorted(
        spans_of(tmp_path / "tr.json"), key=lambda x: x[1])]
    assert names.index("run") < names.index("checkpoint_load") \
        < names.index("run_init") < names.index("restore")
    start = read_events(tmp_path / "ev.jsonl")[0]
    assert start["resume_level"] == DEPTH - 1
    # Given the loaded image, the run knows its level and no path.
    eng.config.events_out = str(tmp_path / "ev2.jsonl")
    eng.run(resume=ckpt_mod.load(os.path.join(
        whole_dir, kind.SNAPSHOT.format(DEPTH - 1))))
    start = read_events(tmp_path / "ev2.jsonl")[0]
    assert (start["resume_level"], start["resume_path"]) == (DEPTH - 1, None)
    assert "checkpoint_load" not in read_events(
        tmp_path / "ev2.jsonl")[-1]["phase_seconds"]
    # A run from roots says neither.
    assert "resume_level" not in whole[3][0]


# -- what a save owes the disk, and in which order -----------------------------

def test_a_save_fsyncs_the_file_renames_it_and_fsyncs_the_directory(
        whole, setup, tmp_path, monkeypatch):
    """A run cannot see a missing ``fsync``; this watches the calls.  For
    every snapshot: the ``.tmp`` file's ``fsync``, then the rename, then
    the directory's ``fsync``, then the retention, and only then the
    ``checkpoint`` event, the acknowledgement."""
    eng = whole[0]
    d = tmp_path / "states"
    events_path = tmp_path / "ev.jsonl"
    log = []

    def acknowledged():
        if not events_path.exists():
            return 0
        return sum(e["event"] == "checkpoint"
                   for e in read_events(events_path))

    real_fsync, real_replace, real_gc = os.fsync, os.replace, ckpt_mod.gc

    def fsync(fd):
        path = os.readlink(f"/proc/self/fd/{fd}")
        if str(d) in path:
            what = ("fsync_dir" if stat.S_ISDIR(os.fstat(fd).st_mode)
                    else "fsync_file")
            log.append((what, os.path.basename(path), acknowledged()))
        return real_fsync(fd)

    def replace(src, dst):
        # The snapshots' directory alone, as ``fsync`` above: the fixture's
        # engine may still hold a tracer that renames its own file.
        if str(d) in str(dst):
            log.append(("rename", os.path.basename(src) + " -> "
                        + os.path.basename(dst), acknowledged()))
        return real_replace(src, dst)

    def retention(directory, keep):
        log.append(("gc", keep, acknowledged()))
        return real_gc(directory, keep)

    monkeypatch.setattr(ckpt_mod.os, "fsync", fsync)
    monkeypatch.setattr(ckpt_mod.os, "replace", replace)
    monkeypatch.setattr(ckpt_mod, "gc", retention)
    eng.config.checkpoint_dir, eng.config.max_diameter = str(d), 3
    eng.config.events_out, eng.config.trace_out = str(events_path), None
    eng.run(initial_states(setup))
    monkeypatch.undo()
    assert acknowledged() == 4
    want = []
    for level in range(4):
        name = kind.SNAPSHOT.format(level)
        # The third field: snapshots acknowledged so far, this one not.
        want += [("fsync_file", name + ".tmp", level),
                 ("rename", f"{name}.tmp -> {name}", level),
                 ("fsync_dir", "states", level), ("gc", 2, level)]
    assert log == want


# -- a run ended by an exception ----------------------------------------------

def test_a_killed_run_lets_go_of_its_device_pools(whole, setup, tmp_path):
    """The killed run's queues, seen-set and trace buffers are free when
    the caller's handler ends, without a collection: the recovery
    allocates its own at once, on the same device."""
    eng = whole[0]
    eng.config.checkpoint_dir = str(tmp_path / "states")
    eng.config.events_out = eng.config.trace_out = None
    eng.config.max_diameter = DEPTH

    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    gc.collect()
    before = live()
    pools = 3 * (CONFIG["queue_capacity"] * CONFIG["shapes"]["row_bytes"])
    faults.install(f"kill@level={START}", hard=False)
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        try:
            eng.run(initial_states(setup))
        except faults.FaultInjected as exc:
            assert f"kill@level={START}" in str(exc)
            # Inside the handler the traceback holds the run's frame.
            assert live() - before > pools
        assert live() - before < pools // 100
    finally:
        if was_enabled:
            gc.enable()
