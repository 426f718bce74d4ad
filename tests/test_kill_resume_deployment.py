"""The supervised, checkpointed run as a deployment (README "Resilience",
benchmark configuration ``mcraft3-supervised``): killed and recovered
equals uninterrupted equals the plain reference, wherever in a level the
kill falls; what a save owes the disk and in which order; the spans,
counters and event fields of a save and of a load; the save's two halves
(the capture at the boundary, the commit behind the next level's calls on
a thread of its own: the image the boundary's, one in flight, a kill or a
failed write with one in flight, no thread left behind); and that a run
ended by an exception leaves its record and lets go of its device pools.

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
import threading
import types

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import bfs as bfs_mod  # noqa: E402
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
# A save's parts in the order it makes them: the capture's, on the loop's
# thread inside the ``checkpoint`` phase, then the commit's, on the save's
# own thread.
CAPTURE_PARTS = ("ckpt_export", "ckpt_keys", "ckpt_frontier")
COMMIT_PARTS = ("ckpt_sort", "ckpt_deflate", "ckpt_write", "ckpt_gc")
PARTS = CAPTURE_PARTS + COMMIT_PARTS
WRITER = "raft-snapshot"
ARRAYS = ("frontier", "seen_hi", "seen_lo", "trace_fps", "trace_parents",
          "trace_actions")


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
    return [span[:3] for span in spans_by_thread(path)]


def spans_by_thread(path):
    """[(name, start, end, thread's name)] of one run's Chrome trace."""
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    threads = {e["tid"]: e["args"]["name"] for e in trace
               if e["ph"] == "M" and e["name"] == "thread_name"}
    return [(e["name"], e["ts"], e["ts"] + e["dur"], threads.get(e["tid"]))
            for e in trace if e["ph"] == "X"]


def writers_alive():
    return [t for t in threading.enumerate() if t.name == WRITER]


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
            spans_by_thread(d / "tr.json"))


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
        # What the loop was held for, the capture and any wait, is part
        # of what the save took, from the capture's start to this event.
        capture = sum(e["parts"][part] for part in CAPTURE_PARTS)
        assert capture <= e["stall_seconds"] <= e["seconds"]
    last = saved[-1]
    assert last["bytes_written"] == os.path.getsize(last["path"])
    assert end["checkpoints_written"] == len(saved)
    assert end["checkpoint_bytes_raw"] == sum(e["bytes_raw"] for e in saved)
    assert end["checkpoint_bytes_written"] == sum(e["bytes_written"]
                                                  for e in saved)
    assert {"checkpoints_written", "checkpoint_bytes_raw",
            "checkpoint_bytes_written", "checkpoints_overlapped",
            "checkpoints_drained", "checkpoint_wait_s"} <= set(WORK_COUNTERS)
    # Every save was taken off the books, one way or the other.
    assert (end["checkpoints_overlapped"] + end["checkpoints_drained"]
            == len(saved))
    # The parts are no phases: the phases stay a partition of the wall.
    assert not set(PARTS) & set(end["phase_seconds"])
    # The phase is the stall, not the save: what the loop was held for.
    assert end["phase_seconds"]["checkpoint"] == pytest.approx(
        sum(e["stall_seconds"] for e in saved), abs=1e-4)
    assert 0 <= end["checkpoint_wait_s"] < end["phase_seconds"]["checkpoint"]


def test_the_parts_of_a_save_are_spans_inside_its_phase(whole):
    """The two halves.  The capture's parts lie inside a ``checkpoint``
    span of the loop's thread, in order; the commit's lie on the save's
    own thread, in order, after that span's end and before the next
    snapshot's capture begins."""
    _eng, _res, _states, _events, spans = whole
    loop = {thread for name, _s, _e, thread in spans if name == "run"}
    assert len(loop) == 1 and WRITER not in loop
    by_part = {part: sorted((s, e, thread) for name, s, e, thread in spans
                            if name == part) for part in PARTS}
    # A capture is the ``checkpoint`` span that holds an export; the
    # loop's waits for a commit are ``checkpoint`` spans too.
    phases = sorted((s, e) for name, s, e, thread in spans
                    if name == "checkpoint" and thread in loop)
    captures = [(s0, e0) for s0, e0 in phases
                if any(s0 <= s <= e0 for s, _e, _t in by_part["ckpt_export"])]
    assert len(captures) == DEPTH + 1
    assert not [1 for name, _s, _e, thread in spans
                if name == "checkpoint" and thread not in loop]
    for part in PARTS:
        assert len(by_part[part]) == len(captures), part
    begins = [s0 for s0, _e0 in captures[1:]] + [float("inf")]
    for k, (s0, e0) in enumerate(captures):
        at = s0
        for part in CAPTURE_PARTS:
            s, e, thread = by_part[part][k]
            assert at <= s <= e <= e0 and thread in loop, (part, k)
            at = e
        at = e0
        for part in COMMIT_PARTS:
            s, e, thread = by_part[part][k]
            assert at <= s <= e <= begins[k] and thread == WRITER, (part, k)
            at = e


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
    # ``last_torn``: the run dies one step later still, with the level
    # built, in the write of its snapshot, on the save's own thread
    # (the site's soft death there is raised from the loop).
    torn = where == "last_torn"
    plan = (f"ckpt_torn_write@level={KILL + 1}" if torn
            else f"kill@level={KILL};chunk={chunk}")
    # The kind's sequence: a directory that holds the start level's file
    # alone (the walk's engine wrote it), the plan installed soft.
    d, start_path = resumable(eng, setup, tmp_path)
    aim(eng, d, tmp_path / "ev.jsonl", DEPTH)
    faults.install(plan, state_dir=str(tmp_path / "fault_state"),
                   hard=False)
    with pytest.raises(faults.FaultInjected):
        eng.run(resume=start_path)
    assert not writers_alive()
    kill_path = str(d / kind.SNAPSHOT.format(KILL))
    if torn:
        # The complete ``.tmp`` stays unrenamed, as a power cut there
        # leaves it; and a newer file that a crash tore (the rename
        # landed, the data did not) lies beside the intact one.
        newer = kind.SNAPSHOT.format(KILL + 1)
        assert kind.snapshots_in(str(d)) == [
            kind.SNAPSHOT.format(START), kind.SNAPSHOT.format(KILL),
            newer + ".tmp"]
        (d / newer).write_bytes((d / (newer + ".tmp")).read_bytes()[:1000])
    latest = ckpt_mod.latest(str(d))
    assert latest == kill_path
    got = eng.run(resume=latest)

    assert (got.stop_reason, got.distinct, got.generated, got.diameter,
            list(got.levels)) == (
        "diameter_budget", want.distinct, want.generated, DEPTH,
        list(want.levels))
    assert got.action_counts == want.action_counts
    first_run, second_run = kind.runs_of(read_events(tmp_path / "ev.jsonl"))
    built = [KILL, KILL + 1] if torn else [KILL]
    assert lib.level_rows(first_run) == {lv: PINNED[lv] for lv in built}
    assert lib.level_rows(second_run) == {DEPTH: PINNED[DEPTH]}
    # The killed run's record: where it died, and what it had done.
    end = first_run[-1]
    assert (end["event"], end["stop_reason"], end["diameter"]) == (
        "run_end", "error", built[-1])
    assert plan in end["error"]
    assert end["chunk_calls"] == (level_calls(whole_events, KILL)
                                  + (calls if torn else chunk - 1))
    # One snapshot acknowledged, the kill level's: none for the level
    # whose write died.
    assert [e["level"] for e in first_run
            if e["event"] == "checkpoint"] == [KILL]
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
    """A run cannot see a missing ``fsync``; this watches the calls, on
    whichever thread they are made.  For every snapshot: the ``.tmp``
    file's ``fsync``, then the rename, then the directory's ``fsync``,
    then the retention, and only then the ``checkpoint`` event, the
    acknowledgement: not yet written when the directory's ``fsync`` has
    returned, written before the next snapshot's first.  Two ``fsync``s
    a snapshot, no more and no fewer."""
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
        real_fsync(fd)
        if str(d) in path:
            # Logged when the call has returned.
            what = ("fsync_dir" if stat.S_ISDIR(os.fstat(fd).st_mode)
                    else "fsync_file")
            log.append((what, os.path.basename(path), acknowledged()))

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
    assert sum(what.startswith("fsync") for what, _n, _a in log) == 2 * 4
    assert not writers_alive()


# -- the two halves: the commit behind the next level's calls -----------------

def aim(eng, directory, events, depth):
    eng.config.checkpoint_dir, eng.config.max_diameter = str(directory), depth
    eng.config.events_out, eng.config.trace_out = str(events), None
    eng.config.max_seconds = None


class Gate:
    """Holds a commit where it calls ``checkpoint.save`` until the loop
    has come as far as the test wants, so that no test waits on a clock.
    ``hold(level)`` says which snapshots; ``go`` lets them through."""

    def __init__(self, monkeypatch, hold):
        self.go, self.held, self.inside = threading.Event(), [], 0
        self.overlap = False
        real = ckpt_mod.save

        def save(path, ck, metrics=None):
            assert threading.current_thread().name == WRITER
            self.inside += 1
            self.overlap |= self.inside > 1
            try:
                if hold(ck.diameter):
                    self.held.append(ck.diameter)
                    assert self.go.wait(60), "the loop never came"
                    self.go.clear()
                return real(path, ck, metrics=metrics)
            finally:
                self.inside -= 1

        monkeypatch.setattr(ckpt_mod, "save", save)

    def open_when_the_loop_waits(self, monkeypatch):
        """Every held commit ends only once the loop waits for it."""
        real = bfs_mod._SnapshotSave.finish
        gate = self

        def finish(self, wait=True, quiet=False):
            if wait and self._thread is not None:
                gate.go.set()
            return real(self, wait=wait, quiet=quiet)

        monkeypatch.setattr(bfs_mod._SnapshotSave, "finish", finish)

    def open_after_calls(self, monkeypatch, eng, calls):
        """A held commit goes on once ``calls`` chunk calls have been
        dispatched since it was held."""
        real, since = eng._chunk, []

        def chunk(*args):
            out = real(*args)
            if self.held:
                since.append(1)
                if len(since) >= calls:
                    self.go.set()
            return out

        monkeypatch.setattr(eng, "_chunk", chunk)


def test_a_commit_behind_calls_writes_the_boundarys_image(
        whole, setup, tmp_path, monkeypatch):
    """Level ``KILL``'s snapshot, its commit held until two calls of the
    next level have run (they insert into the table and fill the queue the
    capture read), is array for array the file of a run that stops at that
    boundary, whose save is waited for at once."""
    eng = whole[0]
    aim(eng, tmp_path / "drained", tmp_path / "ev0.jsonl", KILL)
    eng.run(initial_states(setup))
    end = read_events(tmp_path / "ev0.jsonl")[-1]
    assert end["checkpoints_drained"] >= 1      # the last one, at least
    gate = Gate(monkeypatch, hold=lambda level: level == KILL)
    gate.open_after_calls(monkeypatch, eng, 2)
    aim(eng, tmp_path / "behind", tmp_path / "ev1.jsonl", DEPTH)
    eng.config.keep_checkpoints = None      # level KILL's file stays
    try:
        eng.run(initial_states(setup))
    finally:
        eng.config.keep_checkpoints = CONFIG["durability"]["keep_checkpoints"]
        monkeypatch.undo()
    events = read_events(tmp_path / "ev1.jsonl")
    names = [(e["event"], e.get("level")) for e in events]
    assert gate.held == [KILL] and events[-1]["checkpoints_overlapped"] >= 1
    assert names.index(("checkpoint", KILL)) \
        < names.index(("level_complete", DEPTH))
    a = ckpt_mod.load(str(tmp_path / "drained" / kind.SNAPSHOT.format(KILL)))
    b = ckpt_mod.load(str(tmp_path / "behind" / kind.SNAPSHOT.format(KILL)))
    for name in ARRAYS:
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    assert np.array_equal(keys_of(a), np.unique(keys_of(b)))
    assert len(keys_of(b)) == PINNED[KILL][1]
    assert (a.distinct, a.generated, a.diameter, a.levels,
            a.action_counts, a.roots) == (
        b.distinct, b.generated, b.diameter, b.levels, b.action_counts,
        b.roots)
    # The next level's file holds what those calls admitted: the table
    # the commit's thread sorted was the boundary's, not the device's.
    c = ckpt_mod.load(str(tmp_path / "behind" / kind.SNAPSHOT.format(DEPTH)))
    assert len(keys_of(c)) == PINNED[DEPTH][1] > len(keys_of(b))


def test_boundaries_faster_than_commits_one_in_flight_in_level_order(
        whole, setup, tmp_path, monkeypatch):
    """Every commit held until the loop waits for it: never two at once,
    acknowledged in level order, each after its level's close and before
    the next level's, and the counters add up."""
    eng = whole[0]
    gate = Gate(monkeypatch, hold=lambda level: True)
    gate.open_when_the_loop_waits(monkeypatch)
    aim(eng, tmp_path / "states", tmp_path / "ev.jsonl", DEPTH)
    eng.run(initial_states(setup))
    monkeypatch.undo()
    events = read_events(tmp_path / "ev.jsonl")
    assert gate.held == list(range(DEPTH + 1)) and not gate.overlap
    order = [(e["event"], e["level"]) for e in events
             if e["event"] in ("level_complete", "checkpoint")]
    assert order == [(name, lv) for lv in range(DEPTH + 1)
                     for name in ("level_complete", "checkpoint")]
    end, saved = events[-1], [e for e in events
                              if e["event"] == "checkpoint"]
    assert (end["checkpoints_drained"], end["checkpoints_overlapped"],
            end["checkpoints_written"]) == (DEPTH + 1, 0, DEPTH + 1)
    assert end["phase_seconds"]["checkpoint"] == pytest.approx(
        sum(e["stall_seconds"] for e in saved), abs=1e-4)
    # The wait is part of the stall, and the stall of what a save took
    # (which holds the next level's calls too).
    for e in saved:
        assert e["stall_seconds"] <= e["seconds"]
    capture = sum(e["parts"][part] for e in saved for part in CAPTURE_PARTS)
    assert 0 < end["checkpoint_wait_s"] <= (
        end["phase_seconds"]["checkpoint"] - capture + 1e-4)
    assert kind.snapshots_in(str(tmp_path / "states")) == [
        kind.SNAPSHOT.format(lv) for lv in (DEPTH - 1, DEPTH)]
    assert not writers_alive()


def resumable(eng, setup, tmp_path):
    """A directory that holds the start level's file alone, and its path:
    ``test_killed_and_recovered``'s."""
    d, walk = tmp_path / "states", tmp_path / "walk"
    d.mkdir()
    aim(eng, walk, tmp_path / "walk.jsonl", START)
    eng.run(initial_states(setup))
    start_path = str(d / kind.SNAPSHOT.format(START))
    os.replace(walk / kind.SNAPSHOT.format(START), start_path)
    return d, start_path


def test_a_kill_with_a_commit_in_flight_waits_for_the_writer(
        whole, setup, tmp_path, monkeypatch):
    """The soft kill fires at the dispatch of the second call after level
    ``KILL``'s capture while that commit is held: ``run()`` raises only
    when the writer has ended and acknowledged, and ``latest()`` called at
    once, as the benchmark's kind calls it, names level ``KILL``."""
    eng = whole[0]
    d, start_path = resumable(eng, setup, tmp_path)
    gate = Gate(monkeypatch, hold=lambda level: level == KILL)
    gate.open_when_the_loop_waits(monkeypatch)
    aim(eng, d, tmp_path / "ev.jsonl", DEPTH)
    faults.install(f"kill@level={KILL};chunk=2", hard=False)
    with pytest.raises(faults.FaultInjected):
        eng.run(resume=start_path)
    assert not writers_alive() and gate.held == [KILL]
    assert ckpt_mod.latest(str(d)) == str(d / kind.SNAPSHOT.format(KILL))
    assert kind.snapshots_in(str(d)) == [
        kind.SNAPSHOT.format(lv) for lv in (START, KILL)]
    monkeypatch.undo()
    events = read_events(tmp_path / "ev.jsonl")
    assert [(e["event"], e.get("level")) for e in events
            if e["event"] in ("level_complete", "checkpoint", "run_end")] \
        == [("level_complete", KILL), ("checkpoint", KILL),
            ("run_end", None)]
    end = events[-1]
    assert (end["stop_reason"], end["chunk_calls"]) == (
        "error", level_calls(whole[3], KILL) + 1)
    assert (end["checkpoints_drained"], end["checkpoints_overlapped"],
            end["checkpoints_written"]) == (1, 0, 1)
    assert end["phase_seconds"]["checkpoint"] == pytest.approx(
        sum(e["stall_seconds"] for e in events
            if e["event"] == "checkpoint"), abs=1e-4)


def test_a_failed_write_fails_the_run_within_one_call(
        whole, setup, tmp_path, monkeypatch):
    """``OSError`` from the file's ``fsync``, on the writer: the run
    raises that error after the accounting of the call during which it
    happened, nothing acknowledges the level, the older snapshot stays
    and ``latest()`` names it."""
    eng = whole[0]
    d, start_path = resumable(eng, setup, tmp_path)
    real_fsync, failed = os.fsync, []

    def fsync(fd):
        path = os.readlink(f"/proc/self/fd/{fd}")
        if str(d) in path and path.endswith(".tmp"):
            assert threading.current_thread().name == WRITER
            failed.append(int(eng.metrics.counter_value(
                "engine/chunk_calls")))
            raise OSError(28, "No space left on device")
        return real_fsync(fd)

    monkeypatch.setattr(ckpt_mod.os, "fsync", fsync)
    aim(eng, d, tmp_path / "ev.jsonl", DEPTH)
    calls_before = int(eng.metrics.counter_value("engine/chunk_calls"))
    with pytest.raises(OSError, match="No space left"):
        eng.run(resume=start_path)
    monkeypatch.undo()
    assert len(failed) == 1 and not writers_alive()
    events = read_events(tmp_path / "ev.jsonl")
    end = events[-1]
    assert (end["event"], end["stop_reason"]) == ("run_end", "error")
    assert "No space left" in end["error"]
    # Accounted when it failed, and one call more at most: the one in
    # flight then, or the one dispatched before the loop next looked.
    assert end["chunk_calls"] <= failed[0] - calls_before + 1
    assert end["chunk_calls"] < (level_calls(whole[3], KILL)
                                 + level_calls(whole[3], KILL + 1))
    assert not [e for e in events if e["event"] == "checkpoint"]
    assert (end["checkpoints_written"], end["checkpoints_overlapped"]
            + end["checkpoints_drained"]) == (0, 1)
    assert ckpt_mod.latest(str(d)) == start_path
    assert kind.SNAPSHOT.format(KILL) not in kind.snapshots_in(str(d))


@pytest.mark.parametrize("how", ["diameter", "budget", "kill"])
def test_no_thread_of_the_engine_outlives_a_run(whole, setup, tmp_path, how):
    """However ``run()`` ends, by its diameter, by a duration budget or by
    an exception, the save's thread has ended; and the next ``run()`` on
    the same engine snapshots again."""
    eng = whole[0]
    before = set(threading.enumerate())
    aim(eng, tmp_path / "states", tmp_path / "ev.jsonl", DEPTH - 1)
    if how == "budget":
        eng.config.max_diameter, eng.config.max_seconds = None, 1e-3
    if how == "kill":
        faults.install(f"kill@level={START};chunk=2", hard=False)
    try:
        res = eng.run(initial_states(setup))
        assert res.stop_reason == {"diameter": "diameter_budget",
                                   "budget": "duration_budget"}[how]
    except faults.FaultInjected:
        assert how == "kill"
    finally:
        eng.config.max_seconds = None
        faults.clear()
    assert set(threading.enumerate()) <= before
    first = read_events(tmp_path / "ev.jsonl")
    acked = [e["level"] for e in first if e["event"] == "checkpoint"]
    assert acked == list(range(len(acked))) and acked
    assert first[-1]["checkpoints_written"] == len(acked)
    aim(eng, tmp_path / "again", tmp_path / "ev2.jsonl", 2)
    eng.run(initial_states(setup))
    assert set(threading.enumerate()) <= before
    again = read_events(tmp_path / "ev2.jsonl")
    assert [e["level"] for e in again if e["event"] == "checkpoint"] \
        == [0, 1, 2]
    assert again[-1]["checkpoints_written"] == 3


def test_loop_and_writer_lose_no_save_under_a_short_switch_interval(
        whole, setup, tmp_path):
    """The loop's looks at the save in flight and the commit's end race on
    every boundary of these short levels; with the interpreter switching
    threads every microsecond, every save is still acknowledged once, in
    order, counted once, and the phase is the sum of the stalls."""
    eng = whole[0]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for i in range(5):
            aim(eng, tmp_path / f"states{i}", tmp_path / f"ev{i}.jsonl",
                START)
            eng.run(initial_states(setup))
            assert not writers_alive()
            events = read_events(tmp_path / f"ev{i}.jsonl")
            end, saved = events[-1], [e for e in events
                                      if e["event"] == "checkpoint"]
            assert [e["level"] for e in saved] == list(range(START + 1))
            assert (end["checkpoints_overlapped"]
                    + end["checkpoints_drained"]
                    == end["checkpoints_written"] == START + 1)
            assert end["phase_seconds"]["checkpoint"] == pytest.approx(
                sum(e["stall_seconds"] for e in saved), abs=1e-4)
    finally:
        sys.setswitchinterval(was)


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
