"""The trace flush of both host loops (engine/bfs.py ``_TraceFlush``,
``flush_plan``; ``BFSEngine._run_impl`` and ``MeshBFSEngine._run_impl``):
its fetch programs have shapes fixed at the engine's build and compile in
warm-up, its host half runs behind the next chunk dispatch, and it is
drained wherever something other than a plain next call reads the store
or leaves the loop.  Every run here takes one batch a call
(``sync_every=1``), so that levels hold several calls and every call
admits another number of states.  The mesh runs over four of the suite's
virtual devices, four parents a chip a call: from one root everything
lies on chip 0 until level 2's 15 rows are dealt out, so its chips'
counts differ and some are 0; its roots' records go through the flush
too, drained at once (the one-chip ingest hands its own back itself).

The one-chip loop owes a level's close (``_LevelClose``) with its last
flush and finishes both behind the next level's first dispatch; the mesh
loop drains at every level's end.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine import bfs as bfs_mod
from raft_tla_tpu.engine import checkpoint as ckpt_mod
from raft_tla_tpu.engine import trace as trace_mod
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig, flush_plan
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok)
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.parallel import mesh as mesh_mod
from raft_tla_tpu.parallel.mesh import MeshBFSEngine

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
# Levels 0-5 hold 1, 3, 15, 52, 162 and 486 states: from level 3 on a
# level is several calls of 16 parents.
LEVELS = [1, 3, 15, 52, 162, 486]
DISTINCT = 1313     # the states the constraint keeps out of a level among them


ENGINES = ["one_chip", "mesh"]


@pytest.fixture(params=ENGINES)
def kind(request):
    return request.param


def make_engine(kind="one_chip", invariants=None, chips=4, **kw):
    base = dict(batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, sync_every=1, max_diameter=5)
    cls = BFSEngine
    if kind == "mesh":
        base["batch"] = 4       # a chip: level 4's 162 rows are 11 calls
        cls = functools.partial(MeshBFSEngine,
                                devices=jax.devices()[:chips])
    base.update(kw)
    return cls(DIMS, invariants=invariants,
               constraint=build_constraint(DIMS, BOUNDS),
               config=EngineConfig(**base))


def events_of(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def run_end(path):
    end = events_of(path)[-1]
    assert end["event"] == "run_end"
    return end


def one_vote_short():
    """A root and the invariants it breaks two levels on (the root of
    tests/test_engine.py: a candidate one vote short of quorum)."""
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))
    return s0, {"TypeOK": build_type_ok(DIMS),
                "NoLeader": lambda st: jnp.all(st.role != LEADER)}


@pytest.fixture
def started(monkeypatch):
    """The records of every flush the loop starts, a number a plan (the
    one-chip loop's one, the mesh's one a chip)."""
    lengths = []
    start = bfs_mod._TraceFlush.start

    def noting(self, parts):
        parts = list(parts)
        lengths.append(tuple(n for _buf, n in parts))
        start(self, parts)

    monkeypatch.setattr(bfs_mod._TraceFlush, "start", noting)
    return lengths


def records(trace):
    fps, parents, actions = trace.export()
    return set(zip(fps.tolist(), parents.tolist(), actions.tolist()))


def assert_every_chain_reaches_a_root(trace):
    for fp in trace.export()[0].tolist():
        chain = trace.chain(fp)
        assert chain[0][1] < 0 and chain[0][0] in trace.roots, hex(fp)


def assert_legal(steps, root, last=None):
    assert steps[0] == (-1, root)
    for prev, step in zip(steps, steps[1:]):
        assert step[1] in orc.successor_set(prev[1], DIMS)
    if last is not None:
        assert steps[-1][1] == last


# -- the plan of fetches --------------------------------------------------------

@pytest.mark.parametrize("n, lengths, size, pieces", [
    (1, (2048, 16384, 131072), 1 << 21, [2048]),
    (2048, (2048, 16384, 131072), 1 << 21, [2048]),
    (2049, (2048, 16384, 131072), 1 << 21, [16384]),
    (131072, (2048, 16384, 131072), 1 << 21, [131072]),
    (186000, (2048, 16384, 131072), 1 << 21, [131072] * 2),
    (4600, (2048, 4608), 4608, [4608]),             # the whole buffer
    (40, (4, 16), 44, [16] * 3),                    # the last piece moved back
    (44, (4, 16), 44, [16] * 3),
])
def test_the_plan_covers_the_records_once_and_in_order(n, lengths, size,
                                                       pieces):
    plan = flush_plan(n, lengths, size)
    assert [length for _s, length, _lo, _hi in plan] == pieces
    got = []
    for start, length, lo, hi in plan:
        assert 0 <= start and start + length <= size
        assert 0 <= lo < hi <= length
        got.extend(range(start + lo, start + hi))
    assert got == list(range(n))
    # Never a whole piece more than the records need.
    assert sum(pieces) - n < pieces[-1]


# -- no compile after warm-up -----------------------------------------------------

def test_no_flush_compiles_whatever_its_length(kind, started, tmp_path,
                                               monkeypatch):
    ev = str(tmp_path / "ev.jsonl")
    # Sizes of its own: nothing here is in the process's jit cache.
    if kind == "mesh":
        # Two lengths under a chip's buffer, and flushes of several pieces.
        monkeypatch.setattr(bfs_mod, "FLUSH_PIECES", (8, 32))
        eng = make_engine(kind, batch=5, queue_capacity=4 * 5 * 233,
                          events_out=ev)
        assert eng._fetch_lens == [8, 32]
        # The fills before warm-up, and a deal-out's own programs.
        outside = {"run", "root_check", "warmup", "rebalance"}
    else:
        eng = make_engine(kind, batch=18, queue_capacity=18 * 233,
                          events_out=ev)
        outside = {"root_check", "run_init", "warmup"}
    res = eng.run([init_state(DIMS)])
    assert res.levels == LEVELS
    assert len(set(started)) >= 10, started
    end = run_end(ev)
    assert end["chunk_calls"] == len(started) - (kind == "mesh")
    in_the_loop = set(end["compiles"]) - outside
    assert not in_the_loop, end["compiles"]
    assert end["compiles"]["warmup"][0] >= 2 + len(eng._fetch_lens)


# -- what reaches the store --------------------------------------------------------

@pytest.mark.parametrize("pieces", [bfs_mod.FLUSH_PIECES, (4, 16)],
                         ids=["one_piece", "several_pieces"])
def test_the_store_equals_a_synchronous_flush(kind, pieces, started,
                                              monkeypatch):
    """The deferred flush against every buffer copied whole to the host
    and cut there, before anything else happens, on the same run (what
    the mesh loop did until PR 34); with pieces of 4 and 16 records a
    flush takes up to eight fetches a buffer."""
    monkeypatch.setattr(bfs_mod, "FLUSH_PIECES", pieces)
    eng = make_engine(kind)
    res = eng.run([init_state(DIMS)])
    assert (res.levels, res.distinct) == (LEVELS, DISTINCT)
    # One record a distinct state, the root's among them.
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)
    if kind == "mesh":
        # Chips whose counts differ, one of them 0; and all four at work.
        assert any(0 in c and max(c) > 0 for c in started), started
        assert any(len(set(c)) > 1 and min(c) > 0 for c in started)

    def whole_buffers_at_once(self, parts):
        for buf, n in parts:
            cols = [np.asarray(x).reshape(-1) for x in buf]
            self._eng._record(self._trace, cols, 0, n)

    monkeypatch.setattr(bfs_mod._TraceFlush, "start", whole_buffers_at_once)
    sync = make_engine(kind)
    assert sync.run([init_state(DIMS)]).distinct == res.distinct
    assert sync.metrics.counter_value("engine/flush_overlapped") == 0
    assert records(eng.trace) == records(sync.trace)


# -- drain points -----------------------------------------------------------------

def drained_before_a_checkpoint(kind, tmp_path):
    """Level 4 is eleven calls; its snapshot holds every record of it,
    and the resumed run ends where an uninterrupted one does."""
    ck = str(tmp_path / "ck")
    first = make_engine(kind, max_diameter=4, checkpoint_dir=ck)
    first.run([init_state(DIMS)])
    path = ckpt_mod.latest(ck)
    assert path.endswith("level_00004.npz")
    kept = ckpt_mod.load(path)
    assert kept.levels == tuple(LEVELS[:5])
    assert kept.trace_fps.size == kept.distinct
    second = make_engine(kind)
    got = second.run(resume=path)
    assert (got.levels, got.distinct) == (LEVELS, DISTINCT)
    assert len(second.trace.export()[0]) == got.distinct
    # A state of level 5: its chain crosses the snapshot.
    deepest = max(second.trace.export()[0].tolist(),
                  key=lambda fp: len(second.trace.chain(fp)))
    steps = second.replay(deepest)
    assert len(steps) == 6
    assert_legal(steps, init_state(DIMS))
    return [first, second]


def drained_before_a_replay(kind, tmp_path):
    """The violation lies in a call dispatched with its predecessor's
    flush still owed; the replay needs both calls' records."""
    s0, invariants = one_vote_short()
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(
        kind, invariants=invariants,
        batch=1 if kind == "mesh" else 2, max_diameter=None, events_out=ev)
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    events = events_of(ev)
    whole_levels = sum(e["chunk_calls"] for e in events
                       if e["event"] == "level_complete")
    assert events[-1]["chunk_calls"] - whole_levels >= 2
    assert len(eng.trace.export()[0]) == res.distinct
    assert_legal(eng.replay(res.violation.fingerprint), s0,
                 last=res.violation.state)
    return [eng]


def drained_at_a_duration_stop(kind, tmp_path):
    """Whatever call the deadline falls after, nothing stays owed."""
    eng = make_engine(kind, max_diameter=None, max_seconds=1.0)
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "duration_budget"
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)
    return [eng]


def drained_where_the_seen_set_grows(kind, tmp_path):
    """A growth inside a level: the rebuilt programs hand the loop
    another trace buffer, and every record of the calls on either side
    of it is in the store.  A table of the least capacity is 2,048
    slots (a chip) and grows past 1,024 keys: in level 5 on one chip, in
    level 6 on each of two."""
    depth = 6 if kind == "mesh" else 5
    ev = str(tmp_path / "grow.jsonl")
    eng = make_engine(kind, chips=2, batch=16, seen_capacity=1 << 11,
                      max_diameter=depth, events_out=ev)
    res = eng.run([init_state(DIMS)])
    assert res.growth_stalls
    # Each growth says what its rehash ran (``fpset.rebuild_unique``): a
    # round at least, and a lane-round a key of the 1,025 or more it
    # moved.
    with open(ev, encoding="utf-8") as f:
        grown = [e for e in map(json.loads, f)
                 if e["event"] == "fpset_resize"]
    assert len(grown) == len(res.growth_stalls)
    assert all(e["rebuild_rounds"] >= 1 and e["rebuild_lane_rounds"] > 1024
               for e in grown)
    assert res.levels == (LEVELS + [1378])[:depth + 1]
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)
    # The growth lay between two calls in a span of the loop's own
    # (``grow``, seconds of compiling here): the rows take it for named
    # work (``named_s``), so it is no stall however long it took.
    named = sum(r["named_s"] for r in eng._calls.rows())
    assert named >= sum(s for _cap, s in res.growth_stalls) - 0.01
    assert run_end(ev)["calls"]["slow_calls"] == 0
    return [eng]


def settled_before_a_snapshot_among_owed_closes(kind, tmp_path):
    """A snapshot every other level: the closes of levels 1 and 3 ride
    behind the next level's first call, those of levels 0, 2 and 4 are
    settled first, and level 4's snapshot holds every record of the
    level it closes, its last call's among them: the resumed run ends
    with the store of an uninterrupted one."""
    ck, ev = str(tmp_path / "ck"), str(tmp_path / "ev.jsonl")
    first = make_engine(kind, max_diameter=4, checkpoint_dir=ck,
                        checkpoint_every=2, events_out=ev)
    first.run([init_state(DIMS)])
    events = events_of(ev)
    order = [(e["event"], e["level"]) for e in events
             if e["event"] in ("level_complete", "checkpoint")]
    if kind == "mesh":
        assert order == [("level_complete", 0), ("checkpoint", 0),
                         ("level_complete", 1), ("level_complete", 2),
                         ("checkpoint", 2), ("level_complete", 3),
                         ("level_complete", 4), ("checkpoint", 4)]
    else:
        # A snapshot's file is made behind the next levels' calls: its
        # acknowledgement follows its level's close and precedes the
        # close of the next level that is snapshotted.
        assert [e for e in order if e[0] == "level_complete"] == [
            ("level_complete", lv) for lv in range(5)]
        assert [e for e in order if e[0] == "checkpoint"] == [
            ("checkpoint", lv) for lv in (0, 2, 4)]
        for lv in (0, 2, 4):
            at = order.index(("checkpoint", lv))
            assert order.index(("level_complete", lv)) < at
            assert lv == 4 or at < order.index(("level_complete", lv + 2))
        end = events[-1]
        assert (end["level_closes_overlapped"],
                end["level_closes_drained"]) == (2, 3)
    path = ckpt_mod.latest(ck)
    kept = ckpt_mod.load(path)
    assert kept.levels == tuple(LEVELS[:5])
    assert kept.trace_fps.size == kept.distinct
    second = make_engine(kind)
    got = second.run(resume=path)
    assert (got.levels, got.distinct) == (LEVELS, DISTINCT)
    whole = make_engine(kind)
    whole.run([init_state(DIMS)])
    if kind == "one_chip":
        assert records(second.trace) == records(whole.trace)
    else:
        # A resumed mesh deals its frontier out anew: a state two
        # parents reach may be recorded under the other.
        assert set(second.trace.export()[0].tolist()) == set(
            whole.trace.export()[0].tolist())
    assert_every_chain_reaches_a_root(second.trace)
    return [first, second, whole]


@pytest.mark.parametrize("case", [drained_before_a_checkpoint,
                                  drained_before_a_replay,
                                  drained_at_a_duration_stop,
                                  drained_where_the_seen_set_grows,
                                  settled_before_a_snapshot_among_owed_closes],
                         ids=lambda f: f.__name__)
def test_nothing_is_owed_at_a_drain_point(case, kind, tmp_path, started):
    engines = case(kind, tmp_path)
    counts = [bfs_mod.work_counts(eng.metrics) for eng in engines]
    assert sum(c["flush_overlapped"] for c in counts) > 0
    # Every flush started was finished, one way or the other.
    assert sum(c["flush_overlapped"] + c["flush_drained"]
               for c in counts) == len(started)


def test_a_violation_in_a_levels_first_call_replays_through_what_was_owed(
        tmp_path, started):
    """The violation lies in the FIRST call of its level: that call was
    dispatched with the last flush of the level before it, and that
    level's close, still owed; the replay walks through those records."""
    s0, invariants = one_vote_short()
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine("one_chip", invariants=invariants, max_diameter=None,
                      events_out=ev)
    res = eng.run([s0])
    assert res.stop_reason == "violation" and res.diameter >= 1
    events = events_of(ev)
    end = events[-1]
    closed = [e for e in events if e["event"] == "level_complete"]
    assert [e["level"] for e in closed] == list(range(res.diameter + 1))
    # One call after the last whole level, and the violation is its.
    assert end["chunk_calls"] - sum(e["chunk_calls"] for e in closed) == 1
    names = [e["event"] for e in events]
    assert names.index("violation") > max(
        i for i, n in enumerate(names) if n == "level_complete")
    assert (end["level_closes_overlapped"],
            end["level_closes_drained"]) == (res.diameter + 1, 0)
    # The violating call's own flush is the one drained.
    assert (end["flush_overlapped"], end["flush_drained"]) == (
        len(started) - 1, 1)
    assert len(eng.trace.export()[0]) == res.distinct
    steps = eng.replay(res.violation.fingerprint)
    assert len(steps) == res.diameter + 2
    assert_legal(steps, s0, last=res.violation.state)


def test_a_degraded_resume_takes_no_record_of_the_run_that_died(kind,
                                                                tmp_path):
    """``_run_degradable`` resumes from the snapshot into a new store:
    what the dead attempt still owed must not reach it."""
    from raft_tla_tpu.resilience import faults
    ck = str(tmp_path / "ck")
    half = 2 if kind == "mesh" else 8
    faults.install("oom@level=4;chunk=5", hard=False)
    try:
        eng = make_engine(kind, checkpoint_dir=ck, min_batch=half)
        res = eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    assert eng.config.batch == half and res.levels == LEVELS
    assert eng.metrics.counter_value("engine/degraded") == 1
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)


def test_an_error_behind_a_boundary_takes_the_owed_close_with_it(tmp_path):
    """``oom`` before the first call of the level after level 4, with
    level 4's last flush and its close owed and no snapshot to go back
    to: the dead attempt's log ends at level 3's close, and the retry
    from the roots at half the batch ends with the whole run's counts
    and a whole store."""
    from raft_tla_tpu.resilience import faults
    ev = str(tmp_path / "ev.jsonl")
    faults.install("oom@level=4;chunk=1", hard=False)
    try:
        eng = make_engine("one_chip", min_batch=8, events_out=ev)
        res = eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    assert eng.config.batch == 8
    assert (res.levels, res.distinct) == (LEVELS, DISTINCT)
    events = events_of(ev)
    names = [e["event"] for e in events]
    died = names.index("degraded")
    assert [e["level"] for e in events[:died]
            if e["event"] == "level_complete"] == [0, 1, 2, 3]
    after = [e for e in events[died:] if e["event"] == "level_complete"]
    assert [(e["level"], e["frontier_rows"]) for e in after] == list(
        enumerate(LEVELS))
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)
    # The retry owed nothing at its end; the attempt that died, one each.
    counts = bfs_mod.work_counts(eng.metrics)
    assert counts["level_closes_overlapped"] \
        + counts["level_closes_drained"] == 4 + 6


@pytest.mark.parametrize("stop, want", [
    (dict(max_diameter=3), ("diameter_budget", 3)),
    # 377 distinct states by level 4's close, 485 and 605 after the first
    # two calls of eleven that build level 5: the run stops inside the
    # level, one call behind the one that level 4's close rode behind...
    (dict(max_diameter=None, exit_conditions=(("distinct", 600),)),
     ("distinct_budget", 4)),
    # ... and here by that call itself.
    (dict(max_diameter=None, exit_conditions=(("distinct", 400),)),
     ("distinct_budget", 4)),
], ids=["max_diameter", "exit_condition", "exit_condition_first_call"])
def test_a_stop_at_or_behind_a_boundary_leaves_no_close_owed(stop, want,
                                                              started,
                                                              tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine("one_chip", events_out=ev, **stop)
    res = eng.run([init_state(DIMS)])
    assert (res.stop_reason, res.diameter) == want
    events = events_of(ev)
    end = events[-1]
    closed = [e for e in events if e["event"] == "level_complete"]
    # The last whole level's close is in the log, before the run's end.
    assert [(e["level"], e["frontier_rows"]) for e in closed] == list(
        enumerate(LEVELS[:res.diameter + 1]))
    assert end["level_closes_overlapped"] + end["level_closes_drained"] \
        == len(closed)
    at_the_boundary = "max_diameter" in stop and stop["max_diameter"]
    assert end["level_closes_drained"] == (1 if at_the_boundary else 0)
    assert end["flush_overlapped"] + end["flush_drained"] == len(started)
    assert end["flush_drained"] == 1
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)


# -- the counters -----------------------------------------------------------------

def test_run_end_counts_every_flush_once(kind, started, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(kind, events_out=ev)
    res = eng.run([init_state(DIMS)])
    end = run_end(ev)
    assert end["flush_overlapped"] + end["flush_drained"] == len(started)
    assert res.diameter == 5
    per_level = [(e["chunk_calls"], e["flush_overlapped"],
                  e["flush_drained"]) for e in events_of(ev)
                 if e["event"] == "level_complete" and e["level"] >= 1]
    closes = (end["level_closes_overlapped"], end["level_closes_drained"])
    if kind == "mesh":
        # One drain a level (and the roots'); every other call of a
        # level is overlapped.  A chip's share of a level, 4 rows a call.
        calls = [1, 1, 1, 4, 12]
        assert end["flush_drained"] == 1 + 5
        assert per_level == [(c, c - 1, 1) for c in calls]
        assert closes == (0, 6)
    else:
        # A level's last flush rides behind the next level's first call
        # and counts in that level's row; the one before ``max_diameter``
        # stops the run is drained, after its level's row was read.
        calls = [-(-n // 16) for n in LEVELS[:5]]
        assert end["flush_drained"] == 1
        assert per_level == [(calls[0], 0, 0)] + [(c, c, 0)
                                                  for c in calls[1:]]
        assert closes == (5, 1)
    assert end["flush_overlapped"] == end["chunk_calls"] \
        - end["flush_drained"] + (kind == "mesh") > 0


# -- the cost of a call -----------------------------------------------------------

def test_a_calls_cost_runs_from_its_dispatch_to_its_statistics(kind,
                                                                monkeypatch):
    """The deadline sizing divides what is left by ``_batch_ema``: a host
    half of a flush that outlasts the device is part of the call it ran
    under (dispatch + fetch seconds alone would leave it out)."""
    finish = bfs_mod._TraceFlush.finish

    def slow(self, counter):
        if counter == "flush_overlapped" and self._owed is not None:
            time.sleep(0.25)
        return finish(self, counter)

    monkeypatch.setattr(bfs_mod._TraceFlush, "finish", slow)
    # Level 3's 52 parents: 4 calls, of 16 or of four chips' 4.
    eng = make_engine(kind, max_diameter=4)
    eng.run([init_state(DIMS)])
    # ... and on one chip the last flush of levels 1, 2 and 3 besides,
    # each behind the next level's first call.
    assert eng.metrics.counter_value("engine/flush_overlapped") == (
        3 if kind == "mesh" else 6)
    assert eng._batch_ema >= 0.25


# -- trace recording off --------------------------------------------------------

def test_without_trace_recording_no_fetch_is_dispatched(kind, started,
                                                        monkeypatch):
    eng = make_engine(kind, record_trace=False)
    fetched = []
    monkeypatch.setattr(eng, "_fetch",
                        lambda *a: fetched.append(a), raising=True)
    res = eng.run([init_state(DIMS)])
    assert (res.levels, res.distinct) == (LEVELS, DISTINCT)
    assert not fetched and not started
    counts = bfs_mod.work_counts(eng.metrics)
    assert counts["flush_overlapped"] == counts["flush_drained"] == 0
    assert counts["chunk_calls"] > 5


# -- what growing costs the store -------------------------------------------------

def _records(n, seed=0):
    rng = np.random.default_rng(seed)
    fps = rng.integers(1, 2 ** 63, size=n, dtype=np.uint64)
    return fps, fps >> np.uint64(1), np.zeros(n, np.int32)


@pytest.mark.parametrize("store", ["native", "python"])
def test_a_store_says_when_it_rehashed(store):
    """The native store doubles at 70 % load with a ``calloc`` and every
    entry inserted again, inside ``add_batch``: ``stats`` counts the
    doublings and their seconds.  The dict fallback grows out of sight
    and reads zeros."""
    trace = (trace_mod.make_trace_store(1 << 10) if store == "native"
             else trace_mod.PyTraceStore())
    if store == "native" and not isinstance(trace,
                                            trace_mod.NativeTraceStore):
        pytest.skip("no compiler on this host: the dict store stands in")
    assert trace.stats()["rehashes"] == 0
    fps, parents, actions = _records(700)
    trace.add_batch(fps, parents, actions)          # 68 % of 1,024
    first = trace.stats()
    trace.add_batch(*_records(100, seed=1))         # past 70 %: one doubling
    grown = trace.stats()
    assert len(trace) == 800
    if store == "python":
        assert first == grown == {"rehashes": 0, "rehash_s": 0.0}
        return
    assert first == {"rehashes": 0, "rehash_s": 0.0}
    assert grown["rehashes"] == 1 and grown["rehash_s"] > 0.0
    # A batch is given its room BEFORE its first record goes in
    # (``reserve``): 6,000 more are ONE rehash to 2^14, not three.
    trace.add_batch(*_records(6000, seed=2))
    assert trace.stats()["rehashes"] == 2
    assert trace.stats()["rehash_s"] > grown["rehash_s"]
    assert trace.get(int(fps[0])) == (int(parents[0]), 0)


def test_run_end_carries_what_the_stores_growing_cost(kind, tmp_path,
                                                      monkeypatch):
    """A run's store started small: ``run_end`` carries the store's own
    total of what crossing the load cost it, read once at the run's end
    (nothing asks the store anything in the loop)."""
    small = functools.partial(trace_mod.make_trace_store, 1 << 10)
    if not isinstance(small(), trace_mod.NativeTraceStore):
        pytest.skip("no compiler on this host")
    monkeypatch.setattr(bfs_mod, "make_trace_store", small)
    monkeypatch.setattr(mesh_mod, "make_trace_store", small)
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(kind, events_out=ev)
    res = eng.run([init_state(DIMS)])
    assert res.distinct == DISTINCT
    end = run_end(ev)
    grown = eng.trace.stats()
    assert end["trace_rehashes"] == grown["rehashes"] == 1
    assert end["trace_rehash_s"] == round(grown["rehash_s"], 6) > 0
    # A second run makes a store of its own: its total starts again.
    eng.config.max_diameter = 2
    eng.run([init_state(DIMS)])
    assert run_end(ev)["trace_rehashes"] == 0


def test_a_resumes_refill_is_one_rehash_of_an_empty_table(kind, tmp_path,
                                                          monkeypatch):
    """``restore`` hands the snapshot's records to a new store in one
    ``add_batch``, which takes its room first: one doubling that moves
    nothing, whatever the snapshot's size (``restore_rehashes``)."""
    if not isinstance(trace_mod.make_trace_store(),
                      trace_mod.NativeTraceStore):
        pytest.skip("no compiler on this host")
    ck, ev = str(tmp_path / "states"), str(tmp_path / "ev.jsonl")
    make_engine(kind, checkpoint_dir=ck).run([init_state(DIMS)])
    snap = ckpt_mod.load(ckpt_mod.latest(ck))
    assert snap.diameter == 5 and snap.trace_fps.size == DISTINCT
    small = functools.partial(trace_mod.make_trace_store, 1 << 10)
    monkeypatch.setattr(bfs_mod, "make_trace_store", small)
    monkeypatch.setattr(mesh_mod, "make_trace_store", small)
    resumed = make_engine(kind, events_out=ev, max_diameter=6)
    resumed.run(resume=snap)
    end = run_end(ev)
    # 1,313 records into 1,024 slots: straight to 2^11, nothing moved.
    assert (end["restore_rehashes"], end["restore_rehash_s"] > 0) == (1, True)
    # Level 6 then outgrows 2^11 inside the run's own flushes.
    assert end["trace_rehashes"] > end["restore_rehashes"]
