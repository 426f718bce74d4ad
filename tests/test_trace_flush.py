"""The trace flush of the one-chip host loop (engine/bfs.py ``_TraceFlush``,
``flush_plan``): its fetch programs have shapes fixed at the engine's
build and compile in warm-up, its host half runs behind the next chunk
dispatch, and it is drained wherever something other than a plain next
call reads the store or leaves the loop.  Every run here takes one batch
a call (``sync_every=1``), so that levels hold several calls and every
call admits another number of states.
"""

import json
import time

import jax.numpy as jnp
import pytest

from raft_tla_tpu.engine import bfs as bfs_mod
from raft_tla_tpu.engine import checkpoint as ckpt_mod
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig, flush_plan
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok)
from raft_tla_tpu.models.pystate import init_state

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
# Levels 0-5 hold 1, 3, 15, 52, 162 and 486 states: from level 3 on a
# level is several calls of 16 parents.
LEVELS = [1, 3, 15, 52, 162, 486]
DISTINCT = 1313     # the states the constraint keeps out of a level among them


def make_engine(invariants=None, **kw):
    base = dict(batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, sync_every=1, max_diameter=5)
    base.update(kw)
    return BFSEngine(DIMS, invariants=invariants,
                     constraint=build_constraint(DIMS, BOUNDS),
                     config=EngineConfig(**base))


def run_end(path):
    with open(path, encoding="utf-8") as f:
        end = [json.loads(line) for line in f][-1]
    assert end["event"] == "run_end"
    return end


@pytest.fixture
def started(monkeypatch):
    """The number of records of every flush the loop starts."""
    lengths = []
    start = bfs_mod._TraceFlush.start

    def noting(self, tbuf, n):
        lengths.append(n)
        start(self, tbuf, n)

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

def test_no_flush_compiles_whatever_its_length(started, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    # Sizes of its own: nothing here is in the process's jit cache.
    eng = make_engine(batch=18, queue_capacity=18 * 233, events_out=ev)
    res = eng.run([init_state(DIMS)])
    assert res.levels == LEVELS
    assert len(set(started)) >= 10, started
    end = run_end(ev)
    assert end["chunk_calls"] == len(started)
    in_the_loop = set(end["compiles"]) - {"root_check", "run_init",
                                          "warmup", "frontier_fetch"}
    assert not in_the_loop, end["compiles"]
    assert end["compiles"]["warmup"][0] >= 2 + len(eng._fetch_lens)


# -- what reaches the store --------------------------------------------------------

@pytest.mark.parametrize("pieces", [bfs_mod.FLUSH_PIECES, (4, 16)],
                         ids=["one_piece", "several_pieces"])
def test_the_store_equals_a_synchronous_flush(pieces, monkeypatch):
    """The deferred flush against ``_record`` applied before anything
    else happens, on the same run; with pieces of 4 and 16 records a
    flush takes up to eight fetches."""
    monkeypatch.setattr(bfs_mod, "FLUSH_PIECES", pieces)
    eng = make_engine()
    res = eng.run([init_state(DIMS)])
    assert (res.levels, res.distinct) == (LEVELS, DISTINCT)
    # One record a distinct state, the root's among them.
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)

    start = bfs_mod._TraceFlush.start

    def at_once(self, tbuf, n):
        start(self, tbuf, n)
        self.finish("flush_drained")

    monkeypatch.setattr(bfs_mod._TraceFlush, "start", at_once)
    sync = make_engine()
    assert sync.run([init_state(DIMS)]).distinct == res.distinct
    assert sync.metrics.counter_value("engine/flush_overlapped") == 0
    assert records(eng.trace) == records(sync.trace)


# -- drain points -----------------------------------------------------------------

def drained_before_a_checkpoint(tmp_path):
    """Level 4 is eleven calls; its snapshot holds every record of it,
    and the resumed run ends where an uninterrupted one does."""
    ck = str(tmp_path / "ck")
    first = make_engine(max_diameter=4, checkpoint_dir=ck)
    first.run([init_state(DIMS)])
    path = ckpt_mod.latest(ck)
    assert path.endswith("level_00004.npz")
    kept = ckpt_mod.load(path)
    assert kept.levels == tuple(LEVELS[:5])
    assert kept.trace_fps.size == kept.distinct
    second = make_engine()
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


def drained_before_a_replay(tmp_path):
    """The violation lies in a call dispatched with its predecessor's
    flush still owed; the replay needs both calls' records.  (The root
    of tests/test_engine.py: a candidate one vote short of quorum.)"""
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(
        invariants={"TypeOK": build_type_ok(DIMS),
                    "NoLeader": lambda st: jnp.all(st.role != LEADER)},
        batch=2, max_diameter=None, events_out=ev)
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    with open(ev, encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    whole_levels = sum(e["chunk_calls"] for e in events
                       if e["event"] == "level_complete")
    assert events[-1]["chunk_calls"] - whole_levels >= 2
    assert len(eng.trace.export()[0]) == res.distinct
    assert_legal(eng.replay(res.violation.fingerprint), s0,
                 last=res.violation.state)
    return [eng]


def drained_at_a_duration_stop(tmp_path):
    """Whatever call the deadline falls after, nothing stays owed."""
    eng = make_engine(max_diameter=None, max_seconds=1.0)
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "duration_budget"
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)
    return [eng]


@pytest.mark.parametrize("case", [drained_before_a_checkpoint,
                                  drained_before_a_replay,
                                  drained_at_a_duration_stop],
                         ids=lambda f: f.__name__)
def test_nothing_is_owed_at_a_drain_point(case, tmp_path, started):
    engines = case(tmp_path)
    counts = [bfs_mod.work_counts(eng.metrics) for eng in engines]
    assert sum(c["flush_overlapped"] for c in counts) > 0
    # Every flush started was finished, one way or the other.
    assert sum(c["flush_overlapped"] + c["flush_drained"]
               for c in counts) == len(started)


def test_a_degraded_resume_takes_no_record_of_the_run_that_died(tmp_path):
    """``_run_degradable`` resumes from the snapshot into a new store:
    what the dead attempt still owed must not reach it."""
    from raft_tla_tpu.resilience import faults
    ck = str(tmp_path / "ck")
    faults.install("oom@level=4;chunk=5", hard=False)
    try:
        eng = make_engine(checkpoint_dir=ck, min_batch=8)
        res = eng.run([init_state(DIMS)])
    finally:
        faults.clear()
    assert eng.config.batch == 8 and res.levels == LEVELS
    assert eng.metrics.counter_value("engine/degraded") == 1
    assert len(eng.trace.export()[0]) == res.distinct
    assert_every_chain_reaches_a_root(eng.trace)


# -- the counters -----------------------------------------------------------------

def test_run_end_counts_every_flush_once(started, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(events_out=ev)
    res = eng.run([init_state(DIMS)])
    end = run_end(ev)
    assert end["flush_overlapped"] + end["flush_drained"] == len(started)
    # One drain a level; every other call of a level is overlapped.
    assert end["flush_drained"] == res.diameter == 5
    assert end["flush_overlapped"] == end["chunk_calls"] - 5 > 0
    with open(ev, encoding="utf-8") as f:
        levels = [json.loads(line) for line in f]
    per_level = [(e["chunk_calls"], e["flush_overlapped"],
                  e["flush_drained"]) for e in levels
                 if e["event"] == "level_complete" and e["level"] >= 1]
    assert per_level == [(-(-n // 16), -(-n // 16) - 1, 1)
                         for n in LEVELS[:5]]


# -- the cost of a call -----------------------------------------------------------

def test_a_calls_cost_runs_from_its_dispatch_to_its_statistics(monkeypatch):
    """The deadline sizing divides what is left by ``_batch_ema``: a host
    half of a flush that outlasts the device is part of the call it ran
    under (dispatch + fetch seconds alone would leave it out)."""
    finish = bfs_mod._TraceFlush.finish

    def slow(self, counter):
        if counter == "flush_overlapped" and self._owed is not None:
            time.sleep(0.25)
        finish(self, counter)

    monkeypatch.setattr(bfs_mod._TraceFlush, "finish", slow)
    eng = make_engine(max_diameter=4)       # level 3's 52 parents: 4 calls
    eng.run([init_state(DIMS)])
    assert eng.metrics.counter_value("engine/flush_overlapped") == 3
    assert eng._batch_ema >= 0.25
