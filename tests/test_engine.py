"""Differential tests: the device BFS engine vs the oracle BFS.

The engine (engine/bfs.py: batched expand + fingerprint dedup + sorted FPSet)
and the oracle (models/oracle.py: Python sets of PyStates) must agree on
distinct-state counts, per-level frontier sizes, and diameters — TLC's
primary observable statistics (SURVEY §4 differential oracle).  Fingerprint
collisions would show up here as count mismatches.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok, constraint_py,
                                            type_ok_py)
from raft_tla_tpu.models.pystate import init_state

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def small_config(**kw):
    base = dict(batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def engine():
    return BFSEngine(DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
                     constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(max_diameter=3))


def test_counts_match_oracle_through_level3(engine):
    res = engine.run([init_state(DIMS)])
    want = orc.bfs([init_state(DIMS)], DIMS,
                   invariants={"TypeOK": type_ok_py},
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=3)
    assert res.violation is None and want.invariant_violation is None
    assert res.distinct == want.distinct_states
    assert res.levels == want.levels
    assert res.stop_reason == "diameter_budget"
    assert res.generated == want.generated_states
    # Per-action-family stats (TLC's per-action counts) partition the
    # generated total.
    assert sum(res.action_counts.values()) == res.generated
    assert res.action_counts.get("Timeout", 0) > 0
    # TLC-style coverage (obs/coverage.py) derives from the same packed
    # stats: generated matches action_counts bit-exactly, distinct
    # partitions the distinct count minus the root, and disabled counts
    # close the guard-evaluation accounting per family size.
    cov = res.coverage
    assert {a: v["generated"] for a, v in cov.items()} == res.action_counts
    assert sum(v["generated"] for v in cov.values()) == res.generated
    assert sum(v["distinct"] for v in cov.values()) == res.distinct - 1
    sizes = dict(zip(DIMS.family_names, DIMS.family_sizes))
    expanded = {name: (v["generated"] + v["disabled"]) / sizes[name]
                for name, v in cov.items()}
    assert len(set(expanded.values())) == 1   # one shared expanded base
    assert next(iter(expanded.values())) > 0


def test_violation_found_at_min_depth_and_replays():
    inv = {"TypeOK": build_type_ok(DIMS),
           "NoLeader": lambda st: jnp.all(st.role != LEADER)}
    eng = BFSEngine(DIMS, invariants=inv,
                    constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config())
    # Seed a candidate one vote short of quorum: the minimal counterexample
    # (receive the pending grant, then BecomeLeader) is a few levels deep,
    # keeping the single-core CPU run fast while exercising the full
    # violation + trace machinery.
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))  # RVR grant r2->r1
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    assert res.violation.invariant == "NoLeader"
    assert LEADER in res.violation.state.role

    # Oracle agrees on the minimal counterexample depth.
    want = orc.bfs([s0], DIMS,
                   invariants={"NoLeader": lambda s, d: LEADER not in s.role},
                   constraint=constraint_py(BOUNDS), check_deadlock=False)
    want_depth = len(want.trace_to(want.invariant_violation[1])) - 1

    # Kernel replay: every step is a legal spec transition (oracle-checked),
    # and the trace ends in the violating state at the oracle's depth.
    steps = eng.replay(res.violation.fingerprint)
    assert len(steps) - 1 == want_depth
    assert steps[-1][1] == res.violation.state
    for (s_prev, s_next) in zip(steps, steps[1:]):
        assert s_next[1] in orc.successor_set(s_prev[1], DIMS)


def test_replay_from_real_init_through_message_actions():
    """Regression: replay must survive message-slot reordering.  Queue rows
    keep the kernel's slot arrangement while replay re-encodes canonically
    (sorted slots), so a deep trace from the true Init that passes through
    multiple in-flight messages used to diverge on slot-indexed actions;
    replay now matches children by fingerprint (engine/bfs.py replay)."""
    dims = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    bounds = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
    eng = BFSEngine(dims, invariants={
        "NoLeader": lambda st: jnp.all(st.role != LEADER)},
        constraint=build_constraint(dims, bounds),
        config=small_config(batch=128))
    res = eng.run([init_state(dims)])
    assert res.stop_reason == "violation"
    steps = eng.replay(res.violation.fingerprint)
    # The minimal election needs both RequestVote sends in flight at once,
    # so the trace necessarily crosses multi-message states.
    assert len(steps) >= 5
    assert steps[-1][1] == res.violation.state
    for (s_prev, s_next) in zip(steps, steps[1:]):
        assert s_next[1] in orc.successor_set(s_prev[1], dims)


def test_multiple_init_states(engine_cls=BFSEngine):
    """Several roots (the smoke-mode shape): counts still match."""
    dims = DIMS
    inits = [init_state(dims)]
    # a couple of hand-built variants: one server already candidate/leader
    s = init_state(dims)
    inits.append(s.replace(role=(1, 0, 0), current_term=(2, 1, 1)))
    inits.append(s.replace(role=(2, 0, 0), votes_granted=(0b11, 0, 0)))
    eng = engine_cls(dims, constraint=build_constraint(dims, BOUNDS),
                     config=small_config(max_diameter=2))
    res = eng.run(inits)
    want = orc.bfs(inits, dims, constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=2)
    assert res.distinct == want.distinct_states
    assert res.levels == want.levels


# MCraft_bounded exact level profile (frontier sizes per level), measured
# by the independent digest-based oracle sweep of 2026-07-29
# (scripts/oracle_exhaust.py; BASELINE.md §b).  The engine must reproduce
# this prefix exactly — the SURVEY §4 differential contract at real depth.
MCRAFT_BOUNDED_LEVELS = [1, 3, 18, 79, 318, 1218, 4433, 15510, 52467,
                         172129, 548904, 1703703, 5151868, 15187022]
MCRAFT_BOUNDED_DISTINCT_L7 = 37054     # cumulative distinct through L7
# (includes constraint-violating states: counted, never expanded)
MCRAFT_BOUNDED_GEN_L7 = 99489          # cumulative generated through L7


def test_levels_match_pinned_oracle_profile():
    """Engine vs the pinned full-scale oracle profile, through level 7
    (37k distinct — deep enough to cross several spills/growths of a tiny
    engine, cheap enough for the single-core CPU suite)."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(here, "configs/MCraft_bounded.cfg"))
    eng = make_engine(setup, small_config(
        batch=256, queue_capacity=1 << 13, seen_capacity=1 << 14,
        max_diameter=7, record_trace=False))
    res = eng.run(initial_states(setup))
    assert res.levels == MCRAFT_BOUNDED_LEVELS[:8]
    assert res.distinct == MCRAFT_BOUNDED_DISTINCT_L7
    assert res.generated == MCRAFT_BOUNDED_GEN_L7
    assert res.violation is None


@pytest.mark.slow   # ~2 min CPU differential; nightly/hardware tier
def test_five_server_north_star_model_matches_oracle():
    """The north-star model (configs/TPUraft.cfg: 5 servers, MaxTerm=4,
    MaxLogLen=4) against a pinned oracle prefix — extends the
    differential contract beyond the 3-server bench model.  Pinned by
    models.oracle.bfs (max_levels=7, 706,142 distinct), 2026-07-30."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config("configs/TPUraft.cfg")
    eng = make_engine(setup, small_config(
        batch=512, queue_capacity=1 << 19, seen_capacity=1 << 21,
        max_diameter=7, record_trace=False))
    res = eng.run(initial_states(setup))
    assert res.levels == [1, 5, 45, 310, 1995, 12306, 72870, 417420]
    assert res.distinct == 706142
    assert res.generated == 2265410
    assert res.violation is None


def test_duration_budget_stops():
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_seconds=0.0))
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "duration_budget"
    assert res.distinct >= 1


def test_duration_budget_promptness():
    """StopAfter must be honored to within ~a batch, not a whole
    sync_every chunk (round-2 BENCH overshot a 45 s budget by 66%).  The
    engine sizes each chunk call from its measured per-batch cost, so the
    overshoot is bounded by a few batches regardless of sync_every."""
    budget = 2.0
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_seconds=budget, sync_every=64))
    res = eng.run([init_state(DIMS)])
    if res.stop_reason == "exhausted":
        pytest.skip("machine fast enough to exhaust inside the budget")
    assert res.stop_reason == "duration_budget"
    # Slack: a few batches at the measured cost, floored for 1-core
    # timing jitter (this guards against the round-2 failure mode of
    # overshooting by a whole sync_every chunk / 66% of the budget —
    # not against scheduler noise).
    slack = max(5 * eng._batch_ema, 2.0)
    assert res.wall_seconds <= budget + slack, \
        (res.wall_seconds, budget, eng._batch_ema)


def test_disk_backed_spill_matches_ram(tmp_path):
    """spill_dir memory-maps level segments to disk (TLC's disk-backed
    state queue); a tiny device queue forces constant spills and the
    counts must match the in-RAM run bit-for-bit.  Segment files are
    unlinked as they are consumed/cleared."""
    cons = build_constraint(DIMS, BOUNDS)
    want = BFSEngine(DIMS, constraint=cons,
                     config=small_config(max_diameter=3)).run(
        [init_state(DIMS)])
    spill = tmp_path / "spill"
    eng = BFSEngine(DIMS, constraint=cons,
                    config=small_config(batch=16, queue_capacity=16,
                                        spill_dir=str(spill),
                                        max_diameter=3))
    got = eng.run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated
    assert list(spill.iterdir()) == []      # all segments consumed
    # An early (budget) stop strands queued segments in the pools; they
    # must still be cleaned up when the run ends (pool finalizer).
    eng2 = BFSEngine(DIMS, constraint=cons,
                     config=small_config(batch=16, queue_capacity=16,
                                         spill_dir=str(spill),
                                         max_diameter=4))
    eng2.run([init_state(DIMS)])
    import gc
    gc.collect()
    assert list(spill.iterdir()) == []      # no leaked segment files


def test_progress_limiting_with_tiny_compact_buffer():
    """Results are invariant under the compacted-lane budget (ops/
    compact.py): a K too small for a whole batch's fan-out must advance
    fewer parents per step, never drop states.  K floors at max(G, B), so
    a large batch with the minimum K forces P < B on every busy step."""
    base = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(max_diameter=3))
    want = base.run([init_state(DIMS)])
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(batch=64, compact_lanes=1,
                                        max_diameter=3))
    assert eng._K == 256          # floor: _pow2(max(1, G=132, B=64))
    got = eng.run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated
    assert got.diameter == want.diameter


def test_order_independence_of_exploration():
    """Metamorphic (SURVEY §5.2, the race-detector analog): the distinct
    count, per-level sizes, and diameter are invariant under (a) frontier
    permutation and (b) batch-boundary changes — guards the claim-scatter
    insert protocol and in-batch dedup against order effects."""
    s = init_state(DIMS)
    roots = [s,
             s.replace(role=(1, 0, 0), current_term=(2, 1, 1)),
             s.replace(role=(0, 1, 0), current_term=(1, 2, 1)),
             s.replace(role=(2, 0, 0), votes_granted=(0b11, 0, 0))]
    base = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(max_diameter=3))
    want = base.run(list(roots))
    for perm, batch in (([3, 1, 0, 2], 32), ([2, 0, 3, 1], 8)):
        eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                        config=small_config(batch=batch, max_diameter=3))
        got = eng.run([roots[i] for i in perm])
        assert got.distinct == want.distinct
        assert got.levels == want.levels
        assert got.generated == want.generated
        assert got.diameter == want.diameter


def test_smokeraft_cfg_end_to_end():
    """The repository's copy of upstream's smoke test, configs/Smokeraft.cfg
    (randomized init, StopAfter budgets, CHECK_DEADLOCK FALSE), runs as it
    stands through the cfg front-end and the engine: budget stop (or
    exhaustion of the random slice) with nonzero distinct states and no
    violation."""
    import os
    from raft_tla_tpu.engine.check import run_check
    cfg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "Smokeraft.cfg")
    res = run_check(cfg, engine_config=small_config(batch=128))
    assert res.violation is None
    assert res.distinct > 0
    assert res.stop_reason in ("duration_budget", "diameter_budget",
                               "exhausted")


def test_spill_to_host_matches_unspilled():
    """Frontier overflow must spill to host memory (TLC's disk queue) and
    change nothing observable: a run whose device queue is far smaller than
    the peak level size must report exactly the counts of a roomy run."""
    roomy = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(max_diameter=4))
    want = roomy.run([init_state(DIMS)])
    # Peak level through diameter 4 is >> 64 rows, so this run spills
    # (queue_capacity rounds up to one batch = 32 rows; watermark is 0).
    tiny = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(batch=32, queue_capacity=32,
                                         max_diameter=4, record_trace=False))
    got = tiny.run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated
    assert got.diameter == want.diameter


def test_ingest_spill_with_many_roots():
    """Root INGEST can overflow the device queue too (a k=3 smoke run has
    19,683 roots): the ingest-phase watermark must drain to the host pool
    without changing any count vs a roomy run."""
    from raft_tla_tpu.models.smoke import smoke_init_states
    sdims = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=24)
    roots = smoke_init_states(sdims, k=2, seed=7)   # ~512 random roots
    assert len(roots) > 64
    cons = build_constraint(
        sdims, Bounds(max_term=2, max_log_len=1, max_msg_count=1))
    want = BFSEngine(sdims, constraint=cons,
                     config=small_config(max_diameter=1)).run(list(roots))
    # queue 32 rows << root count: every ingest wave crosses the
    # watermark and drains to the host pool before exploration starts.
    got = BFSEngine(sdims, constraint=cons,
                    config=small_config(batch=32, queue_capacity=32,
                                        max_diameter=1,
                                        record_trace=False)).run(list(roots))
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated


def test_seen_set_grows_in_place():
    """The FPSet must double (rehash) as load passes the threshold instead
    of dying; counts stay exact across growths."""
    roomy = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(max_diameter=3))
    want = roomy.run([init_state(DIMS)])
    # batch 8 / sync 1 keeps per-host-check insertions well under the free
    # half of the table, so growth always fires before probes could fail.
    small = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(batch=8, sync_every=1,
                                          seen_capacity=256, max_diameter=3))
    got = small.run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    # (Capacities are floored at fpset's minimum table size, so this tiny
    # run exercises the small-capacity insert path, not growth; growth
    # evidence is asserted by test_spillpool_midscale_profile.)


def test_checkpoint_resume_across_spill(tmp_path):
    """A checkpoint written while part of the level lives in host spill
    segments must resume bit-exactly."""
    ck = str(tmp_path / "ck")
    full = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                     config=small_config(max_diameter=4, record_trace=False))
    want = full.run([init_state(DIMS)])
    first = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(batch=32, queue_capacity=32,
                                          max_diameter=3, record_trace=False,
                                          checkpoint_dir=ck))
    first.run([init_state(DIMS)])
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    path = ckpt_mod.latest(ck)
    assert path is not None
    second = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                       config=small_config(batch=32, queue_capacity=32,
                                           max_diameter=4,
                                           record_trace=False))
    got = second.run(resume=path)
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.diameter == want.diameter


def test_distinct_budget_stops_run(tmp_path):
    """A5 proper (SURVEY §5.5): a cfg-defined constraint consulting
    TLCGet("distinct") stops the run without any code changes — the general
    metrics-control coupling, not a special-cased budget."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from tests.test_cfg import _write_exit_model
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(_write_exit_model(tmp_path, "distinct", 500))
    eng = make_engine(setup, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 16,
        record_trace=False, sync_every=4))
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "distinct_budget"
    assert res.distinct > 500
    # Promptness: one sync_every chunk (4 batches x G lanes) past the
    # threshold at most — not a whole level of the unbounded model.
    assert res.distinct < 500 + 4 * 64 * setup.dims.n_instances
    assert res.violation is None


def test_generated_budget_stops_run(tmp_path):
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from tests.test_cfg import _write_exit_model
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(_write_exit_model(tmp_path, "generated", 2000))
    eng = make_engine(setup, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 16,
        record_trace=False, sync_every=4))
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "generated_budget"
    assert res.generated > 2000


@pytest.mark.slow   # ~3 min CPU spill stress; nightly/hardware tier
def test_spillpool_midscale_profile(tmp_path):
    """Mid-scale spill stress: ~795k distinct states
    through a deliberately small queue so the level-11 frontier (548,904
    rows) flows through MANY disk-backed segments — the largest CPU-
    affordable test of SpillPool segment bookkeeping before a north-star
    TPU run.  The level profile must match the pinned full-scale oracle
    exactly, and every segment file must be consumed."""
    import os
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(repo, "configs/MCraft_bounded.cfg"))
    spill = tmp_path / "spill"
    eng = make_engine(setup, EngineConfig(
        batch=512, queue_capacity=1 << 15, seen_capacity=1 << 21,
        record_trace=False, check_deadlock=False, sync_every=16,
        spill_dir=str(spill), max_diameter=10))
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "diameter_budget"
    assert res.levels == MCRAFT_BOUNDED_LEVELS[:11]
    # Pinned by the independent oracle runner (oracle_exhaust.jsonl level
    # 10): distinct counts constraint-violating states too (counted, never
    # expanded), so it exceeds sum(levels).
    assert res.distinct == 1769309
    assert res.generated == 5053467
    # 1.77M keys through a 2M-capacity table: growth must fire, and each
    # doubling is recorded as (capacity-after, off-clock stall seconds)
    # with strictly increasing capacities.
    caps = [c for c, _s in res.growth_stalls]
    assert caps and caps == sorted(caps) and len(set(caps)) == len(caps)
    import gc
    gc.collect()
    assert list(spill.iterdir()) == []


def test_queue_budget_counts_full_unexplored_queue(tmp_path):
    """TLCGet("queue") must measure the FULL unexplored queue (current
    level's remainder + pending host segments + next-level rows + spills),
    not just the next-frontier device rows — a memory bound that missed
    the current level would let the queue blow 5x past the budget."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from tests.test_cfg import _write_exit_model
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(_write_exit_model(tmp_path, "queue", 3000))
    eng = make_engine(setup, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 16,
        record_trace=False, sync_every=4))
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "queue_budget"
    # The unbounded 3-server model's levels grow ~4x per level; the stop
    # must land well before a whole extra level (re-derive the bound from
    # the run: last completed frontier + enqueued when stopped).
    assert res.levels[-1] <= 3000 * 5


def test_duplicate_duration_budgets_min_wins(tmp_path):
    """TLC exits when ANY TLCSet("exit", ...) trips: two CONSTRAINTs
    bounding the same counter must keep the SMALLEST threshold."""
    (tmp_path / "two.tla").write_text(
        "---- MODULE two ----\nEXTENDS raft\n"
        'StopShort ==\n    TLCSet("exit", TLCGet("duration") > 5)\n'
        'StopLong ==\n    TLCSet("exit", TLCGet("duration") > 600)\n'
        'DiaA ==\n    TLCSet("exit", TLCGet("diameter") > 40)\n'
        'DiaB ==\n    TLCSet("exit", TLCGet("diameter") > 7)\n====\n')
    (tmp_path / "two.cfg").write_text(
        "CONSTANTS\n    Server = {r1}\n    Value = {v1}\n"
        "SPECIFICATION Spec\nCONSTRAINT StopShort\nCONSTRAINT StopLong\n"
        "CONSTRAINT DiaA\nCONSTRAINT DiaB\n")
    from raft_tla_tpu.utils.cfg import load_config
    s = load_config(str(tmp_path / "two.cfg"))
    assert s.max_seconds == 5.0
    assert s.max_diameter == 7


def test_progress_lines_emitted(capfd):
    """progress_interval_seconds produces TLC-style stderr progress lines
    with live counters; the default (0) stays silent."""
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=3,
                                        progress_interval_seconds=1e-6))
    eng.run([init_state(DIMS)])
    err = capfd.readouterr().err
    assert "progress:" in err and "queue" in err and "distinct" in err

    quiet = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(max_diameter=3))
    quiet.run([init_state(DIMS)])
    assert "progress:" not in capfd.readouterr().err


def test_path_to_state_recovers_minimal_counterexample():
    """path_to_state extracts a minimal action path to any concrete state
    — the counterexample route for trace-less (e.g. multi-host) runs,
    which report the violating state but record no trace."""
    from raft_tla_tpu.engine.check import path_to_state
    want = orc.bfs([init_state(DIMS)], DIMS,
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=4)
    # Deepest layer: a state whose minimal depth is exactly 4.
    target = next(s for s in want.parent
                  if len(want.trace_to(s)) - 1 == 4)
    steps = path_to_state(
        DIMS, target, constraint=build_constraint(DIMS, BOUNDS),
        config=small_config(record_trace=True))
    assert steps[-1][1] == target
    assert len(steps) - 1 == 4          # minimal depth (BFS order)
    for (s_prev, s_next) in zip(steps, steps[1:]):
        assert s_next[1] in orc.successor_set(s_prev[1], DIMS)


def test_run_emits_level_complete_events(tmp_path):
    """Telemetry contract (obs/): any events_out run logs run_start, one
    level_complete per BFS level with live counters and a per-phase
    wall-time breakdown, and run_end; the result object carries the same
    phase totals.  (Schema details in tests/test_obs.py.)"""
    from raft_tla_tpu.obs import validate_run_events
    ev = str(tmp_path / "events.jsonl")
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=3, events_out=ev))
    res = eng.run([init_state(DIMS)])
    events = validate_run_events(ev)
    levels = [e for e in events if e["event"] == "level_complete"]
    assert [e["frontier_rows"] for e in levels] == res.levels
    assert levels[-1]["distinct"] == res.distinct
    assert levels[-1]["phase_seconds"]
    assert res.phases.get("chunk", 0) > 0


def test_path_to_state_edge_cases():
    """Robustness of the extractor contract: a trace-less caller config
    must not break replay, a root target yields the trivial path, and
    deadlock states on shallower levels must not abort the search."""
    from raft_tla_tpu.engine.check import path_to_state
    # Root target: trivial path, no BFS.
    assert path_to_state(DIMS, init_state(DIMS)) == [(-1, init_state(DIMS))]
    # A config with record_trace=False and deadlock checking on (the
    # multi-host run shape) is overridden internally.
    want = orc.bfs([init_state(DIMS)], DIMS,
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=2)
    target = next(s for s in want.parent
                  if len(want.trace_to(s)) - 1 == 2)
    steps = path_to_state(
        DIMS, target, constraint=build_constraint(DIMS, BOUNDS),
        config=small_config(record_trace=False, check_deadlock=True))
    assert steps[-1][1] == target and len(steps) - 1 == 2


@pytest.mark.parametrize("dead", ["v3", "v4"])
def test_engine_config_rejects_a_deleted_pipeline(dead):
    """``EngineConfig(pipeline="v4")`` fails where it is written, with
    the valid values named, and so does the resolver the swarm and the
    simulator hand a bare name to; ``auto``/``v1``/``v2`` build."""
    from raft_tla_tpu.engine.bfs import _resolve_pipeline
    with pytest.raises(ValueError, match=f"auto/v1/v2.*{dead}"):
        EngineConfig(pipeline=dead)
    with pytest.raises(ValueError, match=f"auto/v1/v2.*{dead}"):
        _resolve_pipeline(dead, DIMS)
    for ok, is_v2 in (("auto", True), ("v1", False), ("v2", True)):
        assert (_resolve_pipeline(EngineConfig(pipeline=ok).pipeline, DIMS)
                is not None) == is_v2


# -- one chunk call against the oracle --------------------------------------
#
# The chunk program's eight stages, held to a reference at once: ONE call
# of ``eng._chunk`` on the oracle's level-3 frontier (the seen-set holding
# levels 0-3) must enqueue exactly the oracle's new constraint-passing
# successors, and record exactly one (child, parent, action) link per new
# successor, each a transition the oracle makes.  ``tests/test_mesh.py``
# runs the same comparison over four chips.

class Level3:
    """The oracle's side: ``cfg``'s level-3 frontier in BFS order, every
    state through level 3, and each NEW successor of the frontier with
    the first frontier state that generates it."""

    def __init__(self, cfg):
        import os
        from raft_tla_tpu.utils.cfg import load_config
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        self.setup = load_config(os.path.join(repo, "configs", cfg))
        self.dims = dims = self.setup.dims
        cons = constraint_py(self.setup.bounds)
        res = orc.bfs([init_state(dims)], dims, constraint=cons,
                      check_deadlock=False, max_levels=3)
        depth = {}
        for t, (parent, _act) in res.parent.items():
            depth[t] = 0 if parent is None else depth[parent] + 1
        self.frontier = [t for t, d in depth.items()
                         if d == 3 and cons(t, dims)]
        self.seen = list(res.parent)
        self.first_parent = {}
        for s in self.frontier:
            for _act, t in orc.successors(s, dims):
                if t not in res.parent:
                    self.first_parent.setdefault(t, s)
        self.enqueued = [t for t in self.first_parent if cons(t, dims)]

    def rows(self):
        from raft_tla_tpu.models.schema import (encode_state,
                                                flatten_state)
        return np.stack([np.asarray(flatten_state(
            encode_state(s, self.dims), self.dims)) for s in self.frontier])

    def fingerprints(self, eng, states):
        from raft_tla_tpu.models.schema import encode_state, stack_states
        hi, lo = eng._fp_batch(stack_states(
            [encode_state(s, self.dims) for s in states]))
        return [(int(h) << 32) | int(l)
                for h, l in zip(np.asarray(hi), np.asarray(lo))]

    def seen_keys(self, eng):
        fps = self.fingerprints(eng, self.seen)
        return (np.array([f >> 32 for f in fps], np.uint32),
                np.array([f & 0xFFFFFFFF for f in fps], np.uint32))

    def check_rows(self, rows):
        """``rows``: the packed rows the call enqueued, all chips'."""
        import collections
        from raft_tla_tpu.models.schema import (decode_state,
                                                unflatten_state)
        got = [decode_state(unflatten_state(r, self.dims), self.dims)
               for r in rows]
        assert collections.Counter(got) \
            == collections.Counter(self.enqueued)
        assert len(got) == len(set(got)) > 0

    def check_records(self, eng, cols, first_parent_wins):
        """``cols``: the five trace columns' written entries.  One link
        per new successor (constraint-failing ones too: TLC counts them
        distinct); every link a transition the oracle makes from a
        frontier state, under the action the link names."""
        dims = self.dims
        child_of = dict(zip(self.fingerprints(eng, list(self.first_parent)),
                            self.first_parent))
        parent_of = dict(zip(self.fingerprints(eng, self.frontier),
                             self.frontier))
        fp = [[(int(h) << 32) | int(l) for h, l in zip(hi, lo)]
              for hi, lo in (cols[0:2], cols[2:4])]
        assert sorted(fp[0]) == sorted(child_of) and fp[0]
        for c, p, g in zip(fp[0], fp[1], (int(a) for a in cols[4])):
            child, parent = child_of[c], parent_of[p]
            if first_parent_wins:   # lanes are parent-major, dedup stable
                assert parent is self.first_parent[child]
            fam, params = dims.instance_info(g)
            if "slot" in params:
                params = (sorted(parent.messages)[params["slot"]][0],)
            else:
                params = tuple(params.values())
            assert ((fam, params), child) in orc.successors(parent, dims), \
                dims.describe_instance(g)


@functools.lru_cache(maxsize=None)
def one_chunk_call(cfg):
    """(oracle side, engine, enqueued rows, trace columns) of one
    ``BFSEngine`` chunk call on ``cfg``'s level-3 frontier."""
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.ops import fpset
    want = Level3(cfg)
    eng = make_engine(want.setup, EngineConfig(
        batch=32, queue_capacity=1 << 12, seen_capacity=1 << 14))
    (qav, _, _, _, _, _seen, tbuf_av, _, _) = eng.chunk_avals()
    qcur = np.zeros(qav.shape, np.uint8)
    qcur[:len(want.frontier)] = want.rows()
    out = eng._chunk(
        jnp.asarray(qcur), jnp.int32(len(want.frontier)), jnp.int32(0),
        jnp.zeros(qav.shape, jnp.uint8), jnp.int32(0),
        fpset.from_host_keys(*want.seen_keys(eng), eng._seen_cap)[0],
        tuple(jnp.zeros(a.shape, a.dtype) for a in tbuf_av),
        jnp.int32(0), jnp.int32(eng._CH))
    qnext, _seen, tbuf, stats = out[:4]
    _offset, _steps, next_count, _size, tcount = (
        int(x) for x in np.asarray(stats)[:5])
    assert int(np.asarray(stats)[12]) == len(want.frontier)    # expanded
    return (want, eng, np.asarray(qnext[:next_count]),
            [np.asarray(c[:tcount]) for c in tbuf])


CHUNK_CFGS = ["MCraft_bounded.cfg", "MCraft_noleader.cfg", "TPUraft.cfg"]


@pytest.mark.parametrize("cfg", CHUNK_CFGS)
def test_one_chunk_call_enqueues_the_oracles_new_successors(cfg):
    want, _eng, rows, _cols = one_chunk_call(cfg)
    want.check_rows(rows)


@pytest.mark.parametrize("cfg", CHUNK_CFGS)
def test_one_chunk_call_records_the_oracles_transitions(cfg):
    want, eng, _rows, cols = one_chunk_call(cfg)
    want.check_records(eng, cols, first_parent_wins=True)


@pytest.mark.parametrize("cfg, pins, through, violation_depth", [
    ("configs/TPUraft.cfg", "artifacts/tpuraft_L9_oracle.jsonl", 5, None),
    ("configs/MCraft_noleader.cfg",
     "benchmark/pinned/mcraft3-noleader.jsonl", 8, 9),
], ids=["raft5", "noleader"])
def test_every_level_matches_the_pinned_profile(cfg, pins, through,
                                                violation_depth, tmp_path):
    """Frontier, cumulative distinct and cumulative generated of EVERY
    level against the oracle's pinned profile (the benchmark's ``correct``
    compares the same three a level): the 5-server model through level 5
    (17,852 distinct), and the verdict cell's whole check under its cfg's
    own small pools — levels 0-8 (37,452 distinct), then the violation in
    level 9."""
    import json
    import os
    from raft_tla_tpu.engine.check import (engine_config_from_backend,
                                           initial_states, make_engine)
    from raft_tla_tpu.obs import validate_run_events
    from raft_tla_tpu.utils.cfg import load_config
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, pins), encoding="utf-8") as f:
        want = [json.loads(line) for line in f][:through + 1]
    assert [w["level"] for w in want] == list(range(through + 1))
    setup = load_config(os.path.join(here, cfg))
    ev = str(tmp_path / "events.jsonl")
    if violation_depth is None:
        config = small_config(batch=256, queue_capacity=1 << 14,
                              seen_capacity=1 << 16, max_diameter=through,
                              record_trace=False, events_out=ev)
    else:
        config = dataclasses.replace(engine_config_from_backend(setup),
                                     events_out=ev)
    eng = make_engine(setup, config)
    res = eng.run(initial_states(setup))
    got = [e for e in validate_run_events(ev)
           if e["event"] == "level_complete"][:through + 1]
    assert [(e["frontier_rows"], e["distinct"], e["generated"])
            for e in got] == [(w["frontier"], w["distinct"], w["generated"])
                              for w in want]
    if violation_depth is None:
        assert res.violation is None and res.distinct == want[-1]["distinct"]
    else:
        assert res.stop_reason == "violation"
        assert res.violation.invariant == "NoLeaderElected"
        path = eng.replay(res.violation.fingerprint)
        assert len(path) == violation_depth + 1
        assert LEADER in path[-1][1].role


# -- what a warm engine keeps from one run() to the next -------------------

@pytest.fixture(scope="module")
def warm_canary(tmp_path_factory):
    """ONE engine of the canary cfg at its own sizes (a 65,536-slot table
    that doubles twice a check), run seven times: from the root with
    snapshots on, from the root again, from the first run's level-3
    snapshot, from the root once more, from the root to level 2 only,
    from the root twice more, and after ``_rebuild_at_batch`` only looked
    at.  Per run: result, replayed steps, events, and the registry's
    ``engine/seen_capacity_kept`` afterwards."""
    import os
    from raft_tla_tpu.engine.check import (engine_config_from_backend,
                                           initial_states, make_engine)
    from raft_tla_tpu.obs import validate_run_events
    from raft_tla_tpu.utils.cfg import load_config
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = tmp_path_factory.mktemp("warm_canary")
    setup = load_config(os.path.join(here, "configs/MCraft_noleader.cfg"))
    eng = make_engine(setup, engine_config_from_backend(setup))
    roots = initial_states(setup)
    out = {"setup": setup, "engine": eng, "runs": {},
           "avals_before": eng.chunk_avals()}

    def run(name, **kw):
        eng.config.events_out = str(d / f"{name}.jsonl")
        res = eng.run(**kw)
        out["runs"][name] = {
            "res": res, "steps": (eng.replay(res.violation.fingerprint)
                                  if res.violation else None),
            "events": validate_run_events(eng.config.events_out),
            "kept": eng.metrics.counter_value("engine/seen_capacity_kept")}

    eng.config.checkpoint_dir = str(d / "ck")
    run("first", init_states=roots)
    eng.config.checkpoint_dir = None
    run("second", init_states=roots)
    run("resumed", resume=str(d / "ck" / "level_00003.npz"))
    run("fourth", init_states=roots)
    eng.config.max_diameter = 2
    run("shallow", init_states=roots)
    eng.config.max_diameter = None
    run("after_shallow", init_states=roots)
    run("last", init_states=roots)
    out["avals_after"] = eng.chunk_avals()
    out["kept_before_rebuild"] = eng._seen_cap_kept
    eng._rebuild_at_batch(128)
    out["start_after_rebuild"] = eng._start_capacity(None)
    return out


def _events(run, kind):
    return [e for e in run["events"] if e["event"] == kind]


def test_a_first_run_grows_as_before(warm_canary):
    first = warm_canary["runs"]["first"]
    assert [c for c, _s in first["res"].growth_stalls] == [1 << 17, 1 << 18]
    assert [e["capacity"] for e in _events(first, "fpset_resize")] == [
        1 << 17, 1 << 18]
    (start,) = _events(first, "run_start")
    assert (start["seen_capacity"], start["seen_capacity_configured"]) == (
        1 << 16, 1 << 16)
    assert _events(first, "run_end")[0]["growth_stalls"] == 2
    assert first["kept"] == 0
    seen = first["res"].report["seen_set"]
    assert (seen["start_capacity"], seen["configured_capacity"],
            seen["capacity"]) == (1 << 16, 1 << 16, 1 << 18)


@pytest.mark.parametrize("name, kept", [("second", 1), ("fourth", 2)])
def test_a_later_run_starts_where_the_engine_grew_to(warm_canary, name,
                                                     kept):
    """No growth, no ``fpset_resize``: the table starts at the capacity
    the first run ended at, ``run_start`` says so, and the registry counts
    the run once (the resumed run between them not at all)."""
    later = warm_canary["runs"][name]
    assert later["res"].growth_stalls == []
    assert _events(later, "fpset_resize") == []
    (start,) = _events(later, "run_start")
    assert (start["seen_capacity"], start["seen_capacity_configured"]) == (
        1 << 18, 1 << 16)
    (end,) = _events(later, "run_end")
    assert end["growth_stalls"] == 0 and "grow" not in end["phase_seconds"]
    assert later["kept"] == kept
    from raft_tla_tpu.obs import report as report_mod
    assert "started at 262,144 kept from an earlier run (configured " \
        "65,536)" in report_mod.render_report(later["res"].report)


@pytest.mark.parametrize("what", ["levels", "distinct", "generated",
                                  "action_counts", "violation", "trace"])
def test_a_later_run_finds_what_the_first_found(warm_canary, what):
    """A larger empty table changes where keys land, not which states are
    new: the same counts a level, the same violation at the same depth,
    and a trace that replays to the same states."""
    first, later = (warm_canary["runs"][n] for n in ("first", "second"))
    if what == "violation":
        got, want = (
            (r["res"].stop_reason, r["res"].violation.invariant,
             len(r["steps"]) - 1, r["res"].diameter)
            for r in (later, first))
        assert want == ("violation", "NoLeaderElected", 9, 8)
    elif what == "trace":
        got, want = later["steps"], first["steps"]
        assert got[0][0] == -1 and LEADER in got[-1][1].role
        dims = warm_canary["engine"].dims
        for (_a, prev), (_b, nxt) in zip(got, got[1:]):
            assert nxt in orc.successor_set(prev, dims)
    else:
        got, want = (getattr(r["res"], what) for r in (later, first))
    assert got == want


def test_the_kept_capacity_follows_the_traffic_down_as_well(warm_canary):
    """A check to level 2 on the warm engine starts at the large table
    and ends needing the configured one; the full check after it starts
    there and grows twice, as a first run does, and the one after that
    starts at what it grew to again.  Every program was loaded by the
    first run: no later one compiles."""
    shallow, after, last = (warm_canary["runs"][n] for n in (
        "shallow", "after_shallow", "last"))
    starts = [_events(r, "run_start")[0]["seen_capacity"]
              for r in (shallow, after, last)]
    assert starts == [1 << 18, 1 << 16, 1 << 18]
    assert shallow["res"].stop_reason == "diameter_budget"
    assert shallow["res"].growth_stalls == []
    assert shallow["res"].levels == after["res"].levels[:3]
    assert [c for c, _s in after["res"].growth_stalls] == [1 << 17, 1 << 18]
    assert last["res"].growth_stalls == []
    first = warm_canary["runs"]["first"]["res"]
    for r in (after, last):
        assert (r["res"].levels, r["res"].distinct, r["res"].generated) == (
            first.levels, first.distinct, first.generated)
        assert len(r["steps"]) == 10
    for r in (shallow, after, last):
        assert _events(r, "run_end")[0]["compiles"] == {}
    assert [r["kept"] for r in (shallow, after, last)] == [3, 3, 4]


def test_a_resumed_run_does_not_inherit_the_kept_capacity(warm_canary):
    """It sizes its table from the configured capacity and its
    snapshot's keys, as before, and so grows again; its ``run_start``
    names no capacity, the keys not being loaded yet."""
    resumed = warm_canary["runs"]["resumed"]
    (start,) = _events(resumed, "run_start")
    assert start["resume"] is True
    assert "seen_capacity" not in start
    assert "seen_capacity_configured" not in start
    assert "start_capacity" not in resumed["res"].report["seen_set"]
    assert [c for c, _s in resumed["res"].growth_stalls] == [1 << 17,
                                                            1 << 18]
    assert resumed["kept"] == warm_canary["runs"]["second"]["kept"]
    assert resumed["res"].levels == warm_canary["runs"]["first"]["res"].levels
    assert len(resumed["steps"]) == 10


def test_the_build_keeps_describing_its_configured_size(warm_canary):
    """``chunk_avals`` (what the compile for a described chip and the
    launch model read) after grown runs, and a degrade rebuild, which
    forgets what the engine grew to."""
    before, after = warm_canary["avals_before"], warm_canary["avals_after"]
    assert before == after and before[5].hi.shape == (1 << 16,)
    assert warm_canary["kept_before_rebuild"] == 1 << 18
    assert warm_canary["start_after_rebuild"] == {
        "seen_capacity": 1 << 16, "seen_capacity_configured": 1 << 16}


def test_a_fresh_engine_of_the_same_setup_starts_at_the_configured_size(
        warm_canary, tmp_path):
    from raft_tla_tpu.engine.check import (engine_config_from_backend,
                                           initial_states, make_engine)
    from raft_tla_tpu.obs import validate_run_events
    setup = warm_canary["setup"]
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(setup, dataclasses.replace(
        engine_config_from_backend(setup), max_diameter=2, events_out=ev))
    eng.run(initial_states(setup))
    start = validate_run_events(ev)[0]
    assert start["event"] == "run_start"
    assert (start["seen_capacity"], start["seen_capacity_configured"]) == (
        1 << 16, 1 << 16)
    assert eng.metrics.counter_value("engine/seen_capacity_kept") == 0


@pytest.fixture(scope="module")
def spilled_roots(tmp_path_factory):
    """512 random roots through a queue whose watermark every ingest call
    passes, so each is followed by a spill and a reset of the count, and
    a table that doubles twice in level 1: one engine run twice, at sizes
    no other test uses, so that nothing of it is in the jit cache.
    (engine, events of the first run, events of the second)"""
    from raft_tla_tpu.models.smoke import smoke_init_states
    from raft_tla_tpu.obs import validate_run_events
    sdims = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=24)
    roots = smoke_init_states(sdims, k=2, seed=7)
    d = tmp_path_factory.mktemp("spilled_roots")
    eng = BFSEngine(sdims, config=small_config(
        batch=40, queue_capacity=40, seen_capacity=256, max_diameter=1,
        record_trace=False), constraint=build_constraint(
            sdims, Bounds(max_term=4, max_log_len=4, max_msg_count=24)))
    runs = []
    for name in ("fresh", "warm"):
        eng.config.events_out = str(d / f"{name}.jsonl")
        eng.run(list(roots))
        runs.append(validate_run_events(eng.config.events_out))
    return (eng, *runs)


@pytest.mark.parametrize("which", ["fresh", "warm"])
def test_ingest_compiles_off_the_clock_when_the_roots_spill(spilled_roots,
                                                            which):
    """Ingest takes its count in ONE placement, after a spill's reset
    too: the fresh engine compiles it in the warm-up and once a growth
    (which loads it for the table it makes), never in the ``ingest`` span
    that the StopAfter clock covers; the warm engine, whose roots go
    through the kept table, compiles nothing at all."""
    eng, fresh, warm = spilled_roots
    events = fresh if which == "fresh" else warm
    start, end = events[0], events[-1]
    spills = [e for e in events if e["event"] == "spill"]
    assert len(spills) > 8 and {e["where"] for e in spills} >= {"ingest"}
    assert end["levels"] == [512, 15616]
    by_name = {p["name"]: p for p in end["jit"].get("programs", [])}
    if which == "fresh":
        assert start["seen_capacity"] == start["seen_capacity_configured"]
        assert end["growth_stalls"] == 2
        assert "ingest" not in end["compiles"], end["compiles"]
        assert by_name["ingest"]["span"] == "grow"
        assert set(end["compiles"]) <= {"run_init", "warmup", "spill",
                                        "grow"}
    else:
        assert start["seen_capacity"] == (
            4 * start["seen_capacity_configured"])
        assert end["growth_stalls"] == 0
        assert end["compiles"] == {} and by_name == {}
