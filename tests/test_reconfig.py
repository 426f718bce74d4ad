"""Joint-consensus reconfiguration (models/reconfig.py) tests.

Three tiers: semantic unit tests of the new actions and the joint-quorum
rule on hand-built states; differential tests (JAX kernels vs the Python
oracle, both extended through the RaftDims variant hooks); and an
end-to-end engine run on configs/reconfig3.cfg whose distinct-state count
must match the oracle BFS exactly.
"""

import jax
import pytest

from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.actions import build_expand
from raft_tla_tpu.models.dims import CANDIDATE, LEADER
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.models.reconfig import (A_FINALIZE, A_INITRECONFIG,
                                          CFG_BASE, ReconfigDims,
                                          config_of_py, final_value,
                                          joint_value)
from raft_tla_tpu.models.schema import StateBatch, decode_state, encode_state

DIMS = ReconfigDims(n_servers=3, n_values=1, max_log=5, n_msg_slots=16,
                    targets=(3, 7))
FULL = 7


@pytest.fixture(scope="module")
def expand():
    return jax.jit(build_expand(DIMS))


def kernel_successors(expand, s):
    st = encode_state(s, DIMS)
    cands, enabled, overflow = jax.device_get(expand(st))
    assert not overflow.any(), "fixed-width overflow on test state"
    out = []
    for g in range(DIMS.n_instances):
        if enabled[g]:
            row = jax.tree.map(lambda a: a[g], cands)
            out.append(decode_state(StateBatch(*row), DIMS))
    return out


def assert_matches_oracle(expand, s):
    got = kernel_successors(expand, s)
    want = orc.successors(s, DIMS)
    assert len(got) == len(want), (
        f"enabled-instance count {len(got)} != oracle {len(want)}\n{s}")
    assert set(got) == {t for _a, t in want}, f"successor sets differ for\n{s}"


def leader_state(log=(), commit=0, votes=0b111):
    """A term-2 leader r0 with the given log, others followers."""
    s = init_state(DIMS)
    return s.replace(
        role=(LEADER, 0, 0),
        current_term=(2, 1, 1),
        votes_granted=(votes, 0, 0),
        log=(tuple(log), (), ()),
        commit_index=(commit, 0, 0),
        next_index=((len(log) + 1,) * 3, (1,) * 3, (1,) * 3))


# ---------------------------------------------------------------------------
# config_of / encoding

def test_config_of_default_is_full_membership():
    assert config_of_py((), 3) == (0, FULL, 0)
    assert config_of_py(((2, 1),), 3) == (0, FULL, 0)   # client entry only


def test_config_of_latest_entry_wins():
    log = ((2, joint_value(7, 3)), (2, 1), (2, final_value(3)))
    assert config_of_py(log, 3) == (0, 3, 3)
    assert config_of_py(log[:2], 3) == (7, 3, 1)        # joint is latest


def test_value_ok_accepts_config_entries():
    assert DIMS.value_ok_py(1)
    assert not DIMS.value_ok_py(2)              # only one client value
    assert DIMS.value_ok_py(joint_value(7, 3))
    assert DIMS.value_ok_py(final_value(3))
    assert not DIMS.value_ok_py(CFG_BASE)       # new_mask must be nonempty


# The kernel's scan reads row i and the latest entry's position by compare,
# select and sum, no ``table[traced]``: held to ``config_of_py`` on logs of
# every shape, with what a truncation leaves past the length still in the
# tensor.
SCAN_LOGS = {
    "empty": (), "client": ((2, 1),),
    "joint": ((2, joint_value(7, 3)),),
    "joint_then_client": ((2, joint_value(7, 3)), (2, 1)),
    "final_after_joint": ((2, joint_value(7, 3)), (2, 1),
                          (2, final_value(3))),
    "joint_after_final": ((2, final_value(3)), (2, joint_value(3, 7))),
    "full_of_config": ((1, final_value(5)), (2, joint_value(5, 6)),
                       (2, final_value(6)), (3, joint_value(6, 7)),
                       (3, final_value(7))),
}


@pytest.mark.parametrize("server", range(3))
@pytest.mark.parametrize("name", sorted(SCAN_LOGS))
def test_config_scan_is_config_of_on_every_prefix(name, server):
    from raft_tla_tpu.models.reconfig import _build_config_scan
    scan = jax.jit(_build_config_scan(DIMS))
    log = SCAN_LOGS[name]
    s = init_state(DIMS)
    others = ((2, joint_value(1, 2)),)      # another server's: not read
    st = encode_state(s.replace(log=tuple(
        log if j == server else others for j in range(3))), DIMS)
    for keep in range(len(log) + 1):
        # entries keep.. stay in the tensor, as no engine state has them
        # (a truncation zeroes its cell) and as the scan must not care.
        lens = st.log_len.copy()
        lens[server] = keep
        cut = st._replace(log_len=lens)
        got = tuple(int(x) for x in scan(cut, server))
        assert got == config_of_py(log[:keep], 3), (name, keep)


def test_both_families_declare_a_log_append_of_the_guards_value():
    """``build_extra_v2``: two ``LogAppend`` declarations whose values are
    the entries the oracle appends."""
    from raft_tla_tpu.models.dims import LogAppend
    initiate, finalize = DIMS.build_extra_v2(None)
    assert isinstance(initiate, LogAppend) and isinstance(finalize, LogAppend)
    st = encode_state(leader_state(log=((2, final_value(3)),)), DIMS)
    assert int(initiate.value_fn(st, 0, 7)) == joint_value(3, 7)
    st = encode_state(leader_state(log=((2, joint_value(7, 3)),), commit=1),
                      DIMS)
    assert int(finalize.value_fn(st, 0)) == final_value(3)
    (after,) = [t for _a, t in DIMS.extra_successors_py(
        leader_state(log=((2, final_value(3)),)))]
    assert after.log[0][-1] == (2, joint_value(3, 7))


# ---------------------------------------------------------------------------
# action semantics (oracle side)

def test_initiate_requires_leader_with_final_config():
    s = leader_state()
    succ = dict(DIMS.extra_successors_py(s))
    # r0 may initiate a move to {r1,r2} (mask 3) but not to the current
    # config (mask 7 == default full membership).
    keys = list(succ)
    assert (A_INITRECONFIG, (0, 3)) in keys
    assert (A_INITRECONFIG, (0, 7)) not in keys
    assert not any(k[0] == A_FINALIZE for k in keys)
    t = succ[(A_INITRECONFIG, (0, 3))]
    assert t.log[0][-1] == (2, joint_value(7, 3))


def test_no_overlapping_reconfig():
    """A leader whose latest config is joint cannot initiate another."""
    s = leader_state(log=((2, joint_value(7, 3)),))
    keys = [k for k, _t in DIMS.extra_successors_py(s)]
    assert not any(k[0] == A_INITRECONFIG for k in keys)


def test_finalize_only_after_joint_committed():
    joint_log = ((2, joint_value(7, 3)),)
    uncommitted = leader_state(log=joint_log, commit=0)
    assert not any(k[0] == A_FINALIZE
                   for k, _t in DIMS.extra_successors_py(uncommitted))
    committed = leader_state(log=joint_log, commit=1)
    succ = dict(DIMS.extra_successors_py(committed))
    t = succ[(A_FINALIZE, (0,))]
    assert t.log[0][-1] == (2, final_value(3))


def test_joint_quorum_needs_both_majorities():
    """Under C_old,new = ({r1,r2,r3}, {r1,r2}), {r1,r3} is a majority of
    C_old but not of C_new — not a quorum; {r1,r2} is a majority of both."""
    s = leader_state(log=((2, joint_value(7, 3)),))
    assert not DIMS.quorum_py(s, 0, 0b101)
    assert DIMS.quorum_py(s, 0, 0b011)
    # Under the final config {r1,r2}, r1+r2 remains a quorum and r1+r3
    # is not ({r3} contributes nothing to C_new).
    s2 = leader_state(log=((2, final_value(3)),))
    assert DIMS.quorum_py(s2, 0, 0b011)
    assert not DIMS.quorum_py(s2, 0, 0b101)


def test_election_under_joint_config():
    """A candidate with votes {r1,r3} wins under the full config but NOT
    when its log holds the joint entry C_{r1r2r3},{r1,r2}."""
    base = init_state(DIMS)
    cand = base.replace(role=(CANDIDATE, 0, 0), current_term=(2, 1, 1),
                        votes_granted=(0b101, 0, 0))
    assert orc.become_leader(cand, DIMS, 0) is not None
    joint = cand.replace(log=(((1, joint_value(7, 3)),), (), ()))
    assert orc.become_leader(joint, DIMS, 0) is None
    both = cand.replace(log=(((1, joint_value(7, 3)),), (), ()),
                        votes_granted=(0b011, 0, 0))
    assert orc.become_leader(both, DIMS, 0) is not None


def test_truncation_reverts_configuration():
    """ConflictAppendEntriesRequest semantics: losing the tail config entry
    falls back to the previous configuration."""
    log = ((2, final_value(3)), (2, joint_value(3, 7)))
    assert config_of_py(log, 3) == (3, 7, 2)
    assert config_of_py(log[:1], 3) == (0, 3, 1)


# ---------------------------------------------------------------------------
# differential: kernels vs oracle

def test_init_successors(expand):
    assert_matches_oracle(expand, init_state(DIMS))


def test_two_bfs_levels(expand):
    res = orc.bfs([init_state(DIMS)], DIMS, max_levels=2)
    for s in res.parent:
        assert_matches_oracle(expand, s)


def test_reconfig_rich_states(expand):
    """States seeded with config entries in every phase of a membership
    change (joint pending, joint committed, finalized), plus their BFS
    offspring."""
    seeds = [
        leader_state(log=((2, joint_value(7, 3)),)),
        leader_state(log=((2, joint_value(7, 3)),), commit=1),
        leader_state(log=((2, final_value(3)), (2, 1))),
        leader_state(log=((2, final_value(3)), (2, joint_value(3, 7))),
                     commit=1),
    ]
    res = orc.bfs(seeds, DIMS, max_levels=1)
    for s in res.parent:
        assert_matches_oracle(expand, s)


def test_deeper_reachable_sample(expand):
    def constraint(t, d):
        return (max(t.current_term) <= 3
                and max(len(l) for l in t.log) <= 2
                and all(c <= 1 for _m, c in t.messages))
    res = orc.bfs([init_state(DIMS)], DIMS, constraint=constraint,
                  max_levels=4)
    sample = sorted(res.parent, key=hash)[::11][:60]
    for s in sample:
        assert_matches_oracle(expand, s)


# ---------------------------------------------------------------------------
# end-to-end: engine vs oracle on the bounded reconfig config

def test_engine_matches_oracle_on_reconfig3():
    import os

    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.models.invariants import (Bounds, constraint_py,
                                                type_ok_py)
    from raft_tla_tpu.utils.cfg import load_config

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(here, "configs/reconfig3.cfg"))
    assert isinstance(setup.dims, ReconfigDims)
    assert setup.dims.targets == (3, 7)

    bounds = Bounds(max_term=3, max_log_len=2, max_msg_count=1)
    oracle_res = orc.bfs(
        [init_state(setup.dims)], setup.dims,
        invariants={"TypeOK": type_ok_py},
        constraint=constraint_py(bounds),
        max_levels=3)

    eng = make_engine(setup, EngineConfig(
        batch=128, queue_capacity=1 << 14, seen_capacity=1 << 16,
        record_trace=False, max_diameter=3))
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "diameter_budget"
    assert res.violation is None
    assert res.distinct == oracle_res.distinct_states
    assert res.levels[:4] == oracle_res.levels[:4]


def test_mesh_engine_matches_single_on_reconfig3():
    """The joint-consensus variant through the mesh engine (its extra
    kernels flow through the shared chunk body and the owner-routed
    dedup): counts must match the single-chip engine exactly."""
    import os

    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    from raft_tla_tpu.utils.cfg import load_config

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(here, "configs/reconfig3.cfg"))
    want = make_engine(setup, EngineConfig(
        batch=128, queue_capacity=1 << 14, seen_capacity=1 << 16,
        record_trace=False, max_diameter=3)).run(initial_states(setup))
    got = make_engine(setup, EngineConfig(
        batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
        record_trace=False, max_diameter=3),
        engine_cls=MeshBFSEngine).run(initial_states(setup))
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated
    assert got.violation is None


def test_engine_matches_oracle_from_leader_roots_deep():
    """Config entries only exist once a leader runs InitiateReconfig, and
    no leader exists within the shallow from-Init diameters the other
    end-to-end tests use — so they never packed a configuration value.
    Seed leader-holding roots and go deep enough that joint entries are
    appended, replicated through AppendEntries messages, and re-expanded
    from packed queue rows: this caught the uint8 value-wrap bug
    (CFG_BASE + (old << 8) + new === new_mask mod 256, silently aliasing
    a joint entry to a client value; fixed by dims.value_bytes == 2
    high-byte planes in the packed row)."""
    import os
    import sys

    from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
    from raft_tla_tpu.models.invariants import (build_constraint,
                                                constraint_py)
    from raft_tla_tpu.utils.cfg import load_config
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "scripts"))
    from leader_bench import leader_states

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(here, "configs/reconfig3.cfg"))
    dims, bounds = setup.dims, setup.bounds
    seeds = leader_states(dims, bounds, 0)
    assert seeds, "leader seeding failed"
    # Depth 4 from a fresh leader covers: InitiateReconfig (level 1),
    # AppendEntries carrying the joint entry (level 2), the follower
    # appending it (level 3), and expansions of all of those (level 4).
    ores = orc.bfs(seeds, dims, constraint=constraint_py(bounds),
                   check_deadlock=False, max_levels=4)
    eng = BFSEngine(dims, constraint=build_constraint(dims, bounds),
                    config=EngineConfig(batch=128, queue_capacity=1 << 14,
                                        seen_capacity=1 << 17,
                                        record_trace=False,
                                        check_deadlock=False,
                                        max_diameter=4))
    res = eng.run(seeds)
    assert res.distinct == ores.distinct_states == 3733
    assert res.levels[:5] == ores.levels[:5]
