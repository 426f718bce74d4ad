"""Raft's safety suite where the logs hold entries: the ten invariants
under joint-consensus reconfiguration (``configs/reconfig3_safety.cfg``,
benchmark configuration ``reconfig3-safety``) and from the 117
leader-holding roots of the base model (``benchmark/reference/leaders.py``,
cell ``leader-rich``): the cfg against ``reconfig3.cfg``, the engine from
the nine roots against the reference's levels, the nine kernels of
``models/safety.py`` against ``reference/safety.py`` on reachable states
whose logs hold entries, witnesses through the chunk program under both
dims, the roots against the same recipe on the program's own oracle, and
``run_start.roots``.

CPU, small sizes.  The reference is ``benchmark/reference``, which imports
nothing of the program.
"""

import dataclasses
import functools
import os
import random
import sys
import types

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import checkpoint as ckpt_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import make_engine  # noqa: E402
from raft_tla_tpu.models import oracle as orc  # noqa: E402
from raft_tla_tpu.models.dims import (A_BECOMELEADER, A_RECEIVE,  # noqa: E402
                                      A_REQUESTVOTE, A_TIMEOUT, LEADER)
from raft_tla_tpu.models.invariants import constraint_py  # noqa: E402
from raft_tla_tpu.models.pystate import PyState, init_state  # noqa: E402
from raft_tla_tpu.models.safety import SAFETY_INVARIANTS  # noqa: E402
from raft_tla_tpu.models.schema import (encode_state,  # noqa: E402
                                        stack_states)
from raft_tla_tpu.parallel.mesh import MeshBFSEngine  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402
from reference import dims as rd  # noqa: E402
from reference import leaders  # noqa: E402
from reference import oracle as ref_oracle  # noqa: E402
from reference import pystate as ref_pystate  # noqa: E402
from reference import reconfig as ref_reconfig  # noqa: E402
from reference import safety as ref_safety  # noqa: E402
from reference import safety_reconfig  # noqa: E402

rooted = lib.load_module("traffic", "rooted_window")
safety_window = lib.load_module("traffic", "safety_window")

CONFIGS = {"reconfig": lib.load_json("configs", "reconfig3-safety.json"),
           "base": lib.load_json("configs", "mcraft3-safety.json")}
CFGS = {k: os.path.join(REPO, "configs", c["cfg_name"])
        for k, c in CONFIGS.items()}
SUITE = CONFIGS["reconfig"]["invariants"]
SAFETY = SUITE[1:]
EXTRA = ["witness_log_matching_high_byte",
         "witness_leader_completeness_config"]
SEED = 2147543001
# The reference's levels the pools are drawn from, a root set.
POOL_LEVELS = {"reconfig": (4, 5, 6), "base": (3, 4, 5)}


def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 15,
                        seen_capacity=1 << 17, **kw)


def to_program(s) -> PyState:
    return PyState(**{f.name: getattr(s, f.name)
                      for f in dataclasses.fields(PyState)})


@pytest.fixture(scope="module")
def setups():
    return {k: load_config(CFGS[k], n_msg_slots=c["n_msg_slots"])
            for k, c in CONFIGS.items()}


@pytest.fixture(scope="module")
def refs():
    """The reference's side of each root set: dims, constraint, suite,
    roots, and its levels from the roots (rows as a rooted pin has them,
    and each level's frontier)."""
    out = {}
    for key, mod, roots_fn, suite in (
            ("reconfig", ref_reconfig, ref_reconfig.canonical_roots,
             safety_reconfig),
            ("base", leaders, leaders.leader_roots, ref_safety)):
        dims = mod.reference_dims(CONFIGS[key])
        constraint = rd.constraint_py(mod.reference_bounds(CONFIGS[key]))
        roots = roots_fn(dims)
        seen = {r.state for r in roots}
        frontier = [r.state for r in roots]
        by_family = dict.fromkeys(mod.FAMILY_NAMES, 0)
        rows, fronts = [], []
        for _level in range(max(POOL_LEVELS[key]) + 1):
            rows.append((len(frontier), len(seen), sum(by_family.values()),
                         dict(by_family)))
            fronts.append(frontier)
            nxt = []
            for s in frontier:
                for (family, _p), t in ref_oracle.successors(s, dims):
                    by_family[mod.FAMILY_NAMES[family]] += 1
                    if t not in seen:
                        seen.add(t)
                        if constraint(t, dims):
                            nxt.append(t)
            frontier = nxt
        rng = random.Random(SEED)
        pool = [s for lv in POOL_LEVELS[key]
                for s in rng.sample(fronts[lv], 67)][:200]
        out[key] = types.SimpleNamespace(
            mod=mod, dims=dims, constraint=constraint, roots=roots,
            suite=suite, rows=rows, fronts=fronts, pool=pool)
    return out


@pytest.fixture(scope="module")
def warm(setups, refs, tmp_path_factory):
    """An engine a root set, as ``make_engine`` builds it from the cfg,
    walked from the roots (nine through level 5, 117 through level 2),
    its events, and the snapshot it wrote last."""
    out = {}
    for key, depth in (("reconfig", 5), ("base", 2)):
        tmp = tmp_path_factory.mktemp(key)
        ev = str(tmp / "ev.jsonl")
        eng = make_engine(setups[key], small(
            max_diameter=depth, events_out=ev, record_trace=True,
            checkpoint_dir=str(tmp / "states"), checkpoint_every=depth,
            checkpoint_interval_seconds=0.0))
        res = eng.run([to_program(r.state) for r in refs[key].roots])
        out[key] = (eng, res, lib.read_events(ev),
                    ckpt_mod.load(ckpt_mod.latest(str(tmp / "states"))))
    return out


# -- the cfg and the configuration's files -------------------------------------

def test_the_cfg_is_reconfig3s_but_for_its_invariants(setups):
    def spec(path):
        """The cfg less its comments (a ``\\* TPU:`` directive is none),
        the invariants' lines apart."""
        lines = [ln.rstrip() for ln in open(path, encoding="utf-8")
                 if ln.strip() and (not ln.startswith("\\*")
                                    or ln.startswith("\\* TPU:"))]
        at = next(i for i, ln in enumerate(lines)
                  if ln.startswith("INVARIANT"))
        end = next(i for i in range(at + 1, len(lines))
                   if not lines[i].startswith(" "))
        return lines[:at] + lines[end:], " ".join(
            " ".join(lines[at:end]).split()[1:])

    safe, invs = spec(CFGS["reconfig"])
    plain, one = spec(os.path.join(REPO, "configs", "reconfig3.cfg"))
    assert safe == plain and one == "TypeOK"
    assert invs.split() == SUITE
    assert "\\* TPU: N_MSG_SLOTS = 24" in safe
    _rest, base_invs = spec(CFGS["base"])
    assert base_invs.split() == SUITE      # MCraft_safety.cfg's, in order
    text = open(CFGS["reconfig"], encoding="utf-8").read()
    assert "SIMPLE MAJORITY" in text
    assert CONFIGS["reconfig"]["cfg_text"] == text.rstrip("\n").split("\n")
    dims = setups["reconfig"].dims
    assert type(dims).__name__ == "ReconfigDims"
    assert dims == load_config(os.path.join(REPO, "configs",
                                            "reconfig3.cfg")).dims
    assert setups["reconfig"].invariants == SUITE


def test_a_suite_that_holds_removes_no_state(refs):
    pinned = rooted.load_pinned("reconfig3-safety")
    assert pinned == rooted.load_pinned("reconfig3")
    assert [pinned[lv] for lv in range(7)] == refs["reconfig"].rows


def test_the_leaders_pin_is_the_references_profile(refs):
    pinned = rooted.load_pinned("mcraft3-safety.leaders")
    assert [pinned[lv] for lv in range(6)] == refs["base"].rows
    assert pinned[0][:3] == (117, 117, 0) and max(pinned) == 8


# -- the engine from the nine roots, under the ten -----------------------------

@pytest.mark.parametrize("level", range(6))
def test_engine_levels_from_the_roots_equal_the_reference(warm, refs,
                                                          level):
    eng, res, events, _ck = warm["reconfig"]
    assert eng.inv_names == SUITE
    assert (res.pipeline, res.stop_reason, res.violation) == (
        "v2", "diameter_budget", None)
    (e,) = [e for e in events
            if e["event"] == "level_complete" and e["level"] == level]
    assert (e["frontier_rows"], e["distinct"], e["generated"],
            e["generated_by_family"]) == refs["reconfig"].rows[level]


def test_the_snapshot_comes_back_as_reconfig_dims(warm, setups):
    _eng, _res, _events, ck = warm["reconfig"]
    assert type(ck.dims).__name__ == "ReconfigDims"
    assert ck.dims == setups["reconfig"].dims and ck.diameter == 5


# -- run_start.roots -----------------------------------------------------------

@pytest.mark.parametrize("key,want", [("reconfig", 9), ("base", 117)])
def test_run_start_says_how_many_states_the_run_was_given(warm, key, want):
    _eng, _res, events, _ck = warm[key]
    (start,) = [e for e in events if e["event"] == "run_start"]
    assert start["roots"] == want and start["resume"] is False


def test_run_start_roots_from_init_on_a_resume_and_on_the_mesh(
        setups, warm, tmp_path):
    setup = setups["base"]
    ev = str(tmp_path / "ev.jsonl")
    _eng, _res, _events, ck = warm["base"]
    eng = make_engine(setup, small(max_diameter=1, events_out=ev))
    eng.run([init_state(setup.dims)])
    eng.config.max_diameter = ck.diameter + 1
    eng.run(resume=ck)
    mesh = make_engine(setup, small(max_diameter=1, events_out=ev),
                       engine_cls=functools.partial(
                           MeshBFSEngine, devices=jax.devices()[:4]))
    mesh.run([init_state(setup.dims)])
    starts = [e for e in lib.read_events(ev) if e["event"] == "run_start"]
    assert [(e["engine"], e["roots"], e["resume"]) for e in starts] == [
        ("BFSEngine", 1, False), ("BFSEngine", 0, True),
        ("MeshBFSEngine", 1, False)]


# -- the roots of leader-rich ---------------------------------------------------

def test_leader_roots_are_117_legal_constrained_and_distinct(refs, setups):
    ref = refs["base"]
    assert len(ref.roots) == len({r.state for r in ref.roots}) == 117
    every = leaders.leader_states(ref.dims)
    assert len(every) == 129 and [r for r in every if ref.constraint(
        r.state, ref.dims)] == ref.roots
    outside = [r.state for r in every if r not in ref.roots]
    assert sorted((max(s.current_term), max(c for _m, c in s.messages))
                  if s.messages else (max(s.current_term), 0)
                  for s in outside) == [(2, 2)] * 6 + [(4, 0)] * 6
    for r in ref.roots:
        assert LEADER in r.state.role and r.state == r.path[-1][1]
        assert leaders.path_is_legal(r, ref.dims)
        assert leaders.values_ok(r.state, ref.dims)
        assert 11 <= len(r.path) <= 13      # an election, then 0-2 steps
    assert tuple(leaders.FAMILY_NAMES) == tuple(
        setups["base"].dims.family_names)


def test_leader_roots_are_the_recipe_on_the_programs_oracle(setups, refs):
    """``reference/leaders.py``'s recipe, every step taken from
    ``models/oracle.py successors`` and the program's own constraint."""
    setup = setups["base"]
    dims, constraint = setup.dims, constraint_py(setup.bounds)
    found = {}
    for i in range(dims.n_servers):
        s = init_state(dims)

        def take(s, family, params):
            (t,) = [t for a, t in orc.successors(s, dims)
                    if a == (family, params)]
            return t

        s = take(s, A_TIMEOUT, (i,))
        for j in range(dims.n_servers):
            if j != i:
                s = take(s, A_REQUESTVOTE, (i, j))
        while s.messages:
            s = take(s, A_RECEIVE, (min(m for m, _c in s.messages),))
        found[take(s, A_BECOMELEADER, (i,))] = None
    frontier = list(found)
    for _level in range(leaders.LEVELS):
        nxt = []
        for s in frontier:
            for _a, t in orc.successors(s, dims):
                if t not in found:
                    found[t] = None
                    if constraint(t, dims):
                        nxt.append(t)
        frontier = nxt
    want = [s for s in found if LEADER in s.role and constraint(s, dims)]
    assert [to_program(r.state) for r in refs["base"].roots] == want


# -- the variant's TypeOK ---------------------------------------------------------

def test_the_variants_type_ok_is_the_base_one_without_config_entries(refs):
    ref = refs["reconfig"]
    states = [s for front in ref.fronts[:6] for s in front]
    without = [s for s in states if not safety_reconfig.holds_config(s)
               and ref_reconfig.values_ok(s, ref.dims)
               and not any(v >= ref_reconfig.CFG_BASE
                           for m, _c in s.messages if m[0] in (1, 2)
                           for _t, v in m[5 if m[0] == 1 else 6])]
    held = [s for s in states if safety_reconfig.holds_config(s)]
    assert len(without) > 1000 and len(held) > 1000
    assert all(safety_reconfig.type_ok(s, ref.dims)
               and ref_safety.type_ok(s, ref.dims) for s in without)
    assert all(safety_reconfig.type_ok(s, ref.dims)
               and not ref_safety.type_ok(s, ref.dims) for s in held)
    bad = held[0].replace(log=(((1, ref_reconfig.CFG_BASE),), (), ()))
    assert not safety_reconfig.type_ok(bad, ref.dims)   # C_new empty


# -- the nine kernels on states whose logs hold entries -------------------------

@pytest.fixture(scope="module")
def witnesses(refs):
    """Three witnesses of each maker of each root set's suite."""
    return {key: {m: [w for w, _f in ref.suite.witness_parents(
        m, ref.pool, 3, SEED, ref.dims, SUITE, ref.constraint)]
        for m in getattr(ref.suite, "MAKERS", ref.suite.WITNESS_MAKERS)}
        for key, ref in refs.items()}


@pytest.mark.parametrize("name", SAFETY)
@pytest.mark.parametrize("key", ["reconfig", "base"])
def test_kernel_equals_reference_on_states_whose_logs_hold_entries(
        key, name, setups, refs, witnesses):
    """200 seeded states of the reference's levels from the root set
    (where every predicate holds) and the witnesses of every maker (where
    each fails on its own and holds on most others')."""
    ref, dims = refs[key], setups[key].dims
    made = [w for ws in witnesses[key].values() for w in ws]
    states = ref.pool + made
    got = np.asarray(jax.vmap(SAFETY_INVARIANTS[name](dims))(
        stack_states([encode_state(to_program(s), dims) for s in states])))
    want = np.array([ref_safety.INVARIANTS[name](s, ref.dims)
                     for s in states])
    assert (got == want).all(), states[int(np.argmax(got != want))]
    assert want[:200].all() and not want[200:].all()
    with_entries = sum(any(s.log) for s in ref.pool)
    assert with_entries > 100, with_entries
    if key == "reconfig":
        assert sum(map(safety_reconfig.holds_config, ref.pool)) > 80


def test_records_that_differ_in_the_high_byte_alone_are_not_equal(
        setups, refs, witnesses):
    """``joint_value(7, 3)`` and ``final_value(3)`` both end in byte 3:
    ``_entry_eq`` compares whole values, so ``LogMatching`` fails."""
    ref, dims = refs["reconfig"], setups["reconfig"].dims
    made = witnesses["reconfig"]["witness_log_matching_high_byte"]
    assert len(made) == 3
    for w in made:
        assert sorted(log[0][1] for log in w.log
                      if log[:1] and log[0][0] == 1) == [4099, 5891]
        assert safety_reconfig.first_failing(w, SUITE, ref.dims) \
            == "LogMatching"
    batch = stack_states([encode_state(to_program(w), dims) for w in made])
    assert not np.asarray(jax.vmap(
        SAFETY_INVARIANTS["LogMatching"](dims))(batch)).any()
    # The same logs with the low bytes alone compared read as equal.
    lo = np.asarray(batch.log_val) & 0xFF
    te = np.asarray(batch.log_term)
    for k, w in enumerate(made):
        i, j = [x for x in range(3)
                if w.log[x][:1] and w.log[x][0][0] == 1]
        assert lo[k, i, 0] == lo[k, j, 0] and te[k, i, 0] == te[k, j, 0]


# -- witnesses through the chunk program, under both dims -----------------------

@pytest.mark.parametrize("key,maker", [
    *[("reconfig", m) for m in SAFETY + EXTRA],
    *[("base", m) for m in SAFETY]])
def test_a_resumed_witness_frontier_stops_under_the_references_name(
        key, maker, setups, refs, warm):
    """What ``rooted_safety_window`` does at the cell's size: witness
    parents made from the reference's levels as the frontier of a
    snapshot, resumed by the warm engine; the chunk program must stop on
    a successor, under the name the reference gives it, and the trace
    must replay from a witness parent."""
    ref, setup = refs[key], setups[key]
    eng, _res, _events, ck = warm[key]
    name = getattr(ref.suite, "made_for", lambda m: m)(maker)
    made = ref.suite.witness_parents(maker, ref.pool, 3, SEED, ref.dims,
                                     SUITE, ref.constraint)
    assert len(made) == 3
    parents = [w for w, _failing in made]
    if key == "reconfig" and maker not in (
            "VotesGrantedInv", "QuorumLogInv", "MoreUpToDateCorrect",
            "LeaderCompleteness"):
        assert sum(map(safety_reconfig.holds_config, parents)) >= 2
    eng.config.max_diameter = ck.diameter + 1
    res = eng.run(resume=safety_window.witness_snapshot(ck, setup, parents))
    assert res.stop_reason == "violation"
    got = lib.to_reference_state(res.violation.state, ref_pystate)
    assert res.violation.invariant == name \
        == ref.suite.first_failing(got, SUITE, ref.dims)
    assert got in set().union(*(failing for _w, failing in made))
    steps = [lib.to_reference_state(s, ref_pystate)
             for _g, s in eng.replay(res.violation.fingerprint)]
    assert len(steps) == 2 and steps[0] in parents and steps[1] == got
    assert got in ref_oracle.successor_set(steps[0], ref.dims)
