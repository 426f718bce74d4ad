"""Joint-consensus reconfiguration as a deployment (``configs/
reconfig3.cfg``, benchmark configuration ``reconfig3``): the quorum kernel
against the plain reference's rule, the nine canonical roots against the
same recipe on the program's own oracle, the engine from those roots
against the reference's levels and families, a snapshot that resumes as
``ReconfigDims``, the family counts on the run's events, and the scopes
``quorum`` and ``extra`` named in the chunk.

CPU, small sizes.  The reference is ``benchmark/reference`` (``reconfig.py``,
``oracle.py``), which imports nothing of the program.
"""

import dataclasses
import os
import random
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import checkpoint as ckpt_mod  # noqa: E402
from raft_tla_tpu.engine import chunk as chunk_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import make_engine  # noqa: E402
from raft_tla_tpu.models import oracle as orc  # noqa: E402
from raft_tla_tpu.models import reconfig as prog  # noqa: E402
from raft_tla_tpu.models.dims import (A_ADVANCECOMMIT,  # noqa: E402
                                      A_APPENDENTRIES, A_BECOMELEADER,
                                      A_RECEIVE, A_REQUESTVOTE, A_TIMEOUT)
from raft_tla_tpu.models.pystate import PyState, init_state  # noqa: E402
from raft_tla_tpu.models.schema import encode_state  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402
from reference import dims as rd  # noqa: E402
from reference import oracle as ref_oracle  # noqa: E402
from reference import reconfig as ref  # noqa: E402

CFG = os.path.join(REPO, "configs", "reconfig3.cfg")
CONFIG = lib.load_json("configs", "reconfig3.json")
RDIMS = ref.reference_dims(CONFIG)
CONSTRAINT = rd.constraint_py(ref.reference_bounds(CONFIG))
NAMES = ref.FAMILY_NAMES
SEED = 2147539001


def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 15,
                        seen_capacity=1 << 17, **kw)


def to_program(s) -> PyState:
    return PyState(**{f.name: getattr(s, f.name)
                      for f in dataclasses.fields(PyState)})


@pytest.fixture(scope="module")
def setup():
    return load_config(CFG, n_msg_slots=CONFIG["n_msg_slots"])


@pytest.fixture(scope="module")
def roots():
    return ref.canonical_roots(RDIMS)


@pytest.fixture(scope="module")
def profile(roots):
    """The reference's levels 0-6 from the roots: rows as the pin has
    them, and each level's frontier."""
    seen = {r.state for r in roots}
    frontier = [r.state for r in roots]
    by_family = dict.fromkeys(NAMES, 0)
    rows, fronts = [], []
    for _level in range(7):
        rows.append((len(frontier), len(seen), sum(by_family.values()),
                     dict(by_family)))
        fronts.append(frontier)
        nxt = []
        for s in frontier:
            for (family, _p), t in ref_oracle.successors(s, RDIMS):
                by_family[NAMES[family]] += 1
                if t not in seen:
                    seen.add(t)
                    if CONSTRAINT(t, RDIMS):
                        nxt.append(t)
        frontier = nxt
    return rows, fronts


@pytest.fixture(scope="module")
def walked(setup, roots, tmp_path_factory):
    """The engine as ``check configs/reconfig3.cfg`` builds it, from the
    nine roots through level 5, a snapshot at every level."""
    tmp = tmp_path_factory.mktemp("walk")
    ev = str(tmp / "ev.jsonl")
    eng = make_engine(setup, small(
        max_diameter=5, events_out=ev, record_trace=True,
        checkpoint_dir=str(tmp / "states"), checkpoint_every=1,
        checkpoint_interval_seconds=0.0))
    res = eng.run([to_program(r.state) for r in roots])
    return eng, res, lib.read_events(ev), str(tmp / "states")


# -- the configuration file ---------------------------------------------------

def test_the_configuration_is_the_cfg_letter_for_letter(setup):
    assert CONFIG["cfg_text"] == open(CFG, encoding="utf-8").read() \
        .rstrip("\n").split("\n")
    dims = setup.dims
    assert type(dims).__name__ == CONFIG["shapes"]["dims_class"]
    assert dict(zip(dims.family_names, dims.family_sizes)) \
        == CONFIG["shapes"]["families"]
    assert tuple(dims.family_names) == NAMES
    assert tuple(dims.family_sizes) == RDIMS.family_sizes
    assert dims.n_instances == CONFIG["shapes"]["action_instances"] == 114
    assert dims.value_bytes == CONFIG["shapes"]["value_bytes"] == 2
    assert (prog.CFG_BASE, prog.joint_value(7, 3), prog.final_value(3)) \
        == (ref.CFG_BASE, ref.joint_value(7, 3), ref.final_value(3))


# -- (1) the quorum kernel against the reference's rule -----------------------

LOGS = {
    "no entry": (),
    "joint 7->3": ((2, ref.joint_value(7, 3)),),
    "final 3": ((2, ref.joint_value(7, 3)), (2, ref.final_value(3))),
    "final 7": ((2, ref.joint_value(3, 7)), (2, ref.final_value(7))),
}


@pytest.fixture(scope="module")
def quorum_kernel(setup):
    return jax.jit(setup.dims.build_quorum())


@pytest.mark.parametrize("decider", range(3))
@pytest.mark.parametrize("log", sorted(LOGS))
@pytest.mark.parametrize("mask", range(8))
def test_quorum_kernel_is_the_references_joint_rule(setup, quorum_kernel,
                                                    mask, log, decider):
    """The deciding server's own log decides: the others hold another
    configuration, which neither side may read."""
    other = LOGS["final 3" if log != "final 3" else "no entry"]
    logs = tuple(LOGS[log] if i == decider else other for i in range(3))
    s = ref.init_state(RDIMS).replace(log=logs, current_term=(2, 2, 2))
    want = RDIMS.quorum_py(s, decider, mask)
    member = jnp.asarray([(mask >> k) & 1 > 0 for k in range(3)])
    got = quorum_kernel(encode_state(to_program(s), setup.dims),
                        jnp.int32(decider), member)
    assert bool(got) == want
    # Written out: the majorities the paper's section 6 asks for.
    old, new = {"no entry": (0, 7), "joint 7->3": (7, 3),
                "final 3": (0, 3), "final 7": (0, 7)}[log]
    maj = lambda c: 2 * bin(mask & c).count("1") > bin(c).count("1")  # noqa: E731
    assert want == (maj(new) and (not old or maj(old)))


def test_simple_majority_is_wrong_where_the_joint_rule_matters():
    """r3 and r1 are a majority of Server and not of C_new = {r1, r2}."""
    s = ref.init_state(RDIMS).replace(
        log=((), (), LOGS["joint 7->3"]))
    assert rd.RaftDims.quorum_py(RDIMS, s, 2, 0b101)
    assert not RDIMS.quorum_py(s, 2, 0b101)
    assert RDIMS.quorum_py(s, 2, 0b111)


# -- (2) the roots: the same recipe on the program's own oracle ---------------

def recipe_on_the_programs_oracle(dims, i: int) -> dict:
    """``reference/reconfig.py canonical_roots``'s recipe for server i,
    every step taken from ``models/oracle.py successors``."""
    path = [init_state(dims)]

    def take(family, params):
        (t,) = [t for a, t in orc.successors(path[-1], dims)
                if a == (family, params)]
        path.append(t)

    def deliver_all():
        while path[-1].messages:
            take(A_RECEIVE, (min(m for m, _c in path[-1].messages),))

    others = [j for j in range(dims.n_servers) if j != i]
    out = {}
    take(A_TIMEOUT, (i,))
    for j in others:
        take(A_REQUESTVOTE, (i, j))
    deliver_all()
    take(A_BECOMELEADER, (i,))
    out[f"E_{i}"] = list(path)
    _old, cur, _idx = prog.config_of_py(path[-1].log[i], dims.n_servers)
    (target,) = [c for c in dims.targets if c != cur]
    take(prog.A_INITRECONFIG, (i, target))
    for j in others:
        take(A_APPENDENTRIES, (i, j))
        deliver_all()
    take(A_ADVANCECOMMIT, (i,))
    out[f"J_{i}"] = list(path)
    take(prog.A_FINALIZE, (i,))
    out[f"F_{i}"] = list(path)
    return out


@pytest.mark.parametrize("k", range(9))
def test_canonical_roots_are_the_recipe_on_the_programs_oracle(
        setup, roots, k):
    root = roots[k]
    assert [r.name for r in roots] == [
        f"{kind}_{i}" for i in range(3) for kind in "EJF"]
    path = recipe_on_the_programs_oracle(setup.dims, k // 3)[root.name]
    assert [to_program(s) for _a, s in root.path] == path
    assert root.state == root.path[-1][1]
    assert ref.path_is_legal(root, RDIMS)
    assert CONSTRAINT(root.state, RDIMS)
    i = k // 3
    assert root.state.role[i] == rd.LEADER
    old, new, index = ref.config_of(root.state.log[i], 3)
    assert (old, new, index, root.state.commit_index[i]) == {
        "E": (0, 7, 0, 0), "J": (7, 3, 1, 1), "F": (0, 3, 2, 1)}[
            root.name[0]]


def test_the_root_outside_the_new_configuration_needs_both_followers():
    """J_2: r3 is not in C_new = {r1, r2}; with one follower's match the
    joint rule does not commit, simple majority would."""
    (j2,) = [r for r in ref.canonical_roots(RDIMS) if r.name == "J_2"]
    before = [s for a, s in j2.path
              if a and a[0] == A_APPENDENTRIES][-1]     # r2 not sent yet
    mask = 0b100 | sum(1 << k for k in range(3)
                       if before.match_index[2][k] >= 1)
    assert mask == 0b101
    assert not RDIMS.quorum_py(before, 2, mask)
    assert ref_oracle.advance_commit_index(before, RDIMS, 2) \
        .commit_index[2] == 0


# -- (3) the engine from the roots, level by level ----------------------------

@pytest.mark.parametrize("level", range(6))
def test_engine_levels_from_the_roots_equal_the_reference(
        walked, profile, level):
    _eng, res, events, _dir = walked
    assert (res.pipeline, res.stop_reason, res.violation) == (
        "v2", "diameter_budget", None)
    (e,) = [e for e in events
            if e["event"] == "level_complete" and e["level"] == level]
    assert (e["frontier_rows"], e["distinct"], e["generated"],
            e["generated_by_family"]) == profile[0][level]


def test_the_pin_is_the_references_profile(profile):
    rooted = lib.load_module("traffic", "rooted_window")
    pinned = rooted.load_pinned(CONFIG["pinned"])
    assert [pinned[lv] for lv in range(7)] == profile[0]
    assert max(pinned) >= 10
    # ISSUE 39's own run of the recipe (levels 0-9).
    assert [pinned[lv][0] for lv in range(10)] == [
        9, 54, 261, 1086, 4028, 13725, 43592, 130671, 372832, 1019444]
    assert [pinned[lv][1] for lv in range(10)] == [
        9, 69, 396, 1971, 8648, 34256, 124591, 421765, 1343902, 4065171]
    assert [pinned[lv][2] for lv in range(10)] == [
        0, 90, 693, 4026, 19506, 82215, 312471, 1094015, 3582043, 11082981]
    for row in pinned.values():
        assert sum(row[3].values()) == row[2]


# -- (4) seeded states of levels 4-6 through one level ------------------------

@pytest.mark.parametrize("level,n", [(4, 67), (5, 67), (6, 66)])
def test_sampled_states_through_one_level(setup, profile, level, n):
    front = profile[1][level]
    picked = [front[i] for i in sorted(random.Random(SEED + level).sample(
        range(len(front)), n))]
    eng = make_engine(setup, small(max_diameter=1))
    got = eng.run([to_program(s) for s in picked])
    seen = set(picked)
    by_family = dict.fromkeys(NAMES, 0)
    frontier = 0
    for s in picked:
        assert ref.values_ok(s, RDIMS)
        for (family, _p), t in ref_oracle.successors(s, RDIMS):
            by_family[NAMES[family]] += 1
            assert ref.values_ok(t, RDIMS)
            if t not in seen:
                seen.add(t)
                frontier += bool(CONSTRAINT(t, RDIMS))
    assert (got.levels[-1], got.distinct, got.generated) == (
        frontier, len(seen), sum(by_family.values()))
    assert {k: got.action_counts.get(k, 0) for k in NAMES} == by_family
    assert got.violation is None and got.pipeline == "v2"
    assert by_family["InitiateReconfig"] + by_family["FinalizeReconfig"] > 0


# -- (5) a snapshot from the roots resumes as ReconfigDims --------------------

@pytest.mark.parametrize("given", ["snapshot", "path"])
def test_a_snapshot_from_the_roots_resumes_as_reconfig_dims(
        setup, walked, profile, roots, tmp_path, given):
    _eng, res, _events, states_dir = walked
    (path,) = [os.path.join(states_dir, f) for f in os.listdir(states_dir)
               if f.startswith("level_00003")]
    ck = ckpt_mod.load(path)
    assert type(ck.dims).__name__ == "ReconfigDims" and ck.dims == setup.dims
    assert (len(ck.frontier), ck.distinct, ck.generated,
            ck.action_counts) == profile[0][3]
    assert {to_program(r.state) for r in roots} == set(ck.roots.values())
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(setup, small(max_diameter=5, events_out=ev,
                                   record_trace=True))
    got = eng.run(resume=ck if given == "snapshot" else path)
    assert (got.levels, got.distinct, got.generated, got.action_counts) == (
        res.levels, res.distinct, res.generated, res.action_counts)
    # The resumed run's events carry its own share by family.
    (end,) = [e for e in lib.read_events(ev) if e["event"] == "run_end"]
    own = end["generated_by_family"]
    assert sum(own.values()) == got.generated - ck.generated
    assert {k: own[k] + ck.action_counts[k] for k in NAMES} \
        == profile[0][5][3]
    # A state the resumed run admitted replays to one of the nine roots.
    fps = np.asarray(eng.trace.export()[0], np.uint64)
    old = (ck.seen_hi.astype(np.uint64) << np.uint64(32)) \
        | ck.seen_lo.astype(np.uint64)
    new = fps[~np.isin(fps, old)]
    steps = eng.replay(int(new[len(new) // 2]))
    assert steps[0][0] == -1 and steps[0][1] in set(ck.roots.values())
    states = [lib.to_reference_state(s, lib.reference(CONFIG).pystate)
              for _a, s in steps]
    assert len(states) in (5, 6)
    assert all(t in ref_oracle.successor_set(s, RDIMS)
               for s, t in zip(states, states[1:]))


# -- (6) the family counts on the events --------------------------------------

def test_generated_by_family_sums_to_generated(walked):
    _eng, res, events, _dir = walked
    (end,) = [e for e in events if e["event"] == "run_end"]
    assert sum(end["generated_by_family"].values()) == end["generated"] \
        == res.generated
    assert end["generated_by_family"] == {
        k: res.action_counts.get(k, 0) for k in NAMES}
    levels = [e for e in events if e["event"] == "level_complete"]
    assert all(sum(e["generated_by_family"].values()) == e["generated"]
               for e in levels)
    assert end["generated_by_family"]["InitiateReconfig"] > 0
    assert end["generated_by_family"]["FinalizeReconfig"] > 0


# -- (7) the scopes in the chunk ----------------------------------------------

def chunk_op_names(eng) -> tuple:
    """(lowered text, the scope paths its operations carry)."""
    text = eng._chunk.lower(*eng.chunk_avals()).as_text(debug_info=True)
    return text, sorted({n for n in text.split('"')
                         if n.startswith("jit(chunk)/")})


@pytest.fixture(scope="module")
def op_names(walked):
    return chunk_op_names(walked[0])


@pytest.mark.parametrize("scope", ["quorum", "extra"])
@pytest.mark.parametrize("site", ["masks", "lane_out"])
def test_the_chunk_names_the_scope_under_the_site(op_names, site, scope):
    variant = lib.load_module("readers", "variant")
    _text, names = op_names
    of = [variant.scope_of(n + "/x:") for n in names]
    assert (site, scope) in of
    # Neither name is a stage or a part: the stage and construct readers
    # give every operation what they gave it.
    stages = lib.load_module("readers", "stages")
    construct = lib.load_module("readers", "construct")
    assert not set(variant.SCOPES) & (set(stages.NAMED)
                                      | set(construct.NESTED))
    stage = {"masks": "masks", "lane_out": "construct"}[site]
    assert all(stages.stage_of(n) == stage
               for n, o in zip(names, of) if o == (site, scope))


def test_lane_out_runs_only_the_appended_value_under_extra(setup, op_names):
    """The two families are ``LogAppend`` declarations (``models/dims.py``)
    and ride ``ClientRequest``'s write, so what ``lane_out`` does under
    ``extra`` is their value: one scan of ``log[i]`` by compare, select
    and sum.  No read or write at a traced position (under ``vmap`` a
    gather on every one of the K lanes: 6.8 ms of a 73.8 ms pass on the
    chip until PR 44) and no select over the message table, which a
    general ``lane_fn``'s successor costs once a family."""
    variant = lib.load_module("readers", "variant")
    text, _names = op_names
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    dims = setup.dims
    table = f"x{dims.n_msg_slots}x{dims.msg_width}x"
    traced = re.compile(
        r"stablehlo\.(gather|scatter|dynamic_slice|dynamic_update_slice)\b")
    by_scope = {}
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)$", line)
        if at and " = " in line and at.group(1) in named:
            of = variant.scope_of(named[at.group(1)] + "/x:")
            by_scope.setdefault(of, []).append(line)
    extra = by_scope[("lane_out", "extra")]
    assert any("stablehlo.reduce" in line for line in extra)
    assert not [line for line in extra if traced.search(line)]
    assert not [line for line in extra if table in line]
    # ... which the rest of ``lane_out`` has, found by the same words.
    rest = by_scope[("lane_out", None)]
    assert any(traced.search(line) for line in rest)
    assert any(table in line for line in rest)


def test_scope_of_reads_only_masks_and_lane_out():
    variant = lib.load_module("readers", "variant")
    w = "jit(chunk)/while/body/"
    assert variant.scope_of(
        w + "masks/vmap(masks)/vmap(bl_one)/vmap(vmap(quorum))/gt:") \
        == ("masks", "quorum")
    assert variant.scope_of(
        w + "construct/lane_out/vmap(lane_out)/vmap(extra)/add:") \
        == ("lane_out", "extra")
    assert variant.scope_of(w + "construct/lane_out/vmap(lane_out)/add:") \
        == ("lane_out", None)
    assert variant.scope_of(
        w + "construct/invariants/vmap(TypeOK)/quorum/add:") is None
    assert variant.scope_of(w + "insert/quorum/add:") is None


def test_the_base_model_names_quorum_and_no_extra():
    variant = lib.load_module("readers", "variant")
    setup = load_config(os.path.join(REPO, "configs", "MCraft_bounded.cfg"))
    _text, names = chunk_op_names(make_engine(setup, small()))
    of = {variant.scope_of(n + "/x:") for n in names}
    assert {("masks", "quorum"), ("lane_out", "quorum")} <= of
    assert not {o for o in of if o and o[1] == "extra"}


def test_the_stage_tag_moved_with_the_scopes(op_names):
    """Scope names are not in the compile-cache key: the parent's tag was
    ``s2``, and an executable cached under it names neither scope."""
    assert chunk_mod.STAGES_TAG not in ("s0", "s1", "s2")
    assert f'stages_tag = "{chunk_mod.STAGES_TAG}"' in op_names[0]


def test_the_variant_reader_gives_nothing_without_the_counter():
    variant = lib.load_module("readers", "variant")
    run = {"events": [{"event": "run_end", "generated": 5}]}
    assert variant.read(run, "family_share", families=["Restart"]) is None
    run = {"events": [{"event": "run_end", "generated_by_family": {
        "Restart": 3, "InitiateReconfig": 1}}]}
    assert variant.read(run, "family_share",
                        families=["InitiateReconfig"]) == 25.0
