"""Upstream's smoke test as a deployment (``configs/Smokeraft.cfg``,
benchmark configuration ``smokeraft``): the program's ``SmokeInit`` roots
against the plain reference's recogniser, the engine from them against the
reference's levels and families, a run the duration budget stops, a replay
to a root, the budget's fields on ``run_end`` and the rule that sizes a
budgeted run's calls.

CPU, small batch.  The reference is ``benchmark/reference`` (``smoke.py``,
``oracle.py``), which imports nothing of the program.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import bfs as bfs_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig, budget_call_size  # noqa: E402
from raft_tla_tpu.engine.check import initial_states, make_engine  # noqa: E402
from raft_tla_tpu.obs.metrics import process_record  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402
from reference import dims as rd  # noqa: E402
from reference import oracle as ref_oracle  # noqa: E402
from reference import pystate as ref_pystate  # noqa: E402
from reference import smoke as ref_smoke  # noqa: E402

CFG = os.path.join(REPO, "configs", "Smokeraft.cfg")
CONFIG = lib.load_json("configs", "smokeraft.json")
RDIMS = rd.RaftDims(n_servers=3, n_values=2, max_log=CONFIG["max_log"],
                    n_msg_slots=CONFIG["n_msg_slots"])
NAMES = list(CONFIG["shapes"]["families"])
SEEDS = (1, 2, 3)
KIND = lib.load_module("traffic", "smoke_loop")


def to_reference(s):
    return lib.to_reference_state(s, ref_pystate)


def reference_levels(roots, levels: int):
    """[(frontier, distinct, generated, by family)] of levels 0..levels
    from ``roots``, as the pin has them, and the states seen."""
    seen, frontier = set(roots), list(roots)
    generated, by_family = 0, dict.fromkeys(NAMES, 0)
    rows = [(len(frontier), len(seen), 0, dict(by_family))]
    for _level in range(levels):
        nxt = []
        for s in frontier:
            for (family, _p), t in ref_oracle.successors(s, RDIMS):
                generated += 1
                by_family[NAMES[family]] += 1
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
        rows.append((len(frontier), len(seen), generated, dict(by_family)))
    return rows, seen


def event_rows(path):
    events = lib.read_events(path)
    return KIND.level_rows(events), events


@pytest.fixture(scope="module")
def setup():
    return load_config(CFG)


@pytest.fixture(scope="module")
def roots(setup):
    return {s: initial_states(setup, seed=s) for s in SEEDS}


@pytest.fixture(scope="module")
def engine(setup):
    """One engine at a small batch for the module: the 1,103-byte chunk
    compiles once."""
    return make_engine(setup, EngineConfig(
        batch=256, queue_capacity=1 << 16, seen_capacity=1 << 18))


@pytest.fixture(scope="module")
def budget_run(setup, engine, roots, tmp_path_factory):
    """Root seed 1 under a short duration budget, on the warm engine."""
    ev = str(tmp_path_factory.mktemp("smoke") / "budget.jsonl")
    engine.config.events_out = ev
    engine.config.max_seconds, engine.config.max_diameter = 0.5, 100
    try:
        # Twice: the second on a warm engine, as every check of a CI job
        # but the first is.
        engine.run(roots[1])
        res = engine.run(roots[1])
    finally:
        engine.config.events_out = None
    # Replayed here: the store holds the last run's records only.
    fps = np.asarray(engine.trace.export()[0], np.uint64)
    rng = np.random.default_rng(47)
    replays = [engine.replay(int(fp))
               for fp in rng.permutation(np.unique(fps))[:6]]
    return res, lib.read_events(ev), (len(fps), replays)


def test_the_cfg_is_the_configurations_text():
    for key, name in (("cfg_text", "Smokeraft.cfg"),
                      ("module_text", "Smokeraft.tla")):
        with open(os.path.join(REPO, "configs", name),
                  encoding="utf-8") as f:
            assert f.read() == "\n".join(CONFIG[key]) + "\n", name
    assert CONFIG["reduced"] == {}
    for key in ("cfg_text", "module_text", "MAX_LOG", "N_MSG_SLOTS",
                "QUEUE_CAPACITY", "SEEN_CAPACITY", "BATCH", "root_seeds"):
        assert key in CONFIG["assumed"], key


def test_the_loaded_setup_is_the_deployment(setup):
    assert (setup.smoke, setup.smoke_k, setup.max_seconds,
            setup.max_diameter, setup.check_deadlock) == (
        True, 2, 1.0, 100, False)
    assert setup.invariants == ["TypeOK"] and setup.constraints == []
    assert setup.backend["BATCH"] == CONFIG["batch"]
    assert setup.backend["QUEUE_CAPACITY"] == CONFIG["queue_capacity"]
    assert setup.backend["SEEN_CAPACITY"] == CONFIG["seen_capacity"]
    from raft_tla_tpu.models.schema import state_width
    assert state_width(setup.dims) == CONFIG["shapes"]["row_bytes"] == 1103
    assert setup.dims.n_instances == CONFIG["shapes"]["action_instances"]


@pytest.mark.parametrize("seed", SEEDS)
def test_roots_are_a_smoke_init_set_by_the_reference(roots, seed):
    states = [to_reference(s) for s in roots[seed]]
    assert ref_smoke.is_smoke_init(states, 2, RDIMS) == []
    draw = ref_smoke.draw_of(states)
    assert set(ref_smoke.product(draw)) == set(states)
    assert ref_smoke.from_json(json.loads(json.dumps(
        ref_smoke.to_json(draw)))) == draw
    assert all(ref_smoke.type_ok(s, RDIMS) for s in states)
    pinned = KIND.load_pinned(CONFIG["pinned"])
    assert ref_smoke.from_json(pinned[seed]["draw"]) == draw


def test_the_recogniser_refuses_what_is_no_smoke_init_set(roots):
    states = [to_reference(s) for s in roots[1]]
    assert ref_smoke.is_smoke_init(states[:-1], 2, RDIMS)
    assert ref_smoke.is_smoke_init([ref_pystate.init_state(RDIMS)], 2, RDIMS)
    # A root with a bag of its own.
    m, _c = next(iter(states[0].messages))
    own = states[0].replace(messages=states[0].messages - {(m, 1)})
    assert any("bags" in w for w in ref_smoke.is_smoke_init(
        [own] + states[1:], 2, RDIMS))
    # A value outside its domain: a term of 3.
    far = [s.replace(current_term=(3,) + s.current_term[1:])
           if s.current_term == states[0].current_term else s
           for s in states]
    assert any("current_term outside" in w
               for w in ref_smoke.is_smoke_init(far, 2, RDIMS))


@pytest.mark.parametrize("seed", SEEDS)
def test_levels_0_and_1_equal_the_reference(engine, roots, seed, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    engine.config.events_out = ev
    engine.config.max_seconds, engine.config.max_diameter = None, 1
    try:
        res = engine.run(roots[seed])
    finally:
        engine.config.events_out = None
    want, _seen = reference_levels([to_reference(s) for s in roots[seed]], 1)
    got, events = event_rows(ev)
    assert res.stop_reason == "diameter_budget" and res.violation is None
    assert [got[lv] for lv in (0, 1)] == want
    assert events[-1]["generated_by_family"] == want[1][3]
    # The leader families: what no cell from Init generates at level 1.
    assert want[1][3]["AppendEntries"] > 0
    assert want[1][3]["ClientRequest"] > 0
    pinned = KIND.load_pinned(CONFIG["pinned"])
    assert [pinned[seed][lv] for lv in (0, 1)] == want


def test_a_short_budget_stops_the_run_on_the_budget(budget_run, roots):
    res, events, _replays = budget_run
    assert res.stop_reason == "duration_budget"
    assert res.violation is None
    assert res.wall_seconds >= 0.5
    runs = KIND.split_runs(events)
    got = KIND.level_rows(runs[-1])
    assert sorted(got) == list(range(res.diameter + 1))
    want, _seen = reference_levels([to_reference(s) for s in roots[1]],
                                   min(res.diameter, 1))
    for lv in range(min(res.diameter, 1) + 1):
        assert got[lv] == want[lv]
    # What it admitted past its last completed level is counted too.
    assert res.distinct >= got[res.diameter][1]
    assert res.distinct == int(runs[-1][-1]["distinct"])


def test_run_end_carries_the_budget(budget_run):
    res, events, _replays = budget_run
    ends = [e for e in events if e["event"] == "run_end"]
    assert len(ends) == 2
    for end in ends:
        assert end["stop_reason"] == "duration_budget"
        assert end["roots"] == 512 and end["budget_s"] == 0.5
        assert end["budget_overshoot_s"] == pytest.approx(
            end["wall_seconds"] - 0.5, abs=1e-5)
        assert end["budget_overshoot_s"] >= 0
        # With no estimate a run's first call is one batch; every call is
        # sized by one of the three rules.
        assert end["probe_calls"] == 1
        assert 0 <= end["deadline_calls"] <= end["chunk_calls"] - 1
    starts = [e for e in events if e["event"] == "run_start"]
    assert [e["roots"] for e in starts] == [512, 512]


def test_a_run_without_a_budget_says_so(engine, roots, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    engine.config.events_out = ev
    engine.config.max_seconds, engine.config.max_diameter = None, 0
    try:
        engine.run(roots[2])
    finally:
        engine.config.events_out = None
    end = lib.read_events(ev)[-1]
    assert (end["roots"], end["budget_s"], end["budget_overshoot_s"],
            end["deadline_calls"], end["probe_calls"]) == (
        512, None, None, 0, 0)


def test_an_admitted_state_replays_to_a_root(budget_run, roots):
    res, _events, (records, replays) = budget_run
    assert records == res.distinct
    root_states = {to_reference(s) for s in roots[1]}
    depths = set()
    for steps in replays:
        states = [to_reference(s) for _a, s in steps]
        assert steps[0][0] == -1 and states[0] in root_states
        assert all(t in ref_oracle.successor_set(s, RDIMS)
                   for s, t in zip(states, states[1:]))
        depths.add(len(steps) - 1)
    assert depths <= set(range(res.diameter + 2))


def test_the_draw_of_the_roots_is_a_span(setup):
    before = dict(process_record()._spans).get("scope/smoke_roots", [0])[0]
    initial_states(setup, seed=5)
    assert process_record()._spans["scope/smoke_roots"][0] == before + 1


@pytest.mark.parametrize("ch, remaining, ema, calls, want", [
    (32, 1.0, 0.0, 0, (1, "probe")),        # no estimate yet
    (32, 1.0, 0.01, 0, (2, "ramp")),        # the ramp starts at 2
    (32, 1.0, 0.01, 2, (8, "ramp")),
    (32, 1.0, 0.01, 9, (32, "ramp")),       # sync_every caps the ramp
    (32, 0.2, 0.01, 9, (10, "deadline")),   # half of what is left
    (32, 0.01, 0.01, 3, (1, "deadline")),   # never less than a batch
])
def test_the_rule_that_sizes_a_budgeted_call(ch, remaining, ema, calls,
                                             want):
    assert budget_call_size(ch, remaining, ema, calls) == want


def test_the_new_counters_are_work_counters():
    assert {"deadline_calls", "probe_calls"} <= set(bfs_mod.WORK_COUNTERS)
