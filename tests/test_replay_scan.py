"""A trace rebuilt in one device call (``engine/replay.py``): the fused
replay against the per-step path it replaced, its fallback to that path,
its dispatches and compiles, the swarm's ``replay_actions`` against the
loop it replaced, and the two bodies the program can be built from.

CPU, small sizes.  "The per-step path" is ``BFSEngine.replay`` with a
scan that holds nothing, so that every step goes through
``_replay_step``: the replay as it was before the fused program.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import checkpoint as ckpt_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import (initial_states,  # noqa: E402
                                       make_engine, make_swarm_engine)
from raft_tla_tpu.engine.replay import (REPLAY_CAPACITY,  # noqa: E402
                                        ReplayScan, build_replay_step)
from raft_tla_tpu.models.actions import build_expand  # noqa: E402
from raft_tla_tpu.models.pystate import PyState  # noqa: E402
from raft_tla_tpu.models.schema import (StateBatch,  # noqa: E402
                                        decode_state, encode_state,
                                        flatten_state, state_width,
                                        unflatten_state)
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

CFGS = ("MCraft_noleader", "MCraft_bounded", "MCraft_safety", "reconfig3")
DEPTHS = ("root", "one", "mid", "deepest")


def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 15,
                        seen_capacity=1 << 17, **kw)


def cfg_path(name: str) -> str:
    return os.path.join(REPO, "configs", name + ".cfg")


def per_step(eng, fp: int) -> list:
    """``eng.replay(fp)`` as it was before the fused program: a scan that
    holds no step, so every step goes through ``_replay_step``."""
    scan = eng._replay_scan
    sw = state_width(eng.dims)
    eng._replay_scan = lambda root, acts: (
        np.zeros((0, sw), np.uint8), np.zeros(0, np.uint64), 0)
    try:
        return eng.replay(fp)
    finally:
        eng._replay_scan = scan


def counters(eng) -> dict:
    return {k: eng.metrics.counter_value("engine/" + k)
            for k in ("replay_scans", "replay_scan_steps",
                      "replay_fallback_steps", "fp_collisions")}


def moved(eng, base: dict) -> dict:
    return {k: v - base[k] for k, v in counters(eng).items()}


# -- the four engines, each with a filled trace store --------------------------

def walk_noleader(tmp):
    setup = load_config(cfg_path("MCraft_noleader"))
    eng = make_engine(setup, small())
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "violation"
    return eng, res.violation.fingerprint


def walk_bounded(tmp):
    setup = load_config(cfg_path("MCraft_bounded"))
    eng = make_engine(setup, small(max_diameter=5))
    eng.run(initial_states(setup))
    return eng, None


def walk_safety(tmp):
    """A witness resume (``benchmark/traffic/safety_window.py``): states
    of level 4 re-encoded as the frontier of the level's snapshot, each a
    root of the trace, and two levels built from them."""
    config = lib.load_json("configs", "mcraft3-safety.json")
    setup = load_config(cfg_path("MCraft_safety"),
                        n_msg_slots=config["n_msg_slots"])
    eng = make_engine(setup, small(
        checkpoint_dir=str(tmp), checkpoint_every=4, max_diameter=4,
        checkpoint_interval_seconds=0.0))
    eng.run(initial_states(setup))
    ck = ckpt_mod.load(ckpt_mod.latest(str(tmp)))
    parents = [decode_state(unflatten_state(row, setup.dims), setup.dims)
               for row in ck.frontier[::7][:24]]
    eng.config.max_diameter = ck.diameter + 2
    safety_window = lib.load_module("traffic", "safety_window")
    res = eng.run(resume=safety_window.witness_snapshot(ck, setup, parents))
    assert res.violation is None and len(eng.trace.roots) == len(parents)
    return eng, None


def walk_reconfig(tmp):
    from reference import reconfig as ref
    config = lib.load_json("configs", "reconfig3.json")
    setup = load_config(cfg_path("reconfig3"),
                        n_msg_slots=config["n_msg_slots"])
    eng = make_engine(setup, small(max_diameter=4))
    import dataclasses
    roots = [PyState(**{f.name: getattr(r.state, f.name)
                        for f in dataclasses.fields(PyState)})
             for r in ref.canonical_roots(ref.reference_dims(config))]
    res = eng.run(roots)
    assert res.action_counts.get("InitiateReconfig", 0) > 0
    return eng, None


WALKS = dict(zip(CFGS, (walk_noleader, walk_bounded, walk_safety,
                        walk_reconfig)))


@pytest.fixture(scope="module")
def walked(request, tmp_path_factory):
    """(engine, {depth name: fingerprint}) of one cfg."""
    name = request.param
    eng, deepest = WALKS[name](tmp_path_factory.mktemp(name))
    fps, parents, actions = eng.trace.export()
    roots = np.fromiter(eng.trace.roots, np.uint64)
    by_depth = {0: int(roots.max()), 1: int(
        fps[np.isin(parents, roots) & (actions >= 0)].max())}
    for fp in np.sort(fps)[:: max(len(fps) // 400, 1)]:
        by_depth.setdefault(len(eng.trace.chain(int(fp))) - 1, int(fp))
    if deepest is not None:
        by_depth[len(eng.trace.chain(deepest)) - 1] = deepest
    top = max(by_depth)
    assert top >= 2, by_depth
    return eng, dict(zip(DEPTHS, (by_depth[0], by_depth[1],
                                  by_depth[(top + 1) // 2], by_depth[top])))


# -- (a) the fused replay is the per-step replay -------------------------------

@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("walked", CFGS, indirect=True)
def test_fused_replay_is_the_per_step_replay(walked, depth):
    eng, fps = walked
    fp = fps[depth]
    base = counters(eng)
    got = eng.replay(fp)
    steps = len(got) - 1
    assert moved(eng, base) == {
        "replay_scans": int(steps > 0), "replay_scan_steps": steps,
        "replay_fallback_steps": 0, "fp_collisions": 0}
    base = counters(eng)
    want = per_step(eng, fp)
    assert moved(eng, base)["replay_fallback_steps"] == steps
    assert got == want          # element for element: label and state
    assert got[0][0] == -1 and got[0][1] in eng.trace.roots.values()
    if depth == "root":
        assert steps == 0
    if depth == "deepest":
        assert steps >= 2


@pytest.mark.parametrize("walked", CFGS[:2], indirect=True)
def test_slot_labels_address_the_canonical_parent(walked):
    """The recorded id of a Receive addresses the kernel's slot; the id
    returned addresses the same message among the parent's SORTED
    messages, as the per-step path's does: over a few hundred traces
    the two paths agree on every label, and some recorded ids moved."""
    eng, _fps = walked
    fps = np.sort(eng.trace.export()[0])
    moved_ids = 0
    for fp in fps[:: max(len(fps) // 150, 1)]:
        chain = eng.trace.chain(int(fp))
        got = eng.replay(int(fp))
        assert got == per_step(eng, int(fp))
        moved_ids += [g for g, _s in got] != [g for _fp, g in chain]
    assert moved_ids > 0


# -- (b) the fallback ----------------------------------------------------------

class PatchedStore:
    """The engine's trace store with one chain's record changed."""

    def __init__(self, store, chain):
        self._store, self._chain = store, chain
        self.roots = store.roots

    def chain(self, fp):
        return list(self._chain)


@pytest.mark.parametrize("k", [1, 4, 9])
@pytest.mark.parametrize("wrong", ["disabled", "another", "negative"])
@pytest.mark.parametrize("walked", CFGS[:1], indirect=True)
def test_a_wrong_recorded_instance_falls_back_from_its_step(walked, wrong,
                                                            k):
    eng, fps = walked
    fp = fps["deepest"]
    want = per_step(eng, fp)
    chain = eng.trace.chain(fp)
    assert len(chain) == 10
    # An instance that is not enabled on step k's parent, one that is
    # and leads elsewhere, and no instance at all.  (Instances of no
    # message slot: the same in the kernel's arrangement of the parent
    # and in the canonical one, which is encoded here.)
    parent = encode_state(want[k - 1][1], eng.dims)
    cands, en, _ovf = eng._expand1(parent)
    hi, lo = (np.asarray(x).astype(np.uint64) for x in eng._fp_batch(cands))
    elsewhere = np.asarray(en) & (((hi << np.uint64(32)) | lo)
                                  != np.uint64(chain[k][0]))
    slotless = [g for g in range(eng.dims.n_instances)
                if "slot" not in eng.dims.instance_info(g)[1]]
    g = {"disabled": next(g for g in slotless if not en[g]),
         "another": next(g for g in slotless if elsewhere[g]),
         "negative": -7}[wrong]
    patched = list(chain)
    patched[k] = (chain[k][0], g)
    store, eng.trace = eng.trace, PatchedStore(eng.trace, patched)
    try:
        base = counters(eng)
        got = eng.replay(fp)
    finally:
        eng.trace = store
    assert got == want
    # The scan ends before an instance that is not enabled; one that is
    # it takes, and what the diverged row still enables after it.
    now = moved(eng, base)
    scanned = now.pop("replay_scan_steps")
    assert k <= scanned <= 9 if wrong == "another" else scanned == k - 1
    assert now == {"replay_scans": 1, "replay_fallback_steps": 10 - k,
                   "fp_collisions": 0}


@pytest.mark.parametrize("walked", CFGS[:1], indirect=True)
def test_a_key_no_candidate_has_still_raises(walked):
    eng, fps = walked
    chain = eng.trace.chain(fps["deepest"])
    patched = list(chain)
    patched[5] = (chain[5][0] ^ 1, chain[5][1])
    store, eng.trace = eng.trace, PatchedStore(eng.trace, patched)
    try:
        base = counters(eng)
        with pytest.raises(RuntimeError, match="replay divergence"):
            eng.replay(fps["deepest"])
    finally:
        eng.trace = store
    assert moved(eng, base) == {
        "replay_scans": 1, "replay_scan_steps": 9,
        "replay_fallback_steps": 1, "fp_collisions": 1}


# -- (c) dispatches and compiles -----------------------------------------------

@pytest.mark.parametrize("walked", CFGS[:1], indirect=True)
def test_one_call_a_trace_and_no_compile_at_another_length(walked):
    eng, fps = walked
    eng.replay(fps["deepest"])              # compiled, if it was not
    compiles = dict(eng.metrics.counters("compile/"))
    spans = eng.metrics.snapshot()["histograms"]["phase/replay_scan"]
    for depth, steps in (("deepest", 9), ("mid", 5), ("one", 1)):
        base = counters(eng)
        assert len(eng.replay(fps[depth])) == steps + 1
        assert moved(eng, base)["replay_scans"] == 1
        assert moved(eng, base)["replay_scan_steps"] == steps
    assert dict(eng.metrics.counters("compile/")) == compiles
    hist = eng.metrics.snapshot()["histograms"]
    assert hist["phase/replay_scan"]["count"] == spans["count"] + 3
    assert eng._replay_scan.capacity == REPLAY_CAPACITY >= 100


@pytest.mark.parametrize("capacity,calls", [(4, 3), (3, 3), (9, 1), (8, 2)])
@pytest.mark.parametrize("walked", CFGS[:1], indirect=True)
def test_a_trace_longer_than_the_buffer_takes_more_calls(walked, capacity,
                                                         calls):
    eng, fps = walked
    want = eng.replay(fps["deepest"])
    scan = eng._replay_scan
    eng._replay_scan = ReplayScan(eng.dims, eng.metrics, capacity=capacity)
    try:
        base = counters(eng)
        assert eng.replay(fps["deepest"]) == want
        assert moved(eng, base) == {
            "replay_scans": calls, "replay_scan_steps": 9,
            "replay_fallback_steps": 0, "fp_collisions": 0}
    finally:
        eng._replay_scan = scan


# -- (d) the swarm's replay_actions against the loop it replaced ---------------

def old_replay_actions(eng, expand1, root, actions) -> list:
    """``SwarmEngine.replay_actions`` before the fused program: one
    expand round trip a step, the encoded candidate threaded."""
    st = encode_state(root, eng.dims)
    trace = [(-1, root)]
    for g in actions:
        g = int(g)
        cands, en, _ovf = expand1(st)
        if g < 0 or not bool(np.asarray(en)[g]):
            break
        st = StateBatch(*jax.tree.map(lambda a: np.asarray(a)[g], cands))
        trace.append((g, decode_state(st, eng.dims)))
    return trace


@pytest.fixture(scope="module")
def hunter():
    config = lib.load_json("configs", "mcraft3-swarm.json")
    setup = load_config(cfg_path("MCraft_swarm"))
    with open(os.path.join(REPO, "benchmark", "pinned",
                           config["pinned_hunts"] + ".jsonl"),
              encoding="utf-8") as f:
        pinned = {r["seed"]: r for r in map(json.loads, f)}
    eng = make_swarm_engine(setup, walks=config["walks"],
                            max_depth=config["max_depth"], batch=1024,
                            hunt=False)
    return (eng, initial_states(setup), pinned,
            jax.jit(build_expand(setup.dims)))


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_replay_actions_is_the_old_loop_on_the_pinned_hunts(hunter, seed):
    eng, roots, pinned, expand1 = hunter
    asked = []
    real = eng.replay_actions
    eng.replay_actions = lambda root, actions: (
        asked.append((root, list(actions))) or real(root, actions))
    try:
        res = eng.run(roots, seed=seed)
    finally:
        del eng.replay_actions
    want = pinned[seed]
    assert (res.violation_step, res.violation_walk,
            f"{res.violation.fingerprint:#018x}",
            len(res.violation_trace)) == (
        want["latch_step"], want["walk"], want["fingerprint"],
        want["trace_len"])
    ((root, actions),) = asked
    assert res.violation_trace == old_replay_actions(eng, expand1, root,
                                                     actions)
    assert eng._counts["reconstruct_scans"] == 1
    assert eng._counts["reconstruct_steps"] == 0
    # ... and it stops where the old loop stopped: before a negative id,
    # before an id that is not enabled, at once on either as the first.
    cut = len(actions) // 2
    en = np.asarray(expand1(encode_state(
        res.violation_trace[cut][1], eng.dims))[1])
    for bad in (-1, int(np.argmin(en))):
        for at in (cut, 0):
            acts = actions[:at] + [bad] + actions[at:]
            got = eng.replay_actions(root, acts)
            assert got == old_replay_actions(eng, expand1, root, acts)
            assert got == res.violation_trace[:at + 1]
    assert eng.replay_actions(root, []) == [(-1, root)]


# -- (e) the two bodies ---------------------------------------------------------

@pytest.mark.parametrize("walked", CFGS[:1] + CFGS[3:], indirect=True)
def test_the_v1_and_v2_bodies_agree_row_for_row(walked):
    """64 (state, instance) pairs drawn from the store's own traces: the
    successor's packed row, its key and the enabled flag, by the body the
    engines ship and by the other."""
    eng, _fps = walked
    dims = eng.dims
    rng = np.random.RandomState(42)
    fps = np.sort(eng.trace.export()[0])
    states = [eng.replay(int(fp))[-1][1] for fp in rng.choice(fps, 16)]
    steps = {body: jax.jit(jax.vmap(build_replay_step(dims, body)))
             for body in ("v1", "v2")}
    pairs = []
    for s in states:
        en = np.asarray(eng._expand1(encode_state(s, dims))[1])
        on, off = np.flatnonzero(en), np.flatnonzero(~en)
        pairs += [(s, int(g)) for g in rng.choice(on, 3)]
        pairs += [(s, int(rng.choice(off)))]
    assert len(pairs) == 64
    batch = StateBatch(*(np.stack(cols) for cols in zip(
        *(encode_state(s, dims) for s, _g in pairs))))
    gs = np.asarray([g for _s, g in pairs], np.int32)
    out = {}
    for body, fn in steps.items():
        succ, hi, lo, en = fn(batch, gs)
        rows = np.asarray(jax.vmap(flatten_state, (0, None))(succ, dims))
        out[body] = (rows, np.asarray(hi), np.asarray(lo), np.asarray(en))
    on = out["v1"][3]
    assert (on == out["v2"][3]).all() and on.sum() == 48
    for a, b in zip(out["v1"][:3], out["v2"][:3]):
        assert (a[on] == b[on]).all()
