"""Raft's safety suite as a deployment (``configs/MCraft_safety.cfg``,
benchmark configuration ``mcraft3-safety``): one way to build its engine,
the plain reference's ten predicates against the kernels and their
mirrors, the reference's witness makers, witnesses through the chunk
program, the parts of ``construct`` named in the programs, and the count
of the lanes the suite runs on.

CPU, small sizes.  The reference is ``benchmark/reference`` (``safety.py``,
``oracle.py``), which imports nothing of the program.
"""

import dataclasses
import functools
import os
import random
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine import checkpoint as ckpt_mod  # noqa: E402
from raft_tla_tpu.engine import chunk as chunk_mod  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import (initial_states,  # noqa: E402
                                       make_engine)
from raft_tla_tpu.models import smoke  # noqa: E402
from raft_tla_tpu.models.invariants import (build_type_ok,  # noqa: E402
                                            type_ok_py)
from raft_tla_tpu.models.pystate import PyState  # noqa: E402
from raft_tla_tpu.models.safety import (SAFETY_INVARIANTS,  # noqa: E402
                                        SAFETY_INVARIANTS_PY)
from raft_tla_tpu.models.schema import (encode_state,  # noqa: E402
                                        stack_states)
from raft_tla_tpu.parallel.mesh import MeshBFSEngine  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

CFG = os.path.join(REPO, "configs", "MCraft_safety.cfg")
CONFIG = lib.load_json("configs", "mcraft3-safety.json")
SUITE = CONFIG["invariants"]
SAFETY = SUITE[1:]
SEED = 2147534101


def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 14,
                        seen_capacity=1 << 17, **kw)


@pytest.fixture(scope="module")
def setup():
    return load_config(CFG, n_msg_slots=CONFIG["n_msg_slots"])


@pytest.fixture(scope="module")
def ref():
    safety_window = lib.load_module("traffic", "safety_window")
    return safety_window.safety_reference(CONFIG)


@pytest.fixture(scope="module")
def reachable(ref):
    """Every state of levels 0-6 of the space, by the reference."""
    res = ref.oracle.bfs([ref.pystate.init_state(ref.dims)], ref.dims,
                         constraint=ref.constraint, max_levels=6)
    return list(res.parent)


def to_program(s) -> PyState:
    return PyState(**{f.name: getattr(s, f.name)
                      for f in dataclasses.fields(PyState)})


def kernel_and_mirror(name, dims):
    if name == "TypeOK":
        return build_type_ok(dims), type_ok_py
    return SAFETY_INVARIANTS[name](dims), SAFETY_INVARIANTS_PY[name]


# -- (1) one normal path -----------------------------------------------------

def test_the_cfg_resolves_to_v2_and_the_ten_in_order(setup, tmp_path):
    """``check configs/MCraft_safety.cfg`` as ``cli.py`` builds it: the
    ten invariants in cfg order, pipeline v2, and, since a suite that
    holds removes no state, MCraft_bounded.cfg's levels."""
    assert CONFIG["cfg_text"] == open(CFG, encoding="utf-8").read() \
        .rstrip("\n").split("\n")
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(setup, EngineConfig(
        batch=256, queue_capacity=1 << 16, seen_capacity=1 << 18,
        max_diameter=6, events_out=ev))
    assert eng.inv_names == SUITE
    res = eng.run(initial_states(setup))
    assert (res.pipeline, res.stop_reason, res.violation) == (
        "v2", "diameter_budget", None)
    pinned = lib.load_pinned(CONFIG["pinned"])
    assert pinned == lib.load_pinned("mcraft3")
    assert lib.level_rows(lib.read_events(ev)) == {
        lv: pinned[lv] for lv in range(7)}
    # (6) the suite ran on K lanes of every pass, as the loop counted.
    end = lib.read_events(ev)[-1]
    assert end["inv_lanes"] == eng._K * end["passes"] > 0
    levels = [e for e in lib.read_events(ev)
              if e["event"] == "level_complete"]
    assert sum(e["inv_lanes"] for e in levels) == end["inv_lanes"]


def test_the_mesh_counts_every_chips_lanes(setup, tmp_path):
    ev = str(tmp_path / "ev.jsonl")
    eng = make_engine(setup, small(max_diameter=4, events_out=ev),
                      engine_cls=functools.partial(
                          MeshBFSEngine, devices=jax.devices()[:4]))
    eng.run(initial_states(setup))
    end = lib.read_events(ev)[-1]
    assert end["inv_lanes"] == 4 * eng._K * end["passes"] > 0
    # An engine without invariants evaluates none.
    bare = MeshBFSEngine(setup.dims, config=small(max_diameter=2),
                         devices=jax.devices()[:4])
    bare.run(initial_states(setup))
    assert bare.metrics.counter_value("engine/inv_lanes") == 0


# -- (2) the reference's predicates against kernels and mirrors --------------

@pytest.mark.parametrize("name", SUITE)
def test_reference_predicate_equals_kernel_and_mirror(name, setup, ref,
                                                      reachable):
    """On reachable states (where all hold) and on unstructured random
    ones (where each fails often).  ``TypeOK`` is compared where the
    fixed-width encoding can hold the state at all: the kernel checks
    what the encoding does not force, the reference the TLA+ types, and
    the random states' negative message fields are outside both."""
    dims = setup.dims
    pool = smoke.random_states(dims, 400, seed=5) + [
        to_program(s) for s in random.Random(1).sample(reachable, 300)]
    if name == "TypeOK":
        pool = [s for s in pool if all(
            isinstance(x, tuple) or x >= 0 for m, _c in s.messages
            for x in m)]
    kernel, mirror = kernel_and_mirror(name, dims)
    got = np.asarray(jax.vmap(kernel)(
        stack_states([encode_state(s, dims) for s in pool])))
    want = np.array([ref.safety.INVARIANTS[name](
        lib.to_reference_state(s, ref.pystate), ref.dims) for s in pool])
    assert (got == want).all(), pool[int(np.argmax(got != want))]
    assert [bool(mirror(s, dims)) for s in pool] == want.tolist()
    assert want[-300:].all()
    if name != "TypeOK":
        assert 0 < want[:400].sum() < 400


# -- (3) the witness makers --------------------------------------------------

@pytest.mark.parametrize("name", SAFETY)
def test_a_witness_fails_its_invariant_and_none_before(name, setup, ref,
                                                       reachable):
    rng = random.Random(f"{SEED}:{name}")
    made = [w for w in (
        ref.safety.witness(name, reachable[rng.randrange(len(reachable))],
                           rng, ref.dims, SUITE, ref.constraint)
        for _ in range(60)) if w is not None]
    assert len(set(made)) >= 10
    batch = stack_states([encode_state(to_program(w), setup.dims)
                          for w in made])
    verdicts = {n: np.asarray(jax.vmap(kernel_and_mirror(
        n, setup.dims)[0])(batch)) for n in SUITE}
    upto = SUITE.index(name)
    for n in SUITE[:upto]:
        assert verdicts[n].all(), n
    assert not verdicts[name].any()
    for w in made:
        assert ref.constraint(w, ref.dims)
        assert ref.safety.first_failing(w, SUITE, ref.dims) == name
        assert ref.safety.first_failing(w, SUITE[:upto], ref.dims) is None


# -- (4) witnesses through the chunk program ---------------------------------

@pytest.fixture(scope="module")
def warm(setup, tmp_path_factory):
    """An engine walked to level 4 at batch 64, and the snapshot it wrote
    there."""
    states = str(tmp_path_factory.mktemp("states"))
    eng = make_engine(setup, small(
        checkpoint_dir=states, checkpoint_every=4, max_diameter=4,
        checkpoint_interval_seconds=0.0))
    eng.run(initial_states(setup))
    return eng, ckpt_mod.load(ckpt_mod.latest(states))


@pytest.mark.parametrize("name", SAFETY)
def test_a_resumed_witness_frontier_stops_under_its_name(name, setup, ref,
                                                         reachable, warm):
    """What the benchmark's kind does at the cell's size: the witness
    parents as the frontier of a snapshot, resumed by the warm engine.
    The chunk program (not the root check, which never sees a resumed
    frontier) must stop on a successor, under the witnesses' name."""
    eng, ck = warm
    safety_window = lib.load_module("traffic", "safety_window")
    made = ref.safety.witness_parents(name, reachable, 3, SEED, ref.dims,
                                      SUITE, ref.constraint)
    assert len(made) == 3
    parents = [w for w, _failing in made]
    eng.config.max_diameter = ck.diameter + 1
    calls = eng.metrics.counter_value("engine/chunk_calls")
    res = eng.run(resume=safety_window.witness_snapshot(ck, setup, parents))
    assert eng.metrics.counter_value("engine/chunk_calls") == calls + 1
    assert res.stop_reason == "violation"
    assert res.violation.invariant == name
    got = lib.to_reference_state(res.violation.state, ref.pystate)
    assert got in set().union(*(failing for _w, failing in made))
    steps = [lib.to_reference_state(s, ref.pystate)
             for _g, s in eng.replay(res.violation.fingerprint)]
    assert len(steps) == 2 and steps[0] in parents and steps[1] == got
    assert got in ref.oracle.successor_set(steps[0], ref.dims)


# -- (5) the parts of construct, named in the programs ------------------------

def lowered_chunk_text(cfg_name: str, mesh: bool = False) -> str:
    setup = load_config(os.path.join(REPO, "configs", cfg_name))
    if not mesh:
        eng = make_engine(setup, small())
        return eng._chunk.lower(*eng.chunk_avals()).as_text(debug_info=True)
    eng = make_engine(setup, small(), engine_cls=functools.partial(
        MeshBFSEngine, devices=jax.devices()[:4]))
    av = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(
                eng.mesh, P("x") if a.ndim else P())),
        eng.chunk_avals())
    return eng._chunk.lower(*av).as_text(debug_info=True)


@pytest.mark.parametrize("cfg_name,mesh", [
    ("MCraft_bounded.cfg", False), ("TPUraft.cfg", False),
    ("MCraft_bounded.cfg", True), ("MCraft_safety.cfg", False)])
def test_the_parts_of_construct_are_named_in_the_chunk(cfg_name, mesh):
    text = lowered_chunk_text(cfg_name, mesh)
    for part in chunk_mod.CONSTRUCT_PARTS:
        assert f"while/body/construct/{part}/" in text, part
    names = SUITE if cfg_name == "MCraft_safety.cfg" else ["TypeOK"]
    for name in names:
        assert f"construct/invariants/vmap({name})/" in text, name
    # No part's name is a stage's: every operation still belongs to the
    # stage the benchmark's readers gave it.
    stages = lib.load_module("readers", "stages")
    mesh_reader = lib.load_module("readers", "mesh")
    walk = lib.load_module("readers", "walk")
    new = set(chunk_mod.CONSTRUCT_PARTS) | set(SUITE)
    assert not new & (set(stages.NAMED) | set(mesh_reader.MESH_STAGES))
    assert new & set(walk.STAGES) == {"lane_out"}   # the walk chunk's own
    assert walk.stage_of("jit(chunk_fn)/while/body/latch/invariants/"
                         "vmap(NoLeaderElected)/ne:") == "latch"


def test_the_stage_tag_moved_with_the_names():
    """Scope names are not in the compile-cache key: the parent's tag was
    ``s1``, and an executable cached under it names no part."""
    assert chunk_mod.STAGES_TAG not in ("s0", "s1")
    text = lowered_chunk_text("MCraft_safety.cfg")
    assert f'stages_tag = "{chunk_mod.STAGES_TAG}"' in text


# -- a run ends where its wall is taken ---------------------------------------

@pytest.mark.parametrize("stop,levels,distinct,generated", [
    ("diameter_budget", [1, 3, 18, 79, 318, 1218], 2300, 5616),
    ("exhausted", [1, 0], 4, 6),
])
def test_a_run_copies_no_frontier_at_its_end(setup, stop, levels, distinct,
                                             generated):
    """Until PR 48 ``run()`` ended by copying the frontier it stopped on
    to the host (phase ``frontier_fetch``, into an attribute: 1,218 rows
    here, 15-17 k a smoke check, 260-397 MB a deep window) for profiling
    scripts PR 46 deleted.  Stopped on a budget or exhausted (from a root
    whose every successor, a ``Timeout`` into term 4, the constraint
    prunes), the run leaves no such phase, and counts what the parent's
    run counted."""
    (root,) = initial_states(setup)
    if stop == "exhausted":
        eng = make_engine(setup, small())
        res = eng.run([dataclasses.replace(root, current_term=(3, 3, 3))])
    else:
        eng = make_engine(setup, small(max_diameter=5))
        res = eng.run([root])
    assert (res.stop_reason, res.levels, res.distinct, res.generated) == (
        stop, levels, distinct, generated)
    assert res.violation is None
    assert "frontier_fetch" not in res.phases
    assert "phase/frontier_fetch" not in eng.metrics.snapshot()["histograms"]
    assert not [name for name in vars(eng) if "frontier" in name]
    # What the run's ends are made of now.
    assert {"roots_encode", "root_check", "run_init", "warmup"} <= set(
        res.phases)
