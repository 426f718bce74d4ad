"""Compiles for the chip, without the chip (on-chip-measurement guide §2).

The TPU's compiler is installed here and compiles for a described
``v5e:2x2`` topology.  These tests keep the cheap ones at REAL widths —
``configs/MCraft_bounded.cfg``: 473-byte packed rows, 132 action
instances; the whole chunk also at ``configs/TPUraft.cfg`` (951-byte
rows, 224 instances, 48 slots), ``configs/MCraft_noleader.cfg`` (403-byte
rows: the verdict cell), ``configs/MCraft_safety.cfg`` (ten invariants on
the K lanes) and ``configs/reconfig3.cfg`` (the extra action families) —
so every later PR is guarded at no chip time: ``ops/fpset.py insert``
over the bench's 2^25-key table, the whole chunk program of ``BFSEngine``
at a small batch (and that it holds no gather expanded into a per-lane
loop, and that what an invariant's operations write has the lanes
minor-most), its ``ingest`` program, the two programs a seen-set growth
dispatches at the verdict cell's sizes, the trace flush's fetch programs
over the bench's trace buffers (the one-chip engine's, and the mesh's
over one chip's shards of the four-chip deployment), the swarm walk
chunk at 1,024 walks and at the size of the benchmark's
``mcraft3-hunt`` cell, the
mesh chunk and ingest over the four described chips (with the
owner-routed dedup's ``all-to-all``).

A compile that passes is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (only the worker
given this file loads the TPU compiler), and the persistent compile cache
is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = jax.ShapeDtypeStruct

# The bench's real shapes (bench.py, chip_smoke.py).
B, K = 2048, 32768
QUEUE, SEEN = 1 << 21, 1 << 25


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def setup():
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.utils.cfg import load_config
    s = load_config(os.path.join(REPO, "configs/MCraft_bounded.cfg"))
    assert state_width(s.dims) == 473 and s.dims.n_instances == 132
    return s


def compile_for(fn, sharding_of, *avals):
    """Lower ``fn`` at ``avals`` placed by ``sharding_of(aval)`` and
    compile — raises what the chip's compiler would raise."""
    placed = jax.tree.map(
        lambda a: S(a.shape, a.dtype, sharding=sharding_of(a)), avals)
    return jax.jit(fn).lower(*placed).compile()


# -- the XLA main path ----------------------------------------------------

def test_fpset_insert_over_the_real_table(one_chip):
    from raft_tla_tpu.ops import fpset
    k = 4096        # K=32,768 compiles too, in 25 s: a hand rehearsal
    seen = jax.eval_shape(lambda: fpset.empty(SEEN))
    c = compile_for(fpset.insert, lambda a: one_chip, seen,
                    S((k,), jnp.uint32), S((k,), jnp.uint32),
                    S((k,), jnp.bool_))
    assert c.memory_analysis().argument_size_in_bytes >= 8 * SEEN


def small_engine(cfg, width, instances, **kw):
    """``cfg``'s engine as ``auto`` resolves it, at a batch that compiles
    in seconds (the bench's 2048 takes a minute or two)."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.utils.cfg import load_config
    s = load_config(os.path.join(REPO, cfg))
    assert (state_width(s.dims), s.dims.n_instances) == (width, instances)
    size = dict(batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17)
    eng = make_engine(s, EngineConfig(**{**size, **kw}))
    assert eng._v2 is not None
    return eng


def invariant_results_over_the_lanes(text, lanes):
    """The results of rank 3 and more, over the ``lanes`` of a pass,
    of the fusions named for an invariant, each held to the lanes as
    its minor-most axis: ``pred[K,3,3]{2,1,0}`` (a row a lane, an axis
    of 3 padded to a 128-wide vector) was 3.9 ms a pass of ``TypeOK``
    alone until PR 38 made its reductions integer sums (PERF.md section
    6)."""
    named = [line.split(" fusion(")[0] for line in text.splitlines()
             if " fusion(" in line and re.search(
                 r'op_name="[^"]*/invariants/', line)]
    assert named, "no fusion is named for an invariant"
    wide = [shape for result in named for shape in re.findall(
        rf"\[{lanes},\d+(?:,\d+)+\]\{{\d+(?:,\d+)+", result)]
    assert all(shape.split("{")[1].startswith("0,") for shape in wide), wide
    return wide


@pytest.mark.parametrize("cfg, width, instances, invariants", [
    ("configs/MCraft_bounded.cfg", 473, 132, 1),
    ("configs/TPUraft.cfg", 951, 224, 1),
    ("configs/MCraft_noleader.cfg", 403, 132, 1),
    ("configs/MCraft_safety.cfg", 473, 132, 10),
    ("configs/reconfig3.cfg", 474, 114, 1),
    ("configs/reconfig3_safety.cfg", 474, 114, 10),
], ids=["mcraft3", "raft5", "noleader", "safety", "reconfig3",
        "reconfig3-safety"])
def test_v2_chunk_program_small_batch(cfg, width, instances, invariants,
                                      one_chip):
    """The whole BFSEngine chunk program (pipeline=auto -> v2, trace
    recording on) at every benchmark model's real widths, with the
    whole invariant suite on the K lanes, and with the reconfiguration
    variant's extra families.

    No ``while`` of the optimised program may be a gather XLA expanded
    into a loop over the lanes: the chunk's own loop and ``insert``'s
    probe loop are the only two.  ``actions2.dvec`` as a traced-start
    ``dynamic_slice`` was four of them at 5 servers (PR 28), each K trips
    a pass."""
    eng = small_engine(cfg, width, instances)
    assert len(eng.inv_names) == invariants
    c = compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals())
    assert c.memory_analysis().generated_code_size_in_bytes > 0
    loops = re.findall(r' while\(.*op_name="([^"]*)"', c.as_text())
    assert len(loops) >= 2, loops       # else the pattern found nothing
    assert not [name for name in loops if name.endswith("/gather")], loops
    wide = invariant_results_over_the_lanes(c.as_text(), eng._K)
    assert wide or invariants == 1      # the suite writes such tensors


@pytest.mark.parametrize("name, invariants", [
    ("reconfig3", 1), ("reconfig3-safety", 10)])
def test_reconfig3_chunk_at_the_cells_sizes(name, invariants, one_chip,
                                            capsys):
    """The chunk of ``configs/reconfig3.cfg`` as cell ``reconfig3`` runs
    it (benchmark/configs/reconfig3.json: B = 2048, queues of 4,194,304
    rows of 474 bytes, 2^25 keys, trace recording on) compiles for the
    described v5e, v2 with the variant's extra families; its arguments
    and temporaries, with the host loop's third queue beside them, fit
    the chip's 16.9 GB.  No gather but ``flatten``'s may have become a
    loop over the lanes, and what the two families cost ``lane_out``
    under ``extra`` stays their value's few operations.  And
    the chunk of ``configs/reconfig3_safety.cfg`` as ``reconfig3-safety``
    runs it: the same pools under the ten invariants, whose results over
    the K lanes lie lanes-minor under ``ReconfigDims`` too.  About a
    minute each here."""
    import json
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    width = config["shapes"]["row_bytes"]
    eng = small_engine(
        "configs/" + config["cfg_name"], width, 114, batch=config["batch"],
        queue_capacity=config["queue_capacity"],
        seen_capacity=config["seen_capacity"], record_trace=True)
    assert (eng._B, eng._K) == (B, K)
    assert len(eng.inv_names) == invariants
    c = compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals())
    m = c.memory_analysis()
    spare_queue = (eng._Q + eng._PAD) * width
    with capsys.disabled():
        print(f"\n{name} chunk at B={eng._B}, K={eng._K}: arguments "
              f"{m.argument_size_in_bytes} bytes, temporaries "
              f"{m.temp_size_in_bytes} bytes, spare queue {spare_queue} "
              f"bytes")
    assert invariant_results_over_the_lanes(c.as_text(), eng._K) \
        or invariants == 1
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + spare_queue) < 16.9e9, m
    text = c.as_text()
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert len(loops) >= 2, loops
    # PR 39's finding, at this size only (the small batch above has
    # none): ``flatten_state``'s read of the message value columns for
    # the high-byte plane (``st.msg[:, cols]``, 2-byte values) is a
    # gather XLA runs as a loop over the K lanes, twice a pass.  Queued
    # in PERF.md section 7, not repaired there; nothing else may be one,
    # the joint rule's scan least of all.
    per_lane = [name for name in loops if name.endswith("/gather")]
    assert len(per_lane) <= 2 and all(
        "/construct/flatten/" in name for name in per_lane), loops
    for site in ("masks", "construct/lane_out"):
        for scope in ("quorum", "extra"):
            assert re.search(rf"/while/body/{site}/[^\"]*\({scope}\)+/",
                             text), (site, scope)
    # PR 44: both families are ``LogAppend`` declarations, so under
    # ``extra`` ``lane_out`` runs their value alone (one scan of log[i]
    # by compare, select and sum).  By the compiler's own estimate that
    # was 17.7 % of ``lane_out`` in 55 operations, 12 of them writing
    # lanes-major for ``table[traced]`` reads; now a few operations,
    # none of either kind.
    import sys
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import hlo_parts
    ops, cycles, rows = hlo_parts.tally(text, eng._K, "lane_out", "extra")
    assert 0 < ops["scope", "all"] <= 16, rows
    assert cycles["scope", "all"] < 0.02 * cycles["lane_out", "all"], rows
    assert ops["scope", "lanes-major"] == 0, rows
    assert not [r for r in rows if r[-1].endswith("/gather")], rows


@pytest.mark.parametrize("cfg, width, instances", [
    ("configs/MCraft_bounded.cfg", 473, 132),
    ("configs/TPUraft.cfg", 951, 224),
], ids=["mcraft3", "raft5"])
def test_ingest_program_small_batch(cfg, width, instances, one_chip):
    """``BFSEngine``'s ingest program (roots and spilled rows into the
    next queue): ``correct`` forbids its compile inside a window, and a
    window never runs it, so only this compiles it for the chip."""
    eng = small_engine(cfg, width, instances)
    _q, i32, _, qnext, _, seen, _tbuf, _, _ = eng.chunk_avals()
    c = compile_for(eng._ingest, lambda a: one_chip,
                    S((eng._B, width), jnp.uint8), S((eng._B,), jnp.bool_),
                    qnext, i32, seen)
    assert c.memory_analysis().generated_code_size_in_bytes > 0
    assert "/insert/" in c.as_text()    # the chunk's stage names


@pytest.mark.parametrize("grown", [1 << 17, 1 << 18])
def test_seen_set_growth_programs_at_the_verdict_cells_sizes(grown,
                                                             one_chip):
    """What ``_grow_precompiled`` dispatches, as the verdict cell runs it
    (``MCraft_noleader.cfg``: batch 256, 16,384-row queues, a 65,536-key
    table that doubles twice a check): the rehash's ``rebuild_piece`` of
    2^15 keys into the grown table, then the chunk at that table."""
    from raft_tla_tpu.ops import fpset
    table = jax.eval_shape(lambda: fpset.empty(grown))
    k = 1 << 15                         # fpset.from_host_keys' piece
    c = compile_for(fpset.rebuild_piece, lambda a: one_chip, table,
                    S((3,), jnp.int32), S((k,), jnp.uint32),
                    S((k,), jnp.uint32), S((k,), jnp.bool_))
    # The staged rounds: a loop a width, the piece's, a quarter of it
    # and a thirty-second.
    assert c.as_text().count(" while(") >= 3
    eng = small_engine("configs/MCraft_noleader.cfg", 403, 132, batch=256,
                       seen_capacity=grown)
    assert (eng._B, eng._Q, eng._seen_cap) == (256, 1 << 14, grown)
    compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals())


def test_trace_flush_fetch_programs_at_the_bench_queue(setup, one_chip):
    """The trace flush's three fetch programs (engine/bfs.py ``_fetch``:
    five slices of a fixed length at a traced start) over trace buffers
    as long as the bench's 2^21-row queue makes them."""
    from raft_tla_tpu.engine.bfs import FLUSH_PIECES, EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    eng = make_engine(setup, EngineConfig(
        batch=64, queue_capacity=QUEUE, seen_capacity=1 << 17))
    tbuf = eng.chunk_avals()[6]
    assert tbuf[0].shape[0] > QUEUE
    assert eng._fetch_lens == list(FLUSH_PIECES)
    for length in eng._fetch_lens:
        c = compile_for(
            lambda t, start, n=length: eng._fetch(t, start, n),
            lambda a: one_chip, tbuf, S((), jnp.int32))
        assert c.memory_analysis().output_size_in_bytes >= 20 * length
        assert " while(" not in c.as_text()


def test_swarm_walk_chunk_1024_walks(one_chip):
    from raft_tla_tpu.engine.check import (resolve_constraint,
                                           resolve_invariants)
    from raft_tla_tpu.engine.swarm import SwarmEngine
    from raft_tla_tpu.utils.cfg import load_config
    s = load_config(os.path.join(REPO, "configs/MCraft_noleader.cfg"))
    eng = SwarmEngine(s.dims, invariants=resolve_invariants(s),
                      constraint=resolve_constraint(s), walks=1024,
                      max_depth=64, batch=1024)
    compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals(1))


def test_swarm_walk_chunk_at_the_hunt_cells_size(one_chip, tmp_path):
    """The walk chunk as the benchmark's ``mcraft3-hunt`` cell builds it
    (``benchmark/configs/mcraft3-swarm.json``: its cfg text, its depth,
    ``make_swarm_engine``): one slice of the cell's width, the hunt
    observatory's lanes x lanes prior included, fits a v5e."""
    import json
    from raft_tla_tpu.engine.check import make_swarm_engine
    from raft_tla_tpu.utils.cfg import load_config
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mcraft3-swarm.json"), encoding="utf-8") as f:
        config = json.load(f)
    cfg = tmp_path / config["cfg_name"]
    cfg.write_text("\n".join(config["cfg_text"]) + "\n")
    eng = make_swarm_engine(load_config(str(cfg)),
                            max_depth=config["max_depth"])
    assert (eng.walks, eng.batch, eng.hunt) == (
        config["walks"], config["batch"], True)
    c = compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals(1))
    m = c.memory_analysis()
    print(f"walk chunk, {eng.batch} lanes of {eng.walks} walks, depth "
          f"{eng.max_depth}: {m}")
    slices = -(-eng.walks // eng.batch)
    assert (slices * m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes) < 16e9


def small_mesh_engine(cfg, topo, **kw):
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    from raft_tla_tpu.utils.cfg import load_config
    eng = make_engine(
        load_config(os.path.join(REPO, cfg)),
        EngineConfig(batch=64, queue_capacity=1 << 14,
                     seen_capacity=1 << 17, **kw),
        engine_cls=functools.partial(MeshBFSEngine,
                                     devices=list(topo.devices)))
    assert eng.n_dev == 4
    return eng, (lambda a: NamedSharding(eng.mesh,
                                         P("x") if a.ndim else P()))


@pytest.mark.parametrize("cfg, width", [
    ("configs/MCraft_bounded.cfg", 473), ("configs/TPUraft.cfg", 951),
], ids=["mcraft3", "raft5"])
def test_mesh_chunk_over_four_described_chips(cfg, width, topo):
    """MeshBFSEngine's chunk for a Mesh of the topology's four devices,
    at both models' widths: it partitions under ``shard_map``, and the
    owner-routed dedup is an all-to-all."""
    eng, over_mesh = small_mesh_engine(cfg, topo, record_trace=False)
    assert eng._sw == width
    c = compile_for(eng._chunk, over_mesh, *eng.chunk_avals())
    assert "all-to-all" in c.as_text()


def test_mesh_ingest_over_four_described_chips(topo):
    """``sharded_ingest`` (the roots, and a resume's spilled rows) at
    473-byte rows with trace recording on: its insert is owner-routed
    too."""
    eng, over_mesh = small_mesh_engine("configs/MCraft_bounded.cfg", topo)
    (qav, counts, _, _, _, shi, slo, ssize, tbuf, tcount,
     _) = eng.chunk_avals()
    c = compile_for(eng._ingest, over_mesh,
                    S((4, eng._B, 473), jnp.uint8),
                    S((4, eng._B), jnp.bool_),
                    qav, counts, shi, slo, ssize, tbuf, tcount)
    assert "all-to-all" in c.as_text()


def test_mesh_key_inserts_over_four_described_chips(topo):
    """The shard rebuild's program (``_insert_keys``:
    ``fpset.rebuild_piece`` under ``shard_map``, a resume's and a
    growth's) at the smallest piece: every chip fills its own shard, so
    no collective, and the rounds are staged over three widths."""
    from raft_tla_tpu.parallel.mesh import KEY_PIECE_MIN
    eng, over_mesh = small_mesh_engine("configs/MCraft_bounded.cfg", topo,
                                       record_trace=False)
    shard = S((4, eng._CL), jnp.uint32)
    keys = S((4, KEY_PIECE_MIN), jnp.uint32)
    text = compile_for(
        eng._insert_keys, over_mesh, shard, shard, S((4,), jnp.int32),
        keys, keys, S((4, KEY_PIECE_MIN), jnp.bool_),
        S((4, 3), jnp.int32)).as_text()
    assert not re.search(r"all-|collective", text)
    assert text.count(" while(") >= 3


def deployment_mesh_engine(setup, topo):
    """The engine of configuration ``mcraft3-mesh4``
    (``benchmark/configs/mcraft3-mesh4.json``: batch 2,048 a chip,
    16,777,216 queue rows and 2^27 keys over the four chips, trace
    recording on) over the described chips."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    eng = make_engine(
        setup, EngineConfig(batch=B, queue_capacity=1 << 24,
                            seen_capacity=1 << 27, record_trace=True),
        engine_cls=functools.partial(MeshBFSEngine,
                                     devices=list(topo.devices)))
    assert (eng.n_dev, eng._QL, eng._CL) == (4, 1 << 22, 1 << 25)
    return eng


def test_mesh_chunk_at_the_four_chip_deployments_sizes(setup, topo):
    """The mesh chunk as configuration ``mcraft3-mesh4`` runs it compiles
    for the described v5e:2x2; its arguments and
    temporaries fit one chip's 16 GB with the host loop's third queue
    beside them; the routed dedup is three ``all-to-all``s (the two
    fingerprint halves out, the novelty bits back).  About 50 s here."""
    eng = deployment_mesh_engine(setup, topo)
    c = compile_for(
        eng._chunk,
        lambda a: NamedSharding(eng.mesh, P("x") if a.ndim else P()),
        *eng.chunk_avals())
    m = c.memory_analysis()
    spare_queue = (eng._QL + eng._PAD) * 473
    # Per chip: the compiler's figures are one partition's.
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + spare_queue) < 16e9, m
    assert len(re.findall(r"= \S+ all-to-all(?:-start)?\(",
                          c.as_text())) == 3
    invariant_results_over_the_lanes(c.as_text(), K)


def test_mesh_trace_flush_fetch_programs_at_the_deployments_buffers(
        setup, topo, one_chip):
    """The mesh's trace flush (parallel/mesh.py ``_fetch_shard``: five
    slices of a fixed length at a traced start of ONE chip's ``[1, TA]``
    shards, a one-device program) at the three lengths, over a chip's
    4.26 M-entry trace buffers of ``mcraft3-mesh4``: no collective, no
    loop, and a piece is what it moves."""
    from raft_tla_tpu.engine.bfs import FLUSH_PIECES
    eng = deployment_mesh_engine(setup, topo)
    shard = tuple(S((1,) + t.shape[1:], t.dtype)
                  for t in eng.chunk_avals()[8])
    assert shard[0].shape == (1, (1 << 22) + 2 * K)
    assert eng._fetch_lens == list(FLUSH_PIECES)
    for length in eng._fetch_lens:
        c = compile_for(
            lambda t, start, n=length: eng._fetch(t, start, n),
            lambda a: one_chip, shard, S((), jnp.int32))
        assert 20 * length <= c.memory_analysis().output_size_in_bytes \
            < 40 * length
        assert not re.search(r" while\(|all-|collective", c.as_text())
