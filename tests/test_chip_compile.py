"""Compiles for the chip, without the chip (on-chip-measurement guide §2).

The TPU's compiler is installed here and compiles for a described
``v5e:2x2`` topology.  These tests keep the cheap ones at REAL widths —
``configs/MCraft_bounded.cfg``: 473-byte packed rows, 132 action
instances; the whole chunk also at ``configs/TPUraft.cfg``: 951-byte
rows, 224 instances, 48 slots — so every later PR is guarded at no chip
time:

- the XLA main path: ``ops/fpset.py insert`` over the bench's 2^25-key
  table, the whole v2 chunk program of ``BFSEngine`` at a small batch
  (and that it holds no gather expanded into a per-lane loop),
  the trace flush's fetch programs over the bench's trace buffers,
  the swarm walk chunk at 1,024 walks, the mesh chunk over the four
  described chips (with the owner-routed dedup's ``all-to-all``);
- one case per Pallas kernel.  None of them compiles today (PR 24 moved
  their scalars to SMEM; what remains is each kernel's design against the
  chip's tiling).  The contract is that the compiler's refusal PROPAGATES:
  asked for on the TPU, a refused kernel is an error carrying the
  compiler's words, never a substituted XLA stage.  When a kernel is
  repaired its case here fails with DID NOT RAISE: turn it into a plain
  ``compile_for`` call then.

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


@pytest.mark.parametrize("cfg, width, instances", [
    ("configs/MCraft_bounded.cfg", 473, 132),
    ("configs/TPUraft.cfg", 951, 224),
], ids=["mcraft3", "raft5"])
def test_v2_chunk_program_small_batch(cfg, width, instances, one_chip):
    """The whole BFSEngine chunk program (pipeline=auto -> v2, trace
    recording on) at both benchmark models' real widths; batch 64
    compiles in seconds, the bench's 2048 in a minute or two (hand
    rehearsal, CHANGES.md).

    No ``while`` of the optimised program may be a gather XLA expanded
    into a loop over the lanes: the chunk's own loop and ``insert``'s
    probe loop are the only two.  ``actions2.dvec`` as a traced-start
    ``dynamic_slice`` was four of them at 5 servers (PR 28), each K trips
    a pass."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.utils.cfg import load_config
    s = load_config(os.path.join(REPO, cfg))
    assert (state_width(s.dims), s.dims.n_instances) == (width, instances)
    eng = make_engine(s, EngineConfig(
        batch=64, queue_capacity=1 << 14, seen_capacity=1 << 17))
    assert eng._v2 is not None
    c = compile_for(eng._chunk, lambda a: one_chip, *eng.chunk_avals())
    assert c.memory_analysis().generated_code_size_in_bytes > 0
    loops = re.findall(r' while\(.*op_name="([^"]*)"', c.as_text())
    assert len(loops) >= 2, loops       # else the pattern found nothing
    assert not [name for name in loops if name.endswith("/gather")], loops


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


def test_mesh_chunk_over_four_described_chips(setup, topo):
    """MeshBFSEngine's chunk for a Mesh of the topology's four devices:
    it partitions, and the owner-routed dedup is an all-to-all."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    eng = make_engine(
        setup, EngineConfig(batch=64, queue_capacity=1 << 14,
                            seen_capacity=1 << 17, record_trace=False),
        engine_cls=functools.partial(MeshBFSEngine,
                                     devices=list(topo.devices)))
    assert eng.n_dev == 4
    c = compile_for(
        eng._chunk,
        lambda a: NamedSharding(eng.mesh, P("x") if a.ndim else P()),
        *eng.chunk_avals())
    assert "all-to-all" in c.as_text()


def test_mesh_chunk_at_the_four_chip_deployments_sizes(setup, topo):
    """The mesh chunk as configuration ``mcraft3-mesh4`` runs it
    (``benchmark/configs/mcraft3-mesh4.json``: batch 2,048 a chip,
    16,777,216 queue rows and 2^27 keys over the four chips, trace
    recording on) compiles for the described v5e:2x2; its arguments and
    temporaries fit one chip's 16 GB with the host loop's third queue
    beside them; the routed dedup is three ``all-to-all``s (the two
    fingerprint halves out, the novelty bits back).  About 50 s here."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    eng = make_engine(
        setup, EngineConfig(batch=B, queue_capacity=1 << 24,
                            seen_capacity=1 << 27, record_trace=True),
        engine_cls=functools.partial(MeshBFSEngine,
                                     devices=list(topo.devices)))
    assert (eng.n_dev, eng._QL, eng._CL) == (4, 1 << 22, 1 << 25)
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


# -- the Pallas kernels -----------------------------------------------------

def _compact(setup):
    from raft_tla_tpu.ops import compact_pallas
    g = setup.dims.n_instances
    return (lambda en, ks: compact_pallas._compact_jit(en, ks, K, False),
            (S((B, g), jnp.bool_), S((K,), jnp.int32)))


def _enqueue(setup):
    from raft_tla_tpu.ops import enqueue_pallas
    return (lambda q, nc, kr, enq: enqueue_pallas._enqueue_jit(
                q, nc, kr, enq, False),
            (S((QUEUE + K, 473), jnp.uint8), S((), jnp.int32),
             S((K, 473), jnp.uint8), S((K,), jnp.bool_)))


def _table():
    from raft_tla_tpu.ops import fpset
    return jax.eval_shape(lambda: fpset.empty(SEEN))


def _insert(setup):
    from raft_tla_tpu.ops import fpset_pallas
    return (lambda s, h, l, v: fpset_pallas._insert_padded(
                s, h, l, v, False),
            (_table(), S((K,), jnp.uint32), S((K,), jnp.uint32),
             S((K,), jnp.bool_)))


def _fused_tail(setup):
    from raft_tla_tpu.ops import fused_tail_pallas
    return (lambda s, h, l, v, e, kr, q, nc:
            fused_tail_pallas._tail_padded(s, h, l, v, e, kr, q, nc,
                                           QUEUE, False),
            (_table(), S((K,), jnp.uint32), S((K,), jnp.uint32),
             S((K,), jnp.bool_), S((K,), jnp.bool_),
             S((K, 473), jnp.uint8), S((QUEUE + K, 473), jnp.uint8),
             S((), jnp.int32)))


def _v4_front(setup):
    from raft_tla_tpu.engine.check import (resolve_constraint,
                                           resolve_invariants)
    from raft_tla_tpu.models.actions2 import build_v2
    from raft_tla_tpu.ops import chunk_front_pallas
    front = chunk_front_pallas.build_front(
        dims=setup.dims, v2=build_v2(setup.dims),
        constraint=resolve_constraint(setup),
        inv_fns=list(resolve_invariants(setup).values()),
        B=B, G=setup.dims.n_instances, K=K, por_mask=None,
        por_priority=None, interpret=False)
    return front, (S((B, 473), jnp.uint8), S((B,), jnp.bool_))


#: kernel -> (builder, the compiler's refusal at the bench's shapes).
#: CHANGES.md (PR 24) quotes each in full.
REFUSED = {
    "compact": (_compact, "Unimplemented primitive in Pallas TPU "
                          "lowering for KernelType.TC: cumsum"),
    "enqueue": (_enqueue, "Failed to prove that a tile index in "
                          "dimension 0 is divisible by the tiling (8)"),
    "insert": (_insert, "Slice shape along dimension 0 must be aligned "
                        "to tiling (1024), but is 1"),
    "fused_tail": (_fused_tail, "Slice shape along dimension 0 must be "
                                "aligned to tiling (1024), but is 1"),
    "v4_front": (_v4_front, "Shape mismatch in input, indices and "
                            "output"),
}


@pytest.mark.parametrize("kernel", sorted(REFUSED))
def test_pallas_kernel_refusal_propagates(kernel, setup, one_chip):
    build, words = REFUSED[kernel]
    fn, avals = build(setup)
    with pytest.raises(Exception) as exc:
        compile_for(fn, lambda a: one_chip, *avals)
    assert words in str(exc.value), str(exc.value)[:600]


@pytest.mark.parametrize("pipeline", ["v3", "v4"])
def test_plan_on_the_chip_raises_instead_of_substituting(pipeline,
                                                         monkeypatch):
    """resolve_plan with ``interpret=False`` (what it resolves to when
    the platform is the TPU): a Pallas stage that cannot be built is an
    error with the compiler's message, not an XLA stage and a reason
    string.  The same failure in interpret mode still degrades."""
    from raft_tla_tpu.ops import (compact_pallas, fused_tail_pallas,
                                  pipeline_v3, pipeline_v4)

    def refuse(*a, **k):
        raise NotImplementedError("mosaic says no")

    monkeypatch.setattr(compact_pallas, "build_compactor", refuse)
    monkeypatch.setattr(fused_tail_pallas, "insert_enqueue", refuse)
    resolve = (pipeline_v3 if pipeline == "v3"
               else pipeline_v4).resolve_plan
    kw = dict(Q=1 << 10, sw=473, force={"compact": "pallas"})
    with pytest.raises(NotImplementedError, match="mosaic says no"):
        resolve(32, 132, 512, interpret=False, **kw)
    plan = resolve(32, 132, 512, interpret=True, **kw)
    assert plan.stages["compact"] == "xla"
    assert "mosaic says no" in plan.reasons["compact"]
