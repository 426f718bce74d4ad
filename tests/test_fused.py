"""v3 fused Pallas chunk pipeline: interpret-mode bit-identity vs XLA.

Every Pallas stage of the v3 chunk (ops/compact_pallas.py,
ops/fused_tail_pallas.py, plus the two pre-existing kernels
ops/fpset_pallas.py and ops/enqueue_pallas.py) is proven bit-identical
to its XLA reference on CPU via interpret mode — property-style over
random batches at the kernel level, then end-to-end against pinned
MCraft_bounded oracle prefixes at the engine level (the chaos_check /
test_actions2 pattern).  The full pinned L0-L9 single-chip and
46,553-state mesh-dryrun differentials run under ``--pipeline v3`` as
well but take ~10 CPU-minutes in interpret mode; the depth-limited
versions here keep tier-1 affordable while covering the identical code
paths (same kernels, same plan, more steps at L9 — verified once at PR
time, recorded in CHANGES.md).

This module is listed in tests/conftest.py's trace-heavy-last reorder:
it builds several full engines (v2 + two v3 plans + a mesh), which is
exactly the trace-churn profile that destabilizes jaxlib's CPU client
when run before the big engine/mesh tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models.invariants import build_constraint
from raft_tla_tpu.ops import compact, compact_pallas, fpset
from raft_tla_tpu.ops import enqueue_pallas, fused_tail_pallas
from raft_tla_tpu.ops import pipeline_v3
from raft_tla_tpu.utils.cfg import load_config

_I32 = jnp.int32


# ---------------------------------------------------------------------------
# Kernel-level bit-identity (property-style over random batches).


def test_compact_pallas_bit_identical():
    """Pallas sequential-scan compaction vs BOTH XLA lowerings: same
    P/total/lane_id/kvalid on random masks across densities, including
    the progress-limited (fan-out > K) and all-dead corners."""
    B, G, K = 24, 132, 256
    xla_sc = compact.build_compactor(B, G, K, method="scatter")
    xla_ss = compact.build_compactor(B, G, K, method="searchsorted")
    pal = compact_pallas.build_compactor(B, G, K)
    rng = np.random.RandomState(7)
    for density in (0.0, 0.06, 0.3, 1.0):
        en = jnp.asarray(rng.rand(B, G) < density)
        want = tuple(np.asarray(x) for x in xla_sc(en))
        want_ss = tuple(np.asarray(x) for x in xla_ss(en))
        got = tuple(np.asarray(x) for x in pal(en))
        for w, ws, g in zip(want, want_ss, got):
            assert (w == ws).all()      # the two XLA methods agree...
            assert (w == g).all()       # ...and Pallas matches them


def test_fpset_pallas_bit_identical():
    """Sequential-grid Pallas insert vs the XLA sort+claim insert:
    identical is_new/size/fail and stored key SET over random duplicate-
    heavy batches (the ops/fpset_pallas.py contract, property-style)."""
    from raft_tla_tpu.ops import fpset_pallas
    rng = np.random.RandomState(3)
    s_x = fpset.empty(4096)
    s_p = fpset.empty(4096)
    for _ in range(4):
        pool = rng.randint(0, 300, size=(512, 2)).astype(np.uint32)
        qhi, qlo = jnp.asarray(pool[:, 0]), jnp.asarray(pool[:, 1])
        valid = jnp.asarray(rng.rand(512) < 0.8)
        s_x, new_x, fail_x = fpset.insert(s_x, qhi, qlo, valid)
        s_p, new_p, fail_p = fpset_pallas.insert(s_p, qhi, qlo, valid)
        assert (np.asarray(new_x) == np.asarray(new_p)).all()
        assert bool(fail_x) == bool(fail_p)
        assert int(s_x.size) == int(s_p.size)
        assert (np.sort(np.asarray(s_x.hi)) ==
                np.sort(np.asarray(s_p.hi))).all()
        assert (np.sort(np.asarray(s_x.lo)) ==
                np.sort(np.asarray(s_p.lo))).all()


def test_enqueue_pallas_live_rows_bit_identical():
    """Run-coalesced DMA append vs the scatter enqueue: identical live
    region [0, next_count + new_n) for random masks including empty,
    full, and sparse runs (trash regions differ by design — the
    'window' precedent)."""
    rng = np.random.RandomState(5)
    K, SW, Q = 128, 37, 512
    for density in (0.0, 0.06, 0.5, 1.0):
        krows = jnp.asarray(rng.randint(0, 255, (K, SW)), jnp.uint8)
        enq = jnp.asarray(rng.rand(K) < density)
        nc = jnp.int32(rng.randint(0, Q - K))
        got = enqueue_pallas.enqueue(
            jnp.zeros((Q + K, SW), jnp.uint8), nc, krows, enq)
        pos = nc + jnp.cumsum(enq.astype(_I32)) - 1
        pos = jnp.where(enq, pos, Q + jnp.arange(K, dtype=_I32))
        want = jnp.zeros((Q + K, SW), jnp.uint8).at[pos].set(krows)
        hi = int(nc) + int(enq.sum())
        assert (np.asarray(got)[:hi] == np.asarray(want)[:hi]).all()


def test_fused_tail_bit_identical_incl_trash():
    """The fused probe/insert->enqueue kernel vs the split XLA pair:
    is_new/fail/size/key set AND the whole queue buffer byte-for-byte —
    the fused tail reproduces even the scatter lowering's per-lane
    trash addresses.  1024 queries = multiple grid programs, so the
    running enqueue cursor is exercised across program boundaries."""
    rng = np.random.RandomState(11)
    K, SW, Q = 1024, 37, 1024
    for trial in range(3):
        pool = rng.randint(0, 400, size=(K, 2)).astype(np.uint32)
        qhi, qlo = jnp.asarray(pool[:, 0]), jnp.asarray(pool[:, 1])
        valid = jnp.asarray(rng.rand(K) < 0.8)
        cons = jnp.asarray(rng.rand(K) < 0.7)
        krows = jnp.asarray(rng.randint(0, 255, (K, SW)), jnp.uint8)
        nc = jnp.int32(rng.randint(0, 64))
        s_x, new_x, fail_x = fpset.insert(fpset.empty(8192),
                                          qhi, qlo, valid)
        enq = new_x & cons
        pos = nc + jnp.cumsum(enq.astype(_I32)) - 1
        pos = jnp.where(enq, pos, Q + jnp.arange(K, dtype=_I32))
        want_q = jnp.zeros((Q + K, SW), jnp.uint8).at[pos].set(krows)
        s_p, new_p, fail_p, got_q = fused_tail_pallas.insert_enqueue(
            fpset.empty(8192), qhi, qlo, valid, krows, cons,
            jnp.zeros((Q + K, SW), jnp.uint8), nc, Q)
        assert (np.asarray(new_x) == np.asarray(new_p)).all(), trial
        assert bool(fail_x) == bool(fail_p)
        assert int(s_x.size) == int(s_p.size)
        assert (np.sort(np.asarray(s_x.hi)) ==
                np.sort(np.asarray(s_p.hi))).all()
        assert (np.asarray(want_q) == np.asarray(got_q)).all(), trial


# ---------------------------------------------------------------------------
# Stage-plan resolution (automatic fallback is the contract).


def test_plan_policy_and_reasons():
    plan = pipeline_v3.resolve_plan(16, 132, 256, Q=512)
    # CPU policy: fused tail on, compact falls back with a reason.
    assert plan.stages["insert"] == "fused"
    assert plan.stages["enqueue"] == "fused"
    assert plan.tail is not None
    assert plan.stages["masks"] == "xla" and "masks" in plan.reasons
    assert plan.stages["fingerprint"] == "xla"
    if jax.devices()[0].platform != "tpu":
        assert plan.stages["compact"] == "xla"
        assert "interpret" in plan.reasons["compact"]
    mesh_plan = pipeline_v3.resolve_plan(16, 132, 256, Q=512, mesh=True)
    assert mesh_plan.tail is None
    assert mesh_plan.stages["insert"] == "xla"
    assert "collective" in mesh_plan.reasons["insert"]
    assert mesh_plan.stages["enqueue"] == "pallas"
    # force is honored where it is sound...
    forced = pipeline_v3.resolve_plan(16, 132, 256, Q=512,
                                      force={"compact": "pallas"})
    assert forced.stages["compact"] == "pallas"
    assert forced.compactor is not None
    # ...and the mesh's collective-stage constraints override it: a
    # forced fused insert or Pallas compact must NOT produce a plan
    # claiming a lowering the mesh engine would never run.
    mesh_forced = pipeline_v3.resolve_plan(16, 132, 256, Q=512, mesh=True,
                                           force={"insert": "fused",
                                                  "compact": "pallas"})
    assert mesh_forced.tail is None
    assert mesh_forced.stages["compact"] == "xla"
    assert mesh_forced.compactor is None
    # A typo'd force must raise, not silently fall back to the policy
    # (a "forced full-Pallas" differential would then pass vacuously).
    with pytest.raises(ValueError, match="v3_force_stages"):
        pipeline_v3.resolve_plan(16, 132, 256, Q=512,
                                 force={"compact": "Pallas"})
    with pytest.raises(ValueError, match="v3_force_stages"):
        pipeline_v3.resolve_plan(16, 132, 256, Q=512,
                                 force={"tail": "fused"})
    # Every non-Pallas stage records why — including explicitly forced
    # ones (the reasons dict rides EngineResult.fused_reasons).
    off = pipeline_v3.resolve_plan(16, 132, 256, Q=512,
                                   force={"compact": "xla",
                                          "insert": "xla"})
    assert off.reasons["compact"] == "forced to xla"
    assert off.reasons["insert"] == "forced to xla"


def test_plan_falls_back_when_stage_cannot_build(monkeypatch):
    """A Pallas stage that cannot even construct must degrade to XLA
    with a recorded reason, never fail the engine build."""
    from raft_tla_tpu.ops import compact_pallas as cp

    def boom(*a, **kw):
        raise RuntimeError("no mosaic for you")

    monkeypatch.setattr(cp, "build_compactor", boom)
    plan = pipeline_v3.resolve_plan(16, 132, 256, Q=512,
                                    force={"compact": "pallas"})
    assert plan.stages["compact"] == "xla"
    assert "no mosaic for you" in plan.reasons["compact"]
    assert plan.compactor is None


def test_v3_requires_v2_kernels():
    """pipeline='v3' on a dims variant without v2 kernels must raise
    (the v2 rule: never silently run the slow path when asked to fuse)."""
    from raft_tla_tpu.engine.bfs import _resolve_pipeline
    from raft_tla_tpu.models.actions2 import V2Unavailable
    from raft_tla_tpu.models.dims import RaftDims

    class NoV2(RaftDims):
        @property
        def extra_families(self):
            return (("Mystery", 2),)

    nov2 = NoV2(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    with pytest.raises(V2Unavailable):
        _resolve_pipeline("v3", nov2)


# ---------------------------------------------------------------------------
# Engine-level differentials (pinned oracle prefixes; the L0-L9 and
# mesh-dryrun full differentials are the same code paths at more depth).


def test_v3_engine_matches_v2_pinned_prefix():
    """Single-chip --pipeline v3 vs v2 through L6 (pinned oracle: 9,457
    cumulative distinct): same counts, levels, verdict, AND the same
    replayed counterexample-path trace links — the v3 trace buffer must
    record identical (parent fp, action) rows, not just totals.  Run
    for both the platform plan and the forced full-Pallas chain (the
    interpret-mode acceptance path)."""
    from raft_tla_tpu.models.pystate import init_state
    setup = load_config("configs/MCraft_bounded.cfg")
    dims = setup.dims

    results = {}
    fps = {}
    for name, pipe, force in (("v2", "v2", None),
                              ("v3", "v3", None),
                              ("v3full", "v3", {"compact": "pallas"})):
        eng = BFSEngine(
            dims, constraint=build_constraint(dims, setup.bounds),
            config=EngineConfig(batch=128, queue_capacity=1 << 14,
                                seen_capacity=1 << 16, record_trace=True,
                                check_deadlock=False, max_diameter=6,
                                pipeline=pipe, v3_force_stages=force))
        res = eng.run([init_state(dims)])
        results[name] = (res.distinct, res.generated, res.levels,
                         res.diameter)
        assert res.distinct == 9457      # pinned oracle L6 cumulative
        # Trace-content identity: the recorded (fp, parent fp, action)
        # link set must match across pipelines, not just the totals.
        tf, tp, ta = eng.trace.export()
        fps[name] = set(zip(tf.tolist(), tp.tolist(), ta.tolist()))
        if name.startswith("v3"):
            assert res.pipeline == "v3"
            assert res.fused_stages["insert"] == "fused"
    assert results["v2"] == results["v3"] == results["v3full"]
    assert fps["v2"] == fps["v3"] == fps["v3full"]


def test_v3_mesh_matches_v2():
    """Mesh --pipeline v3 (XLA collective stages + Pallas enqueue inside
    shard_map) vs v2 on the virtual 8-device mesh: identical counts and
    levels — the dryrun-path acceptance differential at tier-1 depth."""
    from raft_tla_tpu.models.dims import RaftDims
    from raft_tla_tpu.models.invariants import Bounds
    from raft_tla_tpu.models.pystate import init_state
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    dims = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=24)
    bounds = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
    out = {}
    for pipe in ("v2", "v3"):
        eng = MeshBFSEngine(
            dims, constraint=build_constraint(dims, bounds),
            config=EngineConfig(batch=16, queue_capacity=1 << 12,
                                seen_capacity=1 << 15,
                                check_deadlock=False, max_diameter=3,
                                pipeline=pipe))
        res = eng.run([init_state(dims)])
        out[pipe] = (res.distinct, res.generated, res.levels)
        if pipe == "v3":
            assert res.pipeline == "v3"
            assert res.fused_stages["enqueue"] == "pallas"
            assert res.fused_stages["insert"] == "xla"
    assert out["v2"] == out["v3"]


def test_v3_profiler_fused_stage_granularity():
    """--profile-chunks on a v3 engine: the profiler samples the
    fused-stage decomposition (masks/compact/fingerprint/
    insert_enqueue), renders a coherent table, and
    EngineResult.chunk_stages carries the v3 keys bench_diff folds."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    setup = load_config("configs/MCraft_bounded.cfg")
    eng = make_engine(setup, EngineConfig(
        batch=32, queue_capacity=1 << 12, seen_capacity=1 << 14,
        record_trace=False, check_deadlock=False, max_diameter=3,
        pipeline="v3", profile_chunks_every=1))
    res = eng.run(initial_states(setup))
    assert set(res.chunk_stages) == {"masks", "compact", "fingerprint",
                                     "insert_enqueue", "total"}
    prof = eng._profiler
    table = prof.render_table()
    assert "insert_enqueue" in table and "v3 stages" in table
    summary = prof.summary()
    assert summary["pipeline"] == "v3"
    assert set(summary["stages"]["insert_enqueue"]) == {
        "mean_seconds", "total_seconds"}
