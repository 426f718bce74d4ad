"""Mesh-sharded BFS tests on the virtual 8-device CPU mesh.

The distributed engine must produce bit-identical statistics to the
single-device engine (and hence the oracle): fingerprint-owner dedup over
all_to_all must count each global state exactly once regardless of which
chip generates it, and the union of per-chip FPSet shards must behave as one
set.
"""

import jax
import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            constraint_py)
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.parallel.mesh import MeshBFSEngine

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=24)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def test_eight_device_mesh_available():
    assert len(jax.devices()) == 8


def test_mesh_counts_match_single_device():
    cons = build_constraint(DIMS, BOUNDS)
    mesh_eng = MeshBFSEngine(
        DIMS, constraint=cons,
        config=EngineConfig(batch=16, queue_capacity=1 << 12,
                            seen_capacity=1 << 15, check_deadlock=False,
                            max_diameter=3))
    mres = mesh_eng.run([init_state(DIMS)])
    want = orc.bfs([init_state(DIMS)], DIMS, constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=3)
    assert mres.distinct == want.distinct_states
    assert mres.levels == want.levels
    assert mres.generated == want.generated_states


def test_mesh_trace_replay():
    import jax.numpy as jnp
    cons = build_constraint(DIMS, Bounds(max_term=3, max_log_len=1,
                                         max_msg_count=1))
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))
    eng = MeshBFSEngine(
        DIMS, invariants={"NoLeader": lambda st: jnp.all(st.role != LEADER)},
        constraint=cons,
        config=EngineConfig(batch=16, queue_capacity=1 << 12,
                            seen_capacity=1 << 15, check_deadlock=False))
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    steps = eng.replay(res.violation.fingerprint)
    assert steps[-1][1] == res.violation.state
    for (g_prev, s_prev), (g, s_next) in zip(steps, steps[1:]):
        assert s_next in orc.successor_set(s_prev, DIMS)


def small_mesh_config(**kw):
    base = dict(batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False)
    base.update(kw)
    return EngineConfig(**base)


def test_mesh_spill_to_host_matches_roomy():
    """Per-chip queue overflow must drain to the host pool (and re-upload
    balanced) without changing any count — single-chip parity for the
    spill path the round-2 mesh engine lacked."""
    cons = build_constraint(DIMS, BOUNDS)
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=4)).run(
        [init_state(DIMS)])
    # queue_capacity 8/chip rounds up to one batch (= B*G watermark 0):
    # every chunk spills.
    got = MeshBFSEngine(DIMS, constraint=cons,
                        config=small_mesh_config(
                            batch=8, queue_capacity=8, sync_every=4,
                            max_diameter=4)).run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated


def test_mesh_seen_set_grows():
    """Shard growth (host rehash at half load) must keep counts exact."""
    cons = build_constraint(DIMS, BOUNDS)
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=3)).run(
        [init_state(DIMS)])
    small = MeshBFSEngine(DIMS, constraint=cons,
                          config=small_mesh_config(
                              batch=8, sync_every=1, seen_capacity=8,
                              max_diameter=3))
    got = small.run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    # (Per-shard capacity is floored at fpset's minimum, so this run does
    # not grow; growth evidence is asserted by
    # test_dryrun_ground_truth_pinned.)


def test_mesh_checkpoint_resumes_on_mesh_and_single(tmp_path):
    """Mesh checkpoints use the single-chip snapshot format: a run
    interrupted on the mesh must resume bit-exactly BOTH on a mesh (even a
    different device count) and on the single-chip engine."""
    cons = build_constraint(DIMS, BOUNDS)
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=4)).run(
        [init_state(DIMS)])
    ck = str(tmp_path / "ck")
    MeshBFSEngine(DIMS, constraint=cons,
                  config=small_mesh_config(
                      max_diameter=3, record_trace=False,
                      checkpoint_dir=ck)).run([init_state(DIMS)])
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    path = ckpt_mod.latest(ck)
    assert path is not None

    import jax as _jax
    got_mesh = MeshBFSEngine(
        DIMS, constraint=cons,
        config=small_mesh_config(max_diameter=4, record_trace=False),
        devices=_jax.devices()[:4]).run(resume=path)
    assert got_mesh.distinct == want.distinct
    assert got_mesh.levels == want.levels
    assert got_mesh.diameter == want.diameter

    got_single = BFSEngine(
        DIMS, constraint=cons,
        config=small_mesh_config(max_diameter=4, record_trace=False,
                                 queue_capacity=1 << 13)).run(resume=path)
    assert got_single.distinct == want.distinct
    assert got_single.levels == want.levels


def test_mesh_disk_backed_spill_matches_ram(tmp_path):
    """spill_dir on the mesh engine: tiny per-chip queues force constant
    drains through the disk-backed pool (and the oversized-segment
    re-insert path); counts must match the roomy in-RAM run, and all
    segment files must be consumed."""
    cons = build_constraint(DIMS, BOUNDS)
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=4)).run(
        [init_state(DIMS)])
    spill = tmp_path / "spill"
    got = MeshBFSEngine(DIMS, constraint=cons,
                        config=small_mesh_config(
                            batch=8, queue_capacity=8, sync_every=4,
                            spill_dir=str(spill),
                            max_diameter=4)).run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated
    import gc
    gc.collect()
    assert list(spill.iterdir()) == []


def test_mesh_progress_limiting_with_tiny_compact_buffer():
    """P-limiting under the pmin-replicated offset advance (ops/
    compact.py reduce_p): a compact buffer too small for a batch's
    fan-out must not change any count on the mesh — every chip advances
    by the same replicated P, so lockstep trip counts hold even when
    chips see different fan-outs."""
    cons = build_constraint(DIMS, BOUNDS)
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=3)).run(
        [init_state(DIMS)])
    got = MeshBFSEngine(DIMS, constraint=cons,
                        config=small_mesh_config(
                            batch=32, compact_lanes=1,
                            max_diameter=3)).run([init_state(DIMS)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated


def test_mesh_order_independence():
    """Root permutation and batch-boundary changes must not change mesh
    counts (guards the owner-routed all_to_all dedup)."""
    cons = build_constraint(DIMS, BOUNDS)
    s = init_state(DIMS)
    roots = [s,
             s.replace(role=(1, 0, 0), current_term=(2, 1, 1)),
             s.replace(role=(0, 1, 0), current_term=(1, 2, 1)),
             s.replace(role=(2, 0, 0), votes_granted=(0b11, 0, 0))]
    want = MeshBFSEngine(DIMS, constraint=cons,
                         config=small_mesh_config(max_diameter=2)).run(
        list(roots))
    got = MeshBFSEngine(DIMS, constraint=cons,
                        config=small_mesh_config(batch=8, max_diameter=2)
                        ).run([roots[i] for i in (3, 1, 0, 2)])
    assert got.distinct == want.distinct
    assert got.levels == want.levels
    assert got.generated == want.generated


def test_dryrun_ground_truth_pinned():
    """The driver's dryrun_multichip model (__graft_entry__.py) asserts
    46,553 distinct / diameter 31 — re-derive that constant here from BOTH
    the independent Python oracle and the mesh engine, so kernel or oracle
    drift fails the suite before it fails a driver-side dryrun (SURVEY §4
    differential contract)."""
    dims = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    bounds = Bounds(max_term=2, max_log_len=1, max_msg_count=1,
                    max_in_flight=2)
    want = orc.bfs([init_state(dims)], dims,
                   constraint=constraint_py(bounds), check_deadlock=False)
    assert want.distinct_states == 46553
    assert len(want.levels) - 1 == 31    # diameter
    # Exactly the driver's dryrun_multichip config (__graft_entry__.py):
    # batch 64 keeps the per-shard table floor at 8K=8192, so the 46.5k-key
    # run crosses the half-load threshold and exercises shard growth too.
    eng = MeshBFSEngine(
        dims, constraint=build_constraint(dims, bounds),
        config=EngineConfig(batch=64, queue_capacity=1 << 12,
                            seen_capacity=1 << 16, check_deadlock=False,
                            record_trace=False, sync_every=8))
    res = eng.run([init_state(dims)])
    assert res.stop_reason == "exhausted"
    assert res.distinct == 46553 and res.diameter == 31
    assert res.generated == want.generated_states
    # 46,553 keys over 8 shards in 8k-per-shard tables: shard growth must
    # fire and be recorded as (total-capacity-after, stall seconds).
    caps = [c for c, _s in res.growth_stalls]
    assert caps and caps == sorted(caps) and len(set(caps)) == len(caps)


def test_mesh_distinct_budget_stops_run(tmp_path):
    """The TLCGet("distinct") budget must stop the mesh engine too (the
    counters are psum-accumulated on the host side, same as single-chip)."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from tests.test_cfg import _write_exit_model
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(_write_exit_model(tmp_path, "distinct", 500))
    eng = make_engine(setup, EngineConfig(
        batch=16, queue_capacity=1 << 13, seen_capacity=1 << 16,
        record_trace=False, sync_every=4), engine_cls=MeshBFSEngine)
    res = eng.run(initial_states(setup))
    assert res.stop_reason == "distinct_budget"
    assert res.distinct > 500


def test_mesh_progress_lines_emitted(capfd):
    eng = MeshBFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                        config=small_mesh_config(
                            max_diameter=3, progress_interval_seconds=1e-6))
    eng.run([init_state(DIMS)])
    err = capfd.readouterr().err
    assert "progress:" in err and "queue" in err


def test_mesh_skew_telemetry_is_always_on(tmp_path):
    """Per-shard balance telemetry (``_sample_skew``) needs no option:
    balance gauges, skew fields on ``level_complete``, and (with a 1.0
    threshold — any imbalance) ``skew`` warning events whose payload the
    event validator holds."""
    from raft_tla_tpu.obs import validate_run_events
    ev = str(tmp_path / "mesh_events.jsonl")
    eng = MeshBFSEngine(
        DIMS, constraint=build_constraint(DIMS, BOUNDS),
        config=small_mesh_config(max_diameter=2, events_out=ev,
                                 skew_warn_ratio=1.0))
    eng.run([init_state(DIMS)])
    recs = validate_run_events(ev)
    levels = [e for e in recs if e["event"] == "level_complete"]
    assert any(e.get("frontier_skew") is not None for e in levels)
    assert any(isinstance(e.get("shard_frontier"), list) for e in levels)
    skews = [e for e in recs if e["event"] == "skew"]
    assert skews, "threshold 1.0 must warn on any imbalance"
    bal = skews[0]["balance"]
    assert bal["frontier_skew"] >= 1.0
    assert len(bal["shard_frontier"]) == eng.n_dev
    assert "mesh/frontier_skew" in eng.metrics.snapshot()["gauges"]


# -- one chunk call against the oracle, over four chips ---------------------

def rebuilt_shards(eng, keys_hi, keys_lo, most=None):
    """``_shards_from_keys`` of a whole key set, waited for: (shi, slo,
    ssize).  ``most`` as the resume computes it where none is given."""
    import numpy as np
    if most is None:
        most = int(np.bincount(keys_hi % eng.n_dev,
                               minlength=eng.n_dev).max())
    shi, slo, ssize, inserts = eng._shards_from_keys(keys_hi, keys_lo, most)
    inserts.wait()
    return shi, slo, ssize


@pytest.fixture(scope="module")
def one_mesh_chunk_call():
    """tests/test_engine.py's comparison for ``MeshBFSEngine``: the
    level-3 frontier of ``MCraft_bounded.cfg`` dealt over four chips, the
    seen-set sharded by owner, one ``sharded_chunk`` call."""
    import functools
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.parallel import multihost as mh
    from tests.test_engine import Level3
    want = Level3("MCraft_bounded.cfg")
    eng = make_engine(
        want.setup, EngineConfig(batch=32, queue_capacity=1 << 12,
                                 seen_capacity=1 << 14),
        engine_cls=functools.partial(MeshBFSEngine,
                                     devices=jax.devices()[:4]))
    n = eng.n_dev
    (qav, counts_av, _, _, _, _shi, _slo, _size, tbuf_av, _,
     _) = eng.chunk_avals()
    qcur = np.zeros(qav.shape, np.uint8)
    counts = np.zeros(counts_av.shape, np.int32)
    for d in range(n):
        mine = want.rows()[d::n]
        qcur[d, :len(mine)], counts[d] = mine, len(mine)

    def put(a):
        return mh.put_global(a, eng.mesh, P("x"))

    zeros = np.zeros(counts_av.shape, np.int32)
    out = eng._chunk(
        put(qcur), put(counts), jnp.int32(0),
        put(np.zeros(qav.shape, np.uint8)), put(zeros),
        *rebuilt_shards(eng, *want.seen_keys(eng)),
        tuple(put(np.zeros(a.shape, a.dtype)) for a in tbuf_av),
        put(zeros), jnp.int32(eng._CH))
    qnext, ncnt, _hi, _lo, _size, tbuf, tcnt = (
        jax.tree.map(np.asarray, out[:7]))
    assert all(0 < c < len(want.enqueued) for c in ncnt)    # every chip
    rows = np.concatenate([qnext[d, :ncnt[d]] for d in range(n)])
    cols = [np.concatenate([c[d, :tcnt[d]] for d in range(n)])
            for c in tbuf]
    return want, eng, rows, cols


def test_one_mesh_chunk_call_enqueues_the_oracles_new_successors(
        one_mesh_chunk_call):
    want, _eng, rows, _cols = one_mesh_chunk_call
    want.check_rows(rows)


def test_one_mesh_chunk_call_records_the_oracles_transitions(
        one_mesh_chunk_call):
    """The owner decides which of two chips' equal candidates is new, so
    the first parent in frontier order need not win here."""
    want, eng, _rows, cols = one_mesh_chunk_call
    want.check_records(eng, cols, first_parent_wins=False)


# -- the owner's windowed insert against fpset.insert -----------------------

OWNER_K = 64             # lanes a source chip sends an owner: the window
OWNER_SLOTS = 1 << 12    # the owner's shard


def _owner_cases(n):
    """name -> (arrivals [n, OWNER_K] as (hi, lo) with SENTINEL padding,
    keys the shard holds before, lanes that must carry the bit or None).
    A block is rank-packed at its front, as ``route_insert`` builds it."""
    import numpy as np
    from raft_tla_tpu.ops.fingerprint import SENTINEL
    rng = np.random.default_rng(32)
    k = OWNER_K

    def keys(count):
        return (rng.integers(0, 1 << 32, count, dtype=np.uint64),
                rng.integers(0, 1 << 31, count, dtype=np.uint64))

    def blocks(per_block, khi=None, klo=None):
        hi = np.full((n, k), SENTINEL, np.uint64)
        lo = np.full((n, k), SENTINEL, np.uint64)
        if khi is None:
            khi, klo = keys(sum(per_block))
        at = 0
        for b, c in enumerate(per_block):
            hi[b, :c], lo[b, :c] = khi[at:at + c], klo[at:at + c]
            at += c
        return hi.astype(np.uint32), lo.astype(np.uint32)

    def spread(total):
        return [total // n + (b < total % n) for b in range(n)]

    none = (np.zeros(0, np.uint32),) * 2
    cases = {
        "no_arrival": (blocks([0] * n), none, None),
        "typical_pass": (blocks(spread(k - 7)), none, None),
        "exactly_m": (blocks(spread(k)), none, None),
        "m_plus_one": (blocks(spread(k + 1)), none, None),
        "every_lane_valid": (blocks([k] * n), none, None),
    }
    # One key from three source chips, twice from the lowest of them.
    hi, lo = blocks(spread(k - 7))
    for b, lane in ((1, 0), (1, 3), (4, 2), (6, 5)):
        hi[b, lane], lo[b, lane] = 0xC0FFEE, 0xFACADE
    cases["one_key_from_three_chips"] = ((hi, lo), none, [1 * k + 0])
    # A shard 45 % full that holds half of what arrives.
    phi, plo = keys(int(0.45 * OWNER_SLOTS))
    ahi, alo = keys(k - 7)
    ahi[::2], alo[::2] = phi[:len(ahi[::2])], plo[:len(ahi[::2])]
    pre = (phi.astype(np.uint32), plo.astype(np.uint32))
    cases["table_45_percent_full"] = (
        blocks(spread(k - 7), ahi, alo), pre, None)
    # Two windows over a loaded shard, a key on both sides of the cut.
    ahi, alo = keys(2 * k)
    ahi[k:k + 9], alo[k:k + 9] = ahi[:9], alo[:9]
    ahi[9:30], alo[9:30] = phi[9:30], plo[9:30]
    cases["two_windows_table_loaded"] = (
        blocks(spread(2 * k), ahi, alo), pre, None)
    return cases


@pytest.fixture(scope="module")
def owner_inserts():
    """Every case on a chip of its own, in ONE program over the 8
    devices: ``fpset.insert_windowed`` as ``route_insert``'s
    ``owner_insert`` calls it (the flattened blocks, window = a block's
    lanes), each chip at its own trip count, beside ``fpset.insert`` on
    the same arrivals into the same shard."""
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from raft_tla_tpu.ops import fpset
    from raft_tla_tpu.ops.fingerprint import SENTINEL
    devices = jax.devices()
    n = len(devices)
    cases = _owner_cases(n)
    assert len(cases) == n

    def preloaded(khi, klo):
        s, _rounds, _lane_rounds = fpset.from_host_keys(
            khi, klo, OWNER_SLOTS, chunk=OWNER_SLOTS)
        assert int(s.size) == len(khi)
        return np.asarray(s.hi), np.asarray(s.lo), np.int32(len(khi))

    tables = [preloaded(*pre) for _arr, pre, _bit in cases.values()]
    thi, tlo, tsize = (np.stack(col) for col in zip(*tables))
    qhi = np.stack([arr[0].reshape(-1) for arr, _p, _b in cases.values()])
    qlo = np.stack([arr[1].reshape(-1) for arr, _p, _b in cases.values()])

    def both(thi, tlo, tsize, qhi, qlo):
        s = fpset.FPSet(hi=thi[0], lo=tlo[0], size=tsize[0])
        rh, rl = qhi[0], qlo[0]
        rvalid = ~((rh == SENTINEL) & (rl == SENTINEL))
        ws, wnew, wfail, windows = fpset.insert_windowed(
            s, rh, rl, rvalid, OWNER_K)
        ps, pnew, pfail = fpset.insert(s, rh, rl, rvalid)
        return tuple(x[None] for x in (
            ws.hi, ws.lo, ws.size, wnew, wfail, windows,
            ps.hi, ps.lo, ps.size, pnew, pfail))

    sx = P("x")
    out = jax.jit(jax.shard_map(
        both, mesh=Mesh(np.asarray(devices), ("x",)), in_specs=(sx,) * 5,
        out_specs=(sx,) * 11, check_vma=False))(
            *(jnp.asarray(a) for a in (thi, tlo, tsize, qhi, qlo)))
    out = [np.asarray(a) for a in out]
    return {name: (case, [a[d] for a in out])
            for d, (name, case) in enumerate(cases.items())}


@pytest.mark.parametrize("name", [
    "no_arrival", "typical_pass", "exactly_m", "m_plus_one",
    "every_lane_valid", "one_key_from_three_chips",
    "table_45_percent_full", "two_windows_table_loaded"])
def test_owner_windowed_insert_equals_insert_on_the_padded_block(
        owner_inserts, name):
    """Novelty bits lane for lane, the shard's key set, ``size`` and
    ``fail`` are ``fpset.insert``'s on all n x K lanes; the windows run
    are the arrived queries' share of K-lane windows, rounded up."""
    import numpy as np
    from raft_tla_tpu.ops.fingerprint import SENTINEL
    ((ahi, alo), pre, bit_lanes), got = owner_inserts[name]
    (whi, wlo, wsize, wnew, wfail, windows,
     phi, plo, psize, pnew, pfail) = got

    def key_set(hi, lo):
        real = ~((hi == SENTINEL) & (lo == SENTINEL))
        return set(zip(hi[real].tolist(), lo[real].tolist()))

    assert np.array_equal(wnew, pnew)
    assert key_set(whi, wlo) == key_set(phi, plo)
    assert len(key_set(whi, wlo)) == wsize == psize
    assert not wfail and not pfail
    valid = int(np.sum(~((ahi == SENTINEL) & (alo == SENTINEL))))
    assert windows == -(-valid // OWNER_K)
    # What insert itself is held to, so that equal is not equally wrong.
    arrived = key_set(ahi.reshape(-1), alo.reshape(-1))
    held = key_set(*pre)
    assert int(wnew.sum()) == len(arrived - held) == wsize - len(held)
    assert key_set(whi, wlo) == arrived | held
    if bit_lanes is not None:
        dup = (ahi.reshape(-1) == 0xC0FFEE) & (alo.reshape(-1) == 0xFACADE)
        assert np.flatnonzero(dup & wnew).tolist() == bit_lanes
        assert int(dup.sum()) == 4


@pytest.fixture(scope="module")
def short_runs(tmp_path_factory):
    """{engine class: the events of its run to level 4}, each the first
    run of a process by a record of its own (obs/metrics.py), the engine
    built inside the bracket ``make_engine`` puts around any engine."""
    import json
    from raft_tla_tpu.obs import metrics as metrics_mod
    tmp = tmp_path_factory.mktemp("short_runs")
    out = {}
    for engine_cls, kw in ((MeshBFSEngine, {"devices": jax.devices()[:4]}),
                           (BFSEngine, {})):
        events = str(tmp / f"{engine_cls.__name__}.jsonl")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(metrics_mod, "PROCESS", metrics_mod.ProcessRecord())
            with metrics_mod.process_span("make_engine", "engine_begin",
                                          "engine_built"):
                eng = engine_cls(
                    DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_mesh_config(max_diameter=4,
                                             events_out=events), **kw)
            eng.run([init_state(DIMS)])
        with open(events, encoding="utf-8") as f:
            out[engine_cls] = [json.loads(line) for line in f]
        assert out[engine_cls][-1]["event"] == "run_end"
    return out


def test_run_end_carries_the_insert_windows_of_every_chip(short_runs):
    """``chip_insert_windows``: one entry a chip, the windows its owner
    dedup ran over the run's passes — one in every pass that brought it a
    query (a pass of this run sends a chip far fewer than K), none in
    the others; the one-chip engine has no such count."""
    end = short_runs[MeshBFSEngine][-1]
    windows = end["chip_insert_windows"]
    assert len(windows) == 4 == len(end["chip_shard_keys"])
    assert all(0 < w <= end["passes"] for w in windows)
    # Every pass generated a candidate, and some chip owns it.
    assert sum(windows) >= end["passes"] > 0
    assert "chip_insert_windows" not in short_runs[BFSEngine][-1]
    # A run from the root restored nothing, and says nothing of it.
    assert not {"restore_pieces", "restore_rounds",
                "restore_lane_rounds"} & set(end)


def test_the_mesh_run_start_carries_the_process_record(short_runs):
    """The one helper, through ``BFSEngine._telemetry_run``: marks in
    order, eight parts that sum to the age, and in ``run_end`` the mesh
    programs the run compiled or loaded, by name."""
    from tests.test_setup_record import check_process
    start, end = short_runs[MeshBFSEngine][0], short_runs[MeshBFSEngine][-1]
    assert (start["event"], start["engine"]) == ("run_start", "MeshBFSEngine")
    parts = check_process(start["process"])
    assert set(start["process"]["marks"]) == {"engine_begin", "engine_built",
                                              "first_run"}
    assert start["process"]["runs"]["count"] == 0 and parts["runs_s"] == 0.0
    named = {p["name"]: p for p in end["jit"]["programs"]}
    assert {"sharded_chunk", "sharded_ingest"} <= set(named), sorted(named)
    assert named["sharded_chunk"]["cache"] in ("hit", "miss")
    # The backend stage of ``jit`` is what ``compiles`` has always counted.
    assert (end["jit"].get("load", [0])[0] + end["jit"].get("compile", [0])[0]
            == sum(n for n, _s in end["compiles"].values()))


# -- the shard rebuild, a slab at a time (PR 45) ---------------------------

def key_set(kind: str, n: int, piece: int):
    """(keys_hi, keys_lo) for ``n`` chips where a piece holds ``piece``
    keys a chip: ``several_slabs`` is five pieces a chip of hashed keys,
    ``skewed`` the same with owner 0's keys all in front (its first slab
    holds a slab of them and none of any other owner: the carry-over, and
    the others' pieces filled from later slabs), ``small`` under one
    piece, ``empty`` none."""
    import numpy as np
    count = {"several_slabs": 5 * piece * n - 37, "skewed": 5 * piece * n,
             "small": n * piece // 3, "empty": 0}[kind]
    rng = np.random.default_rng(45 + n)
    hi = rng.integers(0, 1 << 32, count, dtype=np.uint32)
    lo = rng.integers(0, 1 << 32, count, dtype=np.uint32)
    if kind == "skewed":
        order = np.argsort(hi % n != 0, kind="stable")
        hi, lo = hi[order], lo[order]
    return hi, lo


@pytest.fixture(scope="module")
def rebuild_engines():
    """{n: a mesh engine on n chips}, shards of 2^14 slots: only their
    rebuild programs are ever compiled."""
    return {n: MeshBFSEngine(
        DIMS, config=EngineConfig(batch=32, queue_capacity=1 << 12,
                                  seen_capacity=n << 14),
        devices=jax.devices()[:n]) for n in (1, 2, 4)}


@pytest.mark.parametrize("kind", ["several_slabs", "skewed", "small",
                                  "empty"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_shards_from_keys_puts_every_key_on_its_owner_once(
        rebuild_engines, monkeypatch, n, kind):
    """Bucketed a slab at a time, pieces of 256 to 1,024 keys a chip:
    chip ``d`` holds exactly the keys with ``fp_hi mod n == d``, ``ssize``
    counts them, and the dispatches are what the fullest owner asks for
    (the number every controller computes alike), however the slabs
    fell."""
    import numpy as np
    from raft_tla_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "KEY_PIECE_MIN", 256)
    monkeypatch.setattr(mesh_mod, "KEY_PIECE_MAX", 1024)
    eng = rebuild_engines[n]
    hi, lo = key_set(kind, n, 1024)
    counts = np.bincount(hi % n, minlength=n)
    most = int(counts.max())
    calls = []
    insert = eng._insert_keys
    monkeypatch.setattr(eng, "_insert_keys",
                        lambda *a: calls.append(a[3].shape) or insert(*a))
    shi, slo, ssize, inserts = eng._shards_from_keys(hi, lo, most)
    inserts.wait()
    piece = {"small": 512, "empty": 256}.get(kind, 1024)
    assert calls == [(n, piece)] * -(-most // piece)
    assert inserts.pieces == len(calls)
    assert (inserts.since is None) == (kind == "empty")
    assert np.asarray(ssize).tolist() == counts.tolist()
    got = eng.shard_keys(shi, slo)
    assert sorted(got) == list(range(n))
    for d, (h, l) in got.items():
        mine = hi % n == d
        assert sorted(zip(h.tolist(), l.tolist())) \
            == sorted(zip(hi[mine].tolist(), lo[mine].tolist()))


def test_shards_from_keys_raises_where_a_shard_overflows(rebuild_engines,
                                                         monkeypatch):
    """More keys for chip 1 than its shard has slots: the pieces are
    dispatched, and the wait raises as the rebuild always has.  Keys past
    the pieces that ``most`` asks for are an error too, not a silent
    loss."""
    import numpy as np
    eng = rebuild_engines[2]
    monkeypatch.setattr(eng, "_CL", 1 << 9)
    rng = np.random.default_rng(4545)
    hi = rng.integers(0, 1 << 31, 700, dtype=np.uint32) * 2 + 1
    lo = rng.integers(0, 1 << 32, 700, dtype=np.uint32)
    _shi, _slo, _ssize, inserts = eng._shards_from_keys(hi, lo, 700)
    assert inserts.pieces == 1
    with pytest.raises(RuntimeError, match="FPSet rebuild overflow: 700 "
                                           "keys into a shard of 512"):
        inserts.wait()
    monkeypatch.setattr(eng, "_CL", 1 << 14)
    with pytest.raises(RuntimeError, match="keys left after the pieces"):
        eng._shards_from_keys(np.tile(hi, 60), np.tile(lo, 60), 700)


def test_grow_seen_keeps_every_shards_keys():
    """``_grow_seen``, the rebuild's other caller: the same key set a
    shard at twice the slots, ``ssize`` equal, waited for."""
    import numpy as np
    eng = MeshBFSEngine(
        DIMS, config=EngineConfig(batch=32, queue_capacity=1 << 12,
                                  seen_capacity=4 << 10),
        devices=jax.devices()[:4])
    hi, lo = key_set("small", 4, 1024)
    shi, slo, ssize = rebuilt_shards(eng, hi, lo)
    before, sizes = eng.shard_keys(shi, slo), np.asarray(ssize).tolist()
    slots = eng._CL
    shi, slo, ssize = eng._grow_seen(shi, slo, max(sizes))
    assert eng._CL == 2 * slots and shi.shape == (4, 2 * slots)
    assert np.asarray(ssize).tolist() == sizes
    after = eng.shard_keys(shi, slo)
    for d in range(4):
        assert sorted(zip(*map(np.ndarray.tolist, after[d]))) \
            == sorted(zip(*map(np.ndarray.tolist, before[d])))
