"""Performance observatory tests (obs/perf.py + obs/roofline.py).

Three contracts from the ISSUE acceptance criteria:

- the static roofline's byte model is cross-checked against HAND-
  COMPUTED traffic for the fingerprint stage and the compactor on
  the seed dims — the walk's windowed-gather/full-read rules are pinned
  to arithmetic a reviewer can redo on paper;
- launch counts are PINNED per pipeline (v1/v2) on the tiny model:
  the counts are deterministic jaxpr device-op totals, so a chunk-body
  change that adds device ops moves the pin and fails CI instead of
  landing as an invisible slowdown.  Re-pin ONLY after confirming the
  delta is intentional (a jax upgrade that re-lowers primitives also
  legitimately moves these);
- engine counts are bit-identical with the perf surfaces on or off,
  single-chip and mesh (the observational contract every obs leg
  keeps).

This module traces full chunk programs through the analyzer walk —
trace-churn-heavy, so it runs in tests/conftest.py's trailing slot with
the other analyzer modules.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models.dims import RaftDims
from raft_tla_tpu.models.invariants import Bounds, build_constraint
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.obs import validate_run_events

# obs.perf / obs.roofline are imported INSIDE the tests, not here:
# pytest imports every test module at collection time, BEFORE any test
# runs, so a module-level import would inject the new modules into the
# heap history of every pre-existing test — the perturbation class the
# conftest trace-heavy-last reorder exists to prevent (jaxlib's CPU
# client is heap-layout fragile under the big mesh tests; kept off the
# collection path as a precaution).


def _roofline():
    from raft_tla_tpu.obs import roofline
    return roofline

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
B, K = 32, 512


def small_config(**kw):
    base = dict(batch=B, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# Roofline byte model vs hand-computed traffic


def test_fingerprint_stage_bytes_match_hand_computed():
    """v1 fingerprint stage: every candidate field array [B*G, ...] is
    consumed ONLY through the lane_id gather, so the modeled read is K
    window rows per field (+ the K-lane index vector); the write is the
    gathered K-lane struct + the two 32-bit hash lanes.  The walk must
    reproduce that arithmetic exactly — windowed-read attribution is
    the whole point of reusing the interp shape walk."""
    import jax.tree_util as jtu

    from raft_tla_tpu.models.schema import state_width
    from raft_tla_tpu.obs.profile import build_stage_programs
    progs = build_stage_programs(DIMS, B, K)
    rows = jax.ShapeDtypeStruct((B, state_width(DIMS)), jnp.uint8)
    valid = jax.ShapeDtypeStruct((B,), jnp.bool_)
    cflat, lane_id, _kvalid = jax.eval_shape(progs["expand"], rows, valid)

    roofline = _roofline()
    traffic = roofline.stage_traffic(DIMS, B, K, pipeline="v1")
    got = traffic["fingerprint"]

    def nbytes(a):
        n = 1
        for d in a.shape:
            n *= d
        return n * np.dtype(a.dtype).itemsize

    leaves, _ = jtu.tree_flatten(cflat)
    # reads: K gathered rows per field (row bytes = leaf bytes / B*G
    # lanes) + the [K] int32 lane_id itself.
    exp_read = sum(K * (nbytes(a) // a.shape[0]) for a in leaves) + K * 4
    kstates, kh, kl = jax.eval_shape(progs["fingerprint"], cflat, lane_id)
    wleaves, _ = jtu.tree_flatten(kstates)
    exp_write = sum(nbytes(a) for a in wleaves) + nbytes(kh) + nbytes(kl)
    assert got["bytes_read"] == exp_read
    assert got["bytes_written"] == exp_write


def test_compact_stage_bytes_match_hand_computed():
    """The chunk's compact stage (ops/compact.py): reads the [B, G] bool
    enabled mask (1 byte per lane), writes the [K] int32 lane ids + [K]
    bool validity."""
    from raft_tla_tpu.ops.compact import build_compactor
    roofline = _roofline()
    compactor = build_compactor(B, DIMS.n_instances, K)
    en = jax.ShapeDtypeStruct((B, DIMS.n_instances), jnp.bool_)
    got = roofline.jaxpr_traffic(
        jax.make_jaxpr(lambda e: compactor(e)[2:])(en), [en])
    assert got["bytes_read"] == B * DIMS.n_instances
    assert got["bytes_written"] == K * 4 + K


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", 819e9),      # what a v5e reports (chip_smoke phase 1)
    ("TPU v5e", 819e9), ("TPU v5p", 2765e9), ("TPU v6 lite", 1638e9)])
def test_peak_bandwidth_table_knows_the_chip(kind, want):
    assert _roofline().peak_bandwidth_for_kind(kind) == want


def test_unknown_accelerator_is_an_error_not_the_cpu_figure():
    roofline = _roofline()
    with pytest.raises(ValueError, match="PEAK_BW_TABLE"):
        roofline.peak_bandwidth_for_kind("Mystery Accelerator 9000")
    # The CPU figure is lent only to the CPU (the suite's platform).
    assert roofline.peak_bandwidth()["source"] == "assumed (cpu ddr-class)"


def test_roofline_rows_and_advisor():
    """Floors + measured means join into fractions; the advisor ranks by
    launch tax + headroom and names a stage."""
    roofline = _roofline()
    traffic = roofline.stage_traffic(DIMS, B, K, pipeline="v1")
    peak = {"bytes_per_sec": 100e9, "source": "test"}
    means = {s: 0.010 for s in traffic}      # 10 ms/stage measured
    rows = roofline.build_roofline(traffic, means, peak)
    for s, r in rows.items():
        assert r["floor_seconds"] == pytest.approx(
            traffic[s]["bytes_total"] / 100e9, abs=1e-9)
        assert r["bandwidth_fraction"] == pytest.approx(
            traffic[s]["bytes_total"] / 0.010 / 100e9, abs=1e-6)
        assert r["headroom_seconds"] <= 0.010
    adv = roofline.advise(rows, overhead_seconds=5e-6)
    assert adv["top"] in traffic
    assert adv["top"] in adv["verdict"]
    # With near-equal headrooms the launch tax breaks the tie toward
    # the op-heaviest stage (expand: the hundreds-of-kernels story).
    assert adv["ranking"][0]["score_seconds"] >= \
        adv["ranking"][-1]["score_seconds"]


# ---------------------------------------------------------------------------
# Pinned launch counts

#: Deterministic jaxpr device-op counts of the REAL chunk programs on
#: the tiny model above (batch=32, trace on, deadlock off).  These move
#: only when the chunk body (or a jax upgrade's lowering) changes — an
#: intentional change re-pins with the delta explained in its PR.  The
#: v1 pin moved 1948 -> 2119 with the BLEST family grouping
#: (models/actions.py): the stacked group kernels add where-cascade
#: selects to the PRE-fusion eqn count while cutting the per-family
#: launch fan-out XLA must schedule.
#: Re-pinned for jax 0.9.0 (PR 24; was 2119 / 3178 under
#: 0.4.36) with no change to any chunk body.  Two causes: 0.9.0 names
#: the nested-jit primitive ``jit`` (was ``pjit``), which the walk did
#: not recurse into until analysis/interp.py learned the name (v2 read
#: 2993 in between); and with the recursion restored 0.9.0 still
#: traces the same bodies to fewer pre-fusion equations (v1 -84,
#: v2 -38) — not attributable per primitive without the old
#: installation.
#: ``launches_fixed`` 6 -> 7 (PR 27): the ``add 0`` outside the loop that
#: carries the stage names' tag into the compile-cache key
#: (engine/chunk.py tag_stages).  This model counts equations before
#: XLA; XLA folds that one, and the compiled program has the
#: instructions it had (described-v5e compile and the chip's operation
#: count, PERF.md).  The per-batch counts did not move.
LAUNCH_PINS = {
    "v1": {"launches_per_batch": 2035, "launches_fixed": 7},
    "v2": {"launches_per_batch": 3140, "launches_fixed": 7},
}


@pytest.mark.parametrize("pipe", ["v1", "v2"])
def test_launch_counts_pinned_per_pipeline(pipe):
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(perf=True, pipeline=pipe))
    lm = eng._perf.launch_model
    assert lm is not None, "launch model failed to build"
    got = {k: lm[k] for k in ("launches_per_batch", "launches_fixed")}
    assert got == LAUNCH_PINS[pipe], (
        f"{pipe} chunk-program launch count moved: {got} != pinned "
        f"{LAUNCH_PINS[pipe]}.  If the chunk body changed "
        f"intentionally (or jax re-lowered primitives), re-pin WITH "
        f"the delta explained; otherwise the chunk grew device ops.")


#: Swarm chunk program on the same tiny model (walks=batch=32,
#: depth=12, ring=8, chunk=8, TypeOK+NoLeader, hunt_cells=2^16).  Keyed
#: by the hunt flag: the +147-op delta IS the observatory's whole
#: static footprint (bloom probes/pushes + the O(B^2) same-fingerprint
#: prior + depth/family tallies), pinned so analytics creep into the
#: walk hot loop fails CI the same way a grown chunk body does.  Only 4
#: fixed ops (vs the BFS engines' 6): the swarm scaffolding is the
#: scan wrapper alone — no queue/frontier plumbing.  Re-pinned for jax
#: 0.9.0 (PR 24; was 3104 / 3251): the same -36 on both, same cause as
#: LAUNCH_PINS above, the +147 hunt delta unchanged.  Fixed 3 -> 4 in
#: PR 33: the ``add 0`` on ``k0`` that carries ``WALK_STAGES_TAG`` into
#: the compile-cache key (engine/swarm.py; XLA folds it away), as
#: ``tag_stages`` is one of the BFS chunk's; the named stages themselves
#: add no equation to the step.  +25 on both in PR 38 (was 3068 / 3215):
#: ``TypeOK``'s thirteen ``jnp.all`` became twelve int32 counts of the
#: cells that fail (models/invariants.py ``_count``: a ``not``, a
#: convert and a ``reduce_sum`` where one ``reduce_and`` stood).  This
#: model counts equations BEFORE XLA, which fuses them back into the
#: six operations a pass had (and lays them out lanes-minor: PERF.md
#: section 6).
SWARM_LAUNCH_PINS = {
    False: {"launches_per_batch": 3093, "launches_fixed": 4},
    True: {"launches_per_batch": 3240, "launches_fixed": 4},
}


@pytest.mark.parametrize("hunt", [False, True])
def test_swarm_launch_counts_pinned(hunt):
    from raft_tla_tpu.engine.swarm import SwarmEngine
    from raft_tla_tpu.models.dims import LEADER
    from raft_tla_tpu.models.invariants import build_type_ok
    eng = SwarmEngine(
        DIMS,
        invariants={"TypeOK": build_type_ok(DIMS),
                    "NoLeader": lambda st: jnp.all(st.role != LEADER)},
        constraint=build_constraint(DIMS, BOUNDS),
        walks=32, max_depth=12, batch=32, chunk=8, ring=8,
        hunt=hunt, hunt_cells=1 << 16, perf=True)
    lm = eng._perf.launch_model
    assert lm is not None, "swarm launch model failed to build"
    got = {k: lm[k] for k in ("launches_per_batch", "launches_fixed")}
    assert got == SWARM_LAUNCH_PINS[hunt], (
        f"swarm chunk-program launch count moved (hunt={hunt}): {got} "
        f"!= pinned {SWARM_LAUNCH_PINS[hunt]}.  If the walk body or "
        f"hunt tallies changed intentionally, re-pin WITH the delta "
        f"explained; otherwise the walk loop just grew device ops.")
    # The observatory's footprint is bounded: hunt adds device ops to
    # the scan body but never an order of magnitude.
    assert SWARM_LAUNCH_PINS[True]["launches_per_batch"] \
        <= 1.10 * SWARM_LAUNCH_PINS[False]["launches_per_batch"]


# ---------------------------------------------------------------------------
# Observational contract + event surfaces


def test_perf_observational_single_chip(tmp_path):
    """Engine counts bit-identical with --perf on vs off; the perf
    event validates, carries launch accounting + a roofline fraction
    for every profiled stage, and the advisor names one of them.  Also
    pins the per-level HBM watermark field (None on CPU devices that
    report no memory stats — present either way)."""
    plain = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                      config=small_config(max_diameter=3))
    res0 = plain.run([init_state(DIMS)])
    ev = str(tmp_path / "events.jsonl")
    eng = BFSEngine(DIMS, constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(max_diameter=3, perf=True,
                                        events_out=ev))
    res1 = eng.run([init_state(DIMS)])
    assert (res0.distinct, res0.generated, res0.levels) \
        == (res1.distinct, res1.generated, res1.levels)
    assert res0.action_counts == res1.action_counts

    recs = validate_run_events(ev)              # payload schema enforced
    perf_evs = [e for e in recs if e["event"] == "perf"]
    assert len(perf_evs) == 1
    perf = perf_evs[0]["perf"]
    assert perf == res1.perf
    launch = perf["launch"]
    assert launch["launches_per_batch"] == \
        LAUNCH_PINS["v2"]["launches_per_batch"]   # auto resolves to v2
    assert launch["launches_per_chunk"] > 0
    assert launch["chunk_calls"] > 0
    assert 0.0 <= launch["launch_overhead_share"] <= 1.0
    assert launch["per_level"], "end_level never fired"
    stages = perf["roofline"]["stages"]
    assert set(stages) == {"expand", "fingerprint", "dedup_insert",
                           "enqueue"}
    for r in stages.values():                  # profiler ran: measured
        assert r["mean_seconds"] is not None
        assert r["bandwidth_fraction"] is not None
    assert perf["advisor"]["top"] in stages
    # perf gauges landed
    g = eng.metrics.snapshot()["gauges"]
    assert g.get("perf/launches_per_chunk", 0) > 0
    # per-level HBM watermark field present on every level row
    assert res1.level_stats
    assert all("hbm_peak_bytes" in row for row in res1.level_stats)


def test_perf_observational_mesh_dryrun_and_skew(tmp_path):
    """Mesh dryrun: counts bit-identical perf on/off; the perf block
    carries the mesh launch model + modeled collective share; skew
    telemetry lands balance gauges, level_complete fields, and (with a
    1.0 threshold — any imbalance) skew warning events."""
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    base = dict(batch=16, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, max_diameter=2)
    res0 = MeshBFSEngine(
        DIMS, constraint=build_constraint(DIMS, BOUNDS),
        config=EngineConfig(**base)).run([init_state(DIMS)])
    ev = str(tmp_path / "mesh_events.jsonl")
    eng = MeshBFSEngine(
        DIMS, constraint=build_constraint(DIMS, BOUNDS),
        config=EngineConfig(**base, perf=True, events_out=ev,
                            skew_warn_ratio=1.0))
    res1 = eng.run([init_state(DIMS)])
    assert (res0.distinct, res0.generated, res0.levels) \
        == (res1.distinct, res1.generated, res1.levels)

    recs = validate_run_events(ev)
    perf = [e for e in recs if e["event"] == "perf"][0]["perf"]
    assert perf["launch"]["launches_per_batch"] > 0
    assert perf["collectives"]["collectives_per_batch"] > 0
    assert perf["collectives"]["probe_seconds"] > 0
    levels = [e for e in recs if e["event"] == "level_complete"]
    assert any(e.get("frontier_skew") is not None for e in levels)
    assert any(isinstance(e.get("shard_frontier"), list) for e in levels)
    skews = [e for e in recs if e["event"] == "skew"]
    assert skews, "threshold 1.0 must warn on any imbalance"
    bal = skews[0]["balance"]
    assert bal["frontier_skew"] >= 1.0
    assert len(bal["shard_frontier"]) == eng.n_dev
    g = eng.metrics.snapshot()["gauges"]
    assert "mesh/frontier_skew" in g


# ---------------------------------------------------------------------------
# bench_diff --launch-drift + xplane_summary


def _bench_doc(lpc, frac=0.5, value=1000.0):
    return {"value": value, "unit": "states/s",
            "distinct_states": 1000, "generated_states": 3000,
            "perf": {"launch": {"launches_per_chunk": lpc},
                     "roofline": {"stages": {
                         "expand": {"bandwidth_fraction": frac}}},
                     "advisor": {"top": "expand"}}}


def test_bench_diff_gates_launch_drift(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import bench_diff

    old = tmp_path / "old.json"
    old.write_text(json.dumps(_bench_doc(1000.0)))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_bench_doc(1100.0)))     # +10% < 25%
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_bench_doc(2000.0)))    # +100%
    slowbw = tmp_path / "slowbw.json"
    slowbw.write_text(json.dumps(_bench_doc(1000.0, frac=0.1)))
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps(
        {"value": 1000.0, "distinct_states": 1000}))

    assert bench_diff.main([str(old), str(ok)]) == 0
    assert bench_diff.main([str(old), str(bad)]) == 1
    assert bench_diff.main([str(old), str(bad),
                            "--launch-drift", "2.0"]) == 0
    assert bench_diff.main([str(old), str(slowbw)]) == 1
    # one side predates the perf block: noted, never gated
    assert bench_diff.main([str(legacy), str(bad)]) == 0
    assert bench_diff.main([str(old), str(legacy)]) == 0


def _write_fake_xplane(logdir, chunks=4, kernels_per_chunk=50):
    run = os.path.join(logdir, "plugins", "profile", "2026_08_04")
    os.makedirs(run, exist_ok=True)
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "python host"}},
    ]
    t = 0
    for c in range(chunks):
        events.append({"ph": "X", "pid": 1, "tid": 0, "name": "chunk",
                       "ts": t, "dur": 1000})
        for k in range(kernels_per_chunk):
            events.append({"ph": "X", "pid": 1, "tid": 0,
                           "name": f"fusion.{k % 7}",
                           "ts": t + k, "dur": 10})
        # host-side noise must not count as kernels
        events.append({"ph": "X", "pid": 9, "tid": 0,
                       "name": "python_call", "ts": t, "dur": 500})
        # device work BETWEEN chunk windows (per-level ingest /
        # profiler re-executions) must not inflate launches_per_chunk
        events.append({"ph": "X", "pid": 1, "tid": 0,
                       "name": "ingest.fusion", "ts": t + 1500,
                       "dur": 10})
        t += 2000
    path = os.path.join(run, "host.trace.json.gz")
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_xplane_summary_counts_and_ledger(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import bench_diff
    import xplane_summary

    logdir = str(tmp_path / "xla_profile")
    _write_fake_xplane(logdir, chunks=4, kernels_per_chunk=50)
    out = str(tmp_path / "summary.json")
    ledger = str(tmp_path / "ledger.jsonl")
    rc = xplane_summary.main([logdir, "--out", out, "--history", ledger,
                              "--label", "xplane_test"])
    assert rc == 0
    doc = json.loads(open(out).read())
    launch = doc["perf"]["launch"]
    assert launch["chunk_calls"] == 4
    # host noise AND out-of-window device work excluded
    assert launch["kernel_events"] == 200
    assert launch["launches_per_chunk"] == 50.0
    assert doc["top_kernels"]

    from raft_tla_tpu.obs import history as history_mod
    entries = history_mod.read_history(ledger)
    assert entries[0]["kind"] == "xplane"
    assert entries[0]["bench"]["perf"]["launch"][
        "launches_per_chunk"] == 50.0
    # the dialect diffs + gates through bench_diff like any bench pair
    worse = str(tmp_path / "worse")
    _write_fake_xplane(worse, chunks=4, kernels_per_chunk=100)
    out2 = str(tmp_path / "summary2.json")
    assert xplane_summary.main([worse, "--out", out2]) == 0
    assert bench_diff.main([out, out2]) == 1           # 2x launches
    assert bench_diff.main([out2, out]) == 0           # improvement
    # empty capture dir fails loudly (rc 2)
    assert xplane_summary.main([str(tmp_path / "nothing")]) == 2


def test_perf_event_requires_payload(tmp_path):
    """The validator's schema table knows the new events: a perf/skew
    record without its payload object is a malformed log."""
    p = tmp_path / "ev.jsonl"
    p.write_text(json.dumps({"event": "run_start", "ts": 1.0}) + "\n"
                 + json.dumps({"event": "perf", "ts": 2.0}) + "\n"
                 + json.dumps({"event": "run_end", "ts": 3.0}) + "\n")
    with pytest.raises(ValueError, match="perf"):
        validate_run_events(str(p))
    p2 = tmp_path / "ev2.jsonl"
    p2.write_text(json.dumps({"event": "run_start", "ts": 1.0}) + "\n"
                  + json.dumps({"event": "skew", "ts": 2.0,
                                "balance": {"frontier_skew": 3.0}}) + "\n"
                  + json.dumps({"event": "run_end", "ts": 3.0}) + "\n")
    assert len(validate_run_events(str(p2))) == 3
