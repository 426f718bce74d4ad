"""The four-chip deployment of ``MCraft_bounded`` (benchmark configuration
``mcraft3-mesh4``), on 4 of the suite's 8 virtual CPU devices.

``make_engine(setup, cfg, "auto")`` picks ``MeshBFSEngine`` on a host with
more than one accelerator; on the CPU it never does, so one test shows the
resolution with ``jax.devices()`` patched and the others name the class.
They share one mesh engine (its chunk's CPU compile is the cost) and one
single-chip engine.
"""

import dataclasses
import functools
import json
import os
import random
import types

import jax
import numpy as np
import pytest

from raft_tla_tpu.engine import checkpoint as ckpt_mod
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.engine.check import initial_states, make_engine
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.schema import decode_state, unflatten_state
from raft_tla_tpu.parallel.mesh import MeshBFSEngine
from raft_tla_tpu.utils.cfg import load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPS = 4
# distinct through level 7 of MCraft_bounded (artifacts/mcraft_L14_oracle.jsonl)
LEVEL6, LEVEL7 = 9457, 37054


def reference_levels() -> dict:
    with open(os.path.join(REPO, "artifacts", "mcraft_L14_oracle.jsonl"),
              encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return {r["level"]: (r["frontier"], r["distinct"], r["generated"])
            for r in rows}


def config(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 16,
                        seen_capacity=1 << 18, record_trace=True,
                        pipeline="auto", progress_interval_seconds=0.0,
                        **kw)


def run(eng, *args, **cfg):
    """One run of a shared engine under its own budgets."""
    saved = {k: getattr(eng.config, k) for k in cfg}
    for k, v in cfg.items():
        setattr(eng.config, k, v)
    try:
        return eng.run(*args)
    finally:
        for k, v in saved.items():
            setattr(eng.config, k, v)


@pytest.fixture(scope="module")
def setup():
    return load_config(os.path.join(REPO, "configs", "MCraft_bounded.cfg"))


@pytest.fixture(scope="module")
def mesh(setup):
    eng = make_engine(setup, config(),
                      engine_cls=functools.partial(
                          MeshBFSEngine, devices=jax.devices()[:CHIPS]))
    assert eng.n_dev == CHIPS
    return eng


@pytest.fixture(scope="module")
def single(setup):
    return make_engine(setup, config(), engine_cls=BFSEngine)


@pytest.fixture(scope="module")
def snapshots(setup, mesh, single, tmp_path_factory):
    """A level-5 snapshot written by each engine."""
    out = {}
    for name, eng in (("mesh", mesh), ("single", single)):
        d = str(tmp_path_factory.mktemp(f"ck_{name}"))
        run(eng, initial_states(setup), max_diameter=5, checkpoint_dir=d,
            checkpoint_every=5, checkpoint_interval_seconds=0.0)
        out[name] = ckpt_mod.load(ckpt_mod.latest(d))
        assert out[name].diameter == 5
    return out


@pytest.mark.parametrize("platform, want", [("tpu", "mesh"),
                                            ("cpu", "BFSEngine")])
def test_auto_resolves_to_the_mesh_on_four_accelerators(
        setup, monkeypatch, platform, want):
    from raft_tla_tpu.parallel import mesh as mesh_mod
    fake = [types.SimpleNamespace(platform=platform, id=i)
            for i in range(CHIPS)]
    monkeypatch.setattr(jax, "devices", lambda *a: fake)
    monkeypatch.setattr(mesh_mod, "MeshBFSEngine",
                        lambda dims, **kw: "mesh")
    monkeypatch.setattr("raft_tla_tpu.engine.check.BFSEngine",
                        lambda dims, **kw: "BFSEngine")
    assert make_engine(setup, config(), "auto") == want


def test_mesh_walk_equals_the_reference_at_every_level(setup, mesh,
                                                       tmp_path):
    events = str(tmp_path / "ev.jsonl")
    res = run(mesh, initial_states(setup), max_diameter=6,
              events_out=events)
    assert res.pipeline == "v2" and res.distinct == LEVEL6
    with open(events, encoding="utf-8") as f:
        evs = [json.loads(line) for line in f]
    got = {e["level"]: (e["frontier_rows"], e["distinct"], e["generated"])
           for e in evs if e["event"] == "level_complete"}
    want = reference_levels()
    assert got == {lv: want[lv] for lv in range(7)}
    start, end = evs[0], evs[-1]
    assert (start["event"], start["engine"]) == ("run_start",
                                                 "MeshBFSEngine")
    assert not any(e["event"] == "degraded" for e in evs)
    # run_end carries the mesh's own counts, gathered from the chips
    # by the chunk program: the per-chip lists add up to the totals.
    assert sum(end["chip_parents_expanded"]) == end["parents_expanded"]
    assert sum(end["chip_shard_keys"]) == res.distinct
    assert len(end["chip_next_count"]) == CHIPS


@pytest.mark.parametrize("dealt_out", [True, False])
def test_a_walk_from_one_root_is_dealt_out_over_the_chips(
        setup, mesh, tmp_path, monkeypatch, dealt_out):
    """A row lies on the chip that generated it, so from one root every
    row would stay on chip 0 and chips 1-3 would expand nothing (the
    second case, with ``_deal_out`` switched off, shows it).  At a level
    boundary the mesh deals an uneven frontier out: every chip expands,
    in fewer passes, and the counts are the reference's either way."""
    if not dealt_out:
        monkeypatch.setattr(
            mesh, "_deal_out",
            lambda qcur, counts, pending, level: (qcur, counts))
    events = str(tmp_path / "ev.jsonl")
    res = run(mesh, initial_states(setup), max_diameter=6,
              events_out=events)
    assert res.distinct == LEVEL6
    with open(events, encoding="utf-8") as f:
        evs = [json.loads(line) for line in f]
    end = evs[-1]
    dealt = [e["level"] for e in evs if e["event"] == "rebalance"]
    if dealt_out:
        # Level 4 is the first with over a batch of rows above a
        # chip's even share (318 rows on chip 0, batch 64).
        assert dealt and dealt[0] == 4
        assert min(end["chip_parents_expanded"]) > 0
        assert end["passes"] < 20       # 30 on one chip
    else:
        assert not dealt
        assert end["chip_parents_expanded"][1:] == [0, 0, 0]


@pytest.mark.parametrize("writer, reader", [("single", "mesh"),
                                            ("mesh", "single")])
def test_snapshot_crosses_engines(snapshots, mesh, single, writer, reader):
    eng = {"mesh": mesh, "single": single}[reader]
    res = run(eng, None, snapshots[writer], max_diameter=7)
    assert (res.diameter, res.distinct) == (7, LEVEL7)
    assert res.levels[-1] == reference_levels()[7][0]
    if reader == "mesh":
        assert {"restore_keys", "restore_frontier",
                "restore_trace"} <= set(res.phases)


def test_a_resumed_run_reports_what_its_restore_overlapped(
        snapshots, mesh, tmp_path):
    """The mesh resume sends the frontier up, dispatches the key inserts,
    refills the trace store under them, and waits last: the three
    ``restore_*`` phases stay disjoint on the host clock (their sum lies
    within the run's wall), ``run_end`` says how many pieces went out and
    how the host's seconds under them stand to the final wait, and the
    run's counts are the ones ``test_snapshot_crosses_engines`` pins."""
    events = str(tmp_path / "resume.jsonl")
    ck = dataclasses.replace(snapshots["single"], wall_seconds=0.0)
    res = run(mesh, None, ck, max_diameter=7, events_out=events)
    assert (res.diameter, res.distinct) == (7, LEVEL7)
    assert res.levels[-1] == reference_levels()[7][0]
    restore = [res.phases[k] for k in ("restore_keys", "restore_frontier",
                                       "restore_trace")]
    assert all(s > 0 for s in restore)
    assert sum(restore) < res.wall_seconds
    with open(events, encoding="utf-8") as f:
        end = [json.loads(line) for line in f][-1]
    assert end["event"] == "run_end"
    # One piece of 2^15 keys a chip holds level 5's keys.
    assert end["restore_pieces"] == 1
    assert end["restore_host_s"] >= 0 and end["restore_wait_s"] >= 0
    assert end["restore_host_s"] + end["restore_wait_s"] <= sum(restore)
    # What the chips' rebuild ran (PR 49): every piece a round at least,
    # every key a lane-round at least, and far fewer lane-rounds than
    # rounds on all of a piece's 2^15 lanes a chip would be.
    keys = len(ck.seen_hi)
    assert end["restore_rounds"] >= end["restore_pieces"]
    assert keys <= end["restore_lane_rounds"] \
        < end["restore_rounds"] * CHIPS * (1 << 15)


def test_a_one_chip_resume_reports_its_rebuilds_rounds(setup, snapshots,
                                                       single, tmp_path):
    """``BFSEngine``'s resume rebuilds through ``fpset.from_host_keys``:
    ``run_end`` carries the rounds and lane-rounds it ran, and a run from
    the root carries neither."""
    ends = {}
    for name, args in (("resume", (None, snapshots["single"])),
                       ("root", (initial_states(setup),))):
        events = str(tmp_path / f"{name}.jsonl")
        run(single, *args, max_diameter=6 if name == "resume" else 2,
            events_out=events)
        with open(events, encoding="utf-8") as f:
            ends[name] = [json.loads(line) for line in f][-1]
        assert ends[name]["event"] == "run_end"
    end, keys = ends["resume"], len(snapshots["single"].seen_hi)
    pieces = -(-keys // (1 << 15))
    assert end["restore_rounds"] >= pieces
    assert keys <= end["restore_lane_rounds"] \
        < end["restore_rounds"] * (1 << 15)
    assert not {"restore_rounds", "restore_lane_rounds"} & set(ends["root"])


@pytest.mark.parametrize("record_trace", [True, False])
def test_a_resume_that_cannot_keep_its_trace_raises_before_any_insert(
        snapshots, mesh, monkeypatch, tmp_path, record_trace):
    """A trace-less checkpoint resumed with the trace on (and a traced
    one resumed without it into a checkpoint directory) is refused
    before a piece of keys is dispatched."""
    ck = snapshots["mesh"]
    if record_trace:
        ck = dataclasses.replace(
            ck, trace_fps=np.empty(0, np.uint64),
            trace_parents=np.empty(0, np.uint64),
            trace_actions=np.empty(0, np.int32), roots={})
    calls = []
    insert = mesh._insert_keys
    monkeypatch.setattr(mesh, "_insert_keys",
                        lambda *a: calls.append(1) or insert(*a))
    with pytest.raises(ValueError, match="trace recording disabled"):
        run(mesh, None, ck, max_diameter=6, record_trace=record_trace,
            checkpoint_dir=None if record_trace else str(tmp_path))
    assert not calls


def test_the_shares_add_up(setup, snapshots, mesh, single, tmp_path):
    """One level from 256 seeded roots: the four shards are disjoint,
    every key lies on chip ``fp_hi mod 4``, and their union is the
    single-chip engine's key set and the reference's count."""
    ck = snapshots["single"]
    rows = random.Random(29).sample(range(len(ck.frontier)), 256)
    roots = [decode_state(unflatten_state(ck.frontier[i], setup.dims),
                          setup.dims) for i in sorted(rows)]
    seen_shards = []
    write = mesh._write_checkpoint

    def spy(qcur, cur_counts, pending, shi, slo, *rest, **kw):
        seen_shards.append(mesh.shard_keys(shi, slo))
        return write(qcur, cur_counts, pending, shi, slo, *rest, **kw)
    mesh._write_checkpoint = spy
    try:
        got = run(mesh, roots, max_diameter=1, checkpoint_every=1,
                  checkpoint_dir=str(tmp_path / "m"),
                  checkpoint_interval_seconds=0.0)
    finally:
        del mesh._write_checkpoint
    run(single, roots, max_diameter=1, checkpoint_every=1,
        checkpoint_dir=str(tmp_path / "s"), checkpoint_interval_seconds=0.0)
    one = ckpt_mod.load(ckpt_mod.latest(str(tmp_path / "s")))
    shards = seen_shards[-1]            # at the level-1 boundary
    assert sorted(shards) == list(range(CHIPS))
    keys = {}
    for chip, (hi, lo) in shards.items():
        assert len(hi) and (hi % CHIPS == chip).all()
        keys[chip] = (hi.astype(np.uint64) << np.uint64(32)) | lo
    union = np.concatenate(list(keys.values()))
    assert len(np.unique(union)) == len(union)          # disjoint
    want = (one.seen_hi.astype(np.uint64) << np.uint64(32)) | one.seen_lo
    assert np.array_equal(np.sort(union), np.sort(want))
    ref = set(roots)
    for r in roots:
        ref.update(t for _a, t in orc.successors(r, setup.dims))
    assert len(union) == len(ref) == got.distinct


def test_resume_honours_max_seconds_within_a_call(snapshots, mesh):
    """The budget is the resumed run's own once the snapshot's seconds are
    set to 0 (as the benchmark's window does): restore counts against it,
    calls are sized from what is left, and the run goes over by no more
    than the pass in flight."""
    budget = 0.4
    ck = dataclasses.replace(snapshots["mesh"], wall_seconds=0.0)
    res = run(mesh, None, ck, max_seconds=budget)
    assert res.stop_reason == "duration_budget"
    assert res.distinct > ck.distinct
    assert res.phases["chunk"] > 0
    assert res.wall_seconds < budget + max(0.5, 4 * mesh._batch_ema)


def test_an_admission_on_every_chip_replays_to_a_legal_path(
        setup, snapshots, mesh):
    """One state of level 7 from every chip's shard (owner = fp_hi mod 4),
    admitted by the resumed run, replays from the trace store to a legal
    7-step path from the initial state."""
    ck = snapshots["single"]
    res = run(mesh, None, ck, max_diameter=7)
    assert res.diameter == 7
    fps = np.asarray(mesh.trace.export()[0], np.uint64)
    old = (ck.seen_hi.astype(np.uint64) << np.uint64(32)) | ck.seen_lo
    new = np.setdiff1d(fps, old)
    assert len(new) == res.distinct - ck.distinct
    init = initial_states(setup)[0]
    for chip in range(CHIPS):
        owned = new[(new >> np.uint64(32)) % np.uint64(CHIPS) == chip]
        paths = (mesh.replay(int(fp)) for fp in owned)
        steps = next(p for p in paths if len(p) == 8)
        states = [s for _a, s in steps]
        assert steps[0][0] == -1 and states[0] == init
        assert all(nxt in orc.successor_set(prev, setup.dims)
                   for prev, nxt in zip(states, states[1:]))


def test_frontier_upload_in_steps_ends_with_the_queue(mesh, monkeypatch):
    """``_upload_segment`` writes a segment into the queue in steps of
    ``UPLOAD_ROWS`` a chip; the last step is moved back so that it ends
    with the queue.  Here: queues of 320 rows, steps of 128, 299 rows a
    chip, so the third step starts at 192 and rewrites 64 rows."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from raft_tla_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "UPLOAD_ROWS", 128)
    sw = mesh._sw
    small = types.SimpleNamespace(n_dev=CHIPS, _sw=sw, _QL=300, _PAD=20,
                                  mesh=mesh.mesh,
                                  _write_rows=mesh._write_rows)
    rng = np.random.default_rng(29)
    seg = rng.integers(0, 255, (4 * 299 - 3, sw), dtype=np.uint8)
    pending = [seg, seg[:7]]
    qcur = jax.device_put(jnp.full((CHIPS, 320, sw), 255, jnp.uint8),
                          NamedSharding(mesh.mesh, P("x")))
    qcur, counts = MeshBFSEngine._upload_segment(small, pending, qcur)
    got, counts = np.asarray(qcur), np.asarray(counts)
    assert counts.tolist() == [299, 299, 299, 296] and len(pending) == 1
    for chip, n in enumerate(counts):
        assert np.array_equal(got[chip, :n],
                              seg[chip * 299:chip * 299 + n])
