"""Swarm tier (engine/swarm.py + ops/walk_kernels.py) contract tests.

The pins that make swarm a *product* tier rather than a lucky fuzzer:

- **determinism / partition invariance** — a (seed, walks, depth) run
  has a bit-identical visited-fingerprint multiset and identical
  verdict across reruns AND across device batch-size and chunk-size
  changes (the counter-PRNG contract walk_kernels.py promises);
- **replayability** — a latched violation reconstructs into a full
  trace whose every step is a legal Python-oracle successor, decoded
  field-for-field through the one canonical formatter (the same
  contract test_explain.py pins for the exhaustive engines);
- **telemetry dialect** — swarm runs emit validate_run_events-clean
  logs with ``swarm_progress`` carrying its registered ``swarm``
  payload object, and run_end carries the same block;
- **serving admission** — an unknown ``mode`` is a clean protocol
  reject (``server/rejected/bad_mode``) at both the blocking check arm
  and job admission, never an executor-thread exception.
"""

import json
import os
import socket
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tla_tpu.engine import explain
from raft_tla_tpu.engine.swarm import SwarmEngine
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok)
from raft_tla_tpu.models.pystate import (diff_states, init_state,
                                         state_fields)
from raft_tla_tpu.obs import validate_run_events
from raft_tla_tpu.ops.walk_kernels import (family_subset, masked_choice,
                                           preferred_choice, walk_bits)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


def invariants():
    return {"TypeOK": build_type_ok(DIMS),
            "NoLeader": lambda st: jnp.all(st.role != LEADER)}


def seeded_root():
    """Candidate one vote short of quorum (test_explain's shape): the
    minimal NoLeader counterexample is two steps away."""
    return init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))


def safe_root():
    """Plain init state: no violation reachable quickly at these
    bounds within a short step budget — the determinism runs below
    must exercise restarts/rings, not stop at a latch."""
    return init_state(DIMS)


def run_swarm(*, batch=None, chunk=8, seed=5, walks=48, num_steps=24,
              **kw):
    eng = SwarmEngine(DIMS, invariants=invariants(),
                      constraint=build_constraint(DIMS, BOUNDS),
                      walks=walks, max_depth=12, batch=batch, chunk=chunk,
                      ring=8, collect_fingerprints=True, **kw)
    res = eng.run([safe_root()], seed=seed, num_steps=num_steps)
    fps = res.visited_fingerprints
    order = np.lexsort((fps[:, 1], fps[:, 0]))
    return eng, res, fps[order]


# ---------------------------------------------------------------------------
# Determinism: the counter-PRNG contract.

def test_multiset_bit_identical_across_batch_chunk_and_rerun():
    _e, ra, a = run_swarm(batch=48)
    _e, rb, b = run_swarm(batch=16)
    _e, rc, c = run_swarm(batch=7)
    _e, rd, d = run_swarm(batch=48, chunk=5)
    _e, ra2, a2 = run_swarm(batch=48)
    assert np.array_equal(a, b)          # batch slicing invisible
    assert np.array_equal(a, c)          # remainder slice too
    assert np.array_equal(a, d)          # chunk size invisible
    assert np.array_equal(a, a2)         # rerun bit-identical
    assert ra.visited == rb.visited == rc.visited == rd.visited
    assert (ra.stop_reason == rb.stop_reason == rc.stop_reason
            == rd.stop_reason)
    # The exact num_steps budget: every walk stepped exactly num_steps.
    assert ra.steps == 48 * 24
    assert ra.visited > 0 and ra.traces >= 48


def test_multiset_is_seed_sensitive():
    _e, _ra, a = run_swarm(seed=5)
    _e, _rb, b = run_swarm(seed=6)
    assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Walk-kernel primitives: the family-diversified draw.

def test_walk_bits_is_a_pure_function_and_stream_separated():
    ids = jnp.arange(7, dtype=jnp.int32)
    a = np.asarray(walk_bits(3, ids, 9, 0x9E3779B1))
    b = np.asarray(walk_bits(3, ids, 9, 0x9E3779B1))
    c = np.asarray(walk_bits(3, ids, 9, 0x85EBCA77))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)      # streams decorrelated
    # Per-lane epoch arrays key the family mask: lanes with different
    # epochs draw different words, equal epochs draw equal words.
    ep = jnp.asarray([0, 0, 1, 1, 2, 2, 3], jnp.int32)
    m = np.asarray(walk_bits(3, ids, ep, 0x165667B1))
    m0 = np.asarray(walk_bits(3, ids, 0, 0x165667B1))
    assert m[0] == m0[0] and m[1] == m0[1] and m[2] != m0[2]


def test_preferred_choice_biases_and_never_stalls():
    fam = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    en = jnp.asarray([[True] * 6, [True] * 6, [False, True] + [False] * 4],
                     bool)
    # Mask keeping only family 1 (bit 1): lanes 2,3 preferred.
    keep1 = jnp.full((3,), 1 << 1, jnp.uint32)
    pref = family_subset(keep1, fam)
    bits = jnp.asarray([0, 1, 2], jnp.uint32)
    ch = np.asarray(preferred_choice(bits, en, pref))
    assert ch[0] in (2, 3) and ch[1] in (2, 3)
    # Lane 2's only enabled action (1, family 0) is OUTSIDE the kept
    # subset: the draw falls back to all-enabled — bias never stalls.
    assert ch[2] == 1
    # Empty mask word: every lane falls back to the unbiased draw.
    none = jnp.zeros((3,), jnp.uint32)
    ch2 = np.asarray(preferred_choice(bits, en, family_subset(none, fam)))
    assert np.array_equal(ch2, np.asarray(masked_choice(bits, en)))


# ---------------------------------------------------------------------------
# Violation: latch, replay, oracle agreement.

@pytest.fixture(scope="module")
def violation_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("swarm")
    ev = str(tmp / "events.jsonl")
    eng = SwarmEngine(DIMS, invariants=invariants(),
                      constraint=build_constraint(DIMS, BOUNDS),
                      walks=32, max_depth=8, chunk=8, ring=8,
                      events_out=ev, counterexample_dir=str(tmp))
    res = eng.run([seeded_root()], seed=1, num_steps=64)
    return eng, res, str(tmp), ev


def test_swarm_latches_the_seeded_violation(violation_run):
    _eng, res, _tmp, _ev = violation_run
    assert res.stop_reason == "violation"
    assert res.violation is not None
    assert res.violation.invariant == "NoLeader"
    assert res.violation_at_seconds is not None
    assert res.violation_trace is not None and len(res.violation_trace) >= 2


def test_replayed_trace_matches_oracle_field_for_field(violation_run):
    eng, res, _tmp, _ev = violation_run
    steps = eng.replay(res.violation.fingerprint)
    decoded = explain.decode_steps(steps, DIMS)
    assert decoded[0]["action"] == "Initial predicate"
    prev = steps[0][1]
    assert decoded[0]["state"] == state_fields(prev, DIMS)
    for rec, (g, st) in zip(decoded[1:], steps[1:]):
        oracle_succ = orc.successor_set(prev, DIMS)
        assert st in oracle_succ
        oracle_match = next(o for o in oracle_succ if o == st)
        assert rec["state"] == state_fields(oracle_match, DIMS)
        fam = DIMS.family_names[DIMS.instance_info(g)[0]]
        assert rec["action"].startswith(fam)
        assert rec["changed"] == diff_states(prev, st, DIMS)
        prev = st
    assert steps[-1][1] == res.violation.state


def test_counterexample_artifacts_land_in_workdir(violation_run):
    _eng, res, tmp, _ev = violation_run
    assert res.counterexample.get("txt")
    assert os.path.exists(os.path.join(tmp, "counterexample.txt"))
    with open(os.path.join(tmp, "counterexample.json")) as f:
        doc = json.load(f)
    assert doc["invariant"] == "NoLeader"


# ---------------------------------------------------------------------------
# Telemetry dialect.

def test_swarm_events_validate_and_carry_the_swarm_payload(violation_run):
    _eng, res, _tmp, ev = violation_run
    events = validate_run_events(ev)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert "swarm_progress" in kinds and "violation" in kinds
    prog = next(e for e in events if e["event"] == "swarm_progress")
    assert isinstance(prog["swarm"], dict)
    assert prog["swarm"]["walks"] == 32
    end = events[-1]
    assert end["stop_reason"] == "violation"
    assert isinstance(end["swarm"], dict)
    assert end["swarm"]["steps"] == res.steps
    assert end["counterexample_path"]
    viol = next(e for e in events if e["event"] == "violation")
    assert viol["invariant"] == "NoLeader"
    assert viol["at_seconds"] == res.violation_at_seconds


def test_swarm_run_start_carries_the_process_record(violation_run, tmp_path,
                                                    monkeypatch):
    """The one helper at ``SwarmEngine``'s emit site: the warm engine's
    next hunt is the first run of a process by a record of this test's
    own, and compiles nothing."""
    from raft_tla_tpu.obs import metrics as metrics_mod
    from tests.test_setup_record import check_process
    eng = violation_run[0]
    record = metrics_mod.ProcessRecord()
    monkeypatch.setattr(metrics_mod, "PROCESS", record)
    monkeypatch.setattr(eng, "events_out", str(tmp_path / "again.jsonl"))
    monkeypatch.setattr(eng, "counterexample_dir", str(tmp_path))
    for _ in range(2):
        eng.run([seeded_root()], seed=1, num_steps=64)
    events = validate_run_events(eng.events_out)
    starts = [e for e in events if e["event"] == "run_start"]
    ends = [e for e in events if e["event"] == "run_end"]
    assert [e["engine"] for e in starts] == ["SwarmEngine"] * 2
    parts = [check_process(e["process"]) for e in starts]
    assert starts[0]["process"]["marks"].keys() == {"first_run"}
    assert (starts[0]["process"]["marks"]["first_run"]
            == starts[1]["process"]["marks"]["first_run"])
    assert parts[0]["runs_s"] == 0.0 < parts[1]["runs_s"]
    assert starts[1]["process"]["runs"]["count"] == 1
    assert "swarm_fetch" in starts[1]["process"]["runs"]["phases"]
    assert [e["jit"] for e in ends] == [{}, {}]


def test_swarm_progress_without_payload_object_is_rejected(tmp_path):
    p = tmp_path / "ev.jsonl"
    lines = [{"event": "run_start", "ts": 0.0},
             {"event": "swarm_progress", "ts": 1.0},   # payload missing
             {"event": "run_end", "ts": 2.0}]
    p.write_text("".join(json.dumps(e) + "\n" for e in lines))
    with pytest.raises(ValueError, match="swarm_progress"):
        validate_run_events(str(p))


# ---------------------------------------------------------------------------
# Serving admission (satellite: unknown mode is a protocol reject).

@pytest.fixture(scope="module")
def server():
    from raft_tla_tpu import server as srv_mod
    srv = srv_mod.serve(port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address
    srv.shutdown()


def roundtrip(addr, req: dict) -> dict:
    with socket.create_connection(addr, timeout=600) as s:
        s.sendall((json.dumps(req) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)


def test_server_swarm_check_and_mode_directive(server):
    cfg = os.path.join(REPO, "configs/MCraft_noleader.cfg")
    r = roundtrip(server, {"op": "check", "cfg": cfg, "mode": "swarm",
                           "walks": 32, "max_depth": 8, "num_steps": 16,
                           "seed": 5, "batch": 32})
    assert r["ok"] is True and r["mode"] == "swarm"
    assert r["walks"] == 32 and r["steps"] == 32 * 16
    assert isinstance(r["report"]["swarm"], dict)
    # The hunt report rides the response top-level (ISSUE 20).
    assert isinstance(r["hunt"], dict)
    assert 0.0 <= r["hunt"]["saturation"] <= 1.0
    assert r["hunt"]["observations"] > 0
    # The cfg MODE/WALKS directives drive the same path when the
    # request leaves mode unset.
    with open(cfg) as f:
        text = f.read()
    text += "\n\\* TPU: MODE = swarm\n\\* TPU: WALKS = 16\n"
    r2 = roundtrip(server, {"op": "check", "cfg_text": text,
                            "max_depth": 8, "num_steps": 16, "seed": 5})
    assert r2["ok"] is True and r2["mode"] == "swarm"
    assert r2["walks"] == 16


# ---------------------------------------------------------------------------
# Hunt observatory (obs/hunt.py): coverage estimation + walk analytics.

def test_hunt_is_purely_observational():
    """ISSUE 20 acceptance: the observatory can never perturb the hunt
    — verdict and visited-fingerprint multiset are bit-identical with
    hunt on vs off (the off engine builds a bare chunk with no bloom
    args at all, so this pins the whole analytics block out of the
    walk semantics)."""
    _e, ron, a = run_swarm(hunt=True)
    _e, roff, b = run_swarm(hunt=False)
    assert np.array_equal(a, b)
    assert ron.stop_reason == roff.stop_reason
    assert ron.visited == roff.visited and ron.steps == roff.steps
    assert ron.traces == roff.traces and ron.diameter == roff.diameter
    assert "hunt" in ron.report and "hunt" not in roff.report


def _hunt_run(num_steps):
    """TypeOK-only invariant set: no reachable violation, so the budget
    runs to completion at every size (the honesty pin needs growing
    samples, not a latch race)."""
    eng = SwarmEngine(DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
                     constraint=build_constraint(DIMS, BOUNDS),
                     walks=48, max_depth=12, chunk=8, ring=8,
                     collect_fingerprints=True)
    return eng, eng.run([safe_root()], seed=5, num_steps=num_steps)


def _species_counts(fps):
    key = (fps[:, 0].astype(np.uint64) << np.uint64(32)
           | fps[:, 1].astype(np.uint64))
    uniq, counts = np.unique(key, return_counts=True)
    return len(key), len(uniq), int((counts == 1).sum())


def test_hunt_estimator_is_honest_against_exact_recount():
    """Estimator honesty: the device Bloom tallies must reproduce the
    exact species counts recomputed on host from the full collected
    fingerprint multiset (the oracle for this run), within the pinned
    collision tolerance — and the saturation estimate must grow toward
    1 as the walk budget grows."""
    sats, distincts = [], []
    for num_steps in (8, 64, 512):
        _eng, res = _hunt_run(num_steps)
        h = res.report["hunt"]
        n, distinct, n1 = _species_counts(res.visited_fingerprints)
        # The observation stream IS the accepted-visit multiset.
        assert h["observations"] == n
        # Oracle recount: distinct species, singletons, saturation.
        # Tolerances pin the only permitted error source — two-probe
        # Bloom collisions — at these loads (~1k species in 2^20
        # cells) they are near zero.
        assert abs(h["distinct_observed"] - distinct) \
            <= max(2, 0.01 * distinct)
        assert abs(h["singletons"] - n1) <= max(2, 0.02 * n1)
        sat_exact = 1.0 - (n1 / n if n else 1.0)
        assert abs(h["saturation"] - sat_exact) <= 0.01
        sats.append(h["saturation"])
        distincts.append(h["distinct_observed"])
    assert sats == sorted(sats)                 # never regresses
    assert sats[-1] > sats[0] + 0.01            # and genuinely grows
    assert distincts[-1] > distincts[0]


def test_hunt_report_schema_and_partitions():
    from raft_tla_tpu.obs.hunt import RESTART_REASONS
    eng, res, _fps = run_swarm()
    h = res.report["hunt"]
    # Good-Turing identities.
    assert abs(h["saturation"] + h["unseen_mass"] - 1.0) <= 2e-6
    assert (h["singletons"] + h["doubletons_plus"]
            == h["distinct_observed"])
    assert 0 < h["distinct_observed"] <= h["observations"]
    assert h["steps"] == res.steps
    # Restart census partitions cleanly and every completed trace is
    # one restart (walks still in flight at budget end are not traces).
    r = h["restarts"]
    assert r["total"] == sum(r[k] for k in RESTART_REASONS)
    d = h["depth"]
    assert sum(d["histogram"]) == d["traces"] == r["total"]
    assert len(d["histogram"]) == eng.max_depth + 1
    assert 0 <= d["p50"] <= d["p90"] <= eng.max_depth
    # Family efficacy table: canonical names, nested tallies, and the
    # Holzmann diversification visibly spreading the hunt.
    fams = h["families"]
    assert [f["family"] for f in fams] == list(DIMS.family_names)
    for f in fams:
        assert 0 <= f["fresh"] <= f["accepted"] <= f["chosen"]
    assert sum(1 for f in fams if f["fresh"]) >= 2
    # Estimator-health block: filter geometry + audited collision bias.
    b = h["bloom"]
    assert b["cells"] == eng.hunt_cells
    assert 0.0 < b["load"] <= 1.0
    assert b["collision_probability"] == round(b["load"] ** 2, 8)
    # Novelty curve: bounded, rates in [0, 1], step axis monotone.
    curve = h["novelty_curve"]
    assert 0 < len(curve) <= 2048
    assert all(0.0 <= p[1] <= 1.0 for p in curve)
    assert [p[0] for p in curve] == sorted(p[0] for p in curve)
    assert h["time_to_violation_seconds"] is None
    assert h["wall_seconds"] > 0


def test_hunt_event_and_progress_embed_the_report(violation_run):
    """The ``hunt`` run event validates with its registered payload
    object, agrees with ``SwarmResult.report["hunt"]``, and the
    enriched ``swarm_progress``/``run_end`` swarm blocks carry the live
    snapshot; a violating hunt stamps time-to-violation."""
    _eng, res, _tmp, ev = violation_run
    h = res.report["hunt"]
    assert h["time_to_violation_seconds"] == res.violation_at_seconds
    events = validate_run_events(ev)
    hunts = [e for e in events if e["event"] == "hunt"]
    assert len(hunts) == 1
    assert hunts[0]["hunt"]["saturation"] == h["saturation"]
    assert hunts[0]["hunt"]["observations"] == h["observations"]
    prog = next(e for e in events if e["event"] == "swarm_progress")
    assert 0.0 <= prog["swarm"]["hunt"]["saturation"] <= 1.0
    end = events[-1]
    assert end["event"] == "run_end"
    assert end["swarm"]["hunt"]["distinct_observed"] \
        == h["distinct_observed"]


def test_hunt_event_without_payload_object_is_rejected(tmp_path):
    p = tmp_path / "ev.jsonl"
    lines = [{"event": "run_start", "ts": 0.0},
             {"event": "hunt", "ts": 1.0, "hunt": "saturated"},
             {"event": "run_end", "ts": 2.0}]
    p.write_text("".join(json.dumps(e) + "\n" for e in lines))
    with pytest.raises(ValueError, match="hunt"):
        validate_run_events(str(p))


def test_server_rejects_unknown_mode_cleanly(server):
    cfg = os.path.join(REPO, "configs/MCraft_noleader.cfg")
    r = roundtrip(server, {"op": "check", "cfg": cfg, "mode": "zigzag"})
    assert r["ok"] is False
    assert "mode" in r["error"]
    # Job admission rejects BEFORE the executor thread ever sees it.
    r2 = roundtrip(server, {"op": "submit",
                            "job": {"op": "check", "cfg": cfg,
                                    "mode": "zigzag"}})
    assert r2["ok"] is False
    assert "mode" in r2["error"]
    st = roundtrip(server, {"op": "stats"})
    assert st["metrics"]["counters"]["server/rejected/bad_mode"] >= 2
    assert st["swarm_cache"]["capacity"] >= 1
