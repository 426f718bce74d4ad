"""TLC-parity statespace report (obs/report.py) + run-history ledger
(obs/history.py) tests.

The load-bearing contract: the report is pure host-side arithmetic over
counters the engines already fetch — engine counts are BIT-IDENTICAL
with the report on or off (single-chip and mesh), while the on-path
emits the ``statespace`` event, feeds the ``statespace/*`` gauges, and
surfaces ``EngineResult.report``.  The ledger records one line per run
and lets bench_diff auto-resolve a same-host baseline.
"""

import json
import os

import pytest

from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok, constraint_py,
                                            type_ok_py)
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.obs import history as history_mod
from raft_tla_tpu.obs import report as report_mod

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=32)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_config(**kw):
    base = dict(batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# Pure report math.

def test_collision_probability_is_tlcs_formula():
    # d * (g - d) / 2^64, zero when nothing was deduplicated.
    assert report_mod.collision_probability(10, 10) == 0.0
    p = report_mod.collision_probability(1 << 32, (1 << 33))
    # d = 2^32, dupes = 2^32 -> p = 2^64 / 2^64 = 1.
    assert p == pytest.approx(1.0)
    assert report_mod.collision_probability(0, 100) == 0.0


def test_build_report_table_and_render():
    class R:
        distinct, generated, diameter = 100, 400, 2
        levels = [1, 9, 90]
        stop_reason, violation, deadlock = "exhausted", None, None
        growth_stalls = [(2048, 0.5)]
    stats = [{"level": 1, "frontier": 9, "distinct": 10, "generated": 40,
              "seen_size": 10, "seen_capacity": 1024},
             {"level": 2, "frontier": 90, "distinct": 100,
              "generated": 400, "seen_size": 100, "seen_capacity": 1024}]
    rep = report_mod.build_report(R, level_stats=stats,
                                  seen_capacity=1024, seen_size=100)
    assert [r["frontier"] for r in rep["levels"]] == [1, 9, 90]
    assert rep["levels"][1]["seen_load"] == pytest.approx(10 / 1024,
                                                          abs=1e-4)
    assert rep["frontier_peak"] == {"level": 2, "frontier": 90}
    assert rep["collision"]["calculated"] == pytest.approx(
        100 * 300 / 2.0 ** 64)
    assert rep["seen_set"]["final_load"] == pytest.approx(100 / 1024,
                                                          abs=1e-4)
    text = report_mod.render_report(rep)
    assert "400 states generated, 100 distinct states found" in text
    assert "calculated (optimistic)" in text
    assert "widest level: 2" in text
    assert "1 growth(s)" in text
    # Summary projection (the ledger's report column).
    summ = report_mod.summarize(rep)
    assert summ["diameter"] == 2 and summ["frontier_peak"] == 90


# ---------------------------------------------------------------------------
# Engine integration: bit-identity on/off + the surfaces.

def run_once(report_on, tmp_path=None, diameter=3):
    cfg = small_config(max_diameter=diameter, statespace_report=report_on,
                       events_out=(str(tmp_path / "ev.jsonl")
                                   if tmp_path else None))
    eng = BFSEngine(DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
                    constraint=build_constraint(DIMS, BOUNDS), config=cfg)
    return eng, eng.run([init_state(DIMS)])


def test_report_on_off_bit_identity_and_oracle(tmp_path):
    eng_on, on = run_once(True, tmp_path)
    _eng_off, off = run_once(False)
    # THE acceptance contract: identical engine counts either way.
    assert (on.distinct, on.generated, on.levels, on.diameter) \
        == (off.distinct, off.generated, off.levels, off.diameter)
    want = orc.bfs([init_state(DIMS)], DIMS,
                   invariants={"TypeOK": type_ok_py},
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=3)
    assert on.distinct == want.distinct_states
    assert on.levels == want.levels
    # Report-on surfaces...
    rep = on.report
    assert rep["distinct"] == on.distinct
    assert [r["frontier"] for r in rep["levels"]] == on.levels
    assert rep["collision"]["calculated"] == pytest.approx(
        report_mod.collision_probability(on.distinct, on.generated))
    assert rep["collision"]["observed_dual_key"] == 0
    assert rep["verdict"] == "ok"
    # Out-degree closes against the coverage accounting: mean * expanded
    # parents == generated (expansion phase).
    od = rep["out_degree"]
    gen = sum(v["generated"] for v in on.coverage.values())
    assert od["mean"] == pytest.approx(gen / od["expanded_parents"],
                                       abs=5e-5)   # 4-decimal rounding
    # ...gauges...
    snap = eng_on.metrics.snapshot()["gauges"]
    assert snap["statespace/diameter"] == on.diameter
    assert snap["statespace/collision_probability"] == pytest.approx(
        rep["collision"]["calculated"])
    # ...and report-off drops every surface.
    assert off.report == {} and off.level_stats == []


def test_statespace_event_validates(tmp_path):
    from raft_tla_tpu.obs import validate_run_events
    _eng, res = run_once(True, tmp_path)
    events = validate_run_events(str(tmp_path / "ev.jsonl"))
    ss = [e for e in events if e["event"] == "statespace"]
    assert len(ss) == 1
    assert ss[0]["report"]["distinct"] == res.distinct
    # Payload enforcement: a statespace event without its report object
    # must fail validation (KNOWN_EVENTS satellite).
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "run_start", "ts": 1}\n'
                   '{"event": "statespace", "ts": 2}\n'
                   '{"event": "run_end", "ts": 3}\n')
    with pytest.raises(ValueError, match="statespace"):
        validate_run_events(str(bad))


def test_mesh_report_on_off_bit_identity():
    from raft_tla_tpu.parallel.mesh import MeshBFSEngine
    cons = build_constraint(DIMS, BOUNDS)
    runs = {}
    for flag in (True, False):
        eng = MeshBFSEngine(
            DIMS, constraint=cons,
            config=small_config(batch=16, max_diameter=2,
                                statespace_report=flag))
        res = eng.run([init_state(DIMS)])
        runs[flag] = res
    on, off = runs[True], runs[False]
    assert (on.distinct, on.generated, on.levels) \
        == (off.distinct, off.generated, off.levels)
    want = orc.bfs([init_state(DIMS)], DIMS,
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=2)
    assert on.distinct == want.distinct_states
    assert on.report["distinct"] == on.distinct
    assert [r["frontier"] for r in on.report["levels"]] == on.levels
    assert off.report == {}


@pytest.mark.slow
def test_report_on_off_pinned_L9_ground_truth():
    """The full acceptance differential: report on vs off on the pinned
    MCraft_bounded L0-L9 ground truths (505004 distinct / 1421121
    generated — tests/test_por.py's pinned values).  CPU-heavy, so
    tier-1 runs the L0-L3 + mesh variants above; this is the
    hardware/nightly form."""
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(os.path.join(REPO, "configs/MCraft_bounded.cfg"))
    out = {}
    for flag in (True, False):
        eng = make_engine(setup, EngineConfig(
            batch=512, queue_capacity=1 << 15, seen_capacity=1 << 21,
            record_trace=False, check_deadlock=False, max_diameter=9,
            statespace_report=flag))
        res = eng.run(initial_states(setup))
        out[flag] = (res.distinct, res.generated, res.levels)
    assert out[True] == out[False]
    assert out[True][0] == 505004 and out[True][1] == 1421121


# ---------------------------------------------------------------------------
# Run-history ledger (obs/history.py).

FP_A = {"cpu_model": "cpuA", "device_kind": "cpu", "device_count": 1,
        "platform": "cpu", "jax": "0.4", "jaxlib": "0.4",
        "hostname": "a"}
FP_B = dict(FP_A, cpu_model="cpuB")


def _bench_doc(value=1000.0, fp=FP_A):
    return {"metric": "distinct_states_per_sec", "value": value,
            "unit": "states/s", "generated_per_sec": 4 * value,
            "distinct_states": 50000, "generated_states": 200000,
            "diameter": 8, "wall_s": 50.0, "stop_reason":
            "duration_budget", "pipeline": "v2",
            "host_fingerprint": fp,
            "phases": {"chunk": 30.0}, "coverage": {},
            "report": {"collision": {"calculated": 1e-12,
                                     "observed_dual_key": 0},
                       "diameter": 8, "verdict": "ok", "levels": [],
                       "frontier_peak": None, "out_degree": {},
                       "seen_set": {}}}


def test_history_entry_append_read_and_host_keys(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    history_mod.append_entry(led, history_mod.entry_from_bench(
        _bench_doc(), label="b1"))
    history_mod.append_entry(led, history_mod.entry_from_bench(
        _bench_doc(value=900.0, fp=FP_B), label="b2"))
    entries = history_mod.read_history(led)
    assert [e["label"] for e in entries] == ["b1", "b2"]
    assert entries[0]["distinct_per_sec"] == 1000.0
    assert entries[0]["bench"]["value"] == 1000.0
    assert entries[0]["report"]["diameter"] == 8
    # Host keys: stable per fingerprint, different across hosts,
    # hostname alone does NOT change identity.
    k1 = history_mod.host_key(FP_A)
    assert k1 == history_mod.host_key(dict(FP_A, hostname="elsewhere"))
    assert k1 != history_mod.host_key(FP_B)
    assert history_mod.host_key(None) is None
    assert history_mod.host_key({"hostname": "x"}) is None
    # The trajectory table flags the host change loudly.
    table = history_mod.render_table(entries)
    assert "HOST-CHANGE" in table
    assert "WARNING" in table and "not comparable" in table


def test_history_resolves_same_host_baseline(tmp_path):
    led = str(tmp_path / "ledger.jsonl")
    for i, (v, fp) in enumerate([(800.0, FP_A), (900.0, FP_B),
                                 (1000.0, FP_A)]):
        history_mod.append_entry(led, history_mod.entry_from_bench(
            _bench_doc(value=v, fp=fp), label=f"b{i}"))
    base = history_mod.resolve_baseline(led, FP_A)
    assert base["label"] == "b2"            # newest same-host, not B's
    assert base["bench"]["value"] == 1000.0
    assert history_mod.resolve_baseline(
        led, dict(FP_A, cpu_model="cpuC")) is None
    # Record-then-gate workflow: the candidate's OWN ledger line must
    # never resolve as its baseline (a self-compare gate is vacuous) —
    # excluding it falls back to the previous same-host entry.
    own = history_mod.resolve_baseline(
        led, FP_A, exclude_bench=_bench_doc(value=1000.0, fp=FP_A))
    assert own["label"] == "b0" and own["bench"]["value"] == 800.0
    # run_id identity survives the captured file being annotated: a
    # candidate with extra keys but the recorded run_id is STILL the
    # same run (doc equality alone would miss it).
    led_id = str(tmp_path / "led_id.jsonl")
    doc = dict(_bench_doc(value=700.0), run_id="abc123")
    history_mod.append_entry(led_id, history_mod.entry_from_bench(
        doc, label="only"))
    annotated = dict(doc, note="captured by hand")
    assert history_mod.resolve_baseline(
        led_id, FP_A, exclude_bench=annotated) is None


def test_history_entry_from_engine_result(tmp_path):
    _eng, res = run_once(True)
    entry = history_mod.entry_from_result(
        "check", res, cfg_text="INVARIANT TypeOK", dims=DIMS,
        host_fingerprint=FP_A, label="unit")
    assert entry["verdict"] == "ok"
    assert entry["distinct"] == res.distinct
    assert entry["report"]["diameter"] == res.diameter
    assert entry["cfg_fingerprint"] and entry["model_fingerprint"]
    led = str(tmp_path / "led.jsonl")
    history_mod.append_entry(led, entry)
    assert history_mod.read_history(led)[0]["label"] == "unit"


def test_family_groups_metadata_and_ledger(tmp_path):
    """models/actions.py family_groups: the base alphabet stacks into
    the four parameter-shape groups (10 families -> 4 launches), and the
    grouping rides EngineResult -> statespace report -> history-ledger
    summary."""
    from raft_tla_tpu.models.actions import family_groups
    from raft_tla_tpu.utils.cfg import load_config
    setup = load_config(os.path.join(REPO, "configs/MCraft_bounded.cfg"))
    dims = setup.dims

    groups = family_groups(dims)
    by_name = {g["group"]: g for g in groups}
    assert set(by_name) == {"server", "server_pair", "server_value",
                            "slot"}
    assert by_name["server"]["kernels"] == 4
    assert by_name["server"]["families"] == ["Restart", "Timeout",
                                             "BecomeLeader",
                                             "AdvanceCommitIndex"]
    assert sum(g["lanes"] for g in groups) == dims.n_instances

    eng = BFSEngine(dims, constraint=build_constraint(dims, setup.bounds),
                    config=EngineConfig(batch=64, queue_capacity=1 << 12,
                                        seen_capacity=1 << 14,
                                        check_deadlock=False,
                                        max_diameter=2))
    res = eng.run([init_state(dims)])
    assert res.family_groups == groups
    assert res.report.get("family_groups") == groups
    summ = report_mod.summarize(res.report)
    assert summ["family_groups"] == {"server": 4, "server_pair": 2,
                                     "server_value": 1, "slot": 3}

    ledger = str(tmp_path / "ledger.jsonl")
    history_mod.append_entry(
        ledger, history_mod.entry_from_result("check", res,
                                              label="family_groups"))
    entry = history_mod.read_history(ledger)[0]
    assert entry["report"]["family_groups"]["server"] == 4


def test_history_rejects_corrupt_ledger(tmp_path):
    led = tmp_path / "led.jsonl"
    led.write_text('{"kind": "bench"}\nnot json\n')
    with pytest.raises(ValueError, match="malformed"):
        history_mod.read_history(str(led))
    with pytest.raises(FileNotFoundError):
        history_mod.read_history(str(tmp_path / "missing.jsonl"))


# ---------------------------------------------------------------------------
# scripts/bench_history.py + scripts/bench_diff.py --history.

def _load_script(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_history_imports_legacy_rounds(tmp_path, capsys):
    bh = _load_script("bench_history")
    led = str(tmp_path / "ledger.jsonl")
    assert bh.main([led, "--import-legacy"]) == 0
    entries = history_mod.read_history(led)
    labels = [e["label"] for e in entries]
    # The committed round files that remain (BENCH_r04/r05, both CPU
    # runs, and MULTICHIP_r01..r05) seed the ledger.
    assert "BENCH_r05" in labels and "BENCH_r04" in labels
    assert {f"MULTICHIP_r0{i}" for i in range(1, 6)} <= set(labels)
    r04 = next(e for e in entries if e["label"] == "BENCH_r04")
    assert r04["distinct_per_sec"] == pytest.approx(39455.3)
    r05 = next(e for e in entries if e["label"] == "BENCH_r05")
    assert r05["distinct_per_sec"] == pytest.approx(38351.8)
    # Legacy rounds predate host fingerprints: flagged unknown-host —
    # the r05 cross-host anomaly rendered not-comparable.
    assert r05["host_key"] is None
    out = capsys.readouterr().out
    assert "host?" in out
    # Idempotent by label: re-import adds nothing.
    n = len(entries)
    assert bh.main([led, "--import-legacy"]) == 0
    assert len(history_mod.read_history(led)) == n


def test_bench_diff_resolves_baseline_from_history(tmp_path, capsys):
    bd = _load_script("bench_diff")
    led = str(tmp_path / "ledger.jsonl")
    history_mod.append_entry(led, history_mod.entry_from_bench(
        _bench_doc(value=1000.0), label="base"))
    new = tmp_path / "new.json"
    new.write_text(json.dumps(_bench_doc(value=980.0)))
    assert bd.main(["--history", led, str(new)]) == 0
    out = capsys.readouterr().out
    assert "auto-resolved from history ledger" in out
    assert "history:base" in out
    # A genuine regression still gates through the resolved baseline.
    new.write_text(json.dumps(_bench_doc(value=400.0)))
    assert bd.main(["--history", led, str(new)]) == 1
    capsys.readouterr()
    # The candidate's own ledger line never self-resolves: with ONLY
    # its own entry in the ledger the gate refuses (exit 2) instead of
    # vacuously passing a self-compare.
    led2 = str(tmp_path / "ledger2.jsonl")
    history_mod.append_entry(led2, history_mod.entry_from_bench(
        _bench_doc(value=980.0), label="self"))
    new.write_text(json.dumps(_bench_doc(value=980.0)))
    assert bd.main(["--history", led2, str(new)]) == 2
    # No same-host entry (candidate from a different host) -> exit 2.
    new.write_text(json.dumps(_bench_doc(value=990.0, fp=FP_B)))
    assert bd.main(["--history", led, str(new)]) == 2
    err = capsys.readouterr().err
    assert "no bench entry with host key" in err
    # Legacy candidate without a fingerprint -> exit 2 too.
    doc = _bench_doc(value=990.0)
    doc.pop("host_fingerprint")
    new.write_text(json.dumps(doc))
    assert bd.main(["--history", led, str(new)]) == 2
