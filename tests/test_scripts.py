"""Every scripts/*.py entry point must run from a fresh clone (round 2
proved they rot silently).  Each is smoke-invoked in a subprocess on CPU
with tiny sizes — exit 0 and a sanity-check of stdout is the contract;
what runs on the chip is chip_smoke.py."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(args, extra_env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # scripts run single-device
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{args} failed rc={proc.returncode}\n--- stdout\n{proc.stdout}"
        f"\n--- stderr\n{proc.stderr[-3000:]}")
    return proc.stdout


def test_profile_fpset_runs():
    out = run_script(["scripts/profile_fpset.py"],
                     extra_env={"FPSET_C": str(1 << 14),
                                "FPSET_K": str(1 << 10)})
    assert "hash insert" in out


def test_rebuild_rounds_runs(tmp_path):
    """``scripts/rebuild_rounds.py`` at a tiny size: one line a
    measurement, and the rebuild's own insert runs the same rounds as
    ``insert_unique`` on under half the lanes."""
    out = run_script(["scripts/rebuild_rounds.py", "--slots", "16",
                      "--piece", "10", "--pieces", "4", "--loads", "0.14",
                      "--out", str(tmp_path / "rounds.jsonl")])
    lines = [json.loads(text) for text in out.splitlines()]
    assert [line["what"] for line in lines] == [
        "device", "insert_unique", "round", "round", "round", "compact",
        "rebuild_unique", "whole", "whole", "whole"]
    with open(tmp_path / "rounds.jsonl", encoding="utf-8") as f:
        assert [json.loads(text) for text in f] == lines
    old, new = lines[-2], lines[-1]
    assert (old["door"], new["door"]) == ("insert_unique", "rebuild_unique")
    assert old["keys"] == new["keys"] == 4 << 10
    assert old["rounds_by_piece"] == new["rounds_by_piece"]
    assert 1 <= new["lane_rounds_a_key"] < old["lane_rounds_a_key"] / 2


@pytest.mark.slow   # ~2 min CPU
def test_leader_bench_runs():
    """The leader-rich bench must actually exercise the log-machinery
    kernels (ClientRequest/AppendEntries/AdvanceCommitIndex > 0 is asserted
    inside the script itself)."""
    out = run_script(["scripts/leader_bench.py", "3", "64"])
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["leader_family_share"] > 0.05
    assert rec["seeds"] > 0


def _hlo_line(name, result, path, cycles):
    return (f'  %{name} = {result} fusion(%p.1), kind=kLoop, '
            f'metadata={{op_name="jit(chunk)/jit(chunk)/while/body/{path}" '
            f'stack_frame_id=4}}, backend_config={{"window_config":'
            f'{{"estimated_cycles":"{cycles}","is_mask":false}}}}')


def test_hlo_parts_reads_a_scope_apart():
    """``scripts/hlo_parts.py --scope extra``: beside the per-part table,
    the operations of ``--part`` whose path names the scope after the
    part, ``vmap(extra)`` unwrapped, as ``benchmark/readers/variant.py
    scope_of`` reads a capture; lanes-major is any axis but the K lanes
    minor-most.  On lines shaped as the TPU compiler writes them (the
    compile itself is ``tests/test_chip_compile.py``'s, in the one
    process that may load the compiler)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import hlo_parts
    lane = "construct/lane_out/vmap(lane_out)/"
    text = "\n".join([
        _hlo_line("fusion.1", "s32[1024,3]{0,1:T(4,128)}",
                  lane + "vmap(extra)/reduce_sum", 700),
        _hlo_line("fusion.2", "(pred[1024,1]{1,0:T(8,128)}, s32[1024]{0})",
                  lane + "vmap(extra)/gather", 5000),
        _hlo_line("fusion.3", "u32[1024]{0:T(1024)}",
                  lane + "vmap(vmap(quorum))/gt", 30),
        _hlo_line("fusion.4", "s32[1024,3,3]{2,1,0:T(4,128)}",
                  lane + "select_n", 11),
        _hlo_line("fusion.5", "pred[1024]{0}",
                  "construct/invariants/vmap(TypeOK)/extra/and", 3),
        _hlo_line("fusion.6", "pred[1024]{0}", "masks/vmap(extra)/and", 90),
        "  %bare = s32[] add(%a, %b)"])
    ops, cycles, rows = hlo_parts.tally(text, 1024, "lane_out", "extra")
    assert (ops["lane_out", "all"], cycles["lane_out", "all"]) == (4, 5741)
    assert (ops["lane_out", "lanes-major"],
            cycles["lane_out", "lanes-major"]) == (2, 5011)
    assert (ops["scope", "all"], cycles["scope", "all"]) == (2, 5700)
    assert (ops["scope", "lanes-major"],
            cycles["scope", "lanes-major"]) == (1, 5000)
    assert (ops["invariants", "all"], ops["scope", "all"]) == (1, 2)
    assert [r[1] for r in sorted(rows, reverse=True)] == ["fusion.2",
                                                          "fusion.1"]
    _ops, _cycles, rows = hlo_parts.tally(text, 1024, "lane_out")
    assert len(rows) == 4


def test_oracle_exhaust_level_capped(tmp_path):
    out = run_script(["scripts/oracle_exhaust.py",
                      "configs/MCraft_bounded.cfg",
                      str(tmp_path / "oracle.jsonl"), "2"])
    rec = json.loads(out.strip().splitlines()[-1])
    # Level-2 prefix of the pinned MCraft_bounded profile
    # (tests/test_engine.py::MCRAFT_BOUNDED_LEVELS, oracle_exhaust.jsonl).
    assert rec["levels"] == [1, 3, 18]
    assert rec["distinct"] == 22 and rec["generated"] == 33
    assert rec["diameter"] == 2


@pytest.mark.slow   # ~1 min CPU; bench.py is exercised by the CI bench_diff steps
def test_bench_runs_with_tiny_budget():
    out = run_script(["bench.py"], extra_env={"BENCH_SECONDS": "3"},
                     timeout=900)
    rec = json.loads(out.strip().splitlines()[-1])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    # Telemetry (obs/): the per-phase wall-time breakdown BENCH_r06+
    # carries; the script itself exits nonzero if the run's event log is
    # missing or malformed, so reaching here also proves that gate.
    assert rec["phases"] and "stats_fetch" in rec["phases"]


# ---------------------------------------------------------------------------
# scripts/bench_diff.py — the regression gate (no jax; imported in-process
# so the rc contract is tested without a subprocess per case).

def _bench_diff_main():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "scripts", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def _fake_bench(value=1000.0, gen=4000.0, **over):
    doc = {"metric": "distinct_states_per_sec", "value": value,
           "unit": "states/s", "generated_per_sec": gen,
           "distinct_states": 100000,
           "phases": {"chunk": 40.0, "stats_fetch": 5.0, "warmup": 2.0},
           "coverage": {"Timeout": {"generated": 600, "distinct": 300,
                                    "disabled": 0},
                        "Receive": {"generated": 400, "distinct": 100,
                                    "disabled": 200}}}
    doc.update(over)
    return doc


def test_bench_diff_trajectory_and_self_compare_pass(capsys):
    main = _bench_diff_main()
    # The real BENCH_r* trajectory (wrapper form) must stay green...
    assert main([os.path.join(REPO, "BENCH_r04.json"),
                 os.path.join(REPO, "BENCH_r05.json")]) == 0
    # ...and self-compare is exactly zero-delta.
    assert main([os.path.join(REPO, "BENCH_r05.json"),
                 os.path.join(REPO, "BENCH_r05.json")]) == 0
    assert "PASS" in capsys.readouterr().out


def test_bench_diff_flags_regressions(tmp_path, capsys):
    main = _bench_diff_main()
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    old.write_text(json.dumps(_fake_bench()))
    # 2x headline slowdown -> rc 1 (the acceptance case).
    new.write_text(json.dumps(_fake_bench(value=500.0, gen=2000.0)))
    assert main([str(old), str(new)]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    # A single host phase blowing past its threshold -> rc 1.
    phases = dict(_fake_bench()["phases"], stats_fetch=50.0)
    new.write_text(json.dumps(_fake_bench(phases=phases)))
    assert main([str(old), str(new)]) == 1
    assert "phase 'stats_fetch'" in capsys.readouterr().out
    # Coverage-mix drift (action shares shifted well past 5 pts) -> rc 1.
    cov = {"Timeout": {"generated": 100, "distinct": 50, "disabled": 0},
           "Receive": {"generated": 900, "distinct": 200, "disabled": 0}}
    new.write_text(json.dumps(_fake_bench(coverage=cov)))
    assert main([str(old), str(new)]) == 1
    assert "coverage mix drift" in capsys.readouterr().out
    # Within-threshold wobble passes.
    new.write_text(json.dumps(_fake_bench(value=950.0, gen=3900.0)))
    assert main([str(old), str(new)]) == 0
    # Thresholds are configurable: the same wobble fails at 1%.
    assert main([str(old), str(new), "--max-regress", "0.01"]) == 1


def test_bench_diff_pruned_fraction_is_gated(tmp_path, capsys):
    """The POR pruned fraction is a first-class compared metric: a
    collapsed reduction (baseline pruned, candidate back to full
    expansion) regresses; matched fractions pass with the note; runs
    that never pruned stay silent on the axis."""
    main = _bench_diff_main()
    old, new = tmp_path / "old.json", tmp_path / "new.json"

    def cov(pruned_t, pruned_r):
        return {"Timeout": {"generated": 600, "distinct": 300,
                            "disabled": 0, "pruned": pruned_t},
                "Receive": {"generated": 400, "distinct": 100,
                            "disabled": 200, "pruned": pruned_r}}

    old.write_text(json.dumps(_fake_bench(coverage=cov(100, 50))))
    new.write_text(json.dumps(_fake_bench(coverage=cov(100, 50))))
    assert main([str(old), str(new)]) == 0
    assert "POR pruned expansions" in capsys.readouterr().out
    # Collapse to zero pruning -> regression past --pruned-drift.
    new.write_text(json.dumps(_fake_bench(coverage=cov(0, 0))))
    assert main([str(old), str(new)]) == 1
    out = capsys.readouterr().out
    assert "pruned fraction fell" in out
    # ... but an explicit loose threshold lets it through.
    assert main([str(old), str(new), "--pruned-drift", "50"]) == 0
    capsys.readouterr()
    # No pruning anywhere: the axis stays silent (legacy benches).
    old.write_text(json.dumps(_fake_bench()))
    new.write_text(json.dumps(_fake_bench()))
    assert main([str(old), str(new)]) == 0
    assert "POR pruned" not in capsys.readouterr().out


def test_bench_diff_malformed_inputs_exit_2(tmp_path, capsys):
    main = _bench_diff_main()
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fake_bench()))
    # Missing file.
    assert main([str(tmp_path / "nope.json"), str(ok)]) == 2
    # Not JSON at all.
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert main([str(bad), str(ok)]) == 2
    # A BENCH_r* wrapper whose run never emitted JSON (parsed: null).
    bad.write_text(json.dumps({"cmd": "x", "rc": 1, "parsed": None}))
    assert main([str(ok), str(bad)]) == 2
    err = capsys.readouterr().err
    assert "bench_diff:" in err
