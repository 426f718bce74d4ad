"""The reader of the host loops' own calls (``readers/calls.py``): every
mode on an event log and a capture built by hand, ``None`` on a parent's
log, and the seven metrics' files found by name."""

import json
import os

import pytest

import bench_lib as lib
from bench_helpers import REPO

calls = lib.load_module("readers", "calls")

MS = 1_000_000
NAMES = ("stall_ms.deep", "stall_ms.verdict", "round_trip_ms.deep",
         "round_trip_ms.verdict", "gc_ms.deep", "gc_ms.verdict",
         "rehash_ms")


def run_end(stall_s=0.0, stall_calls=0, gc_s=0.0, rehash_s=0.0, rehashes=0,
            **extra):
    slowest = {"run": 1, "call": 7, "kind": "chunk", "level": 10,
               "rule": "ramp", "passes": 16, "gap_s": 0.0001, "named_s": 0.0,
               "dispatch_s": 0.0007, "flush_s": 0.004, "wait_s": 1.7,
               "host_s": 0.0003, "cpu_s": 0.006, "gc_s": 0.0,
               "expected_s": 0.5, "excess_s": stall_s, "phase": "wait"}
    return {"event": "run_end", "chunk_calls": 40, "ingest_calls": 0,
            "passes": 600,
            "calls": {"n": 40, "rows": 40, "gap_s": 0.012,
                      "by_rule": {"ramp": {"calls": 38, "passes": 590,
                                           "seconds": 19.0},
                                  "level_end": {"calls": 2, "passes": 10,
                                                "seconds": 0.4}},
                      "slowest": slowest, "stall_s": stall_s,
                      "stall_calls": stall_calls, "slow_calls": 0},
            "gc": {"collections": [120, 9, 1], "seconds": gc_s,
                   "seconds_by_generation": [0.001, 0.002, gc_s - 0.003],
                   "by_span": {"level_end": gc_s}},
            "trace_rehashes": rehashes, "trace_rehash_s": rehash_s,
            **extra}


def parent_run_end():
    """A ``run_end`` of the program before the fields existed."""
    return {"event": "run_end", "chunk_calls": 40, "ingest_calls": 0,
            "passes": 600, "compiles": {}}


def capture(rule=True):
    """Three chunk calls by hand (times in ms).  Call 1: span opens at
    90, the device runs [100, 200), account opens at 203: 10 + 3.  Call
    2: span 290, device [296, 336), account 340: 6 + 4.  Between them a
    seen-set growth runs the program once more for no call.  Call 3's
    account span fell outside the capture."""
    args = [{"rule": "ramp", "parents": 4096, "new": 9000},
            {"rule": "level_end", "parents": 100, "new": 300}]
    if not rule:
        args = [{}, {}]
    host = [["run", 50, 600, {"run": 1}],
            ["chunk", 90, 10, {"run": 1, "call": 1}],
            ["stats_fetch", 100, 102, {"run": 1}],
            ["account", 203, 7, {"run": 1, "call": 1, "passes": 3,
                                 **args[0]}],
            ["chunk", 290, 5, {"run": 1, "call": 2}],
            ["account", 340, 5, {"run": 1, "call": 2, "passes": 1,
                                 **args[1]}],
            ["chunk", 400, 5, {"run": 1, "call": 3}]]
    modules = [["jit_chunk(1)", 100, 100], ["jit_chunk(1)", 250, 1],
               ["jit_chunk(1)", 296, 40], ["jit_fp_rows(2)", 345, 1],
               ["jit_chunk_fn(3)", 350, 5], ["jit_chunk(1)", 410, 80]]
    ms = lambda rows, cols: [  # noqa: E731
        [v * MS if i in cols else v for i, v in enumerate(r)] for r in rows]
    return {"host": ms(host, (1, 2)), "modules": ms(modules, (1, 2)),
            "ops": [], "op_names": [], "op_paths": []}


def test_stall_ms_sums_the_runs_and_prints_the_slowest(capsys):
    run = {"events": [run_end(), run_end(stall_s=1.2, stall_calls=1),
                      {"event": "level_complete"}]}
    assert calls.read(run, "stall_ms") == pytest.approx(1200.0)
    out = capsys.readouterr().out
    assert "80 in 2 runs" in out and "1 stalls in 1 runs" in out
    assert "call 7 (chunk, level 10, rule ramp, 16 passes)" in out
    assert "in wait" in out and "wait 1.7000" in out and "cpu 0.0060" in out
    assert "ramp 76 1180" in out
    # A quiet window reads 0, not None.
    assert calls.read({"events": [run_end()]}, "stall_ms") == 0.0


def test_gc_ms_and_rehash_ms():
    run = {"events": [run_end(gc_s=0.020), run_end(
        gc_s=0.045, rehash_s=0.31, rehashes=3, restore_rehashes=1,
        restore_rehash_s=0.01)]}
    assert calls.read(run, "gc_ms") == pytest.approx(65.0)
    assert calls.read(run, "rehash_ms") == pytest.approx(310.0)
    # The swarm's run_end has no trace store to speak of: gc, no rehash.
    hunt = run_end(gc_s=0.01)
    del hunt["trace_rehashes"], hunt["trace_rehash_s"]
    assert calls.read({"events": [hunt]}, "rehash_ms") is None
    assert calls.read({"events": [hunt]}, "gc_ms") == pytest.approx(10.0)


def test_round_trip_by_hand(capsys):
    trips = calls.round_trips(capture()["host"], capture()["modules"])
    assert trips == [(10 * MS, 3 * MS, "ramp"), (6 * MS, 4 * MS,
                                                 "level_end")]
    run = {"_capture": capture(), "chunk_program": "chunk", "events": []}
    assert calls.read(run, "round_trip_ms") == pytest.approx(11.5)
    out = capsys.readouterr().out
    assert "2 whole chunk calls on chip 0" in out
    assert "dispatch 8.000 + return 3.500" in out
    assert "level_end 1 10.000, ramp 1 13.000" in out
    # Read once a run.
    assert calls.read(run, "round_trip_ms") == pytest.approx(11.5)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("mode", sorted(calls.MODES))
def test_a_parents_log_and_capture_give_none(mode):
    """The parent writes neither the ``run_end`` fields nor ``rule`` on
    ``raft.account``; a run with no capture and one with no log at all
    read None too, and nothing raises."""
    parent = {"events": [parent_run_end()], "_capture": capture(rule=False),
              "chunk_program": "chunk"}
    assert calls.read(parent, mode) is None
    assert calls.read({"events": [], "trace_dir": None}, mode) is None
    assert calls.read({}, mode) is None
    # One run of the window without the field: nothing is read.
    mixed = {"events": [run_end(), parent_run_end()], "trace_dir": None}
    assert calls.read(mixed, mode) is None


def test_every_metric_is_found_by_name(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in manifest["end_to_end"]}
    # (By name, not by place: a later PR appends behind them.)
    for name in NAMES:
        m = by_name[name]
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "calls"
        assert spec["args"]["mode"] in calls.MODES
        assert name.startswith(spec["args"]["mode"])
        assert m["better"] == "lower" and m["unit"] == "ms"
        assert set(m["workloads"]) <= reports[m["moves"]]
        assert m["source"] == ("device_trace" if "round_trip" in name
                               else "program_counter")
    with pytest.raises(ValueError):
        calls.read({}, "nothing")
    # Only new files: the manifest names no reader this PR edited.
    assert os.path.isfile(os.path.join(REPO, "benchmark", "readers",
                                       "calls.py"))
    assert json.dumps(by_name["rehash_ms"]["workloads"]).count("smoke") == 0
