"""The swarm cell (``mcraft3-hunt``) on the CPU: the whole command, traced
and untraced, its two controls, the reader ``walk`` on a hand-made capture,
and that a run of another kind comes through that reader unharmed."""

import numpy as np

import bench_lib as lib
from bench_helpers import run_cell

ARGS = ("--workload", "mcraft3-hunt", "--seed", "3000000019",
        "--seconds", "2")
SPAN_AND_COUNTER_METRICS = {
    "build_s", "walk_steps_per_s", "host_share.hunt", "fetches_per_chunk",
    "steps_past_latch", "reconstruct_ms", "hunt_fixed_ms"}


def test_hunt_cell_runs_to_a_valid_line(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"setup_s", "verdict_s"}
    assert line["metrics"]["verdict_s"]["value"] > 0
    # every number compared is printed beside its limit
    assert out.count("compare ") == line["attempted"] > 80
    assert "half-width hunt" in out and "sample of 64 walkers" in out


def test_traced_hunt_cell_reports_its_span_and_counter_metrics(
        rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    # The CPU capture has no device plane: the device_trace metrics are
    # left out, and no reader of the BFS verdict cell finds anything.
    assert set(line["metrics"]) == SPAN_AND_COUNTER_METRICS
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fetches_per_chunk"] == 19.0       # one slice a round
    assert 0 < m["steps_past_latch"] < 100
    assert 0 <= m["host_share.hunt"] <= 100
    assert m["reconstruct_ms"] > 0 and m["hunt_fixed_ms"] > 0


def test_control_dropped_constraint_is_not_correct(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "constraint", "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls_swarm.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    assert "FAIL" in out


def test_control_constant_choice_is_not_correct(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "choice", "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls_swarm.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0


def test_a_bfs_verdict_run_comes_through_the_walk_reader_unharmed(
        rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "--workload",
                             "mcraft3-noleader", "--seed", "7",
                             "--seconds", "2", "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"build_s", "host_share.verdict",
                                    "calls_per_verdict"}


def capture():
    """Two whole calls of the walk chunk of 1,000 ns each, and one the
    capture cut: a ``while`` holding a ``masks`` fusion of 300 ns, a
    ``hunt`` one of 650 and 50 ns of its own."""
    paths = ["jit(chunk_fn)/while:", "jit(chunk_fn)/while/body/masks/a:",
             "jit(chunk_fn)/while/body/hunt/scatter:",
             "jit(chunk_fn)/while/body/hunting/masks_of/x:"]
    ops = []
    for t in (0, 2000):
        ops += [[0, t, 1000], [1, t, 300], [2, t + 300, 650]]
    ops += [[0, 4000, 500], [3, 4000, 100]]     # 500 of 1,000: cut
    return {"host": [], "modules": [["jit_chunk_fn(1)", 0, 1000],
                                    ["jit_chunk_fn(1)", 2000, 1000],
                                    ["jit_chunk_fn(1)", 4000, 1000],
                                    ["jit__expand1", 6000, 50]],
            "ops": np.asarray(ops, np.int64),
            "op_names": ["while.1", "fusion.1", "fusion.2", "fusion.3"],
            "op_paths": paths}


def test_walk_reader_arithmetic_on_a_hand_made_capture():
    walk = lib.load_module("readers", "walk")
    run = {"walk_kind": "swarm_hunt", "_capture": capture(),
           "chunk_program": "chunk_fn", "walks": 8, "batch": 4,
           "walk_chunk": 32, "ring": 16, "row_bytes": 403,
           "device_kind": "TPU v5 lite", "window_wall_s": 1.0,
           "trace_dir": None}
    # two whole calls of 4 lanes are ONE round of 8 walkers: 32 steps
    assert walk.read(run, "step_ms") == 2000 / 1e6 / 32
    assert walk.read(run, "stage_ms", stage="masks") == 600 / 1e6 / 32
    assert walk.read(run, "stage_ms", stage="hunt") == 1300 / 1e6 / 32
    assert walk.read(run, "stage_ms", stage="other") == 100 / 1e6 / 32
    assert walk.read(run, "stage_ms", stage="ring") == 0
    least = 8 * (2 * 403 + 16 * 8 + 4) / 819e9
    assert abs(walk.read(run, "roofline")
               - 100 * least / (2000 / 1e9 / 32)) < 1e-9
    # under 90 % of the time named: nothing is reported
    run2 = dict(run, _capture=dict(capture(), op_paths=[
        "jit(chunk_fn)/while:", "jit(chunk_fn)/while/body/masks/a:",
        "jit(chunk_fn)/while/body/unnamed:", ""]))
    run2.pop("_walk_table", None)
    assert walk.read(run2, "step_ms") is None
    # a run of any other kind
    assert all(walk.read({"walk_kind": None}, mode) is None
               for mode in ("steps_per_s", "step_ms", "idle_share"))
