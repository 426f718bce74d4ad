"""The whole command on the CPU at a tiny size, and the controls: a run
that computes less reports ``correct: false``."""

import os
import subprocess
import sys

from bench_helpers import REPO, run_cell

ARGS = ("--workload", "tiny3-deep", "--seed", "3000000019",
        "--seconds", "3")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_throw_away_cell_added_by_files_alone_runs_to_a_valid_line(
        rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["attempted"] > 10
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}
    assert line["metrics"]["distinct_per_s"]["unit"] == "states/s"
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # every number compared is printed beside its limit
    assert out.count("compare ") == line["attempted"]


def test_traced_run_reports_the_cells_layer_metrics(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    # The CPU capture has no device plane: the trace readers find nothing
    # to read and their metrics are left out, the span readers report.
    assert set(line["metrics"]) == {"build_s", "host_share.deep",
                                    "batch_ms", "seen_load", "queue_fill"}


def test_verdict_cell_rehearsal(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "--workload",
                             "mcraft3-noleader", "--seed", "7",
                             "--seconds", "2", "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert set(line["metrics"]) == {"setup_s", "verdict_s"}


def test_control_32_fewer_fingerprint_bits_is_not_correct(rehearsal_root):
    """The cells' control is 32 of 64 bits at millions of states; a test
    run holds thousands, where 14 bits collide as often."""
    rc, line, out = run_cell(rehearsal_root, "fp14", "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0


def test_control_masked_action_family_is_not_correct(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "family", "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    assert "FAIL" in out


def test_control_masked_family_fails_the_verdict_cell(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "family", "--", "--workload",
                             "mcraft3-noleader", "--seed", "7",
                             "--seconds", "2", "--trace", "0",
                             script="benchmark/tests/controls.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files the command prints no result and exits non-zero."""
    import shutil
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mcraft3-deep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert '"correct"' not in p.stdout


def test_the_drivers_command_gives_no_result_on_the_cpu(rehearsal_root):
    """The command exactly as the driver runs it, where jax finds only the
    CPU: exit 3, no line, whatever JAX_PLATFORMS says."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS, "--trace", "0"],
        cwd=rehearsal_root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == "" or '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_the_look_for_a_chip():
    """A CPU without --rehearsal, or fewer chips than the cell wants,
    exits 3 before anything runs."""
    import types

    import pytest
    import run
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    fake = lambda devs: types.SimpleNamespace(devices=lambda: devs)  # noqa: E731
    for jax_, chips, rehearsal in ((fake([cpu]), 1, False),
                                   (fake([tpu]), 4, False),
                                   (fake([cpu]), 4, True)):
        with pytest.raises(SystemExit) as e:
            run.device_block(jax_, chips, rehearsal)
        assert e.value.code == 3
    assert run.device_block(fake([tpu] * 4), 1, False) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
