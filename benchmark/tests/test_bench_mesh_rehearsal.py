"""The four-chip cell's kind on 4 virtual CPU devices at a tiny size: the
kept-snapshot path made, then reused; a traced run; the controls."""

import json
import os
import shutil

import pytest

from bench_helpers import BENCH, REPO, run_cell

ARGS = ("--workload", "tiny-mesh4", "--seed", "3000000019", "--seconds", "1")
KEPT = ".bench_kept"


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory, manifest):
    """A checkout-shaped directory whose throw-away cell runs the
    ``mesh_window`` kind: start level 5, walk level 3."""
    root = tmp_path_factory.mktemp("mesh_checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / "mcraft3-mesh4.json").read_text())
    config.update(name="tiny-mesh4", batch=64, queue_capacity=1 << 19,
                  seen_capacity=1 << 21)
    (bench / "configs" / "tiny-mesh4.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "window-l12-kept.json").read_text())
    mix.update(start_level=5, walk_level=3, sample=32, replayed=8)
    (bench / "traffic" / "window-l5-kept.json").write_text(json.dumps(mix))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-mesh4", "source": "test",
                         "file": "benchmark/configs/tiny-mesh4.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny-mesh4", "config": "tiny-mesh4",
                           "traffic": "window-l5-kept", "chips": 4,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


@pytest.fixture(autouse=True)
def four_virtual_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def kept_copies(root):
    d = os.path.join(root, KEPT)
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def test_the_kept_snapshot_is_made_then_reused(mesh_root):
    rc, line, out = run_cell(mesh_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, out[-4000:]
    assert "kept snapshot: made by this run's walk to level 5" in out
    assert "compare set-up level 5 " in out
    made = kept_copies(mesh_root)
    assert len(made) == 1 and made[0].startswith("tiny-mesh4.l5.")
    assert sorted(os.listdir(os.path.join(mesh_root, KEPT, made[0]))) == [
        "kept.json", "level_00005.npz"]
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}

    rc, line2, out2 = run_cell(mesh_root, *ARGS, "--trace", "0")
    assert rc == 0, out2[-3000:]
    assert line2["correct"] is True, out2[-4000:]
    assert "kept snapshot: reused" in out2
    assert "compare set-up level 3 " in out2
    assert "compare set-up level 4 " not in out2
    assert "compare kept snapshot: digest of the frontier rows" in out2
    assert kept_copies(mesh_root) == made
    assert out2.count("compare ") == line2["attempted"]
    for what in ("new distinct == growth of the shards' key counts",
                 "sample (frontier, distinct, generated)",
                 "replayed paths legal under the reference",
                 "window engine"):
        assert f"compare {what}" in out2, what


def test_a_snapshot_that_does_not_match_its_record_is_deleted(mesh_root):
    if not kept_copies(mesh_root):
        run_cell(mesh_root, *ARGS, "--trace", "0")
    (name,) = kept_copies(mesh_root)
    record = os.path.join(mesh_root, KEPT, name, "kept.json")
    with open(record, encoding="utf-8") as f:
        rec = json.load(f)
    rec["keys_digest"] = "0:0:0"
    with open(record, "w", encoding="utf-8") as f:
        json.dump(rec, f)
    rc, line, out = run_cell(mesh_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] == 1
    assert kept_copies(mesh_root) == []


def test_traced_rehearsal_reports_the_span_and_counter_metrics(mesh_root):
    rc, line, out = run_cell(mesh_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-4000:]
    # No device plane on the CPU: the trace readers leave theirs out.
    assert {"chip_skew", "restore_s", "restore_share", "build_s", "pass_fill",
            "passes_per_call", "flush_ms", "seen_load", "queue_fill",
            "host_share.deep"} <= set(line["metrics"])
    assert not {"exchange_ms", "device_idle.mesh"} & set(line["metrics"])
    assert 0 <= line["metrics"]["pass_fill"]["value"] <= 100


@pytest.mark.parametrize("control, level", [("fp14", None), ("family", 3)])
def test_controls_read_not_correct_and_keep_no_copy(mesh_root, control,
                                                    level):
    before = kept_copies(mesh_root)
    rc, line, out = run_cell(mesh_root, control, "--", *ARGS, "--trace", "0",
                             script="benchmark/tests/controls_mesh.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    assert "not kept, a comparison of the walk failed" in out
    assert f"control {control}: copies left under .bench_kept.control: []" \
        in out
    assert kept_copies(mesh_root) == before
    if level is not None:
        assert f"compare set-up level {level} " in out and "FAIL" in out


def test_one_chip_cells_read_none_of_the_mesh_metrics(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "--workload", "tiny3-deep",
                             "--seed", "11", "--seconds", "2",
                             "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    assert not {"chip_skew", "restore_s", "restore_share", "exchange_ms",
                "exchange_exposed", "exchange_roofline", "owner_insert_ms",
                "device_idle.mesh"} & set(line["metrics"])
