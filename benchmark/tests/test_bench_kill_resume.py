"""The supervised, checkpointed deployment's part of the benchmark: its
entries in the manifest, the new traffic kind on the CPU at a tiny size to
a valid line, its three controls, the plain snapshot reader, and the
``recovery`` reader on hand-made events and a hand-made capture."""

import json
import os
import shutil

import numpy as np
import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

recovery = lib.load_module("readers", "recovery")

ARGS = ("--workload", "tiny-kill-resume", "--seed", "3000000054",
        "--seconds", "2")
NEW_METRICS = ["save_stall_s", "save_ms.export", "save_ms.keys",
               "save_ms.frontier", "save_ms.deflate", "save_ms.write",
               "save_mb_s", "idle.checkpoint", "recover_s", "redo_share"]


# -- the manifest's new entries ---------------------------------------------

def test_the_cell_and_its_configuration(manifest):
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("kill-resume", "mcraft3-supervised", "kill-l9-resume", 1)
    assert len(cell["why"]) <= 200
    assert len(manifest["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    entry = manifest["configs"][-1]
    assert entry["name"] == "mcraft3-supervised"
    assert entry["reduced"] == ["depth", "checkpoint_interval", "restart"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    config = lib.load_json("configs", "mcraft3-supervised.json")
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert all(k in config for k in entry["reduced"])
    base = lib.load_json("configs", "mcraft3.json")
    # mcraft3's cfg, constants, pools, shapes and pin, letter for letter.
    for key in ("cfg_name", "cfg_text", "constants", "invariants",
                "constraint", "check_deadlock", "batch", "queue_capacity",
                "seen_capacity", "n_msg_slots", "shapes", "pinned"):
        assert config[key] == base[key], key
    assert config["guarantees"][:4] == base["guarantees"][:4]
    assert any("fsynced" in g for g in config["guarantees"])
    assert any("NEWEST" in g for g in config["guarantees"])
    assert config["durability"] == {
        "record_trace": True, "checkpoint_every": 1,
        "checkpoint_interval_seconds": 0, "keep_checkpoints": 2}
    mix = lib.load_json("traffic", "kill-l9-resume.json")
    assert (mix["kind"], mix["start_level"], mix["kill_level"],
            mix["kill_parents_share"], mix["kills"], mix["sample"],
            mix["replay_sample"]) == ("kill_resume", 8, 9, 0.5, 1, 256, 32)
    assert mix["forbidden_events"] == lib.load_json(
        "traffic", "window-l9.json")["forbidden_events"]
    assert config["assumed"]["fault_plan"].startswith(
        f"kill@level={mix['kill_level']};chunk={mix['kill_chunk']},")


def test_the_new_layer_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-10:]] == NEW_METRICS
    for name in NEW_METRICS:
        m = by_name[name]
        assert (m["moves"], m["layer"], m["workloads"]) == (
            "distinct_per_s", "checkpoint", ["kill-resume"])
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == ("spans" if name == "idle.checkpoint"
                                  else "recovery")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["distinct_per_s"]["workloads"][-1] == "kill-resume"
    # Where the cell is listed, it is the newest entry of the list.
    for m in manifest["per_layer"]:
        if "kill-resume" in m.get("workloads", ()):
            assert m["workloads"][-1] == "kill-resume", m["name"]
    # The window's whole idle share is listed: it alone carries the
    # recovery's idle (idle.checkpoint covers the saves).  The roofline
    # share of a steady span that holds two saves is mcraft3-deep's.
    assert "kill-resume" in by_name["device_idle.deep"]["workloads"]
    assert "kill-resume" not in by_name["chunk_roofline"]["workloads"]


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def kill_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of the
    new kind: new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads(
        (bench / "configs" / "mcraft3-supervised.json").read_text())
    config.update(name="tiny-supervised", batch=64,
                  queue_capacity=1 << 17, seen_capacity=1 << 20)
    (bench / "configs" / "tiny-supervised.json").write_text(
        json.dumps(config))
    mix = json.loads((bench / "traffic" / "kill-l9-resume.json").read_text())
    # Level 5 has 1,218 parents, 20 batches of 64: its third call starts
    # after the ramp's 2 + 4 batches, 384 parents.
    mix.update(start_level=4, kill_level=5, kill_chunk=3,
               kill_parents_held=[0.25, 0.40], sample=32, replay_sample=8)
    (bench / "traffic" / "kill-l5-resume.json").write_text(json.dumps(mix))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-supervised", "source": "test",
                         "file": "benchmark/configs/tiny-supervised.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny-kill-resume",
                           "config": "tiny-supervised",
                           "traffic": "kill-l5-resume", "chips": 1,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_the_window_runs_to_a_correct_line(kill_root):
    rc, line, out = run_cell(kill_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}
    assert out.count("compare ") == line["attempted"]
    for what in ("the engine's durability fields are the configuration's",
                 "the walk's directory holds its newest snapshots: got "
                 "['level_00004.npz', 'level_00005.npz']",
                 "warm resume of level 5's file: stop reason",
                 "warm resume of level 4's file: stop reason",
                 "runs in the window: got 2",
                 "the first run was ended by the injected kill: got True",
                 "the killed run's run_end (stop_reason, diameter): got "
                 "('error', 5)",
                 "the killed run's level_complete and checkpoint events",
                 "killed run level 5 (frontier, distinct, generated)",
                 "parents expanded at the kill, of level 5's 1218, within "
                 "[0.25, 0.4]: got True",
                 "latest() after the kill",
                 "run_start's (resume_level, resume_path)",
                 "recovered run level 6 (frontier, distinct, generated)",
                 "the recovered run's generated starts from the snapshot's",
                 "the directory holds the two newest snapshots, no .tmp",
                 "records of level_", "keys of level_",
                 "replayed paths that start at Init: got 8",
                 "replayed paths legal under the reference, every step: "
                 "got 8",
                 "sample (frontier, distinct, generated) engine == "
                 "reference"):
        assert "compare " + what in out, what
    # Which two files the window leaves depends on the CPU's speed.
    for what in (".npz: keys unique and sorted: got True",
                 ".npz: arrays that differ from checkpoint.load's: got []",
                 ".npz: (level, rows, keys, records, distinct, generated) "
                 "by the plain reader: got ("):
        assert out.count(what) == 2, what
    assert "window snapshot: level 5, 2300 distinct, " in out


def test_a_traced_run_reads_the_new_metrics(kill_root):
    rc, line, out = run_cell(kill_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    # No device plane on the CPU: what reads the device's idle time is
    # left out; the host's spans are in the capture.
    got = set(line["metrics"])
    assert set(NEW_METRICS) - got == {"idle.checkpoint"}, got
    m = {k: v["value"] for k, v in line["metrics"].items()}
    parts = sum(m["save_ms." + p] for p in ("export", "keys", "frontier",
                                            "deflate", "write"))
    assert 0 < parts <= 1000 * m["save_stall_s"]
    assert m["save_mb_s"] > 0 and m["recover_s"] > 0
    assert 0 < m["redo_share"] < 50


@pytest.mark.parametrize("control,failing", [
    ("restore_every_other_key", ("level", "the recovered run's generated")),
    ("latest_oldest", ("latest()", "run_start's", "level",
                       "checkpoint events", "metadata",
                       "the recovered run's")),
    ("drop_last_level_records", ("records", "record keys", "replayed",
                                 "arrays that differ", "by the plain "
                                 "reader"))])
def test_a_program_that_does_less_is_not_correct(kill_root, control,
                                                 failing):
    rc, line, out = run_cell(kill_root, control, "--", *ARGS, "--trace", "0",
                             script="benchmark/tests/controls_kill_resume.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    fails = [ln for ln in out.splitlines() if ln.endswith(" FAIL")]
    print("\n".join(ln[:160] for ln in fails))
    assert fails and all(any(w in ln for w in failing) for ln in fails), \
        [ln[:160] for ln in fails]


# -- the plain reader, on a file the program wrote -----------------------------

def test_the_plain_reader_reads_what_the_program_wrote(tmp_path):
    from raft_tla_tpu.engine import checkpoint as ckpt_mod
    from raft_tla_tpu.models.dims import RaftDims
    from reference import snapshot as plain
    rng = np.random.default_rng(54)
    keys = np.unique(rng.integers(0, 1 << 63, 5000).astype(np.uint64))
    ck = ckpt_mod.Checkpoint(
        dims=RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8),
        frontier=rng.integers(0, 255, (700, 59)).astype(np.uint8),
        seen_hi=(keys >> np.uint64(32)).astype(np.uint32),
        seen_lo=keys.astype(np.uint32), distinct=len(keys), generated=9000,
        diameter=3, levels=(1, 3, 18, 700), action_counts={"Timeout": 7},
        wall_seconds=1.5, trace_fps=rng.permutation(keys),
        trace_parents=rng.permutation(keys),
        trace_actions=rng.integers(-1, 40, len(keys)).astype(np.int32),
        roots={})
    path = str(tmp_path / "level_00003.npz")
    ckpt_mod.save(path, ck)
    snap = plain.read(path)
    assert plain.read_meta(path) == snap["meta"]
    assert (snap["meta"]["diameter"], snap["meta"]["distinct"],
            snap["meta"]["wall_seconds"]) == (3, len(keys), 1.5)
    for name in plain.ARRAYS:
        assert np.array_equal(snap[name], getattr(ck, name)), name
    assert plain.strictly_ascending(plain.keys64(snap))
    assert not plain.strictly_ascending(plain.keys64(snap)[::-1])
    assert plain.missing_from(plain.keys64(snap), keys[::7]) == 0
    assert plain.missing_from(plain.keys64(snap)[1:], keys[:3]) == 1
    rec = plain.records(snap)
    assert plain.records_missing_from(rec, rec[::3]) == 0
    other = rec[::3].copy()
    other[5, 2] += 1                    # one action differs
    assert plain.records_missing_from(rec, other) == 1


def test_the_plain_reader_imports_nothing_of_the_program():
    import ast
    with open(os.path.join(BENCH, "reference", "snapshot.py"),
              encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imports = sorted(
        name for node in ast.walk(tree)
        for name in ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module] if isinstance(node, ast.ImportFrom)
                     else []))
    assert imports == ["__future__", "json", "numpy", "zlib"]


# -- the reader, on hand-made events and a hand-made capture -------------------

def saved(level, seconds, raw, parts=True):
    e = {"event": "checkpoint", "level": level, "distinct": 10 * level}
    if parts:
        e.update(seconds=seconds, bytes_raw=raw, bytes_written=raw // 3,
                 parts={"ckpt_export": 0.1 * seconds,
                        "ckpt_keys": 0.4 * seconds,
                        "ckpt_frontier": 0.05 * seconds,
                        "ckpt_deflate": 0.25 * seconds,
                        "ckpt_write": 0.15 * seconds,
                        "ckpt_gc": 0.01 * seconds})
    return e


def test_the_reader_on_hand_made_events():
    run = {"events": [{"event": "run_start"}, saved(9, 1.0, 100_000_000),
                      {"event": "run_end"}, {"event": "run_start"},
                      saved(10, 3.0, 300_000_000), {"event": "run_end"}],
           "phases": {"checkpoint": 4.2}, "parents_expanded": 300,
           "recovery": {"redo_parents": 60}}
    assert recovery.read(run, mode="save_stall_s") == pytest.approx(2.1)
    assert recovery.read(run, mode="part_ms", part="ckpt_keys") \
        == pytest.approx(800.0)
    assert recovery.read(run, mode="part_ms", part="ckpt_write") \
        == pytest.approx(300.0)
    assert recovery.read(run, mode="save_mb_s") == pytest.approx(100.0)
    assert recovery.read(run, mode="redo_share") == pytest.approx(20.0)
    with pytest.raises(ValueError):
        recovery.read(run, mode="nothing")


def test_a_program_without_the_fields_gives_nothing():
    old = {"events": [saved(9, 1.0, 1, parts=False)],
           "phases": {"checkpoint": 1.0}}
    # The phase and the event are older than their parts.
    assert recovery.read(old, mode="save_stall_s") == pytest.approx(1.0)
    for mode, part in (("part_ms", "ckpt_keys"), ("save_mb_s", ""),
                       ("redo_share", ""), ("recover_s", "")):
        assert recovery.read(old, mode=mode, part=part) is None
    assert recovery.read({"events": []}, mode="save_stall_s") is None
    assert recovery.read({}, mode="part_ms", part="ckpt_keys") is None


def test_recover_s_on_a_hand_made_capture():
    s = 1_000_000_000
    host = [["run", 0, 10 * s, {}],
            ["account", 1 * s, s // 100, {}],
            ["account", 4 * s, s // 100, {}],       # the last before the kill
            ["run", 11 * s, 9 * s, {}],
            ["checkpoint_load", 11 * s, s, {}],
            ["account", 13 * s, s // 100, {}],      # the first after
            ["account", 15 * s, s // 100, {}]]
    cap = {"host": host}
    assert recovery.recover_s(cap) == pytest.approx(13 - 4.01)
    assert recovery.read({"_capture": cap}, mode="recover_s") \
        == pytest.approx(8.99)
    # One run only (no kill), or no call after it: nothing to read.
    assert recovery.recover_s({"host": host[:3]}) is None
    assert recovery.recover_s({"host": host[:5]}) is None
    assert recovery.read({"trace_dir": None}, mode="recover_s") is None
