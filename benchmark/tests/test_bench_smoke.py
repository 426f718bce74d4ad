"""The smoke deployment's part of the benchmark: its entries in the
manifest, its pin (the draws and the reference's levels), the ``smoke``
reader on hand-made runs, and the new traffic kind on the CPU at a small
batch with its three controls."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
smoke = lib.load_module("readers", "smoke")
kind = lib.load_module("traffic", "smoke_loop")

ARGS = ("--workload", "tiny-smoke-1s", "--seed", "3000000047",
        "--seconds", "3")
NEW_METRICS = ["budget_overshoot_ms", "deadline_calls_per_check",
               "check_fixed_ms", "device_idle.smoke"]
TINY = {"BATCH": 256, "QUEUE_CAPACITY": 262144, "SEEN_CAPACITY": 1048576}


# -- the manifest's new entries ---------------------------------------------

def test_the_cell_and_its_configuration(manifest):
    cell = next(w for w in manifest["workloads"] if w["name"] == "smoke-1s")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smokeraft", "smoke-budget-1s", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "smokeraft")
    assert entry["reduced"] == []
    config = lib.load_json("configs", "smokeraft.json")
    assert config["source"] == entry["source"]
    assert config["reduced"] == {}
    for key, name in (("cfg_text", "Smokeraft.cfg"),
                      ("module_text", "Smokeraft.tla")):
        with open(os.path.join(REPO, "configs", name),
                  encoding="utf-8") as f:
            assert f.read() == "\n".join(config[key]) + "\n"
    assert config["root_seeds"] == list(range(1, 17))
    assert (config["smoke_k"], config["roots"]) == (2, 512)
    assert config["budget"] == {"max_seconds": 1.0, "max_diameter": 100}
    mix = lib.load_json("traffic", "smoke-budget-1s.json")
    assert (mix["kind"], mix["min_checks"], mix["sample"],
            mix["replayed"]) == ("smoke_loop", 3, 256, 32)
    assert mix["forbidden_events"] == lib.load_json(
        "traffic", "window-l9.json")["forbidden_events"]


def test_the_new_layer_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(NEW_METRICS) <= set(by_name)
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["moves"] == "distinct_per_s"
        assert m["workloads"] == ["smoke-1s"]
        assert lib.load_json("layer_metrics",
                             name + ".json")["reader"] == "smoke"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "smoke-1s" in e2e["distinct_per_s"]["workloads"]
    for name in ("build_s", "pass_fill", "passes_per_call", "seen_load",
                 "queue_fill", "setup.runs_s", "chunk_roofline"):
        assert "smoke-1s" in by_name[name]["workloads"], name


def test_the_pin_holds_every_root_seeds_draw_and_levels():
    config = lib.load_json("configs", "smokeraft.json")
    pins = kind.load_pinned(config["pinned"])
    assert sorted(pins) == config["root_seeds"]
    from reference import smoke as ref_smoke
    for seed, pin in pins.items():
        levels = sorted(k for k in pin if k != "draw")
        assert levels[:3] == [0, 1, 2] and levels == list(range(len(levels)))
        assert pin[0][:3] == (512, 512, 0)
        for lv in levels:
            assert sum(pin[lv][3].values()) == pin[lv][2]
        draw = ref_smoke.from_json(pin["draw"])
        assert all(len(draw[v]) == 2 for v in ref_smoke.VARIABLES)
        assert len(draw["messages"]) == 8
    assert pins[1][1][:3] == (15872, 16384, 18432)
    assert pins[1][2][:3] == (254272, 270656, 580640)
    # lib.load_pinned still reads the file (the last seed's rows).
    assert lib.load_pinned(config["pinned"])[0] == (512, 512, 0)


# -- the reader, on hand-made runs --------------------------------------------

def end(**kw):
    return {"event": "run_end", "chunk_calls": 10, **kw}


def test_smoke_reader_means_over_the_windows_checks(capsys):
    run = {"kind": "smoke_loop", "events": [
        end(budget_overshoot_s=0.040, deadline_calls=3, probe_calls=1),
        end(budget_overshoot_s=0.060, deadline_calls=5, probe_calls=1)]}
    assert smoke.read(run, "overshoot_ms") == pytest.approx(50.0)
    assert smoke.read(run, "deadline_calls") == 4.0
    assert "1.00 probes" in capsys.readouterr().out
    # No capture: nothing to read, nothing raised.
    assert smoke.read(run, "fixed_ms", spans=["smoke_roots"]) is None
    assert smoke.read(run, "idle_share") is None
    with pytest.raises(ValueError):
        smoke.read(run, "nonsense")


def test_smoke_reader_reads_nothing_elsewhere():
    """Another kind's run, or a program without the fields (the parent):
    None, and no exception."""
    ends = [end(budget_overshoot_s=0.05, deadline_calls=3, probe_calls=1)]
    for mode in ("overshoot_ms", "deadline_calls", "fixed_ms",
                 "idle_share"):
        assert smoke.read({"events": ends}, mode) is None
        assert smoke.read({"kind": "smoke_loop", "events": [end()]},
                          mode) is None


def test_fixed_ms_is_per_run_over_the_capture():
    cap = {"host": [["smoke_roots", 0, 5_000_000, {}],
                    ["run", 10, 1_000_000_000, {}],
                    ["root_check", 20, 3_000_000, {}],
                    ["run_init", 30, 2_000_000, {}],
                    ["chunk", 40, 900_000_000, {}],
                    ["smoke_roots", 1_100_000_000, 5_000_000, {}],
                    ["run", 1_200_000_000, 1_000_000_000, {}],
                    ["run_end", 2_100_000_000, 4_000_000, {}]],
           "modules": [["jit_chunk", 0, 2_200_000_000]],
           "ops": [], "op_names": [], "op_paths": []}
    run = {"kind": "smoke_loop", "_capture": cap, "window_wall_s": 2.3,
           "trace_dir": "x", "events": []}
    spans = ["smoke_roots", "root_check", "run_init", "frontier_fetch",
             "run_end"]
    assert smoke.read(run, "fixed_ms", spans=spans) == pytest.approx(
        (5 + 3 + 2 + 5 + 4) / 2)
    # The parent's capture has no raft.smoke_roots span.
    cap["host"] = [e for e in cap["host"] if e[0] != "smoke_roots"]
    assert smoke.read(run, "fixed_ms", spans=spans) is None


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of the
    new kind: new files and new entries only; the checkout's own
    ``configs/Smokeraft.cfg`` and ``.tla`` at the small sizes beside
    them, since the kind holds the configuration's text to them."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / "smokeraft.json").read_text())
    text = []
    for line in config["cfg_text"]:
        for key, value in TINY.items():
            if line.startswith(f"\\* TPU: {key} ="):
                line = f"\\* TPU: {key} = {value}"
        text.append(line)
    config.update(name="tiny-smoke", cfg_text=text, batch=TINY["BATCH"],
                  queue_capacity=TINY["QUEUE_CAPACITY"],
                  seen_capacity=TINY["SEEN_CAPACITY"])
    (bench / "configs" / "tiny-smoke.json").write_text(json.dumps(config))
    (root / "configs").mkdir()
    (root / "configs" / "Smokeraft.cfg").write_text("\n".join(text) + "\n")
    (root / "configs" / "Smokeraft.tla").write_text(
        "\n".join(config["module_text"]) + "\n")
    mix = json.loads(
        (bench / "traffic" / "smoke-budget-1s.json").read_text())
    mix.update(sample=32, replayed=8)
    (bench / "traffic" / "smoke-budget-tiny.json").write_text(
        json.dumps(mix))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-smoke", "source": "test",
                         "file": "benchmark/configs/tiny-smoke.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny-smoke-1s", "config": "tiny-smoke",
                           "traffic": "smoke-budget-tiny", "chips": 1,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


@pytest.fixture(scope="module")
def traced(smoke_root):
    return run_cell(smoke_root, *ARGS, "--trace", "1")


def test_the_cell_rehearsed_on_the_cpu(traced):
    rc, line, out = traced
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, [
        t for t in out.splitlines() if t.endswith(" FAIL")]
    assert line["failed"] == 0 and line["attempted"] >= 80
    assert line["device"]["platform"] == "cpu"
    # A traced line holds the per-layer metrics only.
    assert "distinct_per_s" not in line["metrics"]
    assert "checks of root seeds [1, 2, 3" in out
    assert "stop reason: got duration_budget" in out
    assert out.count("roots equal, as a set, the product of the pinned "
                     "draw: got True") >= 3
    assert "replayed paths legal under the reference, every step" in out


def test_the_traced_line_carries_the_programs_own_counts(traced):
    """The two metrics that need no device; the two that read the
    capture find no device plane on the CPU and are left out."""
    _rc, line, out = traced
    metrics = line["metrics"]
    assert metrics["budget_overshoot_ms"]["value"] >= 0
    assert metrics["deadline_calls_per_check"]["value"] >= 0
    assert "probes of one batch" in out
    for name in ("check_fixed_ms", "device_idle.smoke"):
        assert name not in metrics
    for name in ("pass_fill", "passes_per_call", "seen_load", "queue_fill",
                 "build_s", "setup.runs_s", "window_compile_s"):
        assert name in metrics, name


@pytest.mark.parametrize("control, catches", [
    ("init_override_dropped", "roots enqueued: got 1 limit == 512 FAIL"),
    ("family", "level 1 (frontier, distinct, generated, by family)"),
    ("bag_per_root", "2 different bags, not one shared"),
])
def test_the_controls_read_incorrect(smoke_root, control, catches):
    rc, line, out = run_cell(
        smoke_root, control, "--", *ARGS, "--trace", "0",
        script="benchmark/tests/controls_smoke.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] >= 1
    failed = [t for t in out.splitlines() if t.endswith(" FAIL")]
    assert any(catches in t for t in failed), failed[:5]


def test_a_checkout_without_the_cfg_fails_at_once(smoke_root, tmp_path):
    """The parent commit has the program and not ``configs/Smokeraft.cfg``:
    asked for the cell it exits 4 before it builds anything."""
    root = tmp_path / "parent"
    root.mkdir()
    for name in ("benchmark", "raft_tla_tpu", "BENCHMARK.json"):
        os.symlink(smoke_root / name, root / name)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS, "--trace", "0",
         "--rehearsal"], cwd=root, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == kind.NO_DEPLOYMENT == 4
    assert "has no configs/Smokeraft.cfg" in p.stderr
    assert not p.stdout.strip().splitlines()[-1:] or not \
        p.stdout.strip().splitlines()[-1].startswith("{")
