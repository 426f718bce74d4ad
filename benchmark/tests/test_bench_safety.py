"""The safety deployment's part of the benchmark: its entries in the
manifest, its pinned profile, the new traffic kind on the CPU at a tiny
size with both controls, and the ``construct`` reader on a recorded
capture."""

import copy
import json
import os
import shutil

import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
construct = lib.load_module("readers", "construct")
stages = lib.load_module("readers", "stages")

SUITE = ["TypeOK", "MessagesInv", "LeaderVotesQuorum",
         "CandidateTermNotInLog", "ElectionSafety", "LogMatching",
         "VotesGrantedInv", "QuorumLogInv", "MoreUpToDateCorrect",
         "LeaderCompleteness"]
BFS_CELLS = ["mcraft3-deep", "raft5-deep", "mcraft3-l12-x4", "safety9"]
ARGS = ("--workload", "tiny3-safety", "--seed", "3000000019",
        "--seconds", "2")


# -- the manifest's new entries ---------------------------------------------

def test_the_cell_and_its_configuration(manifest):
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("safety9", "mcraft3-safety", "window-l9-safety", 1)
    entry = manifest["configs"][-1]
    assert entry["name"] == "mcraft3-safety" and entry["reduced"] == ["depth"]
    config = lib.load_json("configs", "mcraft3-safety.json")
    base = lib.load_json("configs", "mcraft3.json")
    assert config["invariants"] == SUITE
    for key in ("constants", "constraint", "check_deadlock", "batch",
                "queue_capacity", "seen_capacity", "n_msg_slots", "shapes"):
        assert config[key] == base[key], key
    with open(os.path.join(REPO, "configs", config["cfg_name"]),
              encoding="utf-8") as f:
        assert f.read() == "\n".join(config["cfg_text"]) + "\n"
    assert config["guarantees"][:4] == base["guarantees"][:4]
    assert len(config["guarantees"]) == 8
    assert isinstance(config["depth"]["suite_clear_to_level"], int)
    mix = lib.load_json("traffic", "window-l9-safety.json")
    deep = lib.load_json("traffic", "window-l9.json")
    assert mix["kind"] == "safety_window" and mix["witnesses"] == 4
    for key in ("start_level", "sample", "forbidden_events"):
        assert mix[key] == deep[key], key


def test_a_suite_that_holds_removes_no_state():
    assert lib.load_pinned("mcraft3-safety") == lib.load_pinned("mcraft3")


def test_the_new_layer_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    parts = [f"construct_ms.{p}" for p in (*construct.PARTS, "rest")]
    assert [m["name"] for m in manifest["per_layer"][-6:]] == [
        *parts, "inv_lanes_per_new"]
    for name in parts:
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            "ms", "device_trace", "kernels", "distinct_per_s")
        assert m["workloads"] == BFS_CELLS
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "construct"
        assert "FUSED" in spec["what"]
    m = by_name["inv_lanes_per_new"]
    assert (m["source"], m["layer"], m["better"]) == (
        "program_counter", "chunk program", "lower")
    # The cell is read by every metric its pair reads without a capture
    # that must cover the window.
    for name in ("build_s", "host_share.deep", "batch_ms", "seen_load",
                 "queue_fill", "stage_ms.construct", "launches_per_pass",
                 "pass_fill", "passes_per_call", "window_compile_s",
                 "flush_ms"):
        assert by_name[name]["workloads"][-1] == "safety9", name
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["distinct_per_s"]["workloads"][-1] == "safety9"


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def safety_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of the
    new kind: new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads(
        (bench / "configs" / "mcraft3-safety.json").read_text())
    # Pools a fast CPU does not fill in the window (level 9's 172,129
    # rows, half a million keys), so that no spill or rehash falls in.
    config.update(name="tiny3-safety", batch=256, queue_capacity=1 << 19,
                  seen_capacity=1 << 22)
    (bench / "configs" / "tiny3-safety.json").write_text(json.dumps(config))
    (bench / "traffic" / "window-l4-safety.json").write_text(json.dumps(
        {"kind": "safety_window", "start_level": 4, "sample": 32,
         "witnesses": 2,
         "forbidden_events": ["degraded", "fpset_resize", "spill"]}))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny3-safety", "source": "test",
                         "file": "benchmark/configs/tiny3-safety.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny3-safety", "config": "tiny3-safety",
                           "traffic": "window-l4-safety", "chips": 1,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_the_safety_window_runs_to_a_correct_line(safety_root):
    rc, line, out = run_cell(safety_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}
    assert out.count("compare ") == line["attempted"]
    # bfs_window's comparisons and, for each of the nine, the witnesses'
    assert "compare sample (frontier, distinct, generated)" in out
    assert "compare the engine's invariants, in order" in out
    for name in SUITE[1:]:
        assert (f"compare {name}: invariant reported == the one the "
                f"witnesses were made for: got {name} ") in out, name
        assert f"compare {name}: the trace replays" in out


def test_a_traced_run_reads_the_engines_count_of_lanes(safety_root):
    rc, line, out = run_cell(safety_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    # No device plane on the CPU: the split of 'construct' is left out;
    # the counter is the program's own.
    assert set(line["metrics"]) == {"build_s", "host_share.deep",
                                    "batch_ms", "seen_load", "queue_fill",
                                    "pass_fill", "passes_per_call",
                                    "window_compile_s", "flush_ms",
                                    "inv_lanes_per_new"}
    assert line["metrics"]["inv_lanes_per_new"]["value"] > 1


@pytest.mark.parametrize("control", ["predicate", "dispatch"])
def test_a_program_that_evaluates_less_is_not_correct(safety_root, control):
    rc, line, out = run_cell(safety_root, control, "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls_safety.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    # What fails is the witnesses' part, and no count of the window.
    fails = [ln for ln in out.splitlines() if ln.endswith(" FAIL")]
    assert fails and all("witness" in ln or ": invariant reported" in ln
                         or ": reported state" in ln or "replays" in ln
                         for ln in fails), fails


# -- the reader, on a recorded capture ---------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "capture_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def with_parts(cap: dict) -> dict:
    """The recorded capture (of a program from before the parts were
    named) with a part written into every 'construct' path, by hand:
    gathers to ``parents``, reduces to ``invariants`` under two
    predicates, scatters to ``flatten``, the rest left at the stage."""
    cap = copy.deepcopy(cap)
    head = "jit(chunk)/while/body/construct/"
    for i, path in enumerate(cap["op_paths"]):
        if not path.startswith(head):
            continue
        tail = path[len(head):]
        if tail.endswith("gather:"):
            part = "parents/"
        elif "reduce" in tail:
            part = f"invariants/vmap({('TypeOK', 'LogMatching')[i % 2]})/"
            tail = tail.replace("vmap()/", "")
        elif tail.endswith("scatter:"):
            part = "flatten/"
        elif tail.endswith("select_n:"):
            part = "invariants/"
        else:
            continue
        cap["op_paths"][i] = head + part + tail
    return cap


def test_part_of_reads_the_first_nested_scope():
    head = "jit(chunk)/while/body/construct/"
    assert construct.part_of(head + "vmap()/add:") == (None, None)
    assert construct.part_of(head + "lane_out/vmap()/add:") == (
        "lane_out", None)
    assert construct.part_of(
        head + "invariants/vmap(MessagesInv)/vmap(jit(clip))/min:") == (
        "invariants", "MessagesInv")
    assert construct.part_of(
        head + "invariants/vmap(jit(_where))/select_n:") == (
        "invariants", None)
    assert construct.part_of("jit(chunk)/while/body/masks/lane_out/x:") \
        is None
    assert construct.part_of("jit(chunk)/while/cond/lt:") is None


def test_the_split_sums_to_the_stage(recorded):
    run = {"_capture": with_parts(recorded)}
    tab = stages.stage_table(run)
    parts = {p: construct.read(run, mode="part_ms", part=p)
             for p in (*construct.PARTS, "rest")}
    assert parts["parents"] > 0 and parts["invariants"] > 0
    assert parts["flatten"] > 0 and parts["rest"] > 0
    assert parts["lane_out"] == 0
    assert sum(parts.values()) == pytest.approx(
        stages.read(run, mode="stage_ms", stage="construct"), rel=1e-9)
    assert set(run["_construct_split"]["pred_ns"]) == {
        "TypeOK", "LogMatching", None}
    assert run["_construct_split"]["passes"] == tab["passes"]


def test_a_program_without_the_parts_gives_nothing(recorded):
    run = {"_capture": copy.deepcopy(recorded)}
    assert stages.stage_table(run) is not None
    assert construct.read(run, mode="part_ms", part="invariants") is None
    assert construct.read({"trace_dir": None}, mode="part_ms",
                          part="rest") is None


def test_inv_lanes_per_new_is_the_engines_count():
    end = {"event": "run_end", "inv_lanes": 32768 * 287}
    run = {"events": [end], "new_distinct": 3290000}
    assert construct.read(run, mode="inv_lanes_per_new") == pytest.approx(
        32768 * 287 / 3290000)
    assert construct.read({"events": [{"event": "run_end"}],
                           "new_distinct": 5},
                          mode="inv_lanes_per_new") is None
