"""The rooted safety cells' part of the benchmark (``reconfig3-safety``,
``leader-rich``): their entries in the manifest, their pins, the new
traffic kind on the CPU at small pools with its controls, and the ``suite``
reader on a recorded capture."""

import copy
import json
import os
import shutil

import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
construct = lib.load_module("readers", "construct")
suite = lib.load_module("readers", "suite")
rooted = lib.load_module("traffic", "rooted_window")

SUITE = ["TypeOK", "MessagesInv", "LeaderVotesQuorum",
         "CandidateTermNotInLog", "ElectionSafety", "LogMatching",
         "VotesGrantedInv", "QuorumLogInv", "MoreUpToDateCorrect",
         "LeaderCompleteness"]
EXTRA = ["witness_log_matching_high_byte",
         "witness_leader_completeness_config"]
CELLS = ["reconfig3-safety", "leader-rich"]
SEED = "3000000043"


def args(cell):
    return ("--workload", cell, "--seed", SEED, "--seconds", "2")


# -- the manifest's new entries ---------------------------------------------

def test_the_cells_and_the_configuration(manifest):
    by_name = {w["name"]: w for w in manifest["workloads"]}
    assert [w["name"] for w in manifest["workloads"][-2:]] == CELLS
    assert (by_name["reconfig3-safety"]["config"],
            by_name["reconfig3-safety"]["traffic"]) == (
        "reconfig3-safety", "window-reconfig-l8-safety")
    assert (by_name["leader-rich"]["config"],
            by_name["leader-rich"]["traffic"]) == (
        "mcraft3-safety", "window-leaders-l6-safety")
    assert all(by_name[c]["chips"] == 1 for c in CELLS)
    entry = manifest["configs"][-1]
    assert entry["name"] == "reconfig3-safety"
    assert entry["reduced"] == ["depth", "roots"]
    config = lib.load_json("configs", "reconfig3-safety.json")
    base = lib.load_json("configs", "reconfig3.json")
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["architecture"] is None
    assert config["invariants"] == SUITE
    for key in ("constants", "constraint", "check_deadlock", "batch",
                "queue_capacity", "seen_capacity", "n_msg_slots", "shapes",
                "roots", "layout", "reduced"):
        assert config[key] == base[key], key
    assert config["guarantees"][:5] == base["guarantees"][:5]
    assert any("every invariant the cfg names" in g
               for g in config["guarantees"])
    assert "simple majority" in config["assumed"]["quorum_predicates"]
    with open(os.path.join(REPO, "configs", config["cfg_name"]),
              encoding="utf-8") as f:
        assert f.read() == "\n".join(config["cfg_text"]) + "\n"


def test_the_mixes():
    safe = lib.load_json("traffic", "window-reconfig-l8-safety.json")
    plain = lib.load_json("traffic", "window-reconfig-l8.json")
    for key in ("roots", "start_level", "sample", "replayed",
                "families_in_window", "forbidden_events"):
        assert safe[key] == plain[key], key
    assert (safe["kind"], safe["suite"], safe["witnesses"],
            safe["extra_witnesses"]) == (
        "rooted_safety_window", "safety_reconfig", 4, EXTRA)
    rich = lib.load_json("traffic", "window-leaders-l6-safety.json")
    assert (rich["kind"], rich["roots"], rich["pinned"], rich["suite"],
            rich["start_level"], rich["witnesses"],
            rich["extra_witnesses"]) == (
        "rooted_safety_window", "reference.leaders:leader_roots",
        "mcraft3-safety.leaders", "safety", 6, 4, [])
    assert rich["families_in_window"] == [
        "ClientRequest", "AppendEntries", "AdvanceCommitIndex"]
    assert rich["forbidden_events"] == plain["forbidden_events"]


def test_a_suite_that_holds_removes_no_state():
    assert rooted.load_pinned("reconfig3-safety") == rooted.load_pinned(
        "reconfig3")


def test_the_leaders_pin_is_from_the_117_roots_with_families():
    pinned = rooted.load_pinned("mcraft3-safety.leaders")
    assert sorted(pinned) == list(range(9))
    assert pinned[0][:3] == (117, 117, 0)
    assert [pinned[lv][0] for lv in range(9)] == [
        117, 519, 2005, 7368, 24732, 77803, 231921, 658995, 1797225]
    for row in pinned.values():
        assert sum(row[3].values()) == row[2]
    leader = ("ClientRequest", "AppendEntries", "AdvanceCommitIndex")
    assert all(pinned[7][3][f] > pinned[6][3][f] for f in leader)


def test_the_first_levels_of_the_leaders_pin_recomputed():
    from reference import dims as rd
    from reference import leaders, oracle
    config = lib.load_json("configs", "mcraft3-safety.json")
    dims = leaders.reference_dims(config)
    constraint = rd.constraint_py(leaders.reference_bounds(config))
    pinned = rooted.load_pinned("mcraft3-safety.leaders")
    frontier = [r.state for r in leaders.leader_roots(dims)]
    seen, generated = set(frontier), 0
    by_family = dict.fromkeys(leaders.FAMILY_NAMES, 0)
    for lv in range(1, 5):
        nxt = []
        for s in frontier:
            for (family, _p), t in oracle.successors(s, dims):
                generated += 1
                by_family[leaders.FAMILY_NAMES[family]] += 1
                if t not in seen:
                    seen.add(t)
                    if constraint(t, dims):
                        nxt.append(t)
        frontier = nxt
        assert pinned[lv] == (len(frontier), len(seen), generated,
                              by_family), lv


def test_the_new_layer_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-2:]] == [
        "suite_ms.logs", "suite_ms.rest"]
    for name in ("suite_ms.logs", "suite_ms.rest"):
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            "ms", "device_trace", "kernels", "distinct_per_s")
        assert m["workloads"] == ["safety9", *CELLS]
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "suite" and "FUSED" in spec["what"]
    # Both cells are read by every metric that reads safety9 and
    # reconfig3; the variant's own read reconfig3-safety alone.
    for m in manifest["per_layer"]:
        w = m.get("workloads", ())
        if "safety9" in w and "reconfig3" in w:
            assert w[-2:] == CELLS, m["name"]
    for name in ("variant_ms.quorum", "variant_ms.extra",
                 "family_share.reconfig"):
        assert by_name[name]["workloads"] == ["reconfig3",
                                              "reconfig3-safety"]
    assert by_name["family_share.leader"]["workloads"] == [
        "reconfig3", *CELLS]
    assert by_name["chunk_roofline"]["workloads"][-2:] == CELLS
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["distinct_per_s"]["workloads"][-2:] == CELLS


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def rooted_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of each
    mix: new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    m = json.loads(json.dumps(manifest))

    def add(cell, config_of, mix_of, start, **sizes):
        config = json.loads(
            (bench / "configs" / (config_of + ".json")).read_text())
        config.update(name=cell, batch=256, **sizes)
        (bench / "configs" / (cell + ".json")).write_text(
            json.dumps(config))
        mix = json.loads((bench / "traffic" / (mix_of + ".json")).read_text())
        mix.update(start_level=start, sample=32, replayed=8, witnesses=2)
        (bench / "traffic" / (cell + ".json")).write_text(json.dumps(mix))
        m["configs"].append({"name": cell, "source": "test",
                             "file": f"benchmark/configs/{cell}.json",
                             "reduced": [], "why": "throw-away"})
        m["workloads"].append({"name": cell, "config": cell,
                               "traffic": cell, "chips": 1,
                               "why": "throw-away"})

    # Pools a fast CPU does not fill in two seconds from these levels.
    add("tiny-reconfig3-safety", "reconfig3-safety",
        "window-reconfig-l8-safety", 6, queue_capacity=1 << 20,
        seen_capacity=1 << 23)
    add("tiny-leader-rich", "mcraft3-safety", "window-leaders-l6-safety", 4,
        queue_capacity=1 << 20, seen_capacity=1 << 23)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


@pytest.mark.parametrize("cell,makers", [
    ("tiny-reconfig3-safety", SUITE[1:] + [
        "witness_log_matching_high_byte (LogMatching)",
        "witness_leader_completeness_config (LeaderCompleteness)"]),
    ("tiny-leader-rich", SUITE[1:])])
def test_the_kind_runs_to_a_correct_line(rooted_root, cell, makers):
    rc, line, out = run_cell(rooted_root, *args(cell), "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, "\n".join(
        ln for ln in out.splitlines() if ln.endswith(" FAIL"))
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}
    assert out.count("compare ") == line["attempted"]
    # rooted_window's comparisons, then the suite's.
    for what in ("roots whose path from Init is legal under the reference",
                 "sample generated by family engine == reference",
                 "replayed paths legal under the reference, every step",
                 "the engine's invariants, in order",
                 "sampled states and successors failing a reference "
                 "invariant: got []",
                 "stop reasons of the window's run_end events"):
        assert "compare " + what in out, what
    for what in makers:
        name = what.split("(")[-1].rstrip(")")
        assert (f"compare {what}: invariant reported == the one the "
                f"witnesses were made for: got {name} ") in out, what
        assert f"compare {what}: the trace replays" in out, what


def test_the_leaders_window_fires_the_leader_families(rooted_root):
    rc, line, out = run_cell(rooted_root, *args("tiny-leader-rich"),
                             "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    assert "compare roots inside the constraint: got 117 " in out
    for family in ("ClientRequest", "AppendEntries", "AdvanceCommitIndex"):
        assert f"compare window generated {family}: got True" in out
    # The base model has four of the six leader families: the share is
    # theirs, the two absent ones count 0; the variant's own reads 0.
    assert 5 < line["metrics"]["family_share.leader"]["value"] < 40
    assert line["metrics"]["family_share.reconfig"]["value"] == 0
    # No device plane on the CPU: the suite's split is left out.
    assert "suite_ms.logs" not in line["metrics"]


@pytest.mark.parametrize("cell,control,failing", [
    ("tiny-reconfig3-safety", "low_byte_entry_eq",
     ("witness_log_matching_high_byte", "LogMatching")),
    ("tiny-reconfig3-safety", "log_matching_true",
     ("witness_log_matching_high_byte", "LogMatching")),
    ("tiny-reconfig3-safety", "inv_id_minus_one",
     ("stop reason of the resumed witness frontier",)),
    ("tiny-leader-rich", "log_matching_true", ("LogMatching",)),
    ("tiny-leader-rich", "inv_id_minus_one",
     ("stop reason of the resumed witness frontier",)),
    ("tiny-leader-rich", "leader_family",
     ("level", "sample", "window generated AppendEntries",
      "action_counts", "generated by family"))])
def test_a_program_that_checks_less_is_not_correct(rooted_root, cell,
                                                   control, failing):
    rc, line, out = run_cell(
        rooted_root, control, "--", *args(cell), "--trace", "0",
        script="benchmark/tests/controls_rooted_safety.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    fails = [ln for ln in out.splitlines() if ln.endswith(" FAIL")]
    print("\n".join(ln[:160] for ln in fails))
    assert fails and all(any(w in ln for w in failing) for ln in fails), \
        [ln[:160] for ln in fails]
    if control == "low_byte_entry_eq":
        assert any("witness_log_matching_high_byte" in ln for ln in fails)


# -- the reader, on a recorded capture ---------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "capture_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def with_predicates(cap: dict, names) -> dict:
    """The recorded capture with part ``invariants`` written into the
    reduces of ``construct``, each under one of ``names`` in turn, and
    the selects under the part alone (the dispatch)."""
    cap = copy.deepcopy(cap)
    head = "jit(chunk)/while/body/construct/"
    k = 0
    for i, path in enumerate(cap["op_paths"]):
        if not path.startswith(head):
            continue
        tail = path[len(head):]
        if "reduce" in tail:
            cap["op_paths"][i] = (head + f"invariants/vmap({names[k % len(names)]})/"
                                  + tail.replace("vmap()/", ""))
            k += 1
        elif tail.endswith("select_n:"):
            cap["op_paths"][i] = head + "invariants/" + tail
    return cap


def test_the_two_sum_to_the_part(recorded):
    run = {"_capture": with_predicates(
        recorded, ["TypeOK", "LogMatching", "MessagesInv", "QuorumLogInv"])}
    logs = suite.read(run, mode="logs")
    rest = suite.read(run, mode="rest")
    assert logs > 0 and rest > 0
    assert logs + rest == pytest.approx(
        construct.read(run, mode="part_ms", part="invariants"), rel=1e-9)
    tab = run["_construct_split"]
    assert logs == pytest.approx(
        (tab["pred_ns"]["LogMatching"] + tab["pred_ns"]["QuorumLogInv"])
        / 1e6 / tab["passes"])


def test_a_program_without_the_log_predicates_gives_nothing(recorded):
    run = {"_capture": with_predicates(recorded, ["TypeOK"])}
    assert construct.read(run, mode="part_ms", part="invariants") > 0
    assert suite.read(run, mode="logs") is None
    assert suite.read(run, mode="rest") is None
    assert suite.read({"_capture": copy.deepcopy(recorded)},
                      mode="logs") is None
    assert suite.read({"trace_dir": None}, mode="rest") is None
    with pytest.raises(ValueError):
        suite.read(run, mode="all")
