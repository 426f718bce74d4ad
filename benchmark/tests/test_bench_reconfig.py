"""The reconfiguration deployment's part of the benchmark: its entries in
the manifest, its pinned profile, the new traffic kind on the CPU at a
tiny size with its three controls, and the ``variant`` reader on a
recorded capture."""

import copy
import json
import os
import shutil

import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
stages = lib.load_module("readers", "stages")
construct = lib.load_module("readers", "construct")
variant = lib.load_module("readers", "variant")

ARGS = ("--workload", "tiny-reconfig3", "--seed", "3000000039",
        "--seconds", "2")
NEW_METRICS = ["variant_ms.quorum", "variant_ms.extra",
               "family_share.leader", "family_share.reconfig"]


# -- the manifest's new entries ---------------------------------------------

def test_the_cell_and_its_configuration(manifest):
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("reconfig3", "reconfig3", "window-reconfig-l8", 1)
    entry = manifest["configs"][-1]
    assert entry["name"] == "reconfig3"
    assert entry["reduced"] == ["depth", "roots"]
    config = lib.load_json("configs", "reconfig3.json")
    assert config["source"] == entry["source"]
    assert config["architecture"] is None
    assert sorted(config["reduced"]) == ["depth", "roots"]
    with open(os.path.join(REPO, "configs", config["cfg_name"]),
              encoding="utf-8") as f:
        assert f.read() == "\n".join(config["cfg_text"]) + "\n"
    base = lib.load_json("configs", "mcraft3.json")
    assert config["guarantees"][1] == base["guarantees"][1]
    assert any("joint rule" in g for g in config["guarantees"])
    assert any("every root from Init" in g for g in config["guarantees"])
    assert (config["batch"], config["queue_capacity"],
            config["seen_capacity"]) == (2048, 4194304, 1 << 25)
    mix = lib.load_json("traffic", "window-reconfig-l8.json")
    assert (mix["kind"], mix["roots"], mix["start_level"], mix["sample"],
            mix["replayed"]) == (
        "rooted_window", "reference.reconfig:canonical_roots", 8, 256, 32)
    assert mix["forbidden_events"] == lib.load_json(
        "traffic", "window-l9.json")["forbidden_events"]


def test_the_pin_is_from_the_nine_roots_with_families():
    rooted = lib.load_module("traffic", "rooted_window")
    pinned = rooted.load_pinned("reconfig3")
    assert sorted(pinned) == list(range(max(pinned) + 1))
    assert max(pinned) >= 10
    assert pinned[0][:3] == (9, 9, 0)
    assert pinned[8][:3] == (372832, 1343902, 3582043)
    assert pinned[9][:3] == (1019444, 4065171, 11082981)
    for row in pinned.values():
        assert sum(row[3].values()) == row[2]
    # lib.load_pinned reads the same file's three counts.
    assert lib.load_pinned("reconfig3")[9] == pinned[9][:3]


def test_the_new_layer_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-4:]] == NEW_METRICS
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["moves"] == "distinct_per_s"
        assert m["workloads"] == ["reconfig3"]
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "variant"
    for name in NEW_METRICS[:2]:
        assert (by_name[name]["unit"], by_name[name]["source"],
                by_name[name]["layer"]) == ("ms", "device_trace", "kernels")
    for name in NEW_METRICS[2:]:
        assert (by_name[name]["unit"], by_name[name]["source"]) == (
            "%", "program_counter")
    # The cell is read by every metric safety9 is read by.
    for m in manifest["per_layer"]:
        if "safety9" in m.get("workloads", ()):
            assert m["workloads"][-1] == "reconfig3", m["name"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["distinct_per_s"]["workloads"][-1] == "reconfig3"
    assert "reconfig3" in by_name["chunk_roofline"]["workloads"]


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def reconfig_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of the
    new kind: new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / "reconfig3.json").read_text())
    # Pools a fast CPU does not fill in two seconds from level 6 (the
    # first level at which simple majority and the joint rule part).
    config.update(name="tiny-reconfig3", batch=256,
                  queue_capacity=1 << 20, seen_capacity=1 << 23)
    (bench / "configs" / "tiny-reconfig3.json").write_text(
        json.dumps(config))
    mix = json.loads(
        (bench / "traffic" / "window-reconfig-l8.json").read_text())
    mix.update(start_level=6, sample=32, replayed=8)
    (bench / "traffic" / "window-reconfig-l6.json").write_text(
        json.dumps(mix))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-reconfig3", "source": "test",
                         "file": "benchmark/configs/tiny-reconfig3.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny-reconfig3",
                           "config": "tiny-reconfig3",
                           "traffic": "window-reconfig-l6", "chips": 1,
                           "why": "throw-away"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "reconfig3" in e.get("workloads", ()):
            e["workloads"].append("tiny-reconfig3")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_the_rooted_window_runs_to_a_correct_line(reconfig_root):
    rc, line, out = run_cell(reconfig_root, *ARGS, "--trace", "0")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert set(line["metrics"]) == {"setup_s", "distinct_per_s"}
    assert out.count("compare ") == line["attempted"]
    for what in ("action families", "dims class: got ReconfigDims",
                 "engine class: got BFSEngine",
                 "roots whose path from Init is legal under the reference:"
                 " got 9", "roots whose packed row decodes to them: got 9",
                 "set-up level 6 (frontier, distinct, generated, by family)",
                 "snapshot dims class: got ReconfigDims",
                 "window generated InitiateReconfig: got True",
                 "window generated FinalizeReconfig: got True",
                 "window's generated by family sums to its generated",
                 "sample generated by family engine == reference",
                 "replayed paths that start at one of the roots: got 8",
                 "replayed paths legal under the reference, every step: "
                 "got 8"):
        assert "compare " + what in out, what


def test_a_traced_run_reads_the_family_shares(reconfig_root):
    rc, line, out = run_cell(reconfig_root, *ARGS, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True
    # No device plane on the CPU: the scopes' times are left out; the
    # shares are the program's own counts.
    assert set(line["metrics"]) == {"build_s", "host_share.deep",
                                    "batch_ms", "seen_load", "queue_fill",
                                    "pass_fill", "passes_per_call",
                                    "window_compile_s", "flush_ms",
                                    "inv_lanes_per_new",
                                    "family_share.leader",
                                    "family_share.reconfig",
                                    "setup.ready_s", "setup.make_engine_s",
                                    "setup.trace_s", "setup.lower_s",
                                    "setup.cache_load_s", "setup.compile_s",
                                    "setup.runs_s", "setup.outside_s"}
    leader = line["metrics"]["family_share.leader"]["value"]
    reconf = line["metrics"]["family_share.reconfig"]["value"]
    assert 0 < reconf < leader < 100


@pytest.mark.parametrize("control,failing", [
    ("simple_majority", ("level", "sample", "replayed paths legal",
                         "action_counts")),
    ("no_extra", ("level", "sample", "window generated", "action_counts",
                  "window's generated by family")),
    ("one_byte_values", ("row width", "decodes to them", "walk stop reason",
                         "level", "action_counts",
                         "the walk left a snapshot"))])
def test_a_program_that_does_less_is_not_correct(reconfig_root, control,
                                                 failing):
    rc, line, out = run_cell(reconfig_root, control, "--", *ARGS,
                             "--trace", "0",
                             script="benchmark/tests/controls_reconfig.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > 0
    fails = [ln for ln in out.splitlines() if ln.endswith(" FAIL")]
    print("\n".join(ln[:160] for ln in fails))
    assert fails and all(any(w in ln for w in failing) for ln in fails), \
        [ln[:160] for ln in fails]
    # Each is seen in the walk, before the window.
    assert any("set-up level" in ln or "walk stop reason" in ln
               for ln in fails)


# -- the reader, on a recorded capture ---------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "capture_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def with_scopes(cap: dict) -> dict:
    """The recorded capture (of a program from before the scopes) with
    ``quorum`` and ``extra`` written into paths of ``masks`` and of
    ``construct``, by hand: under ``masks`` every other operation to
    ``quorum``; under ``construct`` a ``lane_out`` part, its reduces to
    ``quorum`` and its gathers to ``extra``."""
    cap = copy.deepcopy(cap)
    head = "jit(chunk)/while/body/"
    for i, path in enumerate(cap["op_paths"]):
        if path.startswith(head + "masks/") and i % 2:
            name = path.rsplit("/", 1)[1]
            cap["op_paths"][i] = (head + "masks/vmap(masks)/vmap(bl_one)/"
                                  "vmap(vmap(quorum))/" + name)
        elif path.startswith(head + "construct/"):
            tail = path[len(head + "construct/"):]
            scope = ("vmap(vmap(quorum))/" if "reduce" in tail
                     else "vmap(extra)/" if tail.endswith("gather:")
                     else "")
            cap["op_paths"][i] = (head + "construct/lane_out/"
                                  "vmap(lane_out)/" + scope
                                  + tail.rsplit("/", 1)[-1])
    return cap


def test_the_scopes_are_part_of_their_sites(recorded):
    run = {"_capture": with_scopes(recorded)}
    assert stages.stage_table(run) is not None
    quorum = variant.read(run, mode="scope_ms", scope="quorum")
    extra = variant.read(run, mode="scope_ms", scope="extra")
    assert quorum > 0 and extra > 0
    ns, passes = run["_variant_split"]["ns"], run["_variant_split"]["passes"]
    assert ns[("masks", "quorum")] > 0 and ns[("lane_out", "quorum")] > 0
    assert ("masks", "extra") not in ns and ns[("lane_out", "extra")] > 0
    ms = lambda site: sum(v for (s, _sc), v in ns.items()  # noqa: E731
                          if s == site) / 1e6 / passes
    # The sites are the stage's and the part's own, to the nanosecond.
    assert ms("masks") == pytest.approx(
        stages.read(run, mode="stage_ms", stage="masks"), rel=1e-9)
    assert ms("lane_out") == pytest.approx(
        construct.read(run, mode="part_ms", part="lane_out"), rel=1e-9)
    assert quorum == pytest.approx(
        (ns[("masks", "quorum")] + ns[("lane_out", "quorum")])
        / 1e6 / passes)


def test_a_program_without_the_scopes_gives_nothing(recorded):
    run = {"_capture": copy.deepcopy(recorded)}
    assert stages.stage_table(run) is not None
    assert variant.read(run, mode="scope_ms", scope="quorum") is None
    assert variant.read({"trace_dir": None}, mode="scope_ms",
                        scope="extra") is None
    assert variant.read({"events": [{"event": "run_end"}]},
                        mode="family_share", families=["Restart"]) is None
    with pytest.raises(ValueError):
        variant.read(run, mode="scope_ms", scope="masks")
