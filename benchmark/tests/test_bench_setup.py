"""Reader ``setup`` (the eight ``setup.*`` metrics, PR 37): each part by
hand on a made-up ``run_start``, nothing to read where the program keeps
no process record (the parent), and the traced CPU rehearsal's line,
whose eight parts sum to the ``setup_s`` the harness measured in the same
run."""

import re

import pytest

import bench_lib as lib
from bench_helpers import run_cell
from test_bench_mesh_rehearsal import ARGS as MESH_ARGS
from test_bench_mesh_rehearsal import mesh_root  # noqa: F401  (a fixture)

PARTS = ("ready_s", "make_engine_s", "trace_s", "lower_s", "cache_load_s",
         "compile_s", "runs_s", "outside_s")


def made_up_process():
    """A hunt cell's set-up as the program would record it: two engines
    built, two runs finished, a walk chunk loaded and twelve small
    programs compiled."""
    return {
        "age_s": 30.0,
        "marks": {"package": 0.05, "cache_enabled": 11.5, "cfg_loaded": 11.75,
                  "engine_begin": 12.0, "engine_built": 13.0,
                  "first_run": 13.25},
        "jit": {"trace": [40, 4.0], "lower": [40, 1.5], "load": [9, 2.0],
                "compile": [12, 0.5]},
        "jit_before_engine_s": 0.25,
        "cache": {"retrieval_s": 1.5, "stored": 0},
        "programs": [{"name": "chunk_fn", "trace_s": 3.5, "lower_s": 1.25,
                      "backend_s": 1.75, "cache": "hit",
                      "span": "swarm_chunk"}],
        "compiled": [["_fetch_shard", 12, 0.5]],
        "runs": {"count": 2, "run_s": 6.0, "make_engine_s": 1.5,
                 "phases": {"swarm_fetch": 4.0, "reconstruct": 1.0}},
    }


def run_with(process):
    start = {"event": "run_start", "engine": "SwarmEngine"}
    if process is not None:
        start["process"] = process
    # A verdict cell's log holds a run_start a verdict: the first is read.
    later = {"event": "run_start", "process": {"age_s": 31.0}}
    return {"events": [start, {"event": "run_end"}, later],
            "end_to_end": {"setup_s": 29.9}}


@pytest.fixture(scope="module")
def reader():
    return lib.load_module("readers", "setup")


def test_each_part_by_hand(reader, capsys):
    run = run_with(made_up_process())
    got = {part: reader.read(run, part) for part in PARTS}
    assert got == {
        "ready_s": 12.0 - 0.25,         # the mark, less the jit before it
        "make_engine_s": 1.5, "trace_s": 4.0, "lower_s": 1.5,
        "cache_load_s": 2.0, "compile_s": 0.5, "runs_s": 6.0,
        "outside_s": 30.0 - (11.75 + 1.5 + 4.0 + 1.5 + 2.0 + 0.5 + 6.0)}
    assert sum(got.values()) == pytest.approx(30.0)
    assert reader.PARTS == PARTS
    # The record is printed once, beside the harness's own clock.
    out = capsys.readouterr().out
    assert out.count("setup by the program's own record") == 1
    assert "age 30.000s" in out and "setup_s 29.900s" in out
    assert "_fetch_shard 12 0.500" in out and "chunk_fn: 3.500" in out
    with pytest.raises(ValueError):
        reader.read(run, "import_s")


def test_an_engine_built_without_make_engine_leaves_ready_to_outside(reader):
    process = made_up_process()
    del process["marks"]["engine_begin"]
    parts = reader.partition(process)
    assert parts["ready_s"] == 0.0
    assert parts["outside_s"] == 30.0 - (1.5 + 4.0 + 1.5 + 2.0 + 0.5 + 6.0)


def test_nothing_to_read_without_the_record(reader):
    """The parent's ``run_start`` has no ``process``; a kind may return
    no events at all.  Every part is left out and nothing raises."""
    for run in (run_with(None), {"events": []}, {}, {"events": None}):
        assert [reader.read(run, part) for part in PARTS] == [None] * 8


def test_the_manifest_lists_the_eight_in_every_cell(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    entries = {m["name"]: m for m in manifest["per_layer"]
               if m["name"].startswith("setup.")}
    assert sorted(entries) == sorted("setup." + p for p in PARTS)
    layers = {m["layer"] for m in manifest["per_layer"]
              if not m["name"].startswith("setup.")}
    for name, m in entries.items():
        assert (m["unit"], m["better"], m["moves"]) == ("s", "lower",
                                                        "setup_s")
        assert m["workloads"] == cells and m["layer"] in layers
        spec = lib.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "setup"
        assert spec["args"] == {"part": name[len("setup."):]}
    # The eight come last: nothing that was there moved.
    assert [m["name"] for m in manifest["per_layer"][-8:]] == [
        "setup." + p for p in PARTS]


def partition_of_a_traced_rehearsal(root, *args):
    rc, line, out = run_cell(root, *args, "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    parts = {p: line["metrics"]["setup." + p]["value"] for p in PARTS}
    shown = re.search(r"setup by the program's own record: age ([0-9.]+)s "
                      r"at the window's run_start; the harness's setup_s "
                      r"([0-9.]+)s", out)
    age, harness = float(shown.group(1)), float(shown.group(2))
    assert sum(parts.values()) == pytest.approx(age, abs=1e-3)
    assert sum(parts.values()) == pytest.approx(harness, rel=0.02)
    assert all(parts[p] >= 0 for p in PARTS[:-1]), parts
    return parts, out


def test_the_hunt_cells_two_engines_are_both_in_the_partition(
        rehearsal_root):
    """``swarm_hunt`` builds a second, half-width engine in set-up: a
    record kept per engine would lose one of the two."""
    parts, out = partition_of_a_traced_rehearsal(
        rehearsal_root, "--workload", "mcraft3-hunt", "--seed",
        "2147483693", "--seconds", "2")
    assert "runs so far: 2 in" in out
    assert parts["make_engine_s"] > 0 and parts["runs_s"] > 0


def test_the_mesh_kind_reads_the_partition_too(mesh_root, monkeypatch):  # noqa: F811
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    parts, out = partition_of_a_traced_rehearsal(mesh_root, *MESH_ARGS)
    # The kind's own work between the program's spans (the kept
    # snapshot's load and digests) is what ``outside_s`` is for.
    assert parts["outside_s"] > 0 and parts["runs_s"] > 0


def test_traced_rehearsal_partitions_the_setup_the_harness_measured(
        rehearsal_root):
    """Within 2 % of the harness's own clock (the record's starts with
    the process, the harness's with its script)."""
    values, _out = partition_of_a_traced_rehearsal(
        rehearsal_root, "--workload", "mcraft3-noleader", "--seed",
        "2147483659", "--seconds", "2")
    assert abs(values["outside_s"]) < 0.1 * sum(values.values()), values
    # The verdict cell compiles in its one set-up check and nowhere else.
    assert values["trace_s"] > 0 and values["lower_s"] > 0
    assert values["cache_load_s"] + values["compile_s"] > 0
    assert values["runs_s"] > 0 and values["ready_s"] > 0
