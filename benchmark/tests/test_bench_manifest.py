"""``BENCHMARK.json`` against the contract's form, and every file it names
found by name."""

import json
import os
import re

import bench_lib as lib
from bench_helpers import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536


def test_names_units_and_lines(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert line_ok(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_metrics_form(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line_ok(m["layer"])
        layers.add(m["layer"])
        # each cell that reads it reports the end-to-end metric it moves;
        # one that names no cells is read in all of those
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    # every cell: setup_s, one more end-to-end metric, a per-layer metric
    for c in cells:
        assert sum(c in m.get("workloads", cells)
                   for m in manifest["end_to_end"]) >= 2
        assert any(c in m.get("workloads",
                              e2e[m["moves"]].get("workloads", cells))
                   for m in manifest["per_layer"])


def test_every_file_is_found_by_name(manifest):
    used = set()
    for c in manifest["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        with open(os.path.join(REPO, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
        assert cfg["guarantees"] and "assumed" in cfg
        assert lib.load_pinned(cfg["pinned"])[0] == (1, 1, 0)
    for w in manifest["workloads"]:
        mix = lib.load_json("traffic", w["traffic"] + ".json")
        assert hasattr(lib.load_module("traffic", mix["kind"]), "run")
        used.add(w["config"])
    assert used == {c["name"] for c in manifest["configs"]}
    for m in manifest["per_layer"]:
        spec = lib.load_json("layer_metrics", m["name"] + ".json")
        assert hasattr(lib.load_module("readers", spec["reader"]), "read")


def test_file_names_under_paths_use_only_the_allowed_characters():
    for dp, dns, fns in os.walk(BENCH):
        dns[:] = [d for d in dns if d != "__pycache__"]
        for fn in fns:
            rel = os.path.relpath(os.path.join(dp, fn), REPO)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_the_reference_imports_nothing_of_the_program():
    ref = os.path.join(BENCH, "reference")
    for fn in os.listdir(ref):
        if fn.endswith(".py"):
            with open(os.path.join(ref, fn), encoding="utf-8") as f:
                text = f.read()
            assert "raft_tla_tpu" not in text.replace(
                "``raft_tla_tpu/models/dims.py``", ""), fn
            assert "import jax" not in text, fn


def test_pinned_profiles_are_the_plain_references_own():
    """The first levels of each pinned profile, recomputed here with the
    copy of the reference kept under benchmark/reference."""
    for name, depth in (("mcraft3", 6), ("mcraft3-noleader", 6),
                        ("raft5", 5)):
        cfg = lib.load_json("configs", name + ".json")
        ref = lib.reference(cfg)
        pinned = lib.load_pinned(cfg["pinned"])
        root = ref.pystate.init_state(ref.dims)
        seen, frontier, generated = {root}, [root], 0
        assert pinned[0] == (1, 1, 0)
        for lv in range(1, depth + 1):
            nxt = []
            for s in frontier:
                succ = ref.oracle.successors(s, ref.dims)
                generated += len(succ)
                for _a, t in succ:
                    if t not in seen:
                        seen.add(t)
                        if ref.constraint(t, ref.dims):
                            nxt.append(t)
            frontier = nxt
            assert pinned[lv] == (len(frontier), len(seen), generated), (
                name, lv)
