"""Shared by the benchmark's own tests (``python -m pytest benchmark/tests``;
not part of the repo's ``tests/``)."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_helpers import BENCH, REPO  # noqa: E402

sys.path.insert(0, BENCH)


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="session")
def rehearsal_root(tmp_path_factory, manifest):
    """A checkout-shaped directory with the benchmark, the program, and a
    throw-away configuration, traffic mix and cell ADDED AS NEW FILES
    ONLY: what a later PR does to add a cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / "mcraft3.json").read_text())
    config.update(name="tiny3", batch=256, queue_capacity=1 << 17,
                  seen_capacity=1 << 21)
    (bench / "configs" / "tiny3.json").write_text(json.dumps(config))
    (bench / "traffic" / "window-l4.json").write_text(json.dumps(
        {"kind": "bfs_window", "start_level": 4, "sample": 32,
         "forbidden_events": ["degraded", "fpset_resize", "spill"]}))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny3", "source": "test",
                         "file": "benchmark/configs/tiny3.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny3-deep", "config": "tiny3",
                           "traffic": "window-l4", "chips": 1,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root
