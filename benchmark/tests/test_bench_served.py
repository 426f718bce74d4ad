"""The served deployment's part of the benchmark: its entries in the
manifest, the mix's parameters, the plain reference's schedule and trace
check, the ``served`` reader on a hand-made capture, and the new traffic
kind on the CPU at small pools with its three controls and against a
server without the blocking ``result``."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_lib as lib
from bench_helpers import BENCH, REPO, run_cell

served = lib.load_module("readers", "served")
kind = lib.load_module("traffic", "served_loop")

ARGS = ("--workload", "tiny-served-mix", "--seed", "3000000051",
        "--seconds", "3")
NEW_METRICS = ["serve_overhead_ms", "respond_ms", "turnaround_p95_s",
               "device_idle.served", "idle.between_jobs"]


# -- the manifest's new entries ---------------------------------------------

def test_the_cell_and_its_configuration(manifest):
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "served-mix")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("served3", "tenants3-closed", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "served3")
    config = lib.load_json("configs", "served3.json")
    assert config["source"] == entry["source"]
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == sorted(config["reduced"]) == ["depth"]
    # The three programs are the underlying configurations', copied.
    for name, base in (("canary", "mcraft3-noleader"), ("hunt",
                       "mcraft3-noleader"), ("deep", "mcraft3")):
        prog, under = config["programs"][name], lib.load_json(
            "configs", base + ".json")
        for key in ("cfg_name", "cfg_text", "constants", "shapes"):
            assert prog[key] == under[key], (name, key)
    sizes = lambda c: {k: c[k] for k in ("batch", "queue_capacity",  # noqa: E731
                                         "seen_capacity")}
    req = {k: v["request"] for k, v in config["programs"].items()}
    assert sizes(req["canary"]) == sizes(
        lib.load_json("configs", "mcraft3-noleader.json"))
    assert sizes(req["deep"]) == sizes(
        lib.load_json("configs", "mcraft3.json")) == {
        "batch": 2048, "queue_capacity": 2097152, "seen_capacity": 16777216}
    swarm = lib.load_json("configs", "mcraft3-swarm.json")
    assert (req["hunt"]["mode"], req["hunt"]["walks"],
            req["hunt"]["max_depth"], req["hunt"]["batch"]) == (
        "swarm", swarm["walks"], swarm["max_depth"], swarm["batch"]) == (
        "swarm", 4096, 100, 4096)
    assert req["deep"]["max_diameter"] == 8
    assert config["service"]["job_queue"] == 64
    assert config["service"]["history"] is None
    assert config["service"]["metrics_port"] is None
    pin = lib.load_pinned("mcraft3")
    assert pin[8][1:] == (139327, 384432) == (
        config["programs"]["deep"]["expect"]["distinct"],
        config["programs"]["deep"]["expect"]["generated"])
    assert lib.load_pinned("mcraft3-noleader")[8][1] == 37452


def test_the_mix_states_the_traffic(manifest):
    mix = lib.load_json("traffic", "tenants3-closed.json")
    assert (mix["kind"], mix["loop"], mix["think_time_s"],
            mix["connections_per_tenant"],
            mix["outstanding_per_tenant"]) == ("served_loop", "closed", 0,
                                               1, 1)
    ci, hunt, deep = mix["tenants"]
    assert [t["name"] for t in mix["tenants"]] == ["ci", "hunt", "deep"]
    assert [(j["program"], j["cache"], j["trace"]) for j in ci["jobs"]] \
        == [("canary", False, True), ("canary", True, True)]
    assert ci["warm_up"] == {"program": "canary", "cache": True}
    assert hunt["seeds"] == list(range(1, 17))
    assert (hunt["jobs"][0]["walks"], hunt["jobs"][0]["max_depth"],
            hunt["jobs"][0]["mode"]) == (4096, 100, "swarm")
    assert (deep["jobs"][0]["max_diameter"], deep["jobs"][0]["cache"],
            deep["jobs"][0]["cfg"]) == (8, False, "MCraft_bounded.cfg")
    assert (deep["jobs"][0]["batch"], deep["jobs"][0]["queue_capacity"],
            deep["jobs"][0]["seen_capacity"]) == (2048, 2097152, 16777216)


def test_the_new_layer_metrics(manifest):
    new = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == NEW_METRICS
    for m in new:
        assert m["workloads"] == ["served-mix"] and m["moves"] == "verdict_s"
        spec = lib.load_json("layer_metrics", m["name"] + ".json")
        assert spec["reader"] == "served"
    for m in manifest["per_layer"]:
        if m["name"] == "build_s" or m["name"].startswith("setup."):
            assert m["workloads"][-1] == "served-mix"
    verdict = next(m for m in manifest["end_to_end"]
                   if m["name"] == "verdict_s")
    assert verdict["workloads"][-1] == "served-mix"


# -- the plain reference ------------------------------------------------------

def test_the_reference_schedule_by_hand():
    from reference import served as ref
    sub = lambda j, t, k=None: {"ev": "submit", "job": j, "tenant": t,  # noqa: E731
                                "key": k}
    pick, end = {"ev": "pick"}, lambda j, ok=True: {  # noqa: E731
        "ev": "end", "job": j, "ok": ok}
    log = [sub("a1", "a", "K"), sub("a2", "a", "K"), sub("b1", "b"),
           pick, end("a1"),              # a joined first
           sub("c1", "c"), pick, end("b1"),    # b before c (joined first)
           pick, end("c1"),              # c never served: before a again
           pick, end("a2"),              # a2 repeats K: a hit
           sub("a3", "a", "K"), sub("b2", "b"), {"ev": "cancel",
                                                 "job": "b2"},
           pick, end("a3")]
    got = ref.schedule(log)
    assert got["starts"] == ["a1", "b1", "c1", "a2", "a3"]
    assert got["hits"] == {"a2", "a3"}
    # A run that failed stores nothing.
    got = ref.schedule([sub("x", "a", "K"), pick, end("x", ok=False),
                        sub("y", "a", "K"), pick, end("y")])
    assert got["hits"] == set()
    with pytest.raises(ValueError):
        ref.schedule([pick])
    with open(os.path.join(BENCH, "reference", "served.py")) as f:
        assert "raft_tla_tpu" not in f.read().split('"""', 2)[2]


def test_the_reference_holds_a_trace_to_the_interpreter():
    from reference import served as ref
    r = lib.reference(lib.load_json("configs", "mcraft3-noleader.json"))
    fmt = r.pystate.format_state
    path = _election(r)
    trace = [{"action": "Init" if n == 0 else "step",
              "state": fmt(s, r.dims)} for n, s in enumerate(path)]
    args = (r.dims, r.constraint, r.oracle, r.pystate, r.rd.no_leader_py)
    assert ref.trace_faults(trace, *args) == []
    assert ref.trace_faults(trace[:-1], *args) == [
        "the last state holds no leader"]
    assert ref.trace_faults([trace[0], trace[-1]], *args)[-1].startswith(
        "step 1")
    assert ref.trace_faults(trace[1:], *args) == [
        "the trace does not start at the reference's initial state"]
    assert ref.trace_faults([], *args) == ["no trace"]


def _election(r) -> list:
    """A shortest election of server r1, found by the interpreter itself
    breadth-first over the actions an election needs."""
    o = r.oracle
    needed = {o.A_TIMEOUT: lambda p: p == (0,),
              o.A_REQUESTVOTE: lambda p: p[0] == 0,
              o.A_RECEIVE: lambda p: True,
              o.A_BECOMELEADER: lambda p: True}
    init = r.pystate.init_state(r.dims)
    frontier, seen = [[init]], {init}
    while frontier:
        grown = []
        for path in frontier:
            for (fam, params), s in o.successors(path[-1], r.dims):
                if (fam in needed and needed[fam](params) and s not in seen
                        and r.constraint(s, r.dims)):
                    seen.add(s)
                    if not r.rd.no_leader_py(s, r.dims):
                        return path + [s]
                    grown.append(path + [s])
        frontier = grown
    raise AssertionError("the interpreter elects no leader")


# -- the reader, on a hand-made capture ----------------------------------------

MS = 1_000_000


def built_run():
    """Three jobs on the executor's line (two executed, one hit), the
    device busy inside the runs and once between them (the replay)."""
    executor = [
        ["job", 0, 110 * MS], ["journal", 0, 1 * MS],
        ["job_setup", 2 * MS, 3 * MS], ["run", 5 * MS, 95 * MS],
        ["chunk", 6 * MS, 90 * MS], ["job_respond", 100 * MS, 6 * MS],
        ["replay", 101 * MS, 4 * MS], ["journal", 107 * MS, 2 * MS],
        ["job", 112 * MS, 2 * MS], ["journal", 113 * MS, 1 * MS],
        ["job", 120 * MS, 210 * MS], ["job_setup", 121 * MS, 5 * MS],
        ["run", 126 * MS, 195 * MS], ["job_respond", 321 * MS, 4 * MS],
        ["journal", 326 * MS, 2 * MS]]
    handler = [["request/result", 0, 111 * MS], ["result_wait", 0, 110 * MS]]
    cap = {"host": [], "op_names": ["fusion"], "op_paths": [""],
           "modules": [["jit_chunk(1)", 10 * MS, 80 * MS],
                       ["jit_replay_scan(2)", 102 * MS, 2 * MS],
                       ["jit_chunk_fn(3)", 130 * MS, 180 * MS]],
           "ops": np.asarray([[0, 10 * MS, 80 * MS], [0, 102 * MS, 2 * MS],
                              [0, 130 * MS, 180 * MS]], np.int64)}
    return {"served": {"job_ends": [{"turnaround_s": t} for t in
                                    (0.5, 0.1, 0.9, 0.3)], "jobs": 4},
            "_served_lines": {"/host:CPU#0:python3": handler,
                              "/host:CPU#1:python3": executor},
            "_capture": cap, "trace_dir": "x", "window_wall_s": 0.33,
            "chunk_program": "chunk"}


def test_the_reader_on_a_built_capture(capsys):
    run = built_run()
    # job - run, the two executed: (110 - 95 + 210 - 195) / 2
    assert served.read(run, "overhead_ms") == pytest.approx(15.0)
    assert served.read(run, "respond_ms") == pytest.approx(5.0)
    out = capsys.readouterr().out
    assert "3, of them 2 executed" in out and "a hit, ms: job 2.000" in out
    assert served.read(run, "turnaround_p95") == 0.9
    # Steady span 10..310 ms; outside the runs lie 100..126 ms, of which
    # the replay's 2 ms are busy: 24 of 300 ms.
    assert served.read(run, "idle_between_jobs") == pytest.approx(8.0)
    with pytest.raises(ValueError):
        served.read(run, "nonsense")


def test_the_reader_reads_nothing_elsewhere():
    """Another kind's run, a capture cut short, a program without the
    spans (the parent): None, and no exception."""
    for mode in ("overhead_ms", "respond_ms", "turnaround_p95",
                 "device_idle", "idle_between_jobs"):
        assert served.read({"events": [], "trace_dir": None}, mode) is None
    run = built_run()
    run["window_wall_s"] = 3.0
    assert served.read(run, "idle_between_jobs") is None
    run = built_run()
    run["_served_lines"] = {"/host:CPU#0:python3": [
        e for e in run["_served_lines"]["/host:CPU#1:python3"]
        if e[0] in ("run", "chunk", "replay")]}
    run["served"]["job_ends"] = []
    for mode in ("overhead_ms", "respond_ms", "turnaround_p95",
                 "idle_between_jobs"):
        assert served.read(run, mode) is None


# -- the new kind, on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def served_root(tmp_path_factory, manifest):
    """``conftest.rehearsal_root``'s recipe for a throw-away cell of the
    new kind: new files and new entries only; the deep class at small
    pools to level 5, the canary and the hunts as they stand."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "raft_tla_tpu"), root / "raft_tla_tpu")
    bench = root / "benchmark"
    config = json.loads((bench / "configs" / "served3.json").read_text())
    deep = config["programs"]["deep"]
    deep["request"].update(max_diameter=5, batch=256,
                           queue_capacity=1 << 14, seen_capacity=1 << 16)
    deep["expect"].update(max_diameter=5, distinct=2300, generated=5616)
    config["name"] = "tiny-served"
    (bench / "configs" / "tiny-served.json").write_text(json.dumps(config))
    mix = json.loads((bench / "traffic" / "tenants3-closed.json").read_text())
    mix["tenants"][2]["jobs"][0].update(
        max_diameter=5, batch=256, queue_capacity=1 << 14,
        seen_capacity=1 << 16)
    (bench / "traffic" / "tenants3-tiny.json").write_text(json.dumps(mix))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny-served", "source": "test",
                         "file": "benchmark/configs/tiny-served.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "tiny-served-mix",
                           "config": "tiny-served",
                           "traffic": "tenants3-tiny", "chips": 1,
                           "why": "throw-away"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


@pytest.fixture(scope="module")
def traced(served_root):
    return run_cell(served_root, *ARGS, "--trace", "1")


def test_the_cell_rehearsed_on_the_cpu(traced):
    rc, line, out = traced
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, [
        t for t in out.splitlines() if t.endswith(" FAIL")]
    assert line["failed"] == 0 and line["attempted"] >= 100
    assert line["device"]["platform"] == "cpu"
    assert "verdict_s" not in line["metrics"]       # a traced line
    assert "order of starts: the journal's equals the reference's" in out
    assert "jobs answered cached: true are the reference's hits" in out
    assert "the journal replays to the registry the manager holds" in out
    assert "from the result cache" in out


def test_the_traced_line_carries_what_needs_no_device(traced):
    """The spans are on the CPU's capture too; the two metrics that need
    the device's plane find none there and are left out."""
    _rc, line, out = traced
    metrics = line["metrics"]
    assert metrics["serve_overhead_ms"]["value"] > 0
    assert metrics["respond_ms"]["value"] > 0
    assert metrics["turnaround_p95_s"]["value"] > 0
    assert "a job executed, ms: job" in out
    for name in ("device_idle.served", "idle.between_jobs"):
        assert name not in metrics
    for name in ("build_s", "setup.ready_s", "setup.runs_s",
                 "setup.outside_s"):
        assert name in metrics, name


@pytest.mark.parametrize("control, catches", [
    ("pick_newest", "order of starts: the journal's equals the "
                    "reference's"),
    ("cache_fresh", "jobs answered cached: true are the reference's hits"),
    ("drop_unjournaled", "acknowledged jobs whose journal lines are not"),
])
def test_the_controls_read_incorrect(served_root, control, catches):
    rc, line, out = run_cell(
        served_root, control, "--", *ARGS, "--trace", "0",
        script="benchmark/tests/controls_served.py")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] >= 1
    failed = [t for t in out.splitlines() if t.endswith(" FAIL")]
    assert any(catches in t for t in failed), failed[:5]


def test_a_server_without_the_blocking_result_fails_at_once(served_root,
                                                            tmp_path):
    """The parent's server answers ``ping`` without ``"wait"``: asked for
    the cell the run exits 4 before it builds anything, and never
    polls."""
    patch = tmp_path / "sitecustomize.py"
    patch.write_text(
        "import raft_tla_tpu.server as s\n"
        "_orig = s.handle_request\n"
        "def _old(req, manager=None):\n"
        "    resp = _orig(req, manager)\n"
        "    if req.get('op') == 'ping':\n"
        "        resp.pop('wait', None)\n"
        "    return resp\n"
        "s.handle_request = _old\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           str(served_root)]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS, "--trace", "0",
         "--rehearsal"], cwd=served_root, capture_output=True, text=True,
        timeout=300, env=env)
    assert p.returncode == 4, p.stderr[-2000:]
    assert "has no blocking 'result'" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
