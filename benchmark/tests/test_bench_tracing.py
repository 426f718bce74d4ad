"""The readers of the program's own spans, stage names and counters
(``readers/spans.py``, ``stages.py``, ``events.py``): the arithmetic by
hand on small captures made here, the same on a capture recorded on the
chip, and one CPU rehearsal whose line carries the counter metrics."""

import copy
import json
import os

import pytest

import bench_lib as lib
from bench_helpers import run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
spans = lib.load_module("readers", "spans")
stages = lib.load_module("readers", "stages")
events = lib.load_module("readers", "events")

MS = 1_000_000


def capture():
    """Two chunk calls of one run, by hand (times in ms).

    call 1, 3 passes, device [100, 200): a ``while`` [100, 196) whose body
    ran masks [100, 140), insert [140, 170), an unnamed copy [170, 174)
    and masks again [174, 190) -- 6 ms of the loop ran nothing -- then the
    epilogue [196, 200).
    call 2, 1 pass, device [300, 340): masks [300, 330), record [330, 340).
    Host, all inside level inside run: chunk [90, 100), stats_fetch
    [100, 200) waiting for the device, then while the device idles
    account [200, 210), trace_flush [210, 280), nothing [280, 290) (the
    level's own time), chunk [290, 300) dispatching call 2.
    """
    body = "jit(chunk)/while/body/"
    paths = ["jit(chunk)/while:",                                  # 0
             body + "masks/vmap()/and:",                           # 1
             body + "insert/jit(insert)/sort:",                    # 2
             "",                                                   # 3 copy
             "jit(chunk)/epilogue/concatenate:",                   # 4
             body + "record/scatter:"]                             # 5
    names = ["while.1", "fusion.1", "sort.2", "copy.3", "fusion.4",
             "scatter.5"]
    ops = [[0, 100, 96], [1, 100, 40], [2, 140, 30], [3, 170, 4],
           [1, 174, 16], [4, 196, 4],
           [1, 300, 30], [5, 330, 10]]
    host = [["run", 50, 400, {"run": 1}],
            ["level", 60, 380, {"run": 1, "level": 3}],
            ["chunk", 90, 10, {"run": 1, "call": 1}],
            ["stats_fetch", 100, 100, {"run": 1}],
            ["account", 200, 10, {"run": 1, "call": 1, "passes": 3}],
            ["trace_flush", 210, 70, {"run": 1}],
            ["chunk", 290, 10, {"run": 1, "call": 2}],
            ["stats_fetch", 300, 40, {"run": 1}],
            ["account", 340, 5, {"run": 1, "call": 2, "passes": 1}]]
    ms = lambda rows, cols: [  # noqa: E731
        [v * MS if i in cols else v for i, v in enumerate(r)] for r in rows]
    return {"host": ms(host, (1, 2)),
            "modules": ms([["jit_chunk(1)", 100, 100],
                           ["jit_chunk(1)", 300, 40]], (1, 2)),
            "ops": ms(ops, (1, 2)), "op_names": names, "op_paths": paths}


def run_with(cap, **kw):
    return {"_capture": cap, "window_wall_s": 0.26, "chunk_program": "chunk",
            **kw}


# -- stages -----------------------------------------------------------------

def test_stage_of_reads_the_first_scope_that_names_a_stage():
    body = "jit(chunk)/while/body/"
    assert stages.stage_of(body + "masks/vmap(vmap())/and:") == "masks"
    # a fusion carries what its operations' paths share
    assert stages.stage_of(body + "slice") == "slice"
    # the outermost name wins, and a primitive is not a stage
    assert stages.stage_of(body + "construct/insert/slice:") == "construct"
    assert stages.stage_of(body + "slice:") is None
    assert stages.stage_of("jit(chunk)/while/cond/lt:") is None
    assert stages.stage_of("jit(chunk)/while:") is None
    assert stages.stage_of("") is None


def test_stage_self_time_by_hand():
    tab = stages.table(capture())
    assert (tab["calls"], tab["passes"]) == (2, 4)
    assert tab["device_ns"] == 140 * MS          # 100 + 40, all busy
    # masks 40 + 16 + 30; insert 30; record 10; other = the while's own
    # 6 + the copy's 4 + the epilogue's 4
    assert tab["stage_ns"] == {"masks": 86 * MS, "insert": 30 * MS,
                               "record": 10 * MS, "other": 14 * MS}
    assert tab["named_ns"] == 130 * MS           # all but while and copy
    assert tab["leaves"] == 7                    # every event but the while
    run = run_with(capture())
    assert stages.read(run, "stage_ms", "masks") == pytest.approx(86 / 4)
    assert stages.read(run, "stage_ms", "compact") == 0.0
    assert stages.read(run, "stage_ms", "other") == pytest.approx(14 / 4)
    assert stages.read(run, "launches") == pytest.approx(7 / 4)
    # The stages partition the calls' device time.
    parts = [stages.read(run, "stage_ms", s)
             for s in stages.STAGES + ("other",)]
    assert sum(parts) == pytest.approx(140 / 4)


def test_a_call_cut_by_the_end_of_the_capture_is_left_out():
    cap = capture()
    # The profiler's buffer filled 15 ms into call 2: its later operation
    # is gone, its module event is not.
    cap["ops"] = cap["ops"][:-1]
    cap["ops"][-1][2] = 15 * MS
    tab = stages.table(cap)
    assert (tab["calls"], tab["passes"]) == (1, 3)
    assert tab["device_ns"] == 100 * MS
    # Three passes are too few to report (MIN_PASSES).
    assert stages.read(run_with(cap), "stage_ms", "masks") is None
    # Nor is a call whose account span the capture lost.
    cap = capture()
    cap["host"] = [e for e in cap["host"]
                   if not (e[0] == "account" and e[3]["call"] == 2)]
    assert stages.table(cap)["passes"] == 3


def test_a_seen_set_growth_s_extra_execution_is_not_a_call():
    cap = capture()
    # _grow_precompiled runs the chunk program once more, for no pass,
    # after call 1's raft.chunk span and before call 2's.
    cap["modules"].insert(1, ["jit_chunk(1)", 250 * MS, 1 * MS])
    tab = stages.table(cap)
    assert (tab["calls"], tab["passes"]) == (2, 4)


def test_unnamed_executable_is_not_reported():
    cap = capture()
    # An executable from a compile cache filled before the names existed.
    cap["op_paths"] = [
        p.replace("masks/", "").replace("insert/jit(insert)/",
                                         "jit(insert)/")
        for p in cap["op_paths"]]
    run = run_with(cap)
    assert stages.table(cap)["named_ns"] == 14 * MS
    assert stages.read(run, "stage_ms", "masks") is None
    assert stages.read(run, "launches") is None


# -- spans ------------------------------------------------------------------

def test_idle_goes_to_the_innermost_span_open_on_the_host():
    tab = spans.idle_by_span(capture())
    # steady span [100, 340); busy [100, 200) and [300, 340)
    assert tab["span_ns"] == 240 * MS and tab["idle_ns"] == 100 * MS
    assert tab["innermost"] == {"account": 10 * MS, "trace_flush": 70 * MS,
                                "level": 10 * MS, "chunk": 10 * MS}
    assert tab["under"]["run"] == tab["under"]["level"] == 100 * MS
    run = run_with(capture())
    assert spans.read(run, "idle", spans=["trace_flush"]) == pytest.approx(
        100 * 70 / 240)
    assert spans.read(run, "idle", spans=["run", "level", "replay"],
                      self_only=True) == pytest.approx(100 * 10 / 240)
    # every part of the idle time is somewhere
    assert sum(tab["innermost"].values()) == tab["idle_ns"]


def test_idle_between_runs_is_outside_every_span():
    cap = capture()
    cap["host"] = [e for e in cap["host"] if e[1] < 250 * MS]
    by_name = {e[0]: e for e in cap["host"]}
    by_name["run"][2] = 200 * MS                # run [50, 250)
    by_name["level"][2] = 180 * MS              # level [60, 240)
    by_name["trace_flush"][2] = 30 * MS         # trace_flush [210, 240)
    tab = spans.idle_by_span(cap)
    assert tab["innermost"] == {
        "account": 10 * MS, "trace_flush": 30 * MS,
        "run": 10 * MS,                         # [240, 250)
        "outside": 50 * MS}                     # [250, 300)


def test_a_capture_that_does_not_cover_the_window_gives_no_idle_share():
    run = run_with(capture(), window_wall_s=1.0)       # holds 0.24 s of it
    assert spans.read(run, "idle", spans=["trace_flush"]) is None
    assert spans.read(run, "per_run_ms", spans=["account"]) is None


def test_span_time_per_run_and_per_call():
    run = run_with(capture())
    assert spans.read(run, "per_run_ms",
                      spans=["account", "trace_flush"]) == pytest.approx(85)
    run = {"phases": {"trace_flush": 0.5}, "events": [
        {"event": "run_end", "chunk_calls": 20},
        {"event": "run_end", "chunk_calls": 5}]}
    assert spans.read(run, "per_call_ms",
                      spans=["trace_flush"]) == pytest.approx(20.0)


def test_readers_find_nothing_in_a_program_without_spans_or_counters():
    parent = {"_capture": {**capture(), "host": []}, "window_wall_s": 0.26,
              "phases": {"trace_flush": 1.0},
              "events": [{"event": "run_end", "distinct": 5}], "batch": 256}
    for mode, args in (("idle", {"spans": ["trace_flush"]}),
                       ("per_run_ms", {"spans": ["replay"]}),
                       ("per_call_ms", {"spans": ["trace_flush"]})):
        assert spans.read(parent, mode, **args) is None
    assert stages.read(parent, "stage_ms", "masks") is None
    assert stages.read(parent, "launches") is None
    for mode in ("pass_fill", "passes_per_call", "calls_per_run",
                 "compile_s"):
        assert events.read(parent, mode) is None
    assert spans.read({"trace_dir": None}, "idle", spans=["run"]) is None
    assert stages.read({"trace_dir": None}, "launches") is None


def test_scope_paths_are_read_from_the_event_metadata_of_the_file(tmp_path):
    """``ProfileData`` shows an event's own stats; the scope path is a stat
    of its metadata, so the reader decodes that table from the file."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(number, value):
        if isinstance(value, int):
            return varint(number << 3) + varint(value)
        return varint(number << 3 | 2) + varint(len(value)) + value

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = (field(5, entry(7, field(1, 7) + field(2, b"tf_op")))
                 + field(5, entry(8, field(1, 8) + field(2, b"hlo_category")))
                 + field(5, entry(9, field(1, 9) + field(2, b"custom fusion"))))
    op = (field(1, 3) + field(2, b"%fusion.7 = u32[8]{0} fusion(...)")
          + field(5, field(1, 7) + field(5, b"jit(chunk)/while/body/"
                                            b"masks/and:"))
          + field(5, field(1, 8) + field(7, 9))
          + field(5, field(1, 8) + varint(3 << 3 | 1) + b"\0" * 8))
    device = (field(1, 1) + field(2, b"/device:TPU:0")
              + field(3, field(2, b"XLA Ops") + field(4, field(1, 3)))
              + field(4, entry(3, op)) + stat_meta)
    other = field(1, 2) + field(2, b"/host:CPU") + field(4, entry(3, op))
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(field(1, other) + field(1, device))
    got = spans.metadata_stats(str(path), "/device:TPU:0")
    assert got == {"%fusion.7 = u32[8]{0} fusion(...)": {
        "tf_op": "jit(chunk)/while/body/masks/and:",
        "hlo_category": "custom fusion"}}
    assert spans.scope_path(got["%fusion.7 = u32[8]{0} fusion(...)"]) == (
        "jit(chunk)/while/body/masks/and:")
    assert spans.scope_path({"hlo_category": "custom fusion"}) == ""
    assert spans.metadata_stats(str(path), "/device:TPU:1") == {}


# -- events -----------------------------------------------------------------

def test_counter_metrics_by_hand(capsys):
    run = {"batch": 256, "events": [
        {"event": "run_start"},
        {"event": "run_end", "chunk_calls": 10, "ingest_calls": 1,
         "passes": 40, "parents_expanded": 7680,
         "compiles": {"trace_flush": [30, 2.5], "chunk": [1, 0.25]}},
        {"event": "run_end", "chunk_calls": 14, "ingest_calls": 1,
         "passes": 20, "parents_expanded": 3840,
         "compiles": {"trace_flush": [3, 0.25]}}]}
    assert events.read(run, "pass_fill") == pytest.approx(
        100 * 11520 / (60 * 256))
    assert events.read(run, "passes_per_call") == pytest.approx(2.5)
    assert events.read(run, "calls_per_run") == pytest.approx(13.0)
    assert events.read(run, "compile_s") == pytest.approx(3.0)
    assert "trace_flush 33 2.750, chunk 1 0.250" in capsys.readouterr().out


# -- a capture recorded on the chip ------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """A check of ``configs/MCraft_noleader.cfg`` stopped at diameter 4, on
    one TPU v5 lite (my chip run, PR 27): four chunk calls of one pass each
    at the cfg's own batch of 256, as ``spans.load`` returned the capture
    (times from the first span, scope paths cut to seven components;
    ``scripts/record_capture.py`` records it)."""
    with open(os.path.join(HERE, "data", "capture_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_recorded_capture_names_the_stages(recorded):
    tab = stages.table(copy.deepcopy(recorded))
    assert (tab["calls"], tab["passes"]) == (4, 4)
    accounts = [e[3] for e in recorded["host"] if e[0] == "account"]
    assert [set(a) for a in accounts] == [{"run", "call", "passes"}] * 4
    assert tab["named_ns"] >= 0.9 * tab["device_ns"]
    assert sum(tab["stage_ns"].values()) == tab["device_ns"]
    # Every stage of a pass left operations on the device.
    assert set(stages.STAGES) <= set(tab["stage_ns"])
    assert tab["leaves"] / tab["passes"] > 100


def test_recorded_capture_idle_is_all_attributed(recorded):
    tab = spans.idle_by_span(copy.deepcopy(recorded))
    assert tab["idle_ns"] > 0
    assert sum(tab["innermost"].values()) == tab["idle_ns"]
    assert {"stats_fetch", "chunk"} & set(tab["innermost"])


# -- the command, on the CPU --------------------------------------------------

def test_traced_rehearsal_line_carries_the_counter_metrics(rehearsal_root):
    rc, line, out = run_cell(rehearsal_root, "--workload", "tiny3-deep",
                             "--seed", "3000000019", "--seconds", "3",
                             "--trace", "1")
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    got = line["metrics"]
    # The CPU capture has no device plane, so nothing that needs one is
    # there; what the engine counts and times itself is.
    assert {"pass_fill", "passes_per_call", "window_compile_s",
            "flush_ms"} <= set(got)
    assert not [m for m in got if m.startswith(("stage_ms.", "idle."))]
    assert 0 < got["pass_fill"]["value"] <= 100
    assert got["passes_per_call"]["value"] >= 1
    assert got["flush_ms"]["value"] > 0
    assert "window compiles by span" in out
