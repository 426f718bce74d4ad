"""``idle.level_close.verdict`` (``layer_metrics/idle.level_close.verdict
.json`` through ``readers/spans.py``, mode ``idle``): the device-idle time
under ``raft.trace_flush`` and ``raft.level_end``, by hand on a small
capture of a level boundary in both orders the one-chip loop has had.

Times in ms.  Level 1's last call ran on the device in [100, 200); its
statistics reached the host at 200, its accounting took [200, 210), the
device half of its flush [210, 215).  Then

  drained (until PR 53): the flush's host half [215, 235), ``level_end``
      [235, 245), both inside level 1's span, with the device empty; level
      2 opens at 250 and its first call is dispatched in [250, 260), on
      the device [260, 360).
  riding (since PR 53): level 2 opens at 220, its first call is dispatched
      in [220, 230) and runs on the device [230, 330); the flush's host
      half [230, 250) and ``level_end`` [250, 260) lie inside level 2's
      span behind that dispatch, under a busy device.
"""

import pytest

import bench_lib as lib

spans = lib.load_module("readers", "spans")

MS = 1_000_000


def boundary(riding: bool) -> dict:
    open2 = 220 if riding else 250
    dev2 = open2 + 10
    close = dev2 if riding else 215
    host = [["run", 50, 400, {"run": 1}],
            ["level", 60, open2 - 60, {"run": 1, "level": 1}],
            ["chunk", 90, 10, {"run": 1, "call": 1}],
            ["stats_fetch", 100, 100, {"run": 1}],
            ["account", 200, 10, {"run": 1, "call": 1, "passes": 3}],
            ["trace_flush", 210, 5, {"run": 1}],
            ["level", open2, 150, {"run": 1, "level": 2}],
            ["chunk", open2, 10, {"run": 1, "call": 2}],
            ["trace_flush", close, 20, {"run": 1}],
            ["level_end", close + 20, 10, {"run": 1}],
            ["stats_fetch", dev2 + 30, 70, {"run": 1}],
            ["account", dev2 + 100, 5, {"run": 1, "call": 2, "passes": 1}]]
    ops = [[0, 100, 100], [0, dev2, 100]]
    ms = lambda rows: [[r[0], r[1] * MS, r[2] * MS] + r[3:]  # noqa: E731
                       for r in rows]
    return {"host": ms(host),
            "modules": ms([["jit_chunk(1)", 100, 100],
                           ["jit_chunk(1)", dev2, 100]]),
            "ops": ms(ops), "op_names": ["while.1"],
            "op_paths": ["jit(chunk)/while:"]}


def read(cap, window_wall_s):
    spec = lib.load_json("layer_metrics", "idle.level_close.verdict.json")
    assert spec["reader"] == "spans"
    run = {"_capture": cap, "window_wall_s": window_wall_s,
           "chunk_program": "chunk"}
    return spans.read(run, **spec["args"]), spans.idle_by_span(cap)


def test_a_drained_close_is_idle_under_its_two_spans():
    got, tab = read(boundary(riding=False), 0.26)
    # steady span [100, 360); idle [200, 260)
    assert tab["span_ns"] == 260 * MS and tab["idle_ns"] == 60 * MS
    assert tab["innermost"] == {
        "account": 10 * MS, "trace_flush": 25 * MS, "level_end": 10 * MS,
        "level": 5 * MS,            # [245, 250), the loop between spans
        "chunk": 10 * MS}           # the next level's dispatch
    assert got == pytest.approx(100 * 35 / 260)


def test_a_close_behind_a_dispatched_call_leaves_only_the_fetch_enqueue():
    got, tab = read(boundary(riding=True), 0.23)
    # steady span [100, 330); idle [200, 230)
    assert tab["span_ns"] == 230 * MS and tab["idle_ns"] == 30 * MS
    assert tab["innermost"] == {
        "account": 10 * MS, "trace_flush": 5 * MS,
        "level": 5 * MS,            # [215, 220)
        "chunk": 10 * MS}
    assert got == pytest.approx(100 * 5 / 230)
    # The two spans are leaves beside one another: nothing counts twice.
    assert tab["under"]["trace_flush"] + tab["under"].get("level_end", 0) \
        == tab["innermost"]["trace_flush"]


def test_the_metric_is_listed_where_the_one_chip_loop_gives_verdicts(
        manifest):
    m = next(m for m in manifest["per_layer"]
             if m["name"] == "idle.level_close.verdict")
    assert m == {"name": "idle.level_close.verdict", "unit": "%",
                 "better": "lower", "source": "device_trace",
                 "layer": "host loop", "moves": "verdict_s",
                 "workloads": ["mcraft3-noleader"]}
    assert manifest["per_layer"][-1] is m


def test_a_program_without_spans_gives_nothing():
    cap = boundary(riding=True)
    cap["host"] = []
    assert read(cap, 0.23)[0] is None
