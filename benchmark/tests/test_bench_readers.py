"""The reductions: phases arithmetic, the byte count behind
``chunk_roofline`` by hand, and the trace reduction on hand-made planes
and on a small recorded capture."""

import json
import os

import pytest

import bench_lib as lib
import roofline

HERE = os.path.dirname(os.path.abspath(__file__))
phases = lib.load_module("readers", "phases")
xplane = lib.load_module("readers", "xplane")


# -- phases -----------------------------------------------------------------

def test_host_share_and_batch_ms():
    run = {"phases": {"stats_fetch": 24.0, "chunk": 0.3, "trace_flush": 3.0},
           "window_wall_s": 30.0, "parents_expanded": 4096 * 100,
           "batch": 4096,
           "spans": {"make_engine": 2.5, "warmup": 6.0, "walk": 20.0}}
    assert phases.read(run, mode="host_share") == pytest.approx(20.0)
    assert phases.read(run, mode="per_batch_ms") == pytest.approx(243.0)
    assert phases.read(run, mode="span_sum",
                       spans=["make_engine", "warmup"]) == pytest.approx(8.5)


def test_a_reader_that_finds_nothing_returns_nothing():
    assert phases.read({"phases": {}}, mode="host_share") is None
    assert phases.read({"phases": {"stats_fetch": 1.0},
                        "window_wall_s": 2.0}, mode="per_batch_ms") is None
    assert phases.read({"spans": {"warmup": 1.0}}, mode="span_sum",
                       spans=["make_engine", "warmup"]) is None
    assert xplane.read({"trace_dir": None}, mode="idle_share") is None
    counters = lib.load_module("readers", "counters")
    assert counters.read({}, key="seen_load_pct") is None
    assert counters.read({"counters": {"seen_load_pct": 18.2}},
                         key="seen_load_pct") == 18.2


# -- the byte count, by hand on mcraft3's shapes ------------------------------

def test_batch_bytes_by_hand_on_mcraft3_shapes():
    # B=2048 parents of 473 bytes; level 10 of the pinned profile expands
    # 548,904 parents into 12,301,488 generated and 4,235,973 new states.
    gen, new = 12301488 / 548904, 4235973 / 548904
    by_hand = (2048 * 473                      # parents read
               + 2048 * gen * 8                # one key probe per successor
               + 2048 * new * (8 + 473 + 20))  # key, row, trace record
    assert roofline.batch_bytes(2048, 473, gen, new) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(9.25e6, rel=0.01)
    # 11.3 us at the table's 819 GB/s
    assert roofline.least_batch_seconds(
        2048, 473, gen, new, 819e9) == pytest.approx(1.13e-5, rel=0.01)


def test_unknown_device_kind_is_an_error():
    peaks = lib.load_json("peaks.json")
    assert roofline.peak_for("TPU v5 lite", peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak_for("TPU v9", peaks)


# -- the trace reduction ------------------------------------------------------

def planes_by_hand():
    us = 1000
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_chunk(1)", 100 * us, 400 * us),
                            ("jit_chunk(1)", 700 * us, 300 * us),
                            ("jit_other(2)", 0, 50 * us)],
            "XLA Ops": [
                ("copy.0", 0, 50 * us),                  # before the span
                ("while.1", 100 * us, 400 * us),         # runs the fusions
                ("fusion.1", 120 * us, 100 * us),
                ("fusion.2", 250 * us, 200 * us),
                ("fusion.1", 700 * us, 300 * us),        # no loop: 0 batches
            ]},
        # the driver starts the command as python3, and the line is named so
        "/host:CPU": {"python3": [("stats_fetch", 90 * us, 420 * us),
                                  ("trace_flush", 510 * us, 180 * us)],
                      "tfrt-queue/7": [("Unrelated", 0, 2000 * us)]},
    }


def test_reduce_busy_idle_self_time_and_gaps():
    red = xplane.reduce(planes_by_hand(), "chunk")
    # steady span: first chunk start (100 us) to last chunk end (1000 us)
    assert red["window_s"] == pytest.approx(900e-6)
    # busy: [100, 500] under the while, [700, 1000]
    assert red["busy_s"] == pytest.approx(700e-6)
    assert red["chunk_calls"] == 2
    assert red["batches"] == 1       # one pass of while.1's body
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(400e-6)
    assert ops["fusion.2"] == pytest.approx(200e-6)
    assert ops["while.1"] == pytest.approx(100e-6)    # its own time only
    assert "copy.0" not in ops                        # outside the span
    # one gap, 500..700 us, mostly under trace_flush
    assert red["idle_gaps"] == [["trace_flush", pytest.approx(200e-6)]]
    run = {"trace_dir": "x", "_xplane": red, "window_wall_s": 950e-6}
    assert xplane.read(run, mode="idle_share") == pytest.approx(
        100 * (1 - 700 / 900))
    # one pass of the loop advanced 1,500 of a batch of 2,048 parents
    run.update(parents_expanded=1500, batch=2048)
    assert xplane.read(run, mode="batch_fill") == pytest.approx(
        100 * 1500 / 2048)


def test_loop_iterations_counts_passes_of_the_outermost_loop():
    us = 1000
    body = lambda t: [("fusion.a", t, 10 * us),                  # noqa: E731
                      ("while.inner", t + 10 * us, 30 * us),
                      ("fusion.x", t + 12 * us, 5 * us),   # in the inner loop
                      ("fusion.x", t + 20 * us, 5 * us),
                      ("fusion.b", t + 45 * us, 10 * us)]
    ops = [("copy.0", 0, 5 * us), ("while.main", 10 * us, 300 * us)]
    for k in range(5):
        ops += body(10 * us + k * 60 * us + us)
    ops += [("fusion.rare", 10 * us + 299 * us, us // 2),    # conditional
            ("concatenate.9", 320 * us, 5 * us)]
    assert xplane.loop_iterations(ops) == 5
    assert xplane.loop_iterations([("while.main", 0, 8 * us)]) == 0
    assert xplane.loop_iterations([]) == 0


def test_roofline_share_from_a_reduction():
    red = {"busy_s": 2.0, "window_s": 2.5, "batches": 55}
    run = {"trace_dir": "x", "_xplane": red, "device_kind": "TPU v5 lite",
           "window_wall_s": 2.6,
           "parents_expanded": 2048 * 40, "batch": 2048, "row_bytes": 473,
           "new_generated": 2048 * 40 * 20, "new_distinct": 2048 * 40 * 8}
    least = roofline.least_batch_seconds(2048, 473, 20, 8, 819e9)
    assert xplane.read(run, mode="roofline") == pytest.approx(
        100 * least / (2.0 / 40))
    assert xplane.read(run, mode="idle_share") == pytest.approx(20.0)
    # a capture that holds only a prefix of the window: nothing to read,
    # the idle share of the prefix included
    run["window_wall_s"] = 20.0
    for mode in ("roofline", "batch_fill", "idle_share"):
        assert xplane.read(run, mode=mode) is None


def test_no_device_plane_gives_nothing():
    planes = {"/host:CPU": planes_by_hand()["/host:CPU"]}
    assert xplane.reduce(planes, "chunk") is None


def test_reduction_of_the_recorded_capture():
    """A piece of a real capture of mcraft3-deep on a TPU v5 lite (three
    chunk calls), kept beside this test; the expected numbers were worked
    out from the same events with a plain sweep (below), not with the
    reader."""
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path, encoding="utf-8") as f:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in ls.items()}
                  for p, ls in json.load(f).items()}
    red = xplane.reduce(planes, "chunk")
    dev = planes["/device:TPU:0"]
    mods = [m for m in dev["XLA Modules"] if "chunk" in m[0]]
    lo = min(m[1] for m in mods)
    hi = max(m[1] + m[2] for m in mods)
    # the plain sweep: mark every nanosecond boundary
    marks = []
    for _n, s, d in dev["XLA Ops"]:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(marks):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert red["chunk_calls"] == len(mods) == 3
    # two zero-trip warm-up calls, then the resumed run's first call: one
    # batch (the engine probes a level with a single batch)
    assert red["batches"] == 1
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(s for _n, s in red["device_ops"]) == pytest.approx(
        red["busy_s"], rel=1e-6)
