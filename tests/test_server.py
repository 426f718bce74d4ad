"""Checker-service protocol tests (SURVEY §2.4 R10 delegation endpoint).

A live server on an ephemeral port, a socket client speaking the same
newline-delimited JSON the TLC override (native/tlc_override/
TPUraftOverride.java) sends.  Counts are asserted against the pinned
MCraft_bounded oracle profile, so the service is checked end-to-end
through the real engine, not a stub.
"""

import json
import os
import socket
import threading

import pytest

from raft_tla_tpu import server as srv_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def server():
    srv = srv_mod.serve(port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address
    srv.shutdown()


def roundtrip(addr, req: dict) -> dict:
    with socket.create_connection(addr, timeout=600) as s:
        s.sendall((json.dumps(req) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)


def test_ping(server):
    resp = roundtrip(server, {"op": "ping"})
    assert resp["ok"] is True
    assert resp["platform"] == "cpu"


def test_check_matches_pinned_profile(server):
    resp = roundtrip(server, {
        "op": "check",
        "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
        "batch": 128, "max_diameter": 3,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert resp["ok"] is True, resp
    # Pinned oracle prefix (BASELINE.md §b): cumulative 113 distinct /
    # 222 generated through level 3.
    assert resp["distinct"] == 113
    assert resp["generated"] == 222
    assert resp["diameter"] == 3
    assert resp["levels"] == [1, 3, 18, 79]
    assert resp["violation"] is None


def test_check_engine_stays_warm_and_budgets_refresh(server):
    # Second request with a DIFFERENT diameter budget must reuse the
    # compiled engine but honor the new budget — budgets are host-side
    # and per-request, not baked into the cache entry.
    base = {"op": "check",
            "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
            "batch": 128,
            "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
            "check_deadlock": False}
    r1 = roundtrip(server, dict(base, max_diameter=3))
    assert r1["ok"] and r1["distinct"] == 113
    r2 = roundtrip(server, dict(base, max_diameter=4))
    assert r2["ok"] and r2["distinct"] == 527     # pinned L4 cumulative
    assert r2["levels"] == [1, 3, 18, 79, 318]


def test_a_cached_engine_keeps_the_capacity_its_last_check_grew_to(server):
    """Two ``check`` requests of the canary on one cached engine: the
    first grows the cfg's 65,536-slot table twice, the second starts at
    what it grew to, pays no growth and answers the same."""
    req = {"op": "check", "trace": True,
           "cfg": os.path.join(REPO, "configs/MCraft_noleader.cfg")}
    before = roundtrip(server, {"op": "stats"})["metrics"]["counters"]
    r1 = roundtrip(server, req)
    r2 = roundtrip(server, req)
    assert r1["ok"] and r2["ok"], (r1, r2)
    assert [c for c, _s in r1["growth_stalls"]] == [1 << 17, 1 << 18]
    assert r2["growth_stalls"] == [] and "grow" not in r2["phases"]
    for key in ("distinct", "generated", "levels", "diameter",
                "action_counts", "stop_reason"):
        assert r2[key] == r1[key], key
    assert r1["stop_reason"] == "violation" and r1["diameter"] == 8
    assert r2["violation"]["invariant"] == "NoLeaderElected"
    assert [s["action"] for s in r2["violation"]["trace"]] == [
        s["action"] for s in r1["violation"]["trace"]]
    assert len(r2["violation"]["trace"]) == 10
    seen = r2["report"]["seen_set"]
    assert (seen["start_capacity"], seen["configured_capacity"]) == (
        1 << 18, 1 << 16)
    after = roundtrip(server, {"op": "stats"})["metrics"]["counters"]
    assert (after.get("engine/seen_capacity_kept", 0)
            - before.get("engine/seen_capacity_kept", 0)) == 1


def test_cfg_text_and_content_identity(server):
    # cfg_text requests work, and the engine cache keys on CONTENT: two
    # different texts (different MaxTerm) must give different models.
    with open(os.path.join(REPO, "configs/MCraft_bounded.cfg")) as f:
        text = f.read()
    r1 = roundtrip(server, {
        "op": "check", "cfg_text": text, "batch": 128, "max_diameter": 4,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert r1["ok"] and r1["distinct"] == 527     # pinned L4 cumulative
    text2 = text.replace("MaxTerm = 3", "MaxTerm = 2")
    assert text2 != text
    r2 = roundtrip(server, {
        "op": "check", "cfg_text": text2, "batch": 128, "max_diameter": 4,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert r2["ok"]
    assert r2["distinct"] < r1["distinct"]        # tighter term bound


def test_backend_directive_precedence(server):
    # Precedence: request field > cfg "\* TPU:" directive > default.  A
    # cfg_text carrying a BATCH directive must drive the engine batch
    # when the request leaves it unset.
    with open(os.path.join(REPO, "configs/MCraft_bounded.cfg")) as f:
        text = f.read() + "\n\\* TPU: BATCH = 64\n"
    r = roundtrip(server, {
        "op": "check", "cfg_text": text, "max_diameter": 2,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert r["ok"] and r["batch"] == 64 and r["distinct"] == 22
    r2 = roundtrip(server, {
        "op": "check", "cfg_text": text, "batch": 32, "max_diameter": 2,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert r2["ok"] and r2["batch"] == 32 and r2["distinct"] == 22


def test_simulate(server):
    resp = roundtrip(server, {
        "op": "simulate",
        "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
        "batch": 64, "depth": 16, "num_steps": 256})
    assert resp["ok"] is True, resp
    assert resp["steps"] >= 256
    assert resp["traces"] >= 64
    assert resp["violation"] is None


def test_check_mesh_engine(server):
    # engine="mesh" routes through MeshBFSEngine on the virtual 8-device
    # CPU mesh (conftest) and must produce the same pinned counts.
    resp = roundtrip(server, {
        "op": "check",
        "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
        "engine": "mesh", "batch": 16, "max_diameter": 3,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert resp["ok"] is True, resp
    assert resp["distinct"] == 113
    assert resp["levels"] == [1, 3, 18, 79]


def test_bad_request(server):
    resp = roundtrip(server, {"op": "nope"})
    assert resp["ok"] is False
    resp = roundtrip(server, {"op": "check"})
    assert resp["ok"] is False and "cfg" in resp["error"]


def test_metrics_op_parses_and_agrees_with_stats(server):
    """ISSUE 9 acceptance: the metrics op's output is valid Prometheus
    text exposition and agrees with the stats op's counters taken in
    the same instant (both render one snapshot of the same registry;
    the check counter cannot move between the two reads — neither op
    increments it)."""
    from raft_tla_tpu.obs import parse_prometheus
    from raft_tla_tpu.obs.expose import counter_sample
    r = roundtrip(server, {
        "op": "check",
        "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
        "batch": 128, "max_diameter": 2,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False})
    assert r["ok"]
    stats = roundtrip(server, {"op": "stats"})
    m = roundtrip(server, {"op": "metrics"})
    assert m["ok"] and m["content_type"].startswith("text/plain")
    samples = parse_prometheus(m["exposition"])     # raises if invalid
    counters = stats["metrics"]["counters"]
    assert counter_sample(samples, "server/requests/check") \
        == counters["server/requests/check"]
    assert counter_sample(samples, "engine/distinct") \
        == counters["engine/distinct"]
    # Histogram family for the request latencies made it over too.
    assert "raft_phase_request_check_bucket" in samples


def test_watch_op_streams_live_run_snapshots(server):
    """Run attach: a watch stream opened WHILE a check runs sees >= 1
    progress snapshot recorded by that run (seq ordering proves it is
    this run's telemetry, not a stale ring entry), then a done line
    carrying the run_end."""
    from raft_tla_tpu.obs.flight import RECORDER
    seq0 = RECORDER.seq()
    base = {"op": "check",
            "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
            "batch": 128, "max_diameter": 6,
            "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
            "check_deadlock": False}
    out = {}
    th = threading.Thread(
        target=lambda: out.update(resp=roundtrip(server, base)))
    th.start()
    got = []
    with socket.create_connection(server, timeout=600) as s:
        s.sendall((json.dumps({"op": "watch", "interval": 0.2})
                   + "\n").encode())
        s.settimeout(600)
        for line in s.makefile("rb"):
            rec = json.loads(line)
            got.append(rec)
            if rec.get("done"):
                break
    th.join()
    assert out["resp"]["ok"], out["resp"]
    assert got and got[-1].get("done")
    snaps = [g["watch"] for g in got if "watch" in g]
    assert snaps, got
    fresh_progress = [s for s in snaps
                      if s.get("progress")
                      and s["progress"]["seq"] > seq0]
    assert fresh_progress, "watch never saw this run's progress"
    last = fresh_progress[-1]["progress"]
    assert last["distinct"] > 0 and "diameter" in last
    # The done line reports how the watched run ended.
    end = got[-1].get("run_end")
    assert end and end["seq"] > seq0
    assert end["stop_reason"] == "diameter_budget"
    # The attach left its mark in the run's durable event record too:
    # watch_attach rides the flight ring (and the evlog when one is
    # configured — the server runs file-less, so ring-only here).
    att = RECORDER.last_record("watch_attach")
    assert att is not None and att["client"]["transport"] == "server"


def test_stats_request_reports_requests_and_cache_counters(server):
    """The live-stats endpoint (obs/): request counts, per-op latency
    histograms, and LRU cache hit/miss counters.  Self-contained: two
    identical checks guarantee >= 1 engine-cache hit regardless of what
    ran before."""
    base = {"op": "check",
            "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
            "batch": 128, "max_diameter": 2,
            "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
            "check_deadlock": False}
    r = roundtrip(server, base)
    assert r["ok"]
    # Per-run phase breakdown rides the check response too.
    assert r["phases"] and "chunk" in r["phases"]
    r = roundtrip(server, base)          # warm: engine-cache hit
    assert r["ok"]
    stats = roundtrip(server, {"op": "stats"})
    assert stats["ok"] is True
    counters = stats["metrics"]["counters"]
    assert counters["server/requests/check"] >= 2
    assert counters["server/engine_cache/hits"] >= 1
    assert counters["server/engine_cache/misses"] >= 1
    assert stats["engine_cache"]["size"] >= 1
    assert stats["engine_cache"]["capacity"] == srv_mod._CACHE_CAP
    # Latency histograms per op.
    assert stats["metrics"]["histograms"]["phase/request/check"][
        "count"] >= 2
    # The stats op never takes the engine lock, and counts itself.
    assert counters["server/requests/stats"] >= 1


@pytest.mark.parametrize("dead", ["v3", "v4"])
def test_request_for_a_deleted_pipeline_is_rejected(dead, server):
    """A check with ``"pipeline": "v3"`` answers ``ok: false`` naming the
    valid values and runs nothing; a submit is refused at admission."""
    job = {"op": "check",
           "cfg": os.path.join(REPO, "configs/MCraft_bounded.cfg"),
           "batch": 128, "max_diameter": 1, "pipeline": dead}
    resp = roundtrip(server, job)
    assert resp["ok"] is False
    assert "auto/v1/v2" in resp["error"] and dead in resp["error"]
    with pytest.raises(ValueError, match=f"auto/v1/v2.*{dead}"):
        srv_mod._do_submit({"op": "submit", "job": job}, manager=None)
