"""Serving-layer tests (ISSUE 13): the async job manager + server ops.

Two halves:

- **Manager unit tests** against a stub executor (no engine, no device):
  lifecycle + journal, least-recently-served fairness, bounded
  admission, cancel races and the cancelled-never-ran invariant, the
  result cache, and journal replay (queued jobs resume; the job a crash
  caught running is re-run once, then failed with a postmortem
  pointer).
- **Server integration tests** through the real checker service + real
  engine on the pinned MCraft_bounded profile: concurrent multi-tenant
  submits bit-identical to sequential direct checks, per-job scoped
  event logs, per-tenant metrics + SLO histograms agreeing between the
  stats op and the server-native HTTP /metrics endpoint, per-job watch
  streams, the idle-timeout-vs-watch regression, and restart replay.
"""

import json
import os
import socket
import threading
import time
import urllib.request

import pytest

from raft_tla_tpu import server as srv_mod
from raft_tla_tpu.serving import (JobManager, QueueFullError,
                                  TERMINAL_STATES)
from raft_tla_tpu.serving import jobs as jobs_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(REPO, "configs/MCraft_bounded.cfg")


# ---------------------------------------------------------------------------
# Manager unit tests (stub executor — no engine, no device lock).

def wait_terminal(m, timeout=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        doc = m.jobs_doc()
        if all(j["state"] in TERMINAL_STATES for j in doc["jobs"]):
            return doc
        time.sleep(0.02)
    raise AssertionError(f"jobs never settled: {m.jobs_doc()}")


def test_lifecycle_metrics_and_journal(tmp_path):
    ran = []

    def ex(req, job):
        ran.append(job["id"])
        return {"ok": True, "distinct": req["n"]}

    m = JobManager(str(tmp_path), executor=ex, slo_seconds=60.0)
    try:
        s = m.submit({"op": "check", "n": 7}, tenant="acme",
                     label="lbl")
        assert s["state"] == "queued" and s["tenant"] == "acme"
        doc = wait_terminal(m)
        assert doc["by_state"]["done"] == 1
        job = m.get(s["id"])
        assert job["state"] == "done" and job["has_result"]
        # Timestamps + derived durations are populated and ordered.
        assert job["created_ts"] <= job["admitted_ts"] \
            <= job["started_ts"] <= job["finished_ts"]
        assert job["queue_wait_seconds"] >= 0
        assert job["turnaround_seconds"] >= job["run_seconds"]
        assert m.result(s["id"]) == {"ok": True, "distinct": 7}
        snap = m.metrics.snapshot()
        assert snap["counters"]["jobs/submitted/acme"] == 1
        assert snap["counters"]["jobs/done/acme"] == 1
        assert snap["counters"]["jobs/slo_ok/acme"] == 1
        for h in ("jobs/queue_wait_seconds", "jobs/run_seconds",
                  "jobs/turnaround_seconds",
                  "jobs/turnaround_seconds/acme"):
            assert snap["histograms"][h]["count"] == 1, h
        assert snap["gauges"]["jobs/state/done"] == 1
        assert snap["gauges"]["jobs/queue_depth"] == 0
        # The journal replays to the same terminal picture, cleanly.
        jobs, results, problems = jobs_mod.replay(m.journal_path)
        assert jobs[s["id"]]["state"] == "done"
        assert results[s["id"]]["distinct"] == 7
        assert problems == []
    finally:
        m.close()


def test_fair_scheduling_least_recently_served(tmp_path):
    order = []
    gate = threading.Event()

    def ex(req, job):
        gate.wait(10)
        order.append((job["tenant"], req["n"]))
        return {"ok": True}

    # start=False: enqueue everything first, then run the loop, so the
    # pick order is purely the scheduler's.
    m = JobManager(str(tmp_path), executor=ex, start=False)
    for n in (1, 2, 3):
        m.submit({"op": "check", "n": n}, tenant="a")
    m.submit({"op": "check", "n": 10}, tenant="b")
    m.submit({"op": "check", "n": 11}, tenant="b")
    m.submit({"op": "check", "n": 20}, tenant="c")
    gate.set()
    m._thread = threading.Thread(target=m._loop, daemon=True)
    m._thread.start()
    try:
        wait_terminal(m)
        # Round-robin across tenants (a queue-flooding tenant cannot
        # starve b/c), FIFO within a tenant, ties by join order.
        assert order == [("a", 1), ("b", 10), ("c", 20),
                         ("a", 2), ("b", 11), ("a", 3)], order
    finally:
        m.close()


def test_queue_overflow_rejects_cleanly(tmp_path):
    def ex(req, job):
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex, queue_capacity=2,
                   start=False)      # nothing drains: depth is exact
    try:
        m.submit({"op": "check"}, tenant="t")
        m.submit({"op": "check"}, tenant="t")
        with pytest.raises(QueueFullError, match="queue full"):
            m.submit({"op": "check"}, tenant="t")
        snap = m.metrics.snapshot()
        assert snap["counters"]["server/rejected/queue_full"] == 1
        assert snap["counters"]["jobs/rejected/t"] == 1
        # The reject did not corrupt the registry: still 2 queued.
        assert m.jobs_doc()["queue_depth"] == 2
    finally:
        m.close(wait=False)


def test_cancel_invariants_and_submit_cancel_races(tmp_path):
    executed = []
    gate = threading.Event()

    def ex(req, job):
        gate.wait(10)
        executed.append(job["id"])
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        first = m.submit({"op": "check"}, tenant="t")
        victims = [m.submit({"op": "check"}, tenant="t")
                   for _ in range(6)]
        # Concurrent cancels racing each other and the scheduler: each
        # job is cancelled by exactly one winner; double-cancel raises.
        errs = []

        def do_cancel(jid):
            try:
                m.cancel(jid)
            except (ValueError, KeyError) as e:
                errs.append(str(e))

        ts = [threading.Thread(target=do_cancel, args=(v["id"],))
              for v in victims for _ in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        gate.set()
        doc = wait_terminal(m)
        assert doc["by_state"]["cancelled"] == 6
        assert doc["by_state"]["done"] == 1
        # Exactly one cancel per job won; the other raced and raised.
        assert len(errs) == 6 and all("already cancelled" in e
                                      for e in errs)
        # THE invariant: a cancelled job never reached the executor,
        # has no result, and its terminal state stuck.
        assert executed == [first["id"]]
        for v in victims:
            job = m.get(v["id"])
            assert job["state"] == "cancelled"
            assert job["started_ts"] is None
            assert not job["has_result"]
            with pytest.raises(ValueError, match="no result"):
                m.result(v["id"])
        assert m.metrics.snapshot()["counters"]["jobs/cancelled/t"] == 6
    finally:
        m.close(wait=False)


def test_cancel_running_refused(tmp_path):
    gate = threading.Event()
    release = threading.Event()

    def ex(req, job):
        gate.set()
        release.wait(10)
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        s = m.submit({"op": "check"}, tenant="t")
        assert gate.wait(10)
        assert m.running_job_id() == s["id"]
        assert m.has_live_jobs()
        with pytest.raises(ValueError, match="not preemptible"):
            m.cancel(s["id"])
        release.set()
        wait_terminal(m)
        assert m.get(s["id"])["state"] == "done"
        with pytest.raises(ValueError, match="already done"):
            m.cancel(s["id"])
    finally:
        release.set()
        m.close()


def test_result_cache_hit_and_miss(tmp_path):
    calls = []

    def ex(req, job):
        calls.append(job["id"])
        return {"ok": True, "distinct": 42}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        a = m.submit({"op": "check"}, tenant="t", cache_key="K")
        wait_terminal(m)
        b = m.submit({"op": "check"}, tenant="t", cache_key="K")
        c = m.submit({"op": "check"}, tenant="t", cache_key="K2")
        wait_terminal(m)
        assert len(calls) == 2          # a (miss) + c (miss); b hit
        jb = m.get(b["id"])
        assert jb["state"] == "done" and jb["cached"] is True
        assert m.get(a["id"])["cached"] is False
        assert m.result(b["id"]) == m.result(a["id"])
        snap = m.metrics.snapshot()["counters"]
        assert snap["jobs/result_cache/hits"] == 1
        assert snap["jobs/result_cache/misses"] == 2
        # Replay seeds the cache from done jobs: a restarted manager
        # still hits.
        m.close()
        m2 = JobManager(str(tmp_path), executor=ex)
        d = m2.submit({"op": "check"}, tenant="t", cache_key="K")
        wait_terminal(m2)
        assert m2.get(d["id"])["cached"] is True
        assert len(calls) == 2
        m2.close()
    finally:
        m.close(wait=False)


def test_failed_job_records_error(tmp_path):
    def ex(req, job):
        raise RuntimeError("engine exploded")

    m = JobManager(str(tmp_path), executor=ex)
    try:
        s = m.submit({"op": "check"}, tenant="t")
        wait_terminal(m)
        job = m.get(s["id"])
        assert job["state"] == "failed"
        assert "engine exploded" in job["error"]
        assert m.metrics.snapshot()["counters"]["jobs/failed/t"] == 1
        with pytest.raises(ValueError, match="engine exploded"):
            m.result(s["id"])
    finally:
        m.close()


def test_replay_resumes_queued_jobs(tmp_path):
    def ex(req, job):
        return {"ok": True, "n": req["n"]}

    m1 = JobManager(str(tmp_path), executor=ex, start=False)
    a = m1.submit({"op": "check", "n": 1}, tenant="t")
    b = m1.submit({"op": "check", "n": 2}, tenant="t")
    m1.close(wait=False)     # "restart": nothing ever ran
    m2 = JobManager(str(tmp_path), executor=ex)
    try:
        wait_terminal(m2)
        for s, n in ((a, 1), (b, 2)):
            job = m2.get(s["id"])
            assert job["state"] == "done"
            assert job["note"] == "resumed_after_restart"
            assert m2.result(s["id"])["n"] == n
    finally:
        m2.close()


def _craft_running_journal(tmp_path, restarts, with_postmortem):
    """A journal whose last word on job jX is ``running`` — the shape a
    crash leaves behind."""
    base = str(tmp_path)
    journal = os.path.join(base, "jobs.jsonl")
    job = jobs_mod.new_job("jX-cafe42", "acme", {"op": "check"})
    job["job_dir"] = os.path.join(base, job["id"])
    job["events_out"] = os.path.join(job["job_dir"], "events.jsonl")
    jobs_mod.append_record(journal, jobs_mod.submit_record(job))
    job["state"] = "running"
    job["restarts"] = restarts
    jobs_mod.append_record(
        journal, jobs_mod.state_record(
            job, patch={"restarts": restarts,
                        "started_ts": round(time.time(), 6)}))
    if with_postmortem:
        os.makedirs(job["job_dir"], exist_ok=True)
        with open(os.path.join(job["job_dir"], "postmortem.json"),
                  "w") as f:
            json.dump({"postmortem": True, "reason": "test"}, f)
    return job["id"]


def test_replay_reruns_job_caught_running_once(tmp_path):
    ran = []

    def ex(req, job):
        ran.append(job["id"])
        return {"ok": True}

    jid = _craft_running_journal(tmp_path, restarts=0,
                                 with_postmortem=False)
    m = JobManager(str(tmp_path), executor=ex)
    try:
        wait_terminal(m)
        job = m.get(jid)
        assert job["state"] == "done" and ran == [jid]
        assert job["restarts"] == 1
        assert job["note"] == "requeued_after_restart"
        assert m.metrics.snapshot()["counters"][
            "jobs/requeued_after_restart"] == 1
    finally:
        m.close()


def test_replay_fails_twice_lost_job_with_postmortem(tmp_path):
    ran = []

    def ex(req, job):
        ran.append(job["id"])
        return {"ok": True}

    hist = str(tmp_path / "ledger.jsonl")
    jid = _craft_running_journal(tmp_path, restarts=1,
                                 with_postmortem=True)
    m = JobManager(str(tmp_path), executor=ex, history_path=hist)
    try:
        job = m.get(jid)
        assert job["state"] == "failed" and ran == []
        assert "restart" in job["error"]
        assert job["postmortem"] and job["postmortem"].endswith(
            "postmortem.json")
        assert os.path.exists(job["postmortem"])
        # The loss is on the history ledger too (kind=server, job id).
        from raft_tla_tpu.obs import history as history_mod
        entries = history_mod.read_history(hist)
        assert entries[-1]["kind"] == "server"
        assert entries[-1]["verdict"] == "lost-after-restart"
        assert entries[-1]["job_id"] == jid
        assert entries[-1]["tenant"] == "acme"
    finally:
        m.close(wait=False)


def test_terminal_retention_evicts_oldest(tmp_path):
    def ex(req, job):
        return {"ok": True, "n": req["n"]}

    m = JobManager(str(tmp_path), executor=ex, max_terminal_jobs=2)
    try:
        subs = [m.submit({"op": "check", "n": n}, tenant="t")
                for n in range(4)]
        wait_terminal(m)
        doc = m.jobs_doc()
        assert doc["by_state"]["done"] == 2           # census pruned too
        kept = {j["id"] for j in doc["jobs"]}
        assert kept == {subs[2]["id"], subs[3]["id"]}  # oldest evicted
        with pytest.raises(KeyError):
            m.result(subs[0]["id"])
        assert m.result(subs[3]["id"])["n"] == 3
        assert m.metrics.snapshot()["counters"]["jobs/evicted"] == 2
    finally:
        m.close()


def test_journal_failure_does_not_kill_executor(tmp_path):
    """Review fix: a full disk (journal append OSError) must degrade to
    a counted durability loss — the executor keeps draining the queue
    and the in-memory registry stays consistent."""
    def ex(req, job):
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        # Point the journal at a DIRECTORY: every append now raises
        # IsADirectoryError (an OSError) inside submit + transitions.
        broken = tmp_path / "broken.jsonl"
        broken.mkdir()
        m.journal_path = str(broken)
        a = m.submit({"op": "check"}, tenant="t")
        b = m.submit({"op": "check"}, tenant="t")
        wait_terminal(m)
        assert m.get(a["id"])["state"] == "done"
        assert m.get(b["id"])["state"] == "done"
        assert m.metrics.snapshot()["counters"]["jobs/journal_errors"] \
            >= 2
    finally:
        m.close()


def test_requeued_job_queue_wait_excludes_downtime(tmp_path):
    """Review fix: a restart-requeued job's queue_wait must price THIS
    server's queue (enqueued_ts base), not the pre-crash run + the
    downtime (created_ts base) — turnaround still spans the whole
    customer wait."""
    def ex(req, job):
        return {"ok": True}

    jid = _craft_running_journal(tmp_path, restarts=0,
                                 with_postmortem=False)
    # Age the journal's created_ts far into the past.
    journal = os.path.join(str(tmp_path), "jobs.jsonl")
    lines = [json.loads(ln) for ln in open(journal)]
    lines[0]["job"]["created_ts"] -= 600.0
    lines[0]["job"]["enqueued_ts"] -= 600.0
    with open(journal, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    m = JobManager(str(tmp_path), executor=ex)
    try:
        wait_terminal(m)
        job = m.get(jid)
        assert job["state"] == "done"
        assert job["queue_wait_seconds"] < 30, job["queue_wait_seconds"]
        assert job["turnaround_seconds"] > 590, job["turnaround_seconds"]
    finally:
        m.close()


def test_degraded_journal_replays_tolerantly(tmp_path):
    """Round-4 review fix: a journal degraded by best-effort writes (a
    torn trailing line, an orphan state record whose submit line was
    lost) must replay what it can and start — never permanently brick
    every restart on this job dir."""
    def ex(req, job):
        return {"ok": True}

    m1 = JobManager(str(tmp_path), executor=ex, start=False)
    good = m1.submit({"op": "check"}, tenant="t")
    m1.close(wait=False)
    journal = os.path.join(str(tmp_path), "jobs.jsonl")
    with open(journal, "a") as f:
        # Orphan state record (its submit line was lost to a full
        # disk) + a torn line from a crash mid-write.
        f.write(json.dumps({"rec": "state", "id": "j-lost",
                            "state": "running", "ts": 1.0}) + "\n")
        f.write('{"rec": "state", "id": "j-torn", "sta')
    jobs, _results, problems = jobs_mod.replay(journal)
    assert good["id"] in jobs
    assert len(problems) == 2, problems
    m2 = JobManager(str(tmp_path), executor=ex)
    try:
        wait_terminal(m2)
        assert m2.get(good["id"])["state"] == "done"
        assert m2.metrics.snapshot()["counters"][
            "jobs/journal_skipped"] == 2
    finally:
        m2.close()


def test_tenant_label_collision_gets_suffix(tmp_path):
    def ex(req, job):
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex, start=False)
    try:
        m.submit({"op": "check"}, tenant="acme corp")
        m.submit({"op": "check"}, tenant="acme_corp")
        counters = m.metrics.snapshot()["counters"]
        labels = [k.split("/")[-1] for k in counters
                  if k.startswith("jobs/submitted/")]
        # Both tenants submitted once, into DISTINCT series.
        assert len(labels) == 2 and len(set(labels)) == 2, labels
        assert all(counters[f"jobs/submitted/{lb}"] == 1
                   for lb in labels)
    finally:
        m.close(wait=False)


def test_tenant_metric_labels_bounded(tmp_path):
    def ex(req, job):
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex, tenant_cap=2,
                   start=False)
    try:
        m.submit({"op": "check"}, tenant="t/1 weird\nname")
        m.submit({"op": "check"}, tenant="t2")
        m.submit({"op": "check"}, tenant="t3-overflows-the-cap")
        counters = m.metrics.snapshot()["counters"]
        assert counters["jobs/submitted/t_1_weird_name"] == 1
        assert counters["jobs/submitted/t2"] == 1
        # Past the cap, tenants fold into one bounded label.
        assert counters["jobs/submitted/other"] == 1
    finally:
        m.close(wait=False)


# ---------------------------------------------------------------------------
# Server integration (real engine, pinned MCraft_bounded profile).

@pytest.fixture(scope="module")
def jobsrv(tmp_path_factory):
    base = tmp_path_factory.mktemp("serving")
    hist = str(base / "ledger.jsonl")
    srv = srv_mod.serve(port=0, job_dir=str(base / "jobs"),
                        history=hist, metrics_port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv, hist
    srv.shutdown()
    srv.server_close()


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


BASE = {"op": "check", "cfg": CFG, "batch": 128,
        "queue_capacity": 1 << 12, "seen_capacity": 1 << 15,
        "check_deadlock": False}


def _wait_jobs_settled(addr, ids, timeout=600.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        doc = roundtrip(addr, {"op": "jobs"})
        byid = {j["id"]: j for j in doc["jobs"]}
        if all(byid[i]["state"] in TERMINAL_STATES for i in ids):
            return doc
        time.sleep(0.1)
    raise AssertionError(f"jobs never settled: {doc}")


def test_concurrent_multi_tenant_jobs_bitidentical(jobsrv):
    """ISSUE 13 acceptance: N concurrent jobs from >= 2 tenants all
    reach terminal states with results bit-identical to the same
    checks run sequentially through the blocking check op, while the
    jobs observably overlapped in queued/admitted states."""
    srv, _hist = jobsrv
    addr = srv.server_address
    seq3 = roundtrip(addr, dict(BASE, max_diameter=3))
    seq4 = roundtrip(addr, dict(BASE, max_diameter=4))
    assert seq3["ok"] and seq3["distinct"] == 113
    assert seq4["ok"] and seq4["distinct"] == 527
    subs = []
    for tenant, d in (("t1", 3), ("t2", 4), ("t1", 4)):
        r = roundtrip(addr, {"op": "submit", "tenant": tenant,
                             "job": dict(BASE, max_diameter=d)})
        assert r["ok"], r
        assert r["job"]["state"] == "queued"
        subs.append((r["job"]["id"], seq3 if d == 3 else seq4))
    # Overlap is observable: right after the submits, >= 2 jobs are
    # live at once and >= 1 is still waiting in the queue.
    doc = roundtrip(addr, {"op": "jobs"})
    live = [j for j in doc["jobs"]
            if j["state"] in ("queued", "admitted", "running")]
    assert len(live) >= 2, doc
    assert doc["queue_depth"] >= 1, doc
    doc = _wait_jobs_settled(addr, [jid for jid, _ in subs])
    assert doc["by_state"]["failed"] == 0
    for jid, want in subs:
        res = roundtrip(addr, {"op": "result", "job_id": jid})
        assert res["ok"], res
        got = res["result"]
        assert (got["distinct"], got["generated"], got["levels"]) \
            == (want["distinct"], want["generated"], want["levels"])


def test_per_job_event_logs_and_job_metrics(jobsrv):
    """Every executed job has a scoped JSONL event log that
    validate_run_events accepts, and the queue-wait/turnaround/SLO
    surfaces are populated in both the stats op and the server-native
    Prometheus endpoint (which must agree)."""
    from raft_tla_tpu.obs import parse_prometheus, validate_run_events
    from raft_tla_tpu.obs.expose import counter_sample
    srv, _hist = jobsrv
    addr = srv.server_address
    doc = roundtrip(addr, {"op": "jobs", "state": "done"})
    assert doc["jobs"], "run test_concurrent_multi_tenant_jobs first"
    for j in doc["jobs"]:
        evs = validate_run_events(j["events_out"])
        kinds = {e["event"] for e in evs}
        assert {"run_start", "run_end"} <= kinds, (j["id"], kinds)
        assert j["queue_wait_seconds"] is not None
        assert j["turnaround_seconds"] >= (j["run_seconds"] or 0)
    stats = roundtrip(addr, {"op": "stats"})
    counters = stats["metrics"]["counters"]
    hists = stats["metrics"]["histograms"]
    assert counters["jobs/submitted/t1"] >= 2
    assert counters["jobs/submitted/t2"] >= 1
    assert counters["jobs/done/t1"] >= 2
    assert hists["jobs/queue_wait_seconds"]["count"] >= 3
    assert hists["jobs/turnaround_seconds"]["count"] >= 3
    assert counters["jobs/slo_ok/t1"] + counters.get("jobs/slo_miss/t1",
                                                     0) >= 2
    # by-state gauges mirror the jobs op's registry view.
    alldoc = roundtrip(addr, {"op": "jobs"})
    assert stats["metrics"]["gauges"]["jobs/state/done"] \
        == alldoc["by_state"]["done"]
    # Server-native HTTP endpoint: same registry, same numbers.
    hp = srv.metrics_http.server_address
    body = urllib.request.urlopen(
        f"http://{hp[0]}:{hp[1]}/metrics", timeout=60).read().decode()
    samples = parse_prometheus(body)        # raises if invalid
    assert "raft_jobs_queue_wait_seconds_bucket" in samples
    assert counter_sample(samples, "jobs/submitted/t1") \
        == counters["jobs/submitted/t1"]
    jd = json.loads(urllib.request.urlopen(
        f"http://{hp[0]}:{hp[1]}/jobs", timeout=60).read())
    assert jd["ok"] and jd["by_state"]["done"] \
        == alldoc["by_state"]["done"]
    # /flight still serves (the watch console's poll target).
    fd = json.loads(urllib.request.urlopen(
        f"http://{hp[0]}:{hp[1]}/flight?last=4", timeout=60).read())
    assert fd["ok"] and "records" in fd


def test_server_history_ledger_served_traffic(jobsrv):
    """Satellite: server-executed checks land kind=server ledger
    entries (host_key + job/tenant ids) renderable by bench_history
    alongside CLI runs."""
    from raft_tla_tpu.obs import history as history_mod
    srv, hist = jobsrv
    entries = history_mod.read_history(hist)
    server_entries = [e for e in entries if e["kind"] == "server"]
    assert server_entries, "no served-traffic entries"
    jobful = [e for e in server_entries if e.get("job_id")]
    direct = [e for e in server_entries if e.get("job_id") is None]
    assert jobful and direct            # jobs AND blocking checks
    for e in server_entries:
        assert e["host_key"], e         # same-host comparability key
        assert e["verdict"] == "ok"
        assert e["distinct"] in (113, 527)
    assert {e["tenant"] for e in jobful} >= {"t1", "t2"}
    # The trajectory table renders them (kind column = server).
    table = history_mod.render_table(entries)
    assert "server" in table


def test_queue_overflow_op_rejects_cleanly():
    """Satellite: a queue-overflow submit answers a clean
    ``{"ok": false}`` line (the connection stays usable) and bumps the
    ``server/rejected/queue_full`` + per-tenant counters."""
    import tempfile
    srv = srv_mod.serve(port=0, job_dir=tempfile.mkdtemp(),
                        job_queue_capacity=1)
    srv.jobs.close(wait=False)          # executor off: depth is exact
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        addr = srv.server_address
        before = srv_mod._METRICS.counter_value(
            "server/rejected/queue_full")
        r1 = roundtrip(addr, {"op": "submit", "tenant": "flood",
                              "job": dict(BASE, max_diameter=2)})
        assert r1["ok"], r1
        r2 = roundtrip(addr, {"op": "submit", "tenant": "flood",
                              "job": dict(BASE, max_diameter=2)})
        assert r2["ok"] is False and "queue full" in r2["error"], r2
        counters = roundtrip(addr, {"op": "stats"})["metrics"][
            "counters"]
        assert counters["server/rejected/queue_full"] == before + 1
        assert counters["jobs/rejected/flood"] >= 1
        # The queued job is intact and the registry consistent.
        doc = roundtrip(addr, {"op": "jobs", "tenant": "flood"})
        assert doc["queue_depth"] >= 1
    finally:
        srv.shutdown()
        srv.server_close()


def test_cancel_op_terminal_invariants(jobsrv):
    """Cancel through the op: terminal-state invariants over the wire
    against a saturated executor."""
    srv, _hist = jobsrv
    addr = srv.server_address
    # Saturate: a wall-clock-budgeted job occupies the executor while
    # we queue more behind it.
    slow = dict(BASE, max_diameter=None, max_seconds=2.0)
    r1 = roundtrip(addr, {"op": "submit", "tenant": "t1", "job": slow})
    r2 = roundtrip(addr, {"op": "submit", "tenant": "t2",
                          "job": dict(BASE, max_diameter=3)})
    assert r1["ok"] and r2["ok"]
    c = roundtrip(addr, {"op": "cancel", "job_id": r2["job"]["id"]})
    if c["ok"]:          # r2 could already be running on a warm engine
        assert c["job"]["state"] == "cancelled"
        res = roundtrip(addr, {"op": "result",
                               "job_id": r2["job"]["id"]})
        assert not res["ok"] and "no result" in res["error"]
        # A cancelled job's terminal state sticks.
        again = roundtrip(addr, {"op": "cancel",
                                 "job_id": r2["job"]["id"]})
        assert not again["ok"] and "already cancelled" in again["error"]
    bogus = roundtrip(addr, {"op": "cancel", "job_id": "nope"})
    assert not bogus["ok"] and "unknown job" in bogus["error"]
    _wait_jobs_settled(addr, [r1["job"]["id"], r2["job"]["id"]])


def test_submit_cache_flag_and_rejects(jobsrv):
    srv, _hist = jobsrv
    addr = srv.server_address
    req = {"op": "submit", "tenant": "t1", "cache": True,
           "job": dict(BASE, max_diameter=2)}
    r1 = roundtrip(addr, req)
    assert r1["ok"], r1
    _wait_jobs_settled(addr, [r1["job"]["id"]])
    r2 = roundtrip(addr, req)
    assert r2["ok"], r2
    _wait_jobs_settled(addr, [r2["job"]["id"]])
    j2 = roundtrip(addr, {"op": "status", "job_id": r2["job"]["id"]})
    assert j2["job"]["cached"] is True
    a = roundtrip(addr, {"op": "result", "job_id": r1["job"]["id"]})
    b = roundtrip(addr, {"op": "result", "job_id": r2["job"]["id"]})
    assert a["result"] == b["result"]
    stats = roundtrip(addr, {"op": "stats"})
    assert stats["metrics"]["counters"]["jobs/result_cache/hits"] >= 1
    # A wall-clock-budgeted request is not cacheable.
    bad = roundtrip(addr, {"op": "submit", "cache": True,
                           "job": dict(BASE, max_seconds=1.0)})
    assert not bad["ok"] and "max_seconds" in bad["error"]
    # Submit without a proper inner job is a clean error.
    bad = roundtrip(addr, {"op": "submit", "job": {"op": "nope"}})
    assert not bad["ok"]


def test_watch_job_sees_own_progress(jobsrv):
    """Per-job run attach: the stream's snapshots carry THIS job's
    registry state, ring progress attributed via the job-tagged
    run_context (seq-ordered), and a done line with the terminal
    job."""
    from raft_tla_tpu.obs.flight import RECORDER
    srv, _hist = jobsrv
    addr = srv.server_address
    seq0 = RECORDER.seq()
    r = roundtrip(addr, {"op": "submit", "tenant": "t1",
                         "job": dict(BASE, max_diameter=6)})
    assert r["ok"], r
    jid = r["job"]["id"]
    got = []
    with socket.create_connection(addr, timeout=600) as s:
        s.sendall((json.dumps({"op": "watch", "job": jid,
                               "interval": 0.1}) + "\n").encode())
        s.settimeout(600)
        for line in s.makefile("rb"):
            rec = json.loads(line)
            got.append(rec)
            if rec.get("done"):
                break
    assert got[-1].get("done") and got[-1]["job"]["state"] == "done"
    snaps = [g["watch"] for g in got if "watch" in g]
    assert all(s["job"]["id"] == jid for s in snaps)
    tagged = [s for s in snaps if s.get("run")]
    assert tagged, "watch never saw the job's armed run"
    assert all(s["run"]["job_id"] == jid and s["run"]["tenant"] == "t1"
               for s in tagged)
    fresh = [s["progress"] for s in snaps
             if s.get("progress") and s["progress"]["seq"] > seq0]
    assert fresh, "watch never saw this job's progress lines"
    assert fresh[-1]["distinct"] > 0
    # Watching an unknown job is a clean one-line error.
    bad = roundtrip(addr, {"op": "watch", "job": "nope",
                           "interval": 0.1})
    assert not bad["ok"] and "unknown job" in bad["error"]


def test_watch_swarm_job_streams_progress_and_hunt(jobsrv):
    """ISSUE 20 satellite regression: a watch attached to a SWARM job
    streams that job's swarm_progress + hunt flight records with job
    attribution — records newer than the job-tagged run_context
    (seq-ordered), never a stale line from a previous run."""
    from raft_tla_tpu.obs.flight import RECORDER
    srv, _hist = jobsrv
    addr = srv.server_address
    cfg = os.path.join(REPO, "configs/MCraft_noleader.cfg")
    seq0 = RECORDER.seq()
    r = roundtrip(addr, {"op": "submit", "tenant": "t1",
                         "job": {"op": "check", "cfg": cfg,
                                 "mode": "swarm", "walks": 64,
                                 "max_depth": 12, "num_steps": 512,
                                 "seed": 5, "batch": 32,
                                 "progress_seconds": 0.2}})
    assert r["ok"], r
    jid = r["job"]["id"]
    got = []
    with socket.create_connection(addr, timeout=600) as s:
        s.sendall((json.dumps({"op": "watch", "job": jid,
                               "interval": 0.05}) + "\n").encode())
        s.settimeout(600)
        for line in s.makefile("rb"):
            rec = json.loads(line)
            got.append(rec)
            if rec.get("done"):
                break
    assert got[-1].get("done") and got[-1]["job"]["state"] == "done"
    snaps = [g["watch"] for g in got if "watch" in g]
    assert all(s["job"]["id"] == jid for s in snaps)
    tagged = [s for s in snaps if s.get("run")]
    assert tagged, "watch never saw the swarm job's armed run"
    assert all(s["run"]["job_id"] == jid for s in tagged)
    # Swarm progress lines, attributed and fresh (seq > submit point).
    prog = [s["progress"] for s in snaps
            if s.get("progress") and s["progress"]["seq"] > seq0]
    assert prog, "watch never saw the swarm job's progress lines"
    assert all(p["mode"] == "swarm" for p in prog)
    assert prog[-1]["steps"] > 0
    # Hunt snapshots ride the same stream with the same attribution.
    hunts = [s["hunt"] for s in snaps
             if s.get("hunt") and s["hunt"]["seq"] > seq0]
    assert hunts, "watch never saw the swarm job's hunt snapshots"
    assert all(0.0 <= h["saturation"] <= 1.0 for h in hunts)
    assert hunts[-1]["observations"] > 0
    # The job's result carries the full hunt report.
    res = roundtrip(addr, {"op": "result", "job_id": jid})
    assert res["ok"] and isinstance(res["result"]["hunt"], dict)


def test_watch_outlives_idle_timeout_while_job_queued():
    """ISSUE 13 satellite regression: a watcher attached to a QUEUED
    job must not be reaped while the job is alive — neither by the
    socket idle timeout nor by the count-0 no-run grace window, both
    set well below the queue wait here.  The stream closes only on the
    job's terminal state (a cancel, delivered to the watcher)."""
    import tempfile
    srv = srv_mod.serve(port=0, job_dir=tempfile.mkdtemp(),
                        idle_timeout_seconds=0.6)
    srv.watch_grace_seconds = 0.5
    srv.jobs.close(wait=False)      # executor off: jobs stay queued
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        addr = srv.server_address
        r = roundtrip(addr, {"op": "submit", "tenant": "t",
                             "job": dict(BASE, max_diameter=2)})
        assert r["ok"], r
        jid = r["job"]["id"]
        got = []
        t0 = time.monotonic()
        with socket.create_connection(addr, timeout=60) as s:
            s.sendall((json.dumps({"op": "watch", "job": jid,
                                   "interval": 0.15}) + "\n").encode())
            s.settimeout(60)
            f = s.makefile("rb")
            cancelled = False
            for line in f:
                rec = json.loads(line)
                got.append(rec)
                if rec.get("done"):
                    break
                elapsed = time.monotonic() - t0
                if elapsed > 1.6 and not cancelled:
                    # Well past both the 0.6 s idle timeout and the
                    # 0.5 s grace: still streaming.  Now end the job.
                    cancelled = True
                    c = roundtrip(addr, {"op": "cancel", "job_id": jid})
                    assert c["ok"], c
        elapsed = time.monotonic() - t0
        assert elapsed > 1.6, f"watcher reaped early ({elapsed:.2f}s)"
        assert got[-1].get("done")
        assert got[-1]["job"]["state"] == "cancelled"
        queued = [g for g in got
                  if g.get("watch", {}).get("job", {}).get("state")
                  == "queued"]
        assert len(queued) >= 6, len(queued)
        # Plain (runless) count-0 watch: live queued jobs also hold it
        # open past the grace window.
        r2 = roundtrip(addr, {"op": "submit", "tenant": "t",
                              "job": dict(BASE, max_diameter=2)})
        assert r2["ok"]
        n = 0
        t0 = time.monotonic()
        with socket.create_connection(addr, timeout=60) as s:
            s.sendall((json.dumps({"op": "watch", "interval": 0.15})
                       + "\n").encode())
            s.settimeout(60)
            f = s.makefile("rb")
            for line in f:
                rec = json.loads(line)
                if rec.get("done"):
                    pytest.fail("plain watch reaped while a job was "
                                "queued")
                n += 1
                if time.monotonic() - t0 > 1.5:
                    break               # still live well past grace
        assert n >= 6
    finally:
        srv.shutdown()
        srv.server_close()


def test_server_restart_replays_job_journal(tmp_path):
    """ISSUE 13 acceptance: a restart mid-queue replays the journal —
    queued jobs resume on the new server and reach terminal states
    with the pinned results, observable via the jobs op."""
    jobdir = str(tmp_path / "jobs")
    srv1 = srv_mod.serve(port=0, job_dir=jobdir)
    srv1.jobs.close(wait=False)     # executor off: simulate dying mid-queue
    t1 = threading.Thread(target=srv1.serve_forever, daemon=True)
    t1.start()
    addr1 = srv1.server_address
    subs = []
    for tenant, d in (("t1", 3), ("t2", 3)):
        r = roundtrip(addr1, {"op": "submit", "tenant": tenant,
                              "job": dict(BASE, max_diameter=d)})
        assert r["ok"], r
        subs.append(r["job"]["id"])
    doc = roundtrip(addr1, {"op": "jobs"})
    assert doc["by_state"]["queued"] == 2
    srv1.shutdown()
    srv1.server_close()
    # The restarted server on the same --job-dir resumes the queue.
    srv2 = srv_mod.serve(port=0, job_dir=jobdir)
    t2 = threading.Thread(target=srv2.serve_forever, daemon=True)
    t2.start()
    try:
        addr2 = srv2.server_address
        doc = _wait_jobs_settled(addr2, subs)
        assert doc["by_state"]["done"] == 2, doc
        for jid in subs:
            st = roundtrip(addr2, {"op": "status", "job_id": jid})
            assert st["job"]["note"] == "resumed_after_restart"
            res = roundtrip(addr2, {"op": "result", "job_id": jid})
            assert res["result"]["distinct"] == 113
    finally:
        srv2.shutdown()
        srv2.server_close()


# ---------------------------------------------------------------------------
# The blocking wait (ISSUE 50): ``result`` with ``wait``, ``submit --wait``,
# the serving layer's spans and ``job_end``, the plain reference's order.

def _waiter(fn, *args):
    """Run ``fn(*args)`` on a thread; ``box`` gets (answer, time.time()
    at the return)."""
    box = {}

    def go():
        box["answer"] = fn(*args)
        box["at"] = time.time()

    t = threading.Thread(target=go, daemon=True)
    t.start()
    return t, box


def test_wait_terminal_returns_when_the_job_ends_not_a_poll_later(tmp_path):
    def ex(req, job):
        time.sleep(0.3)
        return {"ok": True, "distinct": 7}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        s = m.submit({"op": "check"}, tenant="t")
        t, box = _waiter(m.wait_terminal, s["id"], 30.0)
        t.join(timeout=10.0)
        assert not t.is_alive()
        got = box["answer"]
        assert got["state"] == "done" and got["timed_out"] is False
        assert got["result"] == {"ok": True, "distinct": 7}
        assert got["job"]["id"] == s["id"] and got["job"]["has_result"]
        # On the manager's own timestamps: woken by the terminal
        # transition's notify, not by a timer.
        assert 0.0 <= box["at"] - got["job"]["finished_ts"] < 0.05
        assert m.metrics.counter_value("jobs/wait_wakeups") >= 1
        assert m.metrics.snapshot()["histograms"][
            "serve/result_wait"]["count"] == 1
        # An already terminal job answers at once.
        t0 = time.monotonic()
        assert m.wait_terminal(s["id"], 30.0)["state"] == "done"
        assert time.monotonic() - t0 < 0.05
        with pytest.raises(KeyError):
            m.wait_terminal("nope", 0.1)
    finally:
        m.close(wait=False)


def test_wait_terminal_times_out_with_the_state_it_has(tmp_path):
    gate = threading.Event()

    def ex(req, job):
        gate.wait(10.0)
        return {"ok": True}

    m = JobManager(str(tmp_path), executor=ex)
    try:
        s = m.submit({"op": "check"}, tenant="t")
        t0 = time.monotonic()
        got = m.wait_terminal(s["id"], 0.2)
        assert 0.2 <= time.monotonic() - t0 < 2.0
        assert got["timed_out"] is True and got["result"] is None
        assert got["state"] in ("queued", "admitted", "running")
        gate.set()
        assert m.wait_terminal(s["id"], 10.0)["state"] == "done"
    finally:
        gate.set()
        m.close(wait=False)


def test_wait_terminal_wakes_on_cancel(tmp_path):
    m = JobManager(str(tmp_path), executor=lambda r, j: {"ok": True},
                   start=False)
    s = m.submit({"op": "check"}, tenant="t")
    t, box = _waiter(m.wait_terminal, s["id"], 30.0)
    time.sleep(0.1)
    assert t.is_alive()
    m.cancel(s["id"])
    t.join(timeout=5.0)
    assert not t.is_alive()
    got = box["answer"]
    assert got["state"] == "cancelled" and got["result"] is None
    assert got["timed_out"] is False
    assert box["at"] - got["job"]["finished_ts"] < 0.05


class _Recorder:
    """A duck-typed span tracer (obs/metrics.py ``registry.tracer``)."""

    def __init__(self):
        self.spans, self._lock = [], threading.Lock()

    def begin(self, name, args):
        return name, time.perf_counter(), args, threading.get_ident()

    def end(self, token):
        name, start, args, tid = token
        with self._lock:
            self.spans.append((name, start, time.perf_counter(), args, tid))


def test_the_spans_and_the_job_end_event_of_one_job(tmp_path):
    from raft_tla_tpu.obs import MetricsRegistry
    mt = MetricsRegistry()
    rec = mt.tracer = _Recorder()

    def ex(req, job):
        with mt.scope("run"):
            time.sleep(0.05)
        return {"ok": True, "wall_seconds": 0.05, "distinct": 3}

    m = JobManager(str(tmp_path), executor=ex, metrics=mt)
    try:
        s1 = m.submit({"op": "check", "mode": "swarm"}, tenant="acme",
                      cache_key="k")
        assert m.wait_terminal(s1["id"], 10.0)["state"] == "done"
        s2 = m.submit({"op": "check", "mode": "swarm"}, tenant="acme",
                      cache_key="k")
        assert m.wait_terminal(s2["id"], 10.0)["job"]["cached"] is True
    finally:
        assert m.close(wait=True)
    by = {}
    for span in rec.spans:
        by.setdefault(span[0], []).append(span)
    (j1, j2) = sorted(by["job"], key=lambda s: s[1])
    assert j1[3] == {"job": s1["id"], "tenant": "acme",
                     "job_class": "swarm"}
    inside = lambda s, outer: (s[4] == outer[4] and outer[1] <= s[1]  # noqa: E731
                               and s[2] <= outer[2])
    # The executor's span holds the run and the three transitions'
    # journal appends; the submit's append lies on the caller's thread.
    assert [inside(r, j1) for r in by["run"]] == [True]
    assert sum(inside(j, j1) for j in by["journal"]) == 3
    assert sum(inside(j, j2) for j in by["journal"]) == 3
    assert len(by["journal"]) == 8 and len(by["result_wait"]) == 2
    assert mt.counter_value("jobs/executed") == 1
    hist = mt.snapshot()["histograms"]
    assert hist["serve/job"]["count"] == 2
    assert hist["serve/journal"]["count"] == 8
    assert not any(k.startswith("phase/") for k in hist)
    events = [json.loads(line) for line in
              open(os.path.join(str(tmp_path), "events.jsonl"))]
    e1, e2 = [e for e in events if e["event"] == "job_end"]
    assert (e1["job"], e1["cached"], e1["state"]) == (s1["id"], False,
                                                      "done")
    assert e1["job_class"] == "swarm" and e1["tenant"] == "acme"
    assert e1["engine_wall_s"] == 0.05
    assert e1["turnaround_s"] >= e1["run_s"] >= 0.05
    assert e1["queue_wait_s"] >= 0
    assert e1["result_bytes"] == len(json.dumps(m.result(s1["id"])))
    assert (e2["job"], e2["cached"], e2["engine_wall_s"]) == (
        s2["id"], True, 0.0)
    assert e2["result_bytes"] == e1["result_bytes"]


def _reference_served():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reference_served",
        os.path.join(REPO, "benchmark", "reference", "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference",
                           "served.py")) as f:
        text = f.read()
    assert "raft_tla_tpu" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("n_tenants", [3, 4, 5])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_start_order_equals_the_managers(tmp_path, seed,
                                                       n_tenants):
    """Seeded submit logs with bursts, cancels and repeated cacheable
    requests: the order in which ``JobManager`` starts the jobs, and the
    jobs its result cache answers, are the plain reference's
    (``benchmark/reference/served.py``), which replays the journal's
    order of submits and picks with the keys THIS test sent."""
    import random
    rng = random.Random(1000 * seed + n_tenants)
    m = JobManager(str(tmp_path), executor=lambda r, j: {"ok": True},
                   start=False, queue_capacity=256)
    tenants = [f"t{i}" for i in range(n_tenants)]
    sent = {}
    queued = []
    for _round in range(30):
        for _ in range(rng.choice([0, 1, 1, 2, 5])):        # a burst
            t = rng.choice(tenants)
            key = rng.choice([None, None, "a", "b", "c"])
            s = m.submit({"op": "check"}, tenant=t, cache_key=key)
            sent[s["id"]] = (t, key)
            queued.append(s["id"])
        if queued and rng.random() < 0.2:
            victim = queued.pop(rng.randrange(len(queued)))
            m.cancel(victim)
        for _ in range(rng.choice([0, 1, 2, 3])):
            if m.jobs_doc()["queue_depth"]:
                assert m._run_one()
        queued = [j["id"] for j in m.jobs_doc(state="queued")["jobs"]]
    while m.jobs_doc()["queue_depth"]:
        assert m._run_one()
    log, starts = [], []
    for rec in map(json.loads, open(m.journal_path)):
        if rec["rec"] == "submit":
            t, key = sent[rec["job"]["id"]]
            log.append({"ev": "submit", "job": rec["job"]["id"],
                        "tenant": t, "key": key})
        elif rec["state"] == "admitted":
            log.append({"ev": "pick"})
            starts.append(rec["id"])
        elif rec["state"] == "cancelled":
            log.append({"ev": "cancel", "job": rec["id"]})
        elif rec["state"] in ("done", "failed"):
            log.append({"ev": "end", "job": rec["id"],
                        "ok": rec["state"] == "done"})
    want = _reference_served().schedule(log)
    assert len(starts) >= 20
    assert starts == want["starts"]
    assert {j["id"] for j in m.jobs_doc()["jobs"] if j["cached"]} \
        == want["hits"]
    assert want["hits"], "the log holds no repeat of a cacheable request"


def _stub_server(tmp_path, executor):
    """A checker service on a stub executor (no engine)."""
    srv = srv_mod.CheckerServer(("127.0.0.1", 0), srv_mod._Handler)
    srv.jobs = JobManager(str(tmp_path / "jobs"), executor=executor,
                          metrics=srv_mod._METRICS)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv


def _submit_args(srv, **over):
    import argparse
    args = dict(server=f"127.0.0.1:{srv.server_address[1]}", timeout=15.0,
                poll_interval=1.0)
    args.update(over)
    return argparse.Namespace(**args)


def test_result_wait_over_the_socket_holds_no_lock(tmp_path):
    """The op itself: a waiting client is one handler thread; ``stats``,
    ``status`` and a second waiter answer meanwhile; the answer brings
    state, result and summary; without ``wait`` the op is what it was."""
    gate = threading.Event()

    def ex(req, job):
        gate.wait(20.0)
        return {"ok": True, "distinct": 11}

    srv = _stub_server(tmp_path, ex)
    addr = srv.server_address
    try:
        assert roundtrip(addr, {"op": "ping"})["wait"] is True
        job = roundtrip(addr, {"op": "submit", "tenant": "t",
                               "job": {"op": "check",
                                       "cfg_text": "x"}})["job"]
        t, box = _waiter(roundtrip, addr, {"op": "result",
                                           "job_id": job["id"],
                                           "wait": 30})
        time.sleep(0.2)
        assert t.is_alive()
        assert roundtrip(addr, {"op": "stats"})["ok"]
        assert roundtrip(addr, {"op": "status",
                                "job_id": job["id"]})["job"]["state"] \
            == "running"
        early = roundtrip(addr, {"op": "result", "job_id": job["id"],
                                 "wait": 0.1})
        assert early["ok"] and early["timed_out"] and early["result"] is None
        plain = roundtrip(addr, {"op": "result", "job_id": job["id"]})
        assert not plain["ok"] and "no result yet" in plain["error"]
        gate.set()
        t.join(timeout=10.0)
        assert not t.is_alive()
        got = box["answer"]
        assert got["ok"] and got["state"] == "done"
        assert got["result"] == {"ok": True, "distinct": 11}
        assert got["job"]["id"] == job["id"] and not got["timed_out"]
        assert box["at"] - got["job"]["finished_ts"] < 0.05
        assert roundtrip(addr, {"op": "result", "job_id": job["id"]}) == {
            "ok": True, "state": "done",
            "result": {"ok": True, "distinct": 11}}
        assert srv_mod._METRICS.counter_value("server/result_bytes") > 0
        bad = roundtrip(addr, {"op": "result", "job_id": "nope",
                               "wait": 1})
        assert not bad["ok"] and "unknown job" in bad["error"]
    finally:
        gate.set()
        srv.shutdown()
        srv.server_close()


def test_submit_wait_blocks_on_the_new_server_and_polls_an_old_one(
        tmp_path, monkeypatch, capsys):
    from raft_tla_tpu import cli

    def ex(req, job):
        time.sleep(0.4)
        return {"ok": True, "distinct": 5, "generated": 9, "diameter": 2,
                "stop_reason": "exhausted", "violation": None,
                "deadlock": None}

    srv = _stub_server(tmp_path, ex)
    addr = srv.server_address
    mt = srv_mod._METRICS
    try:
        # The new server: no status poll, the answer within 50 ms of the
        # job's end at the DEFAULT --poll-interval of a second.
        polls = mt.counter_value("server/requests/status")
        job = roundtrip(addr, {"op": "submit", "tenant": "t",
                               "job": {"op": "check",
                                       "cfg_text": "x"}})["job"]
        done, doc = cli._wait_for_job(_submit_args(srv), job)
        at = time.time()
        assert done["state"] == "done" and doc["distinct"] == 5
        assert at - done["finished_ts"] < 0.05
        assert mt.counter_value("server/requests/status") == polls
        # The whole subcommand.
        rc = cli.main(["submit", CFG, "--server",
                       f"127.0.0.1:{addr[1]}", "--wait"])
        assert rc == 0
        assert "distinct 5 | generated 9" in capsys.readouterr().out
        assert mt.counter_value("server/requests/status") == polls

        # A server from before the op: its ping has no "wait" and its
        # ``result`` knows nothing of the field.
        orig = srv_mod.handle_request

        def old_server(req, manager=None):
            if req.get("op") == "result":
                req = {k: v for k, v in req.items() if k != "wait"}
            resp = orig(req, manager)
            if req.get("op") == "ping":
                resp.pop("wait", None)
            return resp

        monkeypatch.setattr(srv_mod, "handle_request", old_server)
        rc = cli.main(["submit", CFG, "--server", f"127.0.0.1:{addr[1]}",
                       "--wait", "--poll-interval", "0.05"])
        assert rc == 0
        assert "distinct 5 | generated 9" in capsys.readouterr().out
        assert mt.counter_value("server/requests/status") > polls
    finally:
        srv.shutdown()
        srv.server_close()


def test_a_served_check_leaves_its_spans_and_job_end(jobsrv):
    """Through the real service and engine: ``job_setup`` and
    ``job_respond`` around the engine's run, inside the executor's
    ``job``; the ``job_end`` line beside the journal."""
    srv, _hist = jobsrv
    addr = srv.server_address
    before = roundtrip(addr, {"op": "stats"})["metrics"]
    job = roundtrip(addr, {"op": "submit", "tenant": "spans",
                           "job": dict(BASE, max_diameter=3)})["job"]
    got = roundtrip(addr, {"op": "result", "job_id": job["id"],
                           "wait": 600})
    assert got["state"] == "done" and got["result"]["distinct"] == 113
    after = roundtrip(addr, {"op": "stats"})["metrics"]

    def grew(name):
        a = after["histograms"].get(name, {"count": 0, "total": 0.0})
        b = before["histograms"].get(name, {"count": 0, "total": 0.0})
        return a["count"] - b["count"], a["total"] - b["total"]

    (n_job, s_job), (n_set, s_set) = grew("serve/job"), grew(
        "serve/job_setup")
    (n_run, s_run), (n_resp, s_resp) = grew("scope/run"), grew(
        "serve/job_respond")
    assert (n_job, n_set, n_run, n_resp) == (1, 1, 1, 1)
    assert s_job >= s_set + s_run + s_resp > 0
    assert grew("serve/journal")[0] == 4
    assert grew("serve/request/result")[0] == 1
    assert grew("phase/request/result")[0] == 0
    assert after["counters"]["jobs/executed"] \
        - before["counters"].get("jobs/executed", 0) == 1
    ends = [e for e in map(json.loads, open(os.path.join(
        srv.jobs.base_dir, "events.jsonl")))
        if e["event"] == "job_end" and e["job"] == job["id"]]
    assert len(ends) == 1 and ends[0]["cached"] is False
    assert ends[0]["engine_wall_s"] == got["result"]["wall_seconds"]
    assert ends[0]["run_s"] >= ends[0]["engine_wall_s"]
