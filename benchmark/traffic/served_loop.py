"""Traffic kind ``served_loop``: tenants in a closed loop over the checker
service's socket, on warm engines of several programs.

The benchmark's process owns the chip, so the service runs in it:
``raft_tla_tpu/server.py serve()`` on a loopback port the OS chooses, its
journal and per-job directories in the run's temporary directory,
``serve_forever`` on a thread.  The clients are threads of this process
that speak to it as ``submit`` does, through ``cli._client_call`` over
real sockets: ``submit``, then ``result`` with ``wait`` (which blocks
until the job is terminal), then the next ``submit``: one connection and
one job outstanding a tenant, think time 0.

Set-up, outside the window: the server started and asked (``ping``)
whether its ``result`` can block (a server that cannot is the parent of
the PR that added the op: the run exits 4 at once, it never polls); each
program run once, in the tenants' order, which builds, compiles or loads
its engine, and held to its pin (a tenant's ``warm_up`` submit is that
first run: ``ci``'s fills the result cache).  The window's clients start
in the same order, each once the submit of the one before was
acknowledged, so the first picks fall alike in every run.  The window: ``--seconds`` of submissions on the harness's clock,
closed when the jobs in flight at the deadline have answered, at least
``min_jobs`` in all.  ``verdict_s`` = the window's wall / the jobs that
reached ``done`` in it, cache hits counted.  Everything compared is
compared after the window, on what the clients were sent.

Mix parameters (``benchmark/traffic/<mix>.json``):
  tenants   in the order they start: ``name``; ``jobs``, the cycle of
            job specs a tenant sends (``program`` names one of the
            configuration's ``programs``, ``cache`` says whether the
            submit may be answered from the result cache, ``seed``:
            ``"cycled"`` takes the tenant's ``seeds`` in turn, from
            ``--seed`` modulo their number; ``cfg`` and whatever request
            field a spec states besides, ``mode``, ``walks``,
            ``max_diameter``, the sizes, must be the program's own: the
            request is the configuration's, the mix says it again for
            the reader and the run holds the two equal); ``warm_up``:
            whether set-up's first run of that program may be cached
  min_jobs  least number of jobs in a window
  forbidden_events  run events no job's own log may hold

The configuration (``benchmark/configs/<config>.json``) states the
service's settings (``service``) and, a program, the cfg text, the
request fields every job of it carries, its pin and what its answer must
hold (``expect``).

``correct`` (every comparison exact): the order of starts the plain
reference derives from the record of submits and picks
(``benchmark/reference/served.py``; tenant and cache key of a submit are
what THIS harness sent, the order is the journal's) equals the
journal's; the reference's hits are the jobs answered ``cached: true``,
and a hit's answer is the answer of the run it repeats; every answer
equals its pin (per-level table, totals, the violation's name and depth,
a trace legal under the interpreter, a hunt's fingerprint and length);
every acknowledged job is in the journal with each of its transitions
once, reached ``done`` and was answered; ``jobs/executed`` rose by the
fresh jobs; no engine- or swarm-cache miss, no compile of an engine's
program, no rejected, failed or cancelled job and no request in error
inside the window; ``jobs.replay`` of the journal gives the registry
the manager holds.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import statistics
import sys
import threading
import time

import bench_lib as lib

ENGINE_PROGRAMS = ("chunk", "ingest")   # substrings of jax's program names


def fingerprint(inner: dict) -> str:
    """The harness's own fingerprint of a request it sent."""
    return hashlib.sha256(json.dumps(inner, sort_keys=True).encode()
                          ).hexdigest()


class Client:
    """What one tenant's connection does: a job at a time, every answer
    kept as it came."""

    def __init__(self, call, addr: str, wait_s: float):
        self.call, self.addr, self.wait_s = call, addr, wait_s
        self.sent = []      # one record a submit, in order

    def serve_one(self, tenant: str, spec: dict, inner: dict,
                  acked=None) -> dict:
        """``acked`` (a ``threading.Event``) is set once the submit was
        answered, whatever the answer."""
        rec = {"tenant": tenant, "spec": spec, "inner": inner,
               "key": fingerprint(inner) if spec["cache"] else None,
               "job": None, "state": None, "doc": None, "summary": None,
               "error": None, "t_submit": time.perf_counter()}
        self.sent.append(rec)
        try:
            try:
                ack = self.call(self.addr,
                                {"op": "submit", "tenant": tenant,
                                 "job": inner,
                                 "cache": bool(spec["cache"])}, 60.0)
            finally:
                if acked is not None:
                    acked.set()
            if not ack.get("ok"):
                raise ValueError(f"submit refused: {ack.get('error')}")
            rec["job"] = ack["job"]["id"]
            while True:
                res = self.call(self.addr,
                                {"op": "result", "job_id": rec["job"],
                                 "wait": self.wait_s}, self.wait_s + 60.0)
                if not res.get("ok"):
                    raise ValueError(f"result refused: {res.get('error')}")
                if not res.get("timed_out"):
                    break
            rec.update(state=res["state"], doc=res["result"],
                       summary=res["job"])
        except (OSError, ValueError, KeyError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t_done"] = time.perf_counter()
        return rec


def job_requests(tenant: dict, programs: dict, seed: int):
    """The endless cycle of (spec, inner request) one tenant sends."""
    seeds = [int(s) for s in tenant.get("seeds") or []]
    n = 0
    while True:
        for spec in tenant["jobs"]:
            prog = programs[spec["program"]]
            inner = dict(prog["request"],
                         cfg_text="\n".join(prog["cfg_text"]) + "\n")
            if spec.get("seed") == "cycled":
                inner["seed"] = seeds[(seed + n) % len(seeds)]
                n += 1
            yield spec, inner


def run(ctx) -> dict:
    from raft_tla_tpu import server as srv_mod
    from raft_tla_tpu.cli import _client_call

    service = ctx.config["service"]
    ready_s = time.perf_counter() - ctx.t_start

    # -- the service, in this process --------------------------------------
    job_dir = os.path.join(ctx.tmp, "jobs")
    srv = srv_mod.serve(service["host"], service["port"], job_dir=job_dir,
                        job_queue_capacity=service["job_queue"])
    thread = threading.Thread(target=srv.serve_forever, name="serve",
                              daemon=True)
    thread.start()
    addr = f"{srv.server_address[0]}:{srv.server_address[1]}"
    try:
        ping = _client_call(addr, {"op": "ping"}, 60.0)
        if not ping.get("wait"):
            print(f"benchmark: this checkout's server has no blocking "
                  f"'result' (ping answered {ping}); the closed loop is "
                  f"not run over a polling client", file=sys.stderr,
                  flush=True)
            raise SystemExit(4)
        return _run(ctx, srv, addr, job_dir, ready_s)
    finally:
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=30.0)


def _run(ctx, srv, addr: str, job_dir: str, ready_s: float) -> dict:
    from raft_tla_tpu.cli import _client_call as call
    from raft_tla_tpu.serving import jobs as jobs_mod

    cell, config, ledger = ctx.cell, ctx.config, ctx.ledger
    programs, metrics = config["programs"], srv.jobs.metrics
    checker = Checker(config, ledger)
    wait_s = float(config["service"]["result_wait_seconds"])
    tenants = cell["tenants"]
    for tenant in tenants:
        for spec in tenant["jobs"]:
            prog = programs[spec["program"]]
            stated = {k: v for k, v in spec.items()
                      if k not in ("program", "cfg", "cache", "seed")}
            ledger.exact(
                f"mix: {tenant['name']}'s {spec['program']} job is the "
                "configuration's request",
                (spec["cfg"], stated),
                (prog["cfg_name"],
                 {k: prog["request"].get(k, "exhaustive" if k == "mode"
                                         else None) for k in stated}))

    # -- set-up: each program once (engines built, compiled or loaded), in --
    # -- the tenants' order; a tenant's warm-up submit is that first run ---
    boot = Client(call, addr, wait_s)
    t0 = time.perf_counter()
    ran = set()
    for tenant in tenants:
        warm = tenant.get("warm_up") or {}
        for spec in tenant["jobs"]:
            if spec["program"] in ran:
                continue
            ran.add(spec["program"])
            cache = (warm["cache"] if warm.get("program") == spec["program"]
                     else False)
            inner = next(i for s, i in job_requests(
                tenant, programs, ctx.args.seed - 1) if s is spec)
            t1 = time.perf_counter()
            boot.serve_one(tenant["name"], dict(spec, cache=cache), inner)
            print(f"setup: first {spec['program']} job "
                  f"{time.perf_counter() - t1:.2f}s", flush=True)
    first_s = time.perf_counter() - t0
    warmup_s = ctx.compiles.between(t0, t0 + first_s)["seconds"]
    hist = metrics.snapshot()["histograms"]
    build_s = hist.get("serve/job_setup", {}).get("total", 0.0)
    print(f"setup: ready {ready_s:.2f}s, {len(boot.sent)} jobs "
          f"{first_s:.2f}s (job_setup, engines built in it, {build_s:.2f}s;"
          f" compiles and cache loads {warmup_s:.2f}s)", flush=True)

    # -- the window --------------------------------------------------------
    clients = [Client(call, addr, wait_s) for _ in tenants]
    per_tenant = math.ceil(int(cell["min_jobs"]) / len(tenants))
    base = metrics.snapshot()["counters"]
    seconds = ctx.args.seconds

    # The clients start in the mix's order, each once the submit of the
    # one before was acknowledged: the order set-up served the tenants in,
    # so the first picks fall the same way whichever thread runs first.
    turns = [threading.Event() for _ in range(len(tenants) + 1)]

    def loop(n, client, tenant, t_win0):
        reqs = job_requests(tenant, programs, ctx.args.seed)
        turns[n].wait()
        acked = turns[n + 1]
        while (time.perf_counter() - t_win0 < seconds
               or len(client.sent) < per_tenant):
            spec, inner = next(reqs)
            client.serve_one(tenant["name"], spec, inner, acked)
            acked = None

    with lib.traced(ctx):
        t_win0 = time.perf_counter()
        threads = [threading.Thread(target=loop, args=(n, c, t, t_win0),
                                    name=f"client-{t['name']}")
                   for n, (c, t) in enumerate(zip(clients, tenants))]
        for th in threads:
            th.start()
        turns[0].set()
        for th in threads:
            th.join()
        t_win1 = time.perf_counter()
    wall = t_win1 - t_win0
    setup_s = t_win0 - ctx.t_start
    counters = {k: v - base.get(k, 0)
                for k, v in metrics.snapshot()["counters"].items()}
    window = sorted((r for c in clients for r in c.sent),
                    key=lambda r: r["t_done"])
    done = [r for r in window if r["state"] == "done"]
    turn = [r["t_done"] - r["t_submit"] for r in window]
    print(f"window: {wall:.3f}s wall, {len(window)} jobs sent, "
          f"{len(done)} done ({sum(bool(r['summary'] and r['summary']['cached']) for r in done)} "
          f"from the result cache); a client's submit-to-answer median "
          f"{statistics.median(turn):.4f}s, max {max(turn):.4f}s",
          flush=True)
    ends = sorted(r["t_done"] - t_win0 - seconds for r in window)
    print(f"window: the last answer before the deadline came "
          f"{-max((e for e in ends if e < 0), default=0.0):.3f}s before it,"
          f" the first after it {min((e for e in ends if e >= 0), default=0.0):.3f}s"
          f" after: a client's next submit falls inside the window or not "
          f"by that margin", flush=True)
    by_tenant = {t["name"]: [r["t_done"] - r["t_submit"] for r in c.sent]
                 for t, c in zip(tenants, clients)}
    print("window by tenant (jobs, mean submit-to-answer s): " + ", ".join(
        f"{k} {len(v)} {statistics.mean(v):.4f}"
        for k, v in by_tenant.items()), flush=True)

    # -- the service's own record, then the service stopped ----------------
    registry = srv.jobs.jobs_doc()["jobs"]
    held = {}
    for j in registry:
        if j["has_result"]:
            # As a client is sent it: through JSON (tuples are lists).
            held[j["id"]] = json.loads(json.dumps(
                srv.jobs.result(j["id"]), default=str))
    srv.jobs.close(wait=True)       # the journal settled
    journal = lib.read_events(os.path.join(job_dir, "jobs.jsonl"))
    in_window = {r["job"] for r in window}
    job_ends = [e for e in lib.read_events(os.path.join(job_dir,
                                                        "events.jsonl"))
                if e.get("event") == "job_end" and e.get("job") in in_window]
    by_class = {}
    for e in job_ends:
        by_class.setdefault((e["tenant"], e["cached"]), []).append(e)
    print("window by tenant and cached, the manager's clock (jobs, mean "
          "queue_wait_s, run_s, engine_wall_s): " + "; ".join(
              f"{t}{' hit' if c else ''} {len(v)} "
              + " ".join(f"{statistics.mean(e[k] for e in v):.4f}"
                         for k in ("queue_wait_s", "run_s",
                                   "engine_wall_s"))
              for (t, c), v in sorted(by_class.items())), flush=True)

    # -- correct -----------------------------------------------------------
    everything = boot.sent + window
    for rec in boot.sent:
        checker.answer("set-up", rec)
    starts = checker.schedule(journal, everything)
    for n, rec in enumerate(window):
        checker.answer(f"job {n} ({rec['tenant']})", rec)
    checker.window(window, counters, ctx.compiles, t_win0, t_win1)
    checker.durable(journal, everything, registry, held,
                    jobs_mod.replay(os.path.join(job_dir, "jobs.jsonl")),
                    jobs_mod.summarize)

    # -- for the readers ---------------------------------------------------
    events = []
    for job in starts:
        path = os.path.join(job_dir, job, "events.jsonl")
        if job in in_window and os.path.exists(path):
            events += lib.read_events(path)
    for bad in cell["forbidden_events"]:
        ledger.exact(f"'{bad}' events in the window's jobs",
                     sum(e["event"] == bad for e in events), 0)
    return {
        "end_to_end": {"setup_s": setup_s,
                       "verdict_s": wall / max(len(done), 1)},
        "window_wall_s": wall, "phases": {}, "events": events,
        "counters": counters, "verdicts": len(done),
        "spans": {"make_engine": build_s, "warmup": warmup_s,
                  "first_check": first_s},
        "trace_dir": ctx.trace_dir, "chunk_program": "chunk",
        "served": {"job_ends": job_ends, "jobs": len(window)},
    }


class Checker:
    """The comparisons that decide ``correct``, each printed beside its
    limit by the ledger."""

    def __init__(self, config: dict, ledger):
        self.config, self.ledger = config, ledger
        self.programs = config["programs"]
        self.refs, self.pins, self.hunts = {}, {}, {}
        for name, prog in self.programs.items():
            self.refs[name] = lib.reference(prog)   # benchmark/ on sys.path
            self.pins[name] = lib.load_pinned(prog["pinned"])
            if prog.get("pinned_hunts"):
                self.hunts[name] = lib.load_module(
                    "traffic", "swarm_hunt").load_hunts(prog["pinned_hunts"])
        from reference import served
        self.served = served
        self.stored = {}        # cache key -> the answer of the run stored
        self.hits = set()       # the reference's, once ``schedule`` ran

    # -- one answer against its pin ----------------------------------------
    def answer(self, what: str, rec: dict) -> None:
        ledger, served = self.ledger, self.served
        ledger.exact(f"{what}: acknowledged, reached a terminal state, "
                     "answered", (rec["error"], rec["state"],
                                  rec["doc"] is not None),
                     (None, "done", True))
        doc = rec["doc"]
        if doc is None:
            return
        name = rec["spec"]["program"]
        prog, ref, pin = self.programs[name], self.refs[name], self.pins[name]
        expect = prog["expect"]
        if rec["key"] is not None:
            if not rec["summary"]["cached"]:
                self.stored.setdefault(rec["key"], doc)
            else:
                ledger.true(f"{what}: a hit carries the answer of the run "
                            "it repeats", doc == self.stored.get(rec["key"]))
        if "invariant" in expect:
            viol = doc.get("violation") or {}
            trace = viol.get("trace") or []
            ledger.exact(f"{what}: invariant reported violated",
                         viol.get("invariant"), expect["invariant"])
            faults = served.trace_faults(
                trace, ref.dims, ref.constraint, ref.oracle, ref.pystate,
                ref.rd.no_leader_py)
            for f in faults[:4]:
                print(f"{what}: {f}", flush=True)
            ledger.exact(f"{what}: faults of the trace under the "
                         "reference", len(faults), 0)
        if name in self.hunts:
            seed = rec["inner"]["seed"]
            pinned = self.hunts[name].get(seed)
            ledger.exact(f"{what}: seed {seed} (fingerprint, trace "
                         "length) equals the pinned record",
                         (int(viol.get("fingerprint", "0x0"), 16),
                          len(trace)),
                         pinned and (int(pinned[2], 16), pinned[3]))
            ledger.true(f"{what}: depth within [shortest, max_depth]",
                        expect["shortest"] <= len(trace) - 1
                        <= expect["max_depth"], f"{len(trace) - 1}")
            ledger.exact(f"{what}: (mode, walks, steps) of one chunk",
                         (doc.get("mode"), doc.get("walks"),
                          doc.get("steps")),
                         ("swarm", prog["request"]["walks"],
                          prog["request"]["walks"] * expect["chunk"]))
            return
        levels = served.levels_of(doc)
        if "depth" in expect:
            ledger.exact(f"{what}: counterexample depth", len(trace) - 1,
                         expect["depth"])
            # The violation sits in level `depth`: those below are whole.
            lib.compare_levels(ledger, levels, pin,
                               range(expect["depth"]), what)
        else:
            top = expect["max_diameter"]
            ledger.exact(f"{what}: (violation, deadlock, diameter, "
                         "distinct, generated)",
                         (doc.get("violation"), doc.get("deadlock"),
                          doc.get("diameter"), doc.get("distinct"),
                          doc.get("generated")),
                         (None, None, top, expect["distinct"],
                          expect["generated"]))
            ledger.exact(f"{what}: the pin's totals at level {top}",
                         pin[top][1:], (expect["distinct"],
                                        expect["generated"]))
            lib.compare_levels(ledger, levels, pin, range(top + 1), what)
        ledger.exact(f"{what}: pipeline", doc.get("pipeline"), "v2")

    # -- the order of starts and the hits ----------------------------------
    def schedule(self, journal: list, sent: list) -> list:
        """Holds the journal's order of starts and the answers' ``cached``
        flags to the reference's; returns the journal's starts."""
        ledger = self.ledger
        mine = {r["job"]: r for r in sent if r["job"]}
        log, starts, unknown = [], [], 0
        for rec in journal:
            if rec.get("rec") == "submit":
                job = rec["job"]["id"]
                unknown += job not in mine
                log.append({"ev": "submit", "job": job,
                            "tenant": mine.get(job, {}).get("tenant"),
                            "key": mine.get(job, {}).get("key")})
            elif rec.get("state") == "admitted":
                log.append({"ev": "pick"})
                starts.append(rec["id"])
            elif rec.get("state") == "cancelled":
                log.append({"ev": "cancel", "job": rec["id"]})
            elif rec.get("state") in ("done", "failed"):
                log.append({"ev": "end", "job": rec["id"],
                            "ok": rec["state"] == "done"})
        ledger.exact("journaled submits this harness never sent", unknown, 0)
        want = self.served.schedule(log)
        ledger.exact("order of starts: the journal's equals the "
                     "reference's", starts, want["starts"])
        cached = {r["job"] for r in sent
                  if r["summary"] and r["summary"]["cached"]}
        ledger.exact("jobs answered cached: true are the reference's hits",
                     sorted(cached), sorted(want["hits"]))
        self.hits = want["hits"]
        return starts

    # -- what the window may not hold --------------------------------------
    def window(self, window, counters, compiles, t0, t1) -> None:
        ledger = self.ledger
        fresh = sum(1 for r in window if r["job"] not in self.hits)
        ledger.exact("jobs/executed in the window equals its fresh jobs",
                     counters.get("jobs/executed", 0), fresh)
        for name in ("server/engine_cache/misses",
                     "server/swarm_cache/misses"):
            ledger.exact(f"{name} in the window", counters.get(name, 0), 0)
        for prefix in ("server/rejected/", "server/errors/", "jobs/failed/",
                       "jobs/cancelled/", "jobs/rejected/",
                       "jobs/journal_errors", "jobs/executor_errors"):
            ledger.exact(f"{prefix}* in the window",
                         sum(v for k, v in counters.items()
                             if k.startswith(prefix)), 0)
        inside = sorted(n for (t, _d, n) in compiles.records
                        if t0 <= t <= t1
                        and any(p in n for p in ENGINE_PROGRAMS))
        comp = compiles.between(t0, t1)
        print(f"window compiles: {comp['count']} taking "
              f"{comp['seconds']:.3f}s in all", flush=True)
        ledger.exact("compiles of an engine's program inside the window",
                     inside, [])

    # -- durability ----------------------------------------------------------
    def durable(self, journal, sent, registry, held, replayed,
                summarize) -> None:
        ledger = self.ledger
        lines = {}
        for rec in journal:
            job = rec["job"]["id"] if rec.get("rec") == "submit" \
                else rec.get("id")
            what = "submit" if rec.get("rec") == "submit" \
                else rec.get("state")
            lines.setdefault(job, []).append(what)
        whole = ["submit", "admitted", "running", "done"]
        acked = [r["job"] for r in sent if r["job"]]
        ledger.exact("acknowledged jobs whose journal lines are not "
                     "submit, admitted, running, done, each once",
                     [j for j in acked if lines.get(j) != whole], [])
        ledger.exact("jobs sent and never acknowledged",
                     sum(1 for r in sent if not r["job"]), 0)
        jobs, results, problems = replayed
        ledger.exact("journal lines the replay could not use",
                     len(problems), 0)
        ledger.exact("the journal replays to the registry the manager "
                     "holds",
                     [summarize(jobs[j["id"]],
                                has_result=j["id"] in results)
                      if j["id"] in jobs else None for j in registry],
                     registry)
        ledger.exact("the replayed results are the ones the manager "
                     "answers with",
                     {k: results.get(k) for k in held}, held)
