"""Command-line interface — the ``java tlc2.TLC -config X.cfg X.tla`` analog.

    python -m raft_tla_tpu check    <cfg> [engine options]
    python -m raft_tla_tpu simulate <cfg> [--num-steps N --depth D]

Platform selection: by default jax picks the ambient backend (the real TPU
where available).  ``--platform cpu`` forces CPU and must be applied before
jax initializes, which is why all heavy imports here are deferred until
after argument parsing.
"""

from __future__ import annotations

import argparse
import os
import sys


def _write_metrics(path: str, registry) -> None:
    """--metrics-out: final registry snapshot as pretty JSON.  Under a
    process group every controller runs this at exit, so the path gets
    the same per-controller piece suffix as event logs/checkpoints and
    the write is atomic (tmp + rename) — two hosts must never interleave
    into one file on the shared filesystem."""
    import json
    try:
        import jax
        pi, pc = jax.process_index(), jax.process_count()
    except Exception:
        pi, pc = 0, 1
    if pc > 1:
        root, ext = os.path.splitext(path)
        path = f"{root}.p{pi}of{pc}{ext or '.json'}"
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp{pi}"
    with open(tmp, "w") as f:
        json.dump(registry.snapshot(), f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def _run_analyze(args) -> int:
    """``analyze``: run the static-analysis passes (analysis/) over one
    model and gate on ERROR findings — the per-PR kernel-correctness
    gate CI runs.  Exit 0 iff no (un-allowlisted) ERROR finding."""
    import json

    from .analysis import PASSES, run_analysis
    from .analysis.lane_map import FIELDS
    from .obs import MetricsRegistry, RunEventLog

    passes = None
    if args.passes is not None:
        passes = tuple(p.strip() for p in args.passes.split(",")
                       if p.strip())
        unknown = [p for p in passes if p not in PASSES]
        if unknown or not passes:
            # Exit 2 (usage error), never a silent no-op run: a typo'd
            # pass name must not report "analysis OK" on zero passes.
            print(f"analyze: unknown pass(es) "
                  f"{', '.join(unknown) or '(none given)'}; valid "
                  f"passes: {', '.join(PASSES)}", file=sys.stderr)
            return 2

    if args.cfg is not None:
        from .engine.check import initial_states
        from .utils.cfg import load_config
        setup = load_config(args.cfg, max_log=args.max_log,
                            n_msg_slots=args.n_msg_slots)
        dims, bounds = setup.dims, setup.bounds
        # The cfg's INVARIANT list narrows the POR visibility condition
        # to what this model actually checks.
        invariant_names = list(setup.invariants)
        # Randomized smoke roots say nothing about the reachable set;
        # the bounds pass then seeds from the declared domain envelope.
        roots = None if setup.smoke else initial_states(setup)
    else:
        from .models.dims import RaftDims
        from .models.pystate import init_state
        dims = RaftDims(n_servers=3, n_values=2,
                        max_log=args.max_log or 8,
                        n_msg_slots=args.n_msg_slots or 32)
        bounds, roots, invariant_names = None, [init_state(dims)], None

    lane_caps = {}
    for spec in args.shrink_lane:
        field, _, hi = spec.partition("=")
        if field not in FIELDS or not hi.lstrip("-").isdigit():
            raise SystemExit(
                f"--shrink-lane wants FIELD=HI with FIELD in {FIELDS}, "
                f"got {spec!r}")
        lane_caps[field] = (0, int(hi))

    metrics = MetricsRegistry()
    with RunEventLog(args.events_out) as evlog:
        report = run_analysis(
            dims, bounds=bounds, init_states=roots,
            **({"passes": passes} if passes else {}),
            allowlist=args.allow, lane_caps=lane_caps or None,
            invariant_names=invariant_names,
            metrics=metrics, evlog=evlog)
    if args.out:
        report.write_json(args.out)
    if args.por_artifact:
        table = report.pass_summaries.get("por", {}).get("table")
        if table is None:
            print("--por-artifact requires the 'por' pass to run "
                  "(add it to --passes)", file=sys.stderr)
            return 2
        unsound = any(f.code == "certificate-unsound"
                      for f in report.findings if f.pass_name == "por")
        if unsound:
            # The pass's certificate-unsound self-check failed: never
            # materialize a validly-fingerprinted artifact for a mask
            # whose side conditions did not verify.  Checked on the raw
            # finding code, not post-allowlist severity — --allow can
            # un-gate the EXIT status, never the artifact.
            print("--por-artifact refused: the por pass reported "
                  "certificate-unsound findings (see report)",
                  file=sys.stderr)
        else:
            with open(args.por_artifact, "w") as f:
                json.dump(table, f, indent=2, sort_keys=True)
                f.write("\n")
    if args.json:
        print(json.dumps(report.to_json(), indent=2, sort_keys=True))
    else:
        print(report.render_text())
    if args.metrics_out:
        _write_metrics(args.metrics_out, metrics)
    return 0 if report.ok else 1


def _render_watch_line(snap: dict) -> str:
    """One console line per watch snapshot — the TLC-style progress
    shape (obs/flight.py records), annotated with the run context."""
    run = snap.get("run") or {}
    prog = snap.get("progress") or {}
    level = snap.get("level") or {}
    parts = []
    if prog:
        parts.append(
            f"distinct {prog.get('distinct', 0):,} | generated "
            f"{prog.get('generated', 0):,} | diameter "
            f"{prog.get('diameter', 0)} | frontier "
            f"{prog.get('frontier', 0):,} | next "
            f"{prog.get('next_count', 0):,} | elapsed "
            f"{prog.get('elapsed', 0):,.0f}s")
    elif level:
        parts.append(
            f"level {level.get('level')} done | distinct "
            f"{level.get('distinct', 0):,} | generated "
            f"{level.get('generated', 0):,}")
    else:
        parts.append("no telemetry yet")
    ctx = " ".join(str(run[k]) for k in ("engine", "pipeline")
                   if run.get(k))
    live = "live" if snap.get("armed") else "idle"
    job = snap.get("job")
    if job is not None:
        # Per-job watch: the job's registry state leads; ring telemetry
        # (progress) renders only while this job owns the device.
        head = f"job {job['id']} [{job['state']}]"
        if snap.get("running"):
            if prog:
                return f"watch[{head}] {parts[0]}" \
                    + (f"  ({ctx})" if ctx else "")
            return f"watch[{head}] compiling/warming — no progress yet"
        return f"watch[{head}] tenant={job.get('tenant')}"
    return f"watch[{live}] {parts[0]}" + (f"  ({ctx})" if ctx else "")


def _watch_http(url: str, interval: float, count: int, timeout: float,
                as_json: bool) -> int:
    """Poll a --metrics-port listener's /flight endpoint and render a
    console; exits when the watched run's run_end shows up (or after
    --count polls).  Tolerates a listener that is not up YET (the watch
    is usually launched alongside the run) with a bounded retry."""
    import json
    import time
    import urllib.error
    import urllib.request
    base = url.rstrip("/")
    if not base.endswith("/flight"):
        base += "/flight"
    # Watchers render only the newest record per kind — ask the
    # listener to trim (full-ring polls would serialize hundreds of KB
    # per tick under the recorder lock the engine writes through).
    poll_url = base + "?last=8"

    def _refused(exc) -> bool:
        """Connection REFUSED (listener torn down) vs merely slow
        (timeout on a pegged host mid-compilation): only refusal means
        the run process is gone."""
        reason = getattr(exc, "reason", exc)
        return isinstance(reason, ConnectionRefusedError)

    sent = 0
    refused = 0
    attach_end_seq = None
    t_start = time.monotonic()
    t_last_ok = None
    while True:
        try:
            with urllib.request.urlopen(poll_url, timeout=timeout) as r:
                doc = json.loads(r.read().decode())
            refused = 0
            t_last_ok = time.monotonic()
        except (OSError, urllib.error.URLError, ValueError) as e:
            refused = refused + 1 if _refused(e) else 0
            if sent and refused >= 3:
                # The listener answered before and now actively refuses:
                # the run process exited (the CLI tears the listener
                # down at run end) — a completed watch, not a failure.
                # Slow/timed-out polls (host pegged by compilation) do
                # NOT count: the console must ride those out.
                print("watch: listener gone — run process exited",
                      flush=True)
                return 0
            # Give-up budgets are ELAPSED-time based (failure counts
            # would stretch with the per-poll timeout): 300 s of
            # silence after a successful poll, and a generous 600 s
            # for the listener to come up at all — it only binds after
            # jax import + backend init + engine build, which takes
            # minutes with a cold compile cache (the server watch op's
            # in-process grace is shorter, 120 s, because there the
            # backend is already up).
            now = time.monotonic()
            if sent and t_last_ok is not None and now - t_last_ok > 300.0:
                print("watch: listener unresponsive too long; giving up",
                      file=sys.stderr)
                return 1
            if not sent and now - t_start > 600.0:
                print("watch: listener unreachable; giving up",
                      file=sys.stderr)
                return 1
            time.sleep(interval)
            continue
        records = doc.get("records") or {}
        events = records.get("event") or []
        run_ends = [e for e in events if e.get("event") == "run_end"]
        if attach_end_seq is None:
            # First successful poll: note the newest pre-existing
            # run_end so only a run ending AFTER attach closes the
            # console.
            attach_end_seq = run_ends[-1]["seq"] if run_ends else 0
        snap = {
            "armed": bool(doc.get("armed")),
            "run": (records.get("run_context") or [None])[-1],
            "progress": (records.get("progress") or [None])[-1],
            "level": next((e for e in reversed(events)
                           if e.get("event") == "level_complete"), None),
        }
        print(json.dumps(doc, default=str) if as_json
              else _render_watch_line(snap), flush=True)
        sent += 1
        ended = run_ends and run_ends[-1]["seq"] > attach_end_seq
        if ended:
            end = run_ends[-1]
            print(f"watch: run ended — stop_reason="
                  f"{end.get('stop_reason')} distinct="
                  f"{end.get('distinct')} generated="
                  f"{end.get('generated')}", flush=True)
            return 0
        if count and sent >= count:
            return 0
        time.sleep(interval)


def _client_call(target: str, req: dict, timeout: float) -> dict:
    """One request/response line against a checker service (pure
    client, no jax) — the submit/jobs subcommands' transport."""
    import json
    import socket
    host, _, port = target.partition(":")
    with socket.create_connection((host or "127.0.0.1",
                                   int(port or 8610)),
                                  timeout=timeout) as s:
        s.sendall((json.dumps(req) + "\n").encode())
        s.settimeout(timeout)
        f = s.makefile("rb")
        line = f.readline()
    if not line:
        raise OSError("connection closed by server")
    return json.loads(line)


def _run_swarm(args, setup, resolve) -> int:
    """``check --mode swarm``: the randomized-walk tier
    (engine/swarm.py).  Same surface contract as the exhaustive
    branch: a summary line, an optional history-ledger entry
    (``kind=swarm``), and on a violation the rendered TLC-style
    counterexample plus exit 1."""
    from .engine.check import initial_states, make_swarm_engine

    ckpt = resolve(args.checkpoint_dir, "CHECKPOINT_DIR", None)
    engine = make_swarm_engine(
        setup, walks=args.walks, max_depth=args.max_depth,
        batch=args.batch, pipeline=args.pipeline,
        events_out=resolve(args.events_out, "EVENTS_OUT", None),
        checkpoint_dir=ckpt,
        counterexample_dir=(
            resolve(args.counterexample_dir, "COUNTEREXAMPLE_DIR", None)
            or ("." if args.render_trace and not ckpt else None)),
        progress_seconds=float(
            resolve(args.progress_interval, "PROGRESS_SECONDS", 5.0)),
        xla_profile_chunks=resolve(args.xla_profile, "XLA_PROFILE",
                                   None),
        xla_profile_dir=args.xla_profile_dir)
    max_seconds = (args.max_seconds if args.max_seconds is not None
                   else setup.max_seconds)
    metrics_srv = None
    metrics_port = resolve(args.metrics_port, "METRICS_PORT", None)
    if metrics_port:
        from .obs import start_metrics_server
        from .obs.flight import RECORDER
        try:
            metrics_srv, _ = start_metrics_server(
                int(metrics_port), engine.metrics, flight=RECORDER)
            print(f"metrics: http://127.0.0.1:"
                  f"{metrics_srv.server_address[1]}/metrics "
                  f"(+ /flight)", file=sys.stderr)
        except OSError as e:
            metrics_srv = None
            print(f"metrics: cannot listen on port {metrics_port} "
                  f"({e}); continuing without the listener",
                  file=sys.stderr)
    try:
        res = engine.run(initial_states(setup, seed=args.seed),
                         seed=args.seed, max_seconds=max_seconds)
    finally:
        if metrics_srv is not None:
            metrics_srv.shutdown()
            metrics_srv.server_close()
    print(f"swarm: {res.walks} walks x depth {engine.max_depth} | "
          f"{res.steps} steps ({res.steps_per_second:,.0f} steps/s, "
          f"{res.walks_per_second:,.0f} walks/s) | visited "
          f"{res.visited} | traces {res.traces} | deepest "
          f"{res.diameter} | stop: {res.stop_reason} | "
          f"{res.wall_seconds:.2f}s")
    if res.report.get("hunt"):
        from .obs import hunt as hunt_mod
        print(hunt_mod.render_report(res.report["hunt"]))
    if args.metrics_out:
        _write_metrics(args.metrics_out, engine.metrics)
    history_path = resolve(args.history, "HISTORY", None)
    if history_path:
        from .obs import history as history_mod
        from .obs.flight import host_fingerprint
        with open(args.cfg) as f:
            cfg_text = f.read()
        hunt_sum = None
        if res.report.get("hunt"):
            from .obs import hunt as hunt_mod
            hunt_sum = hunt_mod.summarize(res.report["hunt"])
        history_mod.append_entry(
            history_path,
            history_mod.entry_from_result(
                "swarm", res, cfg_text=cfg_text, dims=setup.dims,
                host_fingerprint=host_fingerprint(),
                label=os.path.basename(args.cfg),
                extra={"swarm": {
                    "walks": res.walks,
                    "steps_per_sec": round(res.steps_per_second, 1),
                    "walks_per_sec": round(res.walks_per_second, 1),
                    "violation_at_seconds": res.violation_at_seconds},
                    "hunt": hunt_sum}))
        print(f"history: entry appended to {history_path}",
              file=sys.stderr)
    if res.violation is not None:
        print()
        if res.counterexample:
            with open(res.counterexample["txt"], encoding="utf-8") as f:
                print(f.read(), end="")
            print(f"\ncounterexample written: "
                  f"{res.counterexample['txt']} (+ .json)")
        else:
            from .engine import explain as explain_mod
            print(explain_mod.render_text(
                engine.replay(res.violation.fingerprint), setup.dims,
                violation=res.violation), end="")
        return 1
    return 0


#: Seconds one blocking ``result`` asks the server to hold: short of
#: the server's own cap, long enough that a queued job costs a request
#: a minute.
_RESULT_WAIT_SECONDS = 60.0


def _wait_for_job(args, job: dict):
    """``submit --wait``: until the job is terminal.  Returns ``(job
    summary, result document or None)``, or ``(None, None)`` after
    saying why.  Against a server whose ``ping`` answers ``"wait"`` the
    ``result`` op blocks until the job ends (no poll, no sleep: the
    answer leaves when the executor journals the terminal state, and
    brings the result with it); against one that does not, ``status``
    is polled every ``--poll-interval``.  Both tolerate transient
    network errors (a server mid-restart replays its journal and the
    job resumes): a few failed asks print a note and retry; persistent
    failure gives up cleanly instead of a traceback."""
    import time
    try:
        blocking = bool(_client_call(args.server, {"op": "ping"},
                                     args.timeout).get("wait"))
    except (OSError, ValueError):
        blocking = False        # the poll loop below reports a lost server
    misses = 0
    while True:
        if not blocking:
            time.sleep(args.poll_interval)
        try:
            if blocking:
                st = _client_call(
                    args.server,
                    {"op": "result", "job_id": job["id"],
                     "wait": _RESULT_WAIT_SECONDS},
                    args.timeout + _RESULT_WAIT_SECONDS)
            else:
                st = _client_call(args.server,
                                  {"op": "status", "job_id": job["id"]},
                                  args.timeout)
        except (OSError, ValueError) as e:
            misses += 1
            if misses >= 10:
                print(f"submit: lost the server while waiting ({e}); "
                      f"job {job['id']} may still run — poll with "
                      f"'jobs' or 'watch --job'", file=sys.stderr)
                return None, None
            print(f"submit: poll failed ({e}); retrying",
                  file=sys.stderr)
            if blocking:
                time.sleep(args.poll_interval)
            continue
        misses = 0
        if not st.get("ok"):
            print(f"submit: {st.get('error')}", file=sys.stderr)
            return None, None
        job = st["job"]
        if job["state"] in ("done", "failed", "cancelled"):
            return job, st.get("result")
        print(f"job {job['id']} {job['state']}...", file=sys.stderr)


def _run_submit(args) -> int:
    """``submit``: queue a check on a checker service as an async job
    (serving/).  Sends cfg CONTENT (cfg_text), so the service need not
    share a filesystem with the client.  --wait waits until the job is
    terminal (``_wait_for_job``) and renders the result."""
    import json
    try:
        with open(args.cfg, encoding="utf-8") as f:
            cfg_text = f.read()
    except OSError as e:
        print(f"submit: cannot read {args.cfg}: {e}", file=sys.stderr)
        return 2
    inner = {"op": "simulate" if args.simulate else "check",
             "cfg_text": cfg_text}
    if args.trace and not args.simulate:
        inner["trace"] = True
    for key, val in (("batch", args.batch),
                     ("queue_capacity", args.queue_capacity),
                     ("seen_capacity", args.seen_capacity),
                     ("max_diameter", args.max_diameter),
                     ("max_seconds", args.max_seconds),
                     ("seed", args.seed or None),
                     ("engine", args.engine),
                     ("pipeline", args.pipeline),
                     ("mode", getattr(args, "mode", None)),
                     ("walks", getattr(args, "walks", None)),
                     ("max_depth", getattr(args, "max_depth", None)),
                     ("num_steps", getattr(args, "num_steps", None)),
                     ("depth", getattr(args, "depth", None))):
        if val is not None:
            inner[key] = val
    req = {"op": "submit", "tenant": args.tenant, "job": inner}
    if args.cache:
        req["cache"] = True
    if args.slo_seconds is not None:
        req["slo_seconds"] = args.slo_seconds
    try:
        resp = _client_call(args.server, req, args.timeout)
    except (OSError, ValueError) as e:
        print(f"submit: {e}", file=sys.stderr)
        return 1
    if not resp.get("ok"):
        print(f"submit: {resp.get('error')}", file=sys.stderr)
        return 1
    job = resp["job"]
    # With --json stdout is reserved for the final result document
    # (scripts pipe it); the human status lines ride stderr instead.
    status_out = sys.stderr if args.json else sys.stdout
    print(f"job {job['id']} {job['state']} "
          f"(tenant {job['tenant']}, label {job.get('label')})",
          file=status_out)
    if not args.wait:
        return 0
    job, doc = _wait_for_job(args, job)
    if job is None:
        return 1
    print(f"job {job['id']} {job['state']} "
          f"(queue_wait {job.get('queue_wait_seconds')}s, run "
          f"{job.get('run_seconds')}s, turnaround "
          f"{job.get('turnaround_seconds')}s"
          + (", cached" if job.get("cached") else "") + ")",
          file=status_out)
    if job["state"] != "done":
        # A cancelled job has no error string — say what happened
        # rather than printing "error: None".
        print(f"job {job['state']}"
              + (f": {job['error']}" if job.get("error") else ""),
              file=sys.stderr)
        return 1
    if doc is None:
        try:
            res = _client_call(args.server,
                               {"op": "result", "job_id": job["id"]},
                               args.timeout)
        except (OSError, ValueError) as e:
            print(f"submit: cannot fetch result ({e}); job {job['id']} "
                  f"is done — retry with the 'result' op",
                  file=sys.stderr)
            return 1
        if not res.get("ok"):
            print(f"submit: {res.get('error')}", file=sys.stderr)
            return 1
        doc = res["result"]
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True, default=str))
    else:
        print(f"distinct {doc.get('distinct')} | generated "
              f"{doc.get('generated')} | diameter "
              f"{doc.get('diameter')} | stop {doc.get('stop_reason')}"
              if "distinct" in doc else json.dumps(doc, default=str))
    violated = doc.get("violation") is not None \
        or doc.get("deadlock") is not None
    return 1 if violated else 0


def _run_jobs(args) -> int:
    """``jobs``: list the service's job registry (one row per job)."""
    req = {"op": "jobs"}
    if args.tenant:
        req["tenant"] = args.tenant
    if args.state:
        req["state"] = args.state
    try:
        resp = _client_call(args.server, req, args.timeout)
    except (OSError, ValueError) as e:
        print(f"jobs: {e}", file=sys.stderr)
        return 1
    if not resp.get("ok"):
        print(f"jobs: {resp.get('error')}", file=sys.stderr)
        return 1
    if args.json:
        import json
        print(json.dumps(resp, indent=2, sort_keys=True, default=str))
        return 0
    print(f"queue {resp['queue_depth']}/{resp.get('queue_capacity')} "
          f"| running {resp['running']} | by_state "
          + " ".join(f"{k}={v}" for k, v in resp["by_state"].items()
                     if v))
    fmt = "{:18s} {:10s} {:9s} {:>9s} {:>8s} {:24s}"
    print(fmt.format("id", "tenant", "state", "wait_s", "run_s",
                     "label"))
    for j in resp["jobs"]:
        def _s(v):
            return f"{v:.2f}" if isinstance(v, (int, float)) else "--"
        print(fmt.format(j["id"], str(j["tenant"])[:10], j["state"],
                         _s(j.get("queue_wait_seconds")),
                         _s(j.get("run_seconds")),
                         str(j.get("label") or "-")[:24])
              + (f"  [{j['error']}]" if j.get("error") else "")
              + (f"  ({j['note']})" if j.get("note") else ""))
    return 0


def _watch_server(target: str, interval: float, count: int,
                  timeout: float, as_json: bool,
                  job: "str | None" = None) -> int:
    """Attach to a checker service's streaming watch op and render each
    snapshot line until the done record."""
    import json
    import socket
    host, _, port = target.partition(":")
    try:
        s = socket.create_connection((host or "127.0.0.1",
                                      int(port or 8610)), timeout=timeout)
    except OSError as e:
        print(f"watch: cannot connect to {target}: {e}", file=sys.stderr)
        return 1
    with s:
        req = {"op": "watch", "interval": interval, "count": count}
        if job:
            req["job"] = job
        s.sendall((json.dumps(req) + "\n").encode())
        # Snapshot lines arrive one per interval — reads must outlast it.
        s.settimeout(max(timeout, interval * 3 + 5))
        f = s.makefile("rb")
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not rec.get("ok"):
                print(f"watch: {rec.get('error')}", file=sys.stderr)
                return 1
            if rec.get("done"):
                end = rec.get("run_end") or {}
                j = rec.get("job")
                if j is not None:
                    if rec.get("evicted"):
                        # Terminal-retention eviction raced the watch:
                        # the job reached a terminal state (only
                        # terminal jobs are evicted) but the final
                        # summary is gone; the last-seen state may be
                        # stale, so do not report it as the outcome.
                        print(f"watch: job {j['id']} completed and "
                              f"was evicted from the registry "
                              f"(retention cap); last seen "
                              f"{j['state']}", flush=True)
                        return 0
                    if rec.get("truncated") \
                            and j["state"] not in ("done", "failed",
                                                   "cancelled"):
                        print(f"watch: stream truncated after "
                              f"{rec.get('snapshots')} snapshot(s) — "
                              f"job {j['id']} still {j['state']}; "
                              f"re-attach to keep watching",
                              file=sys.stderr, flush=True)
                        return 1
                    print(f"watch: job {j['id']} {j['state']} after "
                          f"{rec.get('snapshots')} snapshot(s)"
                          + (f" — {j['error']}" if j.get("error")
                             else ""), flush=True)
                    return 0 if j["state"] == "done" else 1
                print(f"watch: done after {rec.get('snapshots')} "
                      f"snapshot(s)"
                      + (f" — stop_reason={end.get('stop_reason')} "
                         f"distinct={end.get('distinct')}"
                         if end else ""), flush=True)
                return 0
            print(json.dumps(rec, default=str) if as_json
                  else _render_watch_line(rec.get("watch") or {}),
                  flush=True)
    print("watch: connection closed by server", file=sys.stderr)
    return 1


def _run_watch(args) -> int:
    """``watch``: run attach.  No jax, no cfg — pure client."""
    if args.target.startswith("http://") \
            or args.target.startswith("https://"):
        if args.job:
            print("watch: --job needs a checker service target "
                  "(HOST:PORT) — the HTTP /flight listener has no job "
                  "registry", file=sys.stderr)
            return 2
        return _watch_http(args.target, args.interval, args.count,
                           args.timeout, args.json)
    return _watch_server(args.target, args.interval, args.count,
                         args.timeout, args.json, job=args.job)


def _select_engine_cls(engine_arg: str):
    """--engine -> make_engine's engine_cls: "auto" passes through (mesh
    iff >1 accelerator device), "mesh" forces the mesh class, "single"
    the default BFSEngine.  One copy for check and explain — the
    selection rule must not fork per subcommand."""
    if engine_arg == "mesh":
        from .parallel.mesh import MeshBFSEngine
        return MeshBFSEngine
    return "auto" if engine_arg == "auto" else None


def _force_platform(platform: str):
    if platform == "cpu":
        from .utils.platform import force_cpu
        force_cpu()
        return
    os.environ["JAX_PLATFORMS"] = platform
    import jax
    jax.config.update("jax_platforms", platform)


def main(argv=None):
    p = argparse.ArgumentParser(prog="raft_tla_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    # Flags default to None so the resolution chain is visible at the use
    # sites: CLI flag > cfg "\* TPU:" backend directive > built-in default.
    def common(sp):
        sp.add_argument("cfg", help="TLC .cfg file (e.g. MCraft.cfg)")
        sp.add_argument("--platform", default=None,
                        help="jax platform override (e.g. cpu)")
        sp.add_argument("--batch", type=int, default=None)
        sp.add_argument("--n-msg-slots", type=int, default=None)
        sp.add_argument("--max-log", type=int, default=None)
        sp.add_argument("--seed", type=int, default=0)
        sp.add_argument("--engine", choices=("single", "mesh", "auto"),
                        default="auto",
                        help="mesh = shard over all visible devices (TLC "
                             "-workers / distributed TLC analog); auto = "
                             "mesh iff >1 accelerator device (default)")
        sp.add_argument("--pipeline",
                        choices=("auto", "v1", "v2"), default=None,
                        help="successor pipeline: v1 = classical expand, "
                             "v2 = delta (guards-only masks + delta "
                             "fingerprints).  auto = v2 where it applies "
                             "(default; flag > cfg PIPELINE directive "
                             "> auto)")

    c = sub.add_parser("check", help="exhaustive BFS check")
    common(c)
    c.add_argument("--queue-capacity", type=int, default=None)
    c.add_argument("--seen-capacity", type=int, default=None)
    c.add_argument("--max-diameter", type=int, default=None)
    c.add_argument("--max-seconds", type=float, default=None)
    c.add_argument("--mode", choices=("exhaustive", "swarm"),
                   default=None,
                   help="checking tier: exhaustive BFS (default) or the "
                        "vmap'd randomized-walk swarm — W deterministic "
                        "walks per device, per-walk ring dedup, no "
                        "global seen-set (engine/swarm.py; flag > cfg "
                        "MODE directive > exhaustive)")
    c.add_argument("--walks", type=int, default=None,
                   help="swarm mode: concurrent walks per device (flag "
                        "> cfg WALKS directive > 1024)")
    c.add_argument("--max-depth", type=int, default=None,
                   help="swarm mode: per-trace depth bound before a "
                        "walk restarts onto a fresh root (default 128)")
    c.add_argument("--no-trace", action="store_true",
                   help="disable counterexample trace recording")
    c.add_argument("--checkpoint-dir", default=None,
                   help="write level-boundary snapshots here (TLC states/)")
    c.add_argument("--checkpoint-every", type=int, default=None,
                   help="snapshot every k BFS levels (default 1)")
    c.add_argument("--checkpoint-interval", type=float, default=None,
                   help="min seconds between snapshots (snapshot cost is "
                        "O(seen states); 0 = every eligible level; "
                        "default 60)")
    c.add_argument("--keep-checkpoints", type=int, default=None,
                   help="retention: keep only the newest N intact "
                        "snapshots/piece groups, deleting older ones "
                        "after each successful write (default keep all)")
    c.add_argument("--supervise", nargs="?", const=3, type=int,
                   default=None, metavar="N",
                   help="crash-resume supervisor (resilience/): run the "
                        "check in a child process and, on a crash exit, "
                        "resume it from the latest intact checkpoint "
                        "with exponential backoff, up to N restarts "
                        "(default 3).  Requires --checkpoint-dir (or the "
                        "CHECKPOINT_DIR directive); emits 'restart' "
                        "events into the JSONL event log")
    c.add_argument("--fault-plan", default=None,
                   help="deterministic fault injection (resilience/"
                        "faults.py), e.g. 'ckpt_torn_write@level=3,"
                        "kill@level=5,oom@grow=1'; FAULT_PLAN env is the "
                        "fallback.  Testing/chaos only")
    c.add_argument("--no-degrade", action="store_true",
                   help="disable graceful OOM degradation (batch "
                        "halving + checkpoint resume on "
                        "RESOURCE_EXHAUSTED) — fail fast instead")
    c.add_argument("--resume", default=None,
                   help="checkpoint .npz to resume from, or 'auto' for the "
                        "latest one in --checkpoint-dir")
    c.add_argument("--spill-dir", default=None,
                   help="memory-map spilled level segments here (TLC's "
                        "disk-backed state queue) instead of host RAM")
    c.add_argument("--trace-dir", default=None,
                   help="shared-filesystem dir for MULTI-HOST trace "
                        "piece exchange (defaults to --checkpoint-dir; "
                        "set this alone to trace multi-host runs "
                        "without periodic snapshots)")
    c.add_argument("--progress-interval", "--progress-seconds",
                   dest="progress_interval", type=float, default=None,
                   help="stderr progress line cadence (TLC's ~per-minute "
                        "report: generated/distinct/rate/queue); 0 "
                        "disables; default 60 (flag > cfg PROGRESS_SECONDS "
                        "directive > default)")
    c.add_argument("--events-out", default=None,
                   help="JSONL run-event log (run_start / level_complete "
                        "with per-phase timings / fpset_resize / spill / "
                        "checkpoint / violation / run_end — see README "
                        "Observability).  Defaults to events.jsonl next "
                        "to --checkpoint-dir when that is set")
    c.add_argument("--metrics-out", default=None,
                   help="write the final metrics-registry snapshot "
                        "(counters/gauges/histograms JSON) here after "
                        "the run")
    c.add_argument("--trace-out", default=None,
                   help="write the run's span timeline (every phase, one "
                        "span per BFS level, the whole run) as Chrome "
                        "trace-event JSON — opens directly in Perfetto / "
                        "chrome://tracing (see README Observability)")
    c.add_argument("--por", action="store_true",
                   help="statically-certified partial-order reduction "
                        "(analysis/por.py): certify ample-set "
                        "certificates for this model in-process and "
                        "mask redundant expansions on device.  "
                        "Conservative: with no provable certificate "
                        "the run is identical to full expansion")
    c.add_argument("--por-table", default=None, metavar="FILE",
                   help="apply a pre-certified POR reduction table "
                        "(`analyze --passes por --por-artifact FILE`); "
                        "fingerprint/model/predicate-coverage checked "
                        "before any mask is applied")
    c.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="serve live telemetry over HTTP on 127.0.0.1:"
                        "PORT for the duration of the run: /metrics is "
                        "Prometheus text exposition of the engine's "
                        "registry (point a scraper here), /flight is "
                        "the flight-recorder ring as JSON (what "
                        "`python -m raft_tla_tpu watch http://...` "
                        "polls).  METRICS_PORT directive is the cfg "
                        "fallback")
    c.add_argument("--xla-profile", nargs="?", const=8, type=int,
                   default=None, metavar="N",
                   help="device-profiler capture (jax.profiler): trace "
                        "the first N chunk calls (default 8) into "
                        "--xla-profile-dir — XPlane protos + a "
                        "Perfetto-openable trace of the actual "
                        "XLA/Mosaic kernels, correlated with the "
                        "--trace-out host spans via the shared 'chunk' "
                        "span name.  Observational: results are "
                        "bit-identical with the capture on or off.  "
                        "XLA_PROFILE directive is the cfg fallback")
    c.add_argument("--xla-profile-dir", default=None, metavar="DIR",
                   help="where --xla-profile artifacts land (default: "
                        "<--checkpoint-dir>/xla_profile, else "
                        "./xla_profile)")
    c.add_argument("--render-trace", action="store_true",
                   help="force writing counterexample.{txt,json} even "
                        "with no --counterexample-dir/--checkpoint-dir "
                        "configured (falls back to the current "
                        "directory).  The TLC-style rendered trace "
                        "(numbered states, action names, changed-field "
                        "diffs; engine/explain.py) is printed on every "
                        "traced violation regardless")
    c.add_argument("--counterexample-dir", default=None, metavar="DIR",
                   help="where a traced violation's rendered "
                        "counterexample.{txt,json} land automatically "
                        "(default: --checkpoint-dir; neither set = no "
                        "auto-write unless --render-trace forces one "
                        "into the current directory)")
    c.add_argument("--no-report", action="store_true",
                   help="disable the TLC-parity statespace run report "
                        "(obs/report.py: collision probability, "
                        "per-level table, out-degree, seen-set load; "
                        "REPORT directive is the cfg fallback).  "
                        "Observational either way — engine counts are "
                        "bit-identical report on or off")
    c.add_argument("--history", default=None, metavar="FILE",
                   help="append one run-history ledger entry (JSONL; "
                        "obs/history.py: cfg/model/host fingerprints, "
                        "verdict, counts, rates, report summary) after "
                        "the run.  HISTORY directive is the cfg "
                        "fallback; scripts/bench_history.py renders the "
                        "trajectory")

    a = sub.add_parser(
        "analyze",
        help="static model analysis (no state-space run): jaxpr effect "
             "extraction, interval lane-overflow proofs, hot-loop lint")
    a.add_argument("cfg", nargs="?", default=None,
                   help="TLC .cfg file; omitted = the seed model "
                        "(3 servers, 2 values, no CONSTRAINT bounds)")
    a.add_argument("--platform", default=None,
                   help="jax platform (default cpu — analysis only "
                        "traces, it never runs the device)")
    a.add_argument("--n-msg-slots", type=int, default=None)
    a.add_argument("--max-log", type=int, default=None)
    a.add_argument("--json", action="store_true",
                   help="print the machine-readable report to stdout "
                        "instead of the text rendering")
    a.add_argument("--out", default=None, metavar="FILE",
                   help="also write the JSON report here (the CI "
                        "artifact)")
    a.add_argument("--allow", action="append", default=[],
                   metavar="CODE[:QUALIFIER]",
                   help="downgrade matching ERROR findings to WARNING "
                        "(kept visible, marked allowlisted; README "
                        "'Static analysis')")
    a.add_argument("--passes", default=None,
                   help="comma-separated subset of effects,bounds,lint,"
                        "por (default: all); prerequisite passes are "
                        "added automatically (por/lint pull in "
                        "effects); an unknown pass name exits 2 with "
                        "the valid list")
    a.add_argument("--por-artifact", default=None, metavar="FILE",
                   help="write the POR reduction table (versioned, "
                        "fingerprinted ample_mask + priority) here — "
                        "the artifact `check --por-table` consumes; "
                        "requires the 'por' pass")
    a.add_argument("--shrink-lane", action="append", default=[],
                   metavar="FIELD=HI",
                   help="testing: pretend FIELD's packed lane tops out "
                        "at HI — the bounds pass must then name the "
                        "witness action that overflows it")
    a.add_argument("--events-out", default=None,
                   help="append per-pass 'analysis' events to this "
                        "JSONL log (obs/)")
    a.add_argument("--metrics-out", default=None,
                   help="write the analysis/errors + analysis/warnings "
                        "counter snapshot here")

    e = sub.add_parser(
        "explain",
        help="run a check and render its counterexample the TLC way "
             "(numbered states with action names and changed-field "
             "diffs; text/json/html — engine/explain.py), and/or "
             "export the full reached state graph of a small space "
             "as DOT/GraphML")
    common(e)
    e.add_argument("--format", choices=("text", "json", "html"),
                   default="text",
                   help="counterexample rendering (default text — the "
                        "TLC numbered-state error trace)")
    e.add_argument("--out", default=None, metavar="FILE",
                   help="write the rendering here instead of stdout")
    e.add_argument("--max-diameter", type=int, default=None)
    e.add_argument("--max-seconds", type=float, default=None)
    e.add_argument("--queue-capacity", type=int, default=None)
    e.add_argument("--seen-capacity", type=int, default=None)
    e.add_argument("--graph", default=None, metavar="FILE",
                   help="ALSO export the full reached state graph from "
                        "the trace store (one node per fingerprint, one "
                        "edge per recorded discovery) — small spaces "
                        "only (see --graph-cap)")
    e.add_argument("--graph-format", choices=("dot", "graphml"),
                   default=None,
                   help="graph dialect (default: from the --graph file "
                        "extension, .graphml/.xml = GraphML, else DOT)")
    e.add_argument("--graph-cap", type=int, default=None,
                   help="refuse to export graphs larger than this many "
                        "states (default 50000); raise deliberately for "
                        "bigger spaces")

    # -- serving-layer clients (no jax, no cfg parse: pure sockets) ----
    sb = sub.add_parser(
        "submit",
        help="queue a check on a checker service as an async job "
             "(serving/): bounded admission, per-tenant fair "
             "scheduling, per-job event log + metrics; returns the "
             "job id (or --wait for the result)")
    sb.add_argument("cfg", help="TLC .cfg file (content is sent, so "
                                "the service needs no shared "
                                "filesystem)")
    sb.add_argument("--server", default="127.0.0.1:8610",
                    help="HOST:PORT of the checker service "
                         "(default %(default)s)")
    sb.add_argument("--tenant", default=None,
                    help="tenant id for fair scheduling + per-tenant "
                         "metrics (default: 'default')")
    sb.add_argument("--batch", type=int, default=None)
    sb.add_argument("--queue-capacity", type=int, default=None)
    sb.add_argument("--seen-capacity", type=int, default=None)
    sb.add_argument("--max-diameter", type=int, default=None)
    sb.add_argument("--max-seconds", type=float, default=None)
    sb.add_argument("--seed", type=int, default=0)
    sb.add_argument("--engine", choices=("single", "mesh", "auto"),
                    default=None)
    sb.add_argument("--pipeline", choices=("auto", "v1", "v2"),
                    default=None)
    sb.add_argument("--trace", action="store_true",
                    help="record the counterexample trace (the server "
                         "default is off, like the check op): a "
                         "violating job's result then carries the "
                         "replayed numbered-state trace")
    sb.add_argument("--simulate", action="store_true",
                    help="submit a simulate job instead of a check")
    sb.add_argument("--mode", choices=("exhaustive", "swarm"),
                    default=None,
                    help="check-job tier: exhaustive BFS (default) or "
                         "the randomized-walk swarm — the cheap "
                         "high-QPS tier (engine/swarm.py)")
    sb.add_argument("--walks", type=int, default=None,
                    help="(swarm jobs) concurrent walks per device")
    sb.add_argument("--max-depth", type=int, default=None,
                    help="(swarm jobs) per-trace depth bound")
    sb.add_argument("--num-steps", type=int, default=None,
                    help="(simulate/swarm jobs) total walker-steps")
    sb.add_argument("--depth", type=int, default=None,
                    help="(simulate jobs) trace depth")
    sb.add_argument("--cache", action="store_true",
                    help="serve a repeat submission from the "
                         "fingerprint-keyed result cache (refused for "
                         "--max-seconds jobs — a truncated run is not "
                         "reusable)")
    sb.add_argument("--slo-seconds", type=float, default=None,
                    help="per-job turnaround SLO target (feeds the "
                         "jobs/slo_ok|slo_miss per-tenant counters; "
                         "default: the server's)")
    sb.add_argument("--wait", action="store_true",
                    help="poll until the job is terminal and print the "
                         "result (exit 1 on violation/failure)")
    sb.add_argument("--poll-interval", type=float, default=1.0)
    sb.add_argument("--timeout", type=float, default=15.0)
    sb.add_argument("--json", action="store_true",
                    help="print the full result JSON (with --wait)")

    jl = sub.add_parser(
        "jobs",
        help="list a checker service's job registry (queue depth, "
             "by-state counts, one row per job)")
    jl.add_argument("--server", default="127.0.0.1:8610",
                    help="HOST:PORT of the checker service "
                         "(default %(default)s)")
    jl.add_argument("--tenant", default=None,
                    help="only this tenant's jobs")
    jl.add_argument("--state", default=None,
                    help="only jobs in this state (queued/admitted/"
                         "running/done/failed/cancelled)")
    jl.add_argument("--timeout", type=float, default=15.0)
    jl.add_argument("--json", action="store_true")

    w = sub.add_parser(
        "watch",
        help="attach a live console to a running check (run attach): "
             "stream progress/coverage snapshots from a "
             "checker service's watch op, or poll a --metrics-port "
             "listener's /flight endpoint; --job scopes the stream to "
             "one async job")
    w.add_argument("target", nargs="?", default="127.0.0.1:8610",
                   help="HOST:PORT of a checker service (default "
                        "%(default)s), or http://HOST:PORT of a "
                        "--metrics-port listener")
    w.add_argument("--job", default=None, metavar="JOB_ID",
                   help="watch ONE async job (serving/): job state "
                        "snapshots while it queues, ring progress "
                        "while it runs, closed by its terminal state "
                        "— never reaped as idle while the job is "
                        "alive (exit 0 done, 1 failed/cancelled)")
    w.add_argument("--interval", type=float, default=2.0,
                   help="seconds between snapshots (default 2)")
    w.add_argument("--count", type=int, default=0,
                   help="snapshots before exiting; 0 (default) = until "
                        "the watched run ends")
    w.add_argument("--timeout", type=float, default=15.0,
                   help="connect/read timeout per request (default 15)")
    w.add_argument("--json", action="store_true",
                   help="print raw snapshot JSON lines instead of the "
                        "rendered console lines")

    s = sub.add_parser("simulate", help="random-trace simulation")
    common(s)
    # Default sized for the BASELINE workload (1M traces x depth 100 ~=
    # 1e8 walker-steps) — minutes on a TPU chip; use --max-seconds or a
    # smaller --num-steps on CPU.
    s.add_argument("--num-steps", type=int, default=1 << 27,
                   help="total walker-steps; default %(default)s (~1e8) is "
                        "sized for a TPU chip and takes hours on CPU — "
                        "pass --max-seconds or a smaller value there")
    s.add_argument("--depth", type=int, default=100)
    s.add_argument("--max-seconds", type=float, default=None,
                   help="wall-clock budget; stops cleanly before "
                        "--num-steps is reached")
    s.add_argument("--metrics-out", default=None,
                   help="write the final metrics-registry snapshot "
                        "(sim phase timers + step counters JSON) here")
    s.add_argument("--trace-out", default=None,
                   help="Chrome trace-event JSON of the walker loop "
                        "(sim_chunk/sim_fetch spans); opens in Perfetto")

    args = p.parse_args(argv)

    if args.cmd == "watch":
        # Pure client: no jax, no cfg, no platform — dispatched before
        # any heavy import so the console attaches instantly even while
        # the engine process owns the machine.
        return _run_watch(args)

    if args.cmd == "submit":
        return _run_submit(args)     # pure client, like watch

    if args.cmd == "jobs":
        return _run_jobs(args)       # pure client, like watch

    if args.cmd == "analyze":
        # Dispatched before the cfg-directive platform sniff below: the
        # cfg is optional here, and analysis defaults to CPU (it only
        # traces — taking the chip would be pure startup cost).
        _force_platform(args.platform or "cpu")
        return _run_analyze(args)

    platform = args.platform
    if platform is None:
        # The PLATFORM backend directive must act BEFORE jax initializes,
        # i.e. before the cfg loader (which imports the kernels) runs — so
        # read just that one directive with a self-contained regex.
        import re
        try:
            with open(args.cfg) as f:
                m = re.search(r"^\s*\\\*\s*TPU:\s*PLATFORM\s*=\s*(\S+)",
                              f.read(), flags=re.M | re.I)
            platform = m.group(1) if m else None
        except OSError:
            platform = None
    if platform:
        _force_platform(platform)

    if args.cmd == "check" and args.supervise is not None:
        # Crash-resume supervision (resilience/supervisor.py): re-run
        # this same command in a child process, minus --supervise (the
        # child checks; only the parent supervises) and --resume (the
        # supervisor picks the resume point per attempt).
        from .resilience.supervisor import (run_supervised,
                                            strip_supervisor_flags)
        ckdir, events_out = args.checkpoint_dir, args.events_out
        trace_out = args.trace_out
        if ckdir is None or events_out is None or trace_out is None:
            from .utils.cfg import parse_backend_directives
            try:
                with open(args.cfg) as f:
                    be = parse_backend_directives(f.read())
            except (OSError, ValueError):
                be = {}
            ckdir = ckdir if ckdir is not None else be.get("CHECKPOINT_DIR")
            events_out = (events_out if events_out is not None
                          else be.get("EVENTS_OUT"))
            trace_out = (trace_out if trace_out is not None
                         else be.get("TRACE_OUT"))
        if not ckdir:
            p.error("--supervise requires --checkpoint-dir (or a "
                    "CHECKPOINT_DIR backend directive): crash-resume "
                    "restarts from its snapshots")
        raw = list(argv) if argv is not None else sys.argv[1:]
        child = [sys.executable, "-m", "raft_tla_tpu"] \
            + strip_supervisor_flags(raw)
        # The user's own --resume is honored on the FIRST attempt; the
        # supervisor owns the resume decision for restarts.
        return run_supervised(child, ckdir, max_restarts=args.supervise,
                              events_out=events_out,
                              initial_resume=args.resume,
                              trace_out=trace_out)

    # Persistent compilation cache (utils/platform.py: where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache):
    # repeat CLI runs of the same model skip XLA compilation — which is
    # what makes supervised crash-resume restarts cheap (each restart is
    # a fresh process re-running the same programs).  Enabled below the
    # supervise branch: the supervisor parent only spawns children and
    # must not pay the jax import itself.  Around it, the two marks of
    # the process record (obs/metrics.py) that split what comes before
    # ``make_engine`` into the import of jax and the backend's start-up.
    import jax

    from .obs.metrics import process_record
    from .utils.platform import enable_persistent_cache
    process_record().mark("jax_imported")
    enable_persistent_cache()

    # Multi-host launch contract (parallel/multihost.py): export
    # RAFT_COORDINATOR / RAFT_NUM_PROCESSES / RAFT_PROCESS_ID and run the
    # SAME command on every host; the process group forms before any
    # device is touched and jax.devices() becomes the global mesh.
    if os.environ.get("RAFT_COORDINATOR"):
        from .parallel import multihost as _mh
        _mh.initialize()
        if args.engine == "single":
            # A per-process single-chip engine inside a process group
            # would run N duplicate full checks; the global mesh is the
            # multi-host mode.
            p.error("multi-host mode (RAFT_COORDINATOR) requires "
                    "--engine mesh or auto")
        args.engine = "mesh"
        if args.cmd == "check" and not args.no_trace:
            # The trace store is per-controller; the engine would refuse
            # anyway — say it in CLI terms.
            p.error("multi-host check requires --no-trace "
                    "(counterexample traces are not multi-host yet)")

    # The first look at the devices starts the backend (on a chip,
    # seconds): here, stamped, and not somewhere inside ``make_engine``.
    jax.devices()
    process_record().mark("backend_ready")

    from .engine.bfs import EngineConfig
    from .engine.check import (format_result, initial_states, make_engine)
    from .models.pystate import format_state
    from .utils.cfg import load_config

    setup = load_config(args.cfg, max_log=args.max_log,
                        n_msg_slots=args.n_msg_slots)
    print(f"model: {setup.dims.n_servers} servers "
          f"{tuple(setup.server_names)}, {setup.dims.n_values} values; "
          f"smoke={setup.smoke} invariants={setup.invariants} "
          f"bounds={setup.bounds}"
          + (f" backend={setup.backend}" if setup.backend else ""))

    def resolve(flag, key, default):
        if flag is not None:
            return flag
        return setup.backend.get(key, default)

    batch = resolve(args.batch, "BATCH", 1024)

    if args.cmd == "explain":
        # Counterexample explainer (engine/explain.py): run the check
        # with trace recording FORCED on, then render the violation as
        # TLC-style numbered states (and/or export the reached graph).
        import json as _json

        from .engine import explain as explain_mod
        cfgobj = EngineConfig(
            batch=batch,
            queue_capacity=resolve(args.queue_capacity,
                                   "QUEUE_CAPACITY", 1 << 20),
            seen_capacity=resolve(args.seen_capacity,
                                  "SEEN_CAPACITY", 1 << 22),
            max_diameter=args.max_diameter, max_seconds=args.max_seconds,
            record_trace=True,
            pipeline=resolve(args.pipeline, "PIPELINE", "auto"))
        engine = make_engine(setup, cfgobj,
                             engine_cls=_select_engine_cls(args.engine))
        res = engine.run(initial_states(setup, seed=args.seed))
        rc = 0
        if res.violation is not None:
            steps = engine.replay(res.violation.fingerprint)
            if args.format == "text":
                doc = explain_mod.render_text(steps, setup.dims,
                                              violation=res.violation)
            elif args.format == "json":
                doc = _json.dumps(
                    explain_mod.render_json(steps, setup.dims,
                                            violation=res.violation),
                    indent=2, sort_keys=True) + "\n"
            else:
                doc = explain_mod.render_html(
                    steps, setup.dims, violation=res.violation,
                    title=f"counterexample: {res.violation.invariant}")
            if args.out:
                with open(args.out, "w", encoding="utf-8") as f:
                    f.write(doc)
                print(f"counterexample ({args.format}, {len(steps)} "
                      f"states) -> {args.out}")
            else:
                print(doc, end="")
            rc = 1            # same exit contract as check-on-violation
        else:
            print(format_result(res))
            print("no violation found; nothing to explain"
                  + (" (graph still exported)" if args.graph else ""))
        if args.graph:
            fmt = args.graph_format or (
                "graphml" if args.graph.endswith((".graphml", ".xml"))
                else "dot")
            try:
                text = explain_mod.export_graph(
                    engine.trace, setup.dims, fmt=fmt,
                    cap=(args.graph_cap
                         if args.graph_cap is not None
                         else explain_mod.GRAPH_CAP_DEFAULT))
            except ValueError as exc:
                print(f"explain: {exc}", file=sys.stderr)
                # A found-and-rendered violation keeps its exit-1
                # contract (same as check) — only a graph failure with
                # nothing else to report is a usage error.
                return rc or 2
            with open(args.graph, "w", encoding="utf-8") as f:
                f.write(text)
            print(f"state graph ({fmt}, {len(engine.trace)} recorded "
                  f"states) -> {args.graph}")
        return rc

    if args.cmd == "check":
        mode = resolve(args.mode, "MODE", "exhaustive")
        if mode not in ("exhaustive", "swarm"):
            p.error(f"MODE must be exhaustive or swarm, got {mode!r}")
        if mode == "swarm":
            return _run_swarm(args, setup, resolve)
        cfgobj = EngineConfig(
            batch=batch,
            queue_capacity=resolve(args.queue_capacity,
                                   "QUEUE_CAPACITY", 1 << 20),
            seen_capacity=resolve(args.seen_capacity,
                                  "SEEN_CAPACITY", 1 << 22),
            max_diameter=args.max_diameter, max_seconds=args.max_seconds,
            record_trace=not args.no_trace,
            checkpoint_dir=resolve(args.checkpoint_dir,
                                   "CHECKPOINT_DIR", None),
            checkpoint_every=resolve(args.checkpoint_every,
                                     "CHECKPOINT_EVERY", 1),
            checkpoint_interval_seconds=float(
                resolve(args.checkpoint_interval,
                        "CHECKPOINT_INTERVAL", 60.0)),
            keep_checkpoints=resolve(args.keep_checkpoints,
                                     "KEEP_CHECKPOINTS", None),
            spill_dir=resolve(args.spill_dir, "SPILL_DIR", None),
            trace_dir=resolve(args.trace_dir, "TRACE_DIR", None),
            events_out=resolve(args.events_out, "EVENTS_OUT", None),
            trace_out=resolve(args.trace_out, "TRACE_OUT", None),
            xla_profile_chunks=resolve(args.xla_profile,
                                       "XLA_PROFILE", None),
            xla_profile_dir=args.xla_profile_dir,
            pipeline=resolve(args.pipeline, "PIPELINE", "auto"),
            por=bool(resolve(args.por or None, "POR", False)),
            por_table=resolve(args.por_table, "POR_TABLE", None),
            degrade_on_oom=not args.no_degrade,
            statespace_report=(False if args.no_report
                               else bool(resolve(None, "REPORT", True))),
            # Auto-render workdir for counterexample.{txt,json}: flag >
            # directive > checkpoint dir (engine default); with none of
            # those, --render-trace forces the current directory so the
            # rendering it promises always lands somewhere.
            counterexample_dir=(
                resolve(args.counterexample_dir, "COUNTEREXAMPLE_DIR",
                        None)
                or ("." if args.render_trace
                    and not resolve(args.checkpoint_dir,
                                    "CHECKPOINT_DIR", None) else None)),
            progress_interval_seconds=float(
                resolve(args.progress_interval, "PROGRESS_SECONDS", 60.0)))
        # Fault injection (resilience/): the --fault-plan flag or the
        # FAULT_PLAN env a supervisor child inherits.  Fired markers
        # default next to the checkpoints so a restarted child never
        # re-fires a die-class fault at the same level forever.
        from .resilience import faults as _faults
        state_default = (os.path.join(cfgobj.checkpoint_dir,
                                      ".fault_state")
                         if cfgobj.checkpoint_dir else None)
        _faults.install_from_env(default_state_dir=state_default,
                                 text=args.fault_plan)
        engine = make_engine(setup, cfgobj,
                             engine_cls=_select_engine_cls(args.engine))
        resume = None
        if args.resume:
            if args.resume == "auto":
                if not cfgobj.checkpoint_dir:
                    p.error("--resume auto requires --checkpoint-dir "
                            "(or a CHECKPOINT_DIR backend directive)")
                from .engine import checkpoint as ckpt_mod
                resume = ckpt_mod.latest(cfgobj.checkpoint_dir)
                if resume is None:
                    p.error("--resume auto: no checkpoint found in "
                            f"{cfgobj.checkpoint_dir!r}")
                print(f"resuming from {resume}")
            else:
                resume = args.resume
        # Live exposition listener (obs/expose.py): /metrics for a
        # Prometheus scraper, /flight for the watch console — up for
        # exactly the duration of the run.
        metrics_srv = None
        metrics_port = resolve(args.metrics_port, "METRICS_PORT", None)
        # 0 disables, matching BENCH_METRICS_PORT — a cfg author writing
        # `METRICS_PORT = 0` to turn the listener off for one run must
        # not get an unannounced ephemeral-port endpoint instead.
        if metrics_port:
            from .obs import start_metrics_server
            from .obs.flight import RECORDER
            try:
                metrics_srv, _ = start_metrics_server(
                    int(metrics_port), engine.metrics, flight=RECORDER)
                print(f"metrics: http://127.0.0.1:"
                      f"{metrics_srv.server_address[1]}/metrics "
                      f"(+ /flight)", file=sys.stderr)
            except OSError as e:
                # Observability must never kill the run it observes: a
                # busy/forbidden port degrades to a port-less run, said
                # out loud.
                metrics_srv = None
                print(f"metrics: cannot listen on port {metrics_port} "
                      f"({e}); continuing without the listener",
                      file=sys.stderr)
        try:
            res = engine.run(
                initial_states(setup, seed=args.seed)
                if resume is None else None,
                resume=resume)
        finally:
            if metrics_srv is not None:
                metrics_srv.shutdown()
                # And close the socket: a merely-shut-down server still
                # accepts into the backlog, turning the watcher's clean
                # refused-means-gone exit into read timeouts.
                metrics_srv.server_close()
        print(format_result(res))
        if args.metrics_out:
            _write_metrics(args.metrics_out, engine.metrics)
        history_path = resolve(args.history, "HISTORY", None)
        if history_path:
            # Run-history ledger (obs/history.py): one JSONL line per
            # run — cfg/model/host fingerprints, verdict, counts,
            # rates, report summary.  scripts/bench_history.py renders
            # the trajectory.
            from .obs import history as history_mod
            from .obs.flight import host_fingerprint
            with open(args.cfg) as f:
                cfg_text = f.read()
            history_mod.append_entry(
                history_path,
                history_mod.entry_from_result(
                    "check", res, cfg_text=cfg_text, dims=setup.dims,
                    host_fingerprint=host_fingerprint(),
                    label=os.path.basename(args.cfg)))
            print(f"history: entry appended to {history_path}",
                  file=sys.stderr)
        if res.violation is not None:
            if args.no_trace:
                print("\nviolating state (trace recording disabled):")
                print(format_state(res.violation.state, setup.dims))
            else:
                # TLC-style rendered error trace (engine/explain.py) —
                # the one trace rendering, --render-trace or not.  The
                # engine's run-end hook already replayed the chain (one
                # expand dispatch per step) and rendered this exact
                # text into counterexample.txt whenever a workdir was
                # resolvable (--render-trace guarantees one via the "."
                # fallback above), so print THAT file; only a run with
                # no workdir (or a failed render) replays here.
                print()
                if res.counterexample:
                    with open(res.counterexample["txt"],
                              encoding="utf-8") as f:
                        print(f.read(), end="")
                    print(f"\ncounterexample written: "
                          f"{res.counterexample['txt']} (+ .json)")
                else:
                    from .engine import explain as explain_mod
                    steps = engine.replay(res.violation.fingerprint)
                    print(explain_mod.render_text(
                        steps, setup.dims, violation=res.violation),
                        end="")
            return 1
        if res.deadlock is not None:
            print("\ndeadlock state:")
            print(format_state(res.deadlock, setup.dims))
            return 1
        return 0

    # simulate
    from .engine.check import resolve_constraint, resolve_invariants
    use_mesh = args.engine == "mesh"
    if args.engine == "auto":
        import jax
        devs = jax.devices()
        # Multi-process: the global-mesh fleet IS the multi-host mode —
        # anything else would run N duplicate local simulations.
        use_mesh = (jax.process_count() > 1
                    or (len(devs) > 1 and devs[0].platform != "cpu"))
    if use_mesh:
        from .parallel.simulate import MeshSimulator as Simulator
    else:
        from .engine.simulate import Simulator
    sim = Simulator(setup.dims, invariants=resolve_invariants(setup),
                    constraint=resolve_constraint(setup),
                    batch=batch, depth=args.depth,
                    # "v3" is a chunk-tail story; the simulator runs its
                    # v2 (delta) semantics for it (same resolution rule).
                    pipeline=resolve(args.pipeline, "PIPELINE", "auto"))
    # Span tracing (obs/tracing.py): attaching the tracer to the sim's
    # registry mirrors every sim_chunk/sim_fetch phase into the Chrome
    # trace; one top-level span brackets the whole simulation.
    from .obs import SpanTracer
    tracer = SpanTracer(resolve(args.trace_out, "TRACE_OUT", None))
    sim.metrics.tracer = tracer
    max_seconds = (args.max_seconds if args.max_seconds is not None
                   else setup.max_seconds)   # StopAfter duration budget
    with tracer.span("simulate_run", num_steps=args.num_steps,
                     batch=batch, depth=args.depth):
        res = sim.run(initial_states(setup, seed=args.seed),
                      num_steps=args.num_steps, seed=args.seed,
                      max_seconds=max_seconds)
    tracer.write()
    if args.metrics_out:
        _write_metrics(args.metrics_out, sim.metrics)
    print(f"steps visited      {res.steps}")
    print(f"traces             {res.traces}")
    print(f"wall seconds       {res.wall_seconds:.2f}")
    print(f"states/sec         {res.states_per_second:.0f}")
    if res.violation_invariant is not None:
        print(f"VIOLATION          {res.violation_invariant}")
        if res.violation_trace:
            for g, st in res.violation_trace:
                label = ("Initial state" if g < 0
                         else setup.dims.describe_instance(g))
                print(f"-- {label}")
                print(format_state(st, setup.dims))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
