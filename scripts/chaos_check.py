#!/usr/bin/env python
"""Chaos equivalence harness (resilience/ acceptance gate).

Runs the same bounded check twice on CPU:

1. an UNINTERRUPTED baseline run, and
2. a SUPERVISED run under a deterministic fault plan (default: a torn
   checkpoint write at level 2 and a mid-level kill at level 3),

then asserts the supervised run's ``(distinct, generated, diameter,
levels)`` — read from each run's JSONL ``run_end`` event, the supported
telemetry interface — are BIT-IDENTICAL to the baseline's, and that the
supervised log carries at least one ``restart`` event (i.e. the faults
actually fired and recovery actually ran).  When the plan injects an
``oom`` fault, a ``degraded`` event is required too, and the run must
still complete.  Exit 0 on equivalence, 1 on any mismatch — CI-callable.

    python scripts/chaos_check.py
    python scripts/chaos_check.py --faults 'kill@level=2,oom@chunk=2' \\
        --max-diameter 4

Subprocess-based on purpose: the kill faults die via ``os._exit`` (hard
mode), exactly what a real crash leaves behind; the persistent
compilation cache (enabled by the CLI) keeps the restarts cheap.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def last_event(path, event):
    """Newest JSONL record of ``event`` in ``path`` (None if absent)."""
    hit = None
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("event") == event:
                hit = rec
    return hit


def count_events(path, event):
    with open(path, encoding="utf-8") as f:
        return sum(1 for line in f if line.strip()
                   and json.loads(line).get("event") == event)


def counters_of(run_end):
    return (run_end["distinct"], run_end["generated"],
            run_end["diameter"], tuple(run_end["levels"]))


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chaos_check")
    ap.add_argument("--cfg", default="configs/MCraft_bounded.cfg")
    ap.add_argument("--faults",
                    default="ckpt_torn_write@level=2,kill@level=3")
    ap.add_argument("--max-diameter", type=int, default=4)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--restarts", type=int, default=5)
    ap.add_argument("--keep-workdir", action="store_true")
    ap.add_argument("--workdir", default=None,
                    help="run in this directory (implies --keep-workdir; "
                         "CI points it somewhere uploadable so the event "
                         "logs + Chrome traces become artifacts)")
    args = ap.parse_args(argv)

    if args.workdir:
        work = os.path.abspath(args.workdir)
        os.makedirs(work, exist_ok=True)
        args.keep_workdir = True
    else:
        work = tempfile.mkdtemp(prefix="chaos_")
    base = [sys.executable, "-m", "raft_tla_tpu", "check",
            os.path.join(REPO, args.cfg), "--platform", "cpu",
            "--batch", str(args.batch),
            "--queue-capacity", str(1 << 12),
            "--seen-capacity", str(1 << 15),
            "--max-diameter", str(args.max_diameter),
            "--progress-interval", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)       # single-device children
    env.pop("FAULT_PLAN", None)      # never leak an ambient plan
    ok = True
    try:
        clean_log = os.path.join(work, "clean.jsonl")
        clean_trace = os.path.join(work, "clean_trace.json")
        print(f"chaos: baseline run ({args.cfg}, "
              f"max_diameter={args.max_diameter})", flush=True)
        # cwd=REPO so `python -m raft_tla_tpu` resolves regardless of
        # where the harness itself was invoked from (no installed pkg).
        rc = subprocess.call(base + ["--events-out", clean_log,
                                     "--trace-out", clean_trace],
                             env=env, cwd=REPO)
        if rc not in (0, 1):
            print(f"FAIL: baseline run exited {rc}")
            return 1

        sup_dir = os.path.join(work, "states")
        sup_log = os.path.join(sup_dir, "events.jsonl")
        sup_env = dict(env, FAULT_PLAN=args.faults,
                       FAULT_STATE_DIR=os.path.join(work, "fault_state"))
        print(f"chaos: supervised run under faults {args.faults!r}",
              flush=True)
        sup_trace = os.path.join(work, "sup_trace.json")
        rc_sup = subprocess.call(
            base + ["--checkpoint-dir", sup_dir,
                    "--checkpoint-interval", "0",
                    "--trace-out", sup_trace,
                    "--supervise", str(args.restarts)],
            env=sup_env, cwd=REPO)
        if rc_sup != rc:
            print(f"FAIL: supervised exit {rc_sup} != baseline {rc}")
            ok = False

        clean_end = last_event(clean_log, "run_end")
        sup_end = last_event(sup_log, "run_end")
        if clean_end is None or sup_end is None:
            print(f"FAIL: missing run_end event "
                  f"(clean={clean_end is not None}, "
                  f"sup={sup_end is not None})")
            return 1
        c, s = counters_of(clean_end), counters_of(sup_end)
        if c != s:
            print(f"FAIL: counters diverge\n  baseline  {c}\n"
                  f"  supervised{s}")
            ok = False
        else:
            print(f"chaos: counters bit-identical: distinct={c[0]} "
                  f"generated={c[1]} diameter={c[2]} levels={list(c[3])}")

        restarts = count_events(sup_log, "restart")
        die_faults = any(f.split("@")[0] in ("kill", "ckpt_torn_write")
                         for f in args.faults.split(","))
        if die_faults and restarts < 1:
            print("FAIL: no 'restart' event — the faults never fired or "
                  "the supervisor never recovered")
            ok = False
        else:
            print(f"chaos: {restarts} restart event(s) in {sup_log}")

        # Flight-recorder gate (obs/flight.py): a hard-killed child must
        # leave its black box behind — postmortem.json next to the
        # checkpoints, holding the last progress snapshot, surfaced by
        # the supervisor as a 'postmortem' event.
        if any(f.split("@")[0] == "kill" for f in args.faults.split(",")):
            pm_path = os.path.join(sup_dir, "postmortem.json")
            if not os.path.exists(pm_path):
                print(f"FAIL: injected kill left no postmortem dump at "
                      f"{pm_path}")
                ok = False
            else:
                with open(pm_path, encoding="utf-8") as f:
                    pm = json.load(f)
                recs = pm.get("records") or {}
                prog = recs.get("progress") or []
                if not pm.get("reason", "").startswith("fault_kill"):
                    print(f"FAIL: postmortem reason {pm.get('reason')!r} "
                          f"is not the injected kill")
                    ok = False
                elif not prog:
                    print("FAIL: postmortem has no progress snapshots")
                    ok = False
                else:
                    print(f"chaos: postmortem ok ({pm['reason']!r}, "
                          f"{len(prog)} progress snapshot(s), last "
                          f"distinct={prog[-1].get('distinct')})")
            if count_events(sup_log, "postmortem") < 1:
                print("FAIL: supervisor surfaced no 'postmortem' event")
                ok = False

        if any(f.startswith("oom") for f in args.faults.split(",")):
            degraded = count_events(sup_log, "degraded")
            if degraded < 1:
                print("FAIL: oom fault in plan but no 'degraded' event")
                ok = False
            else:
                print(f"chaos: {degraded} degraded event(s)")

        # Trace-format gate: both runs' --trace-out files must be valid
        # Chrome trace JSON arrays (obs.validate_chrome_trace) — the
        # supervised engine trace is the LAST attempt's (each child
        # rewrites it), and the supervisor adds its own attempt/restart
        # timeline next to it.
        sys.path.insert(0, REPO)
        from raft_tla_tpu.obs import validate_chrome_trace
        for tpath in (clean_trace, sup_trace,
                      sup_trace + ".supervisor.json"):
            try:
                n = len(validate_chrome_trace(tpath))
                print(f"chaos: trace ok ({n} events): {tpath}")
            except (OSError, ValueError) as e:
                print(f"FAIL: invalid Chrome trace: {e}")
                ok = False
        print("chaos: OK" if ok else "chaos: FAILED")
        return 0 if ok else 1
    finally:
        if args.keep_workdir:
            print(f"chaos: workdir kept at {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
