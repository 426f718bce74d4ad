#!/usr/bin/env python3
"""Run a plan of benchmark runs one after another, for one ``chiprun`` call.

    chiprun --chips 1 --timeout 3000 -- python3 scripts/chip_plan.py [--within SECONDS] <plan.json> [<name>]

A plan file holds ``{name: [[tag, root, workload, seed, trace, env, how?],
...]}`` (``scripts/chip_plans/``; ``<name>`` picks one list, all run
without it).  Each entry is one process (this one never imports jax, so
the chip is free for each): ``benchmark/run.py`` of ``workload`` with that
``seed`` and ``trace``, from ``root`` (a directory of this checkout: ""
for the checkout itself, or a git-ignored copy of another commit such as
``_archive_check/parent``), with ``env`` added to the environment
(``$ROOT`` stands for this checkout).  ``how`` = ``"ops"`` runs
``scripts/ops_per_pass.py`` instead, ``"probe"`` ``scripts/setup_probe.py``,
``"record"`` ``scripts/record_capture.py``, ``"waitprobe"``
``scripts/served_wait_probe.py``, ``"cost"`` ``scripts/call_cost.py`` on
``root``'s program, ``"d2h"`` ``scripts/d2h_probe.py`` (what a snapshot's
capture moves from the device, timed), ``"stall"`` the same run under ``env``'s
``FAULT_PLAN`` (``scripts/stall_bench.py``), ``"check"`` the CLI's ``check``
with ``workload`` as its arguments (one string, split at spaces; no result
line: what it printed of the run's totals is shown; ``$RUN_TMP`` in it
stands for a directory made for this run under ``TMPDIR`` and removed
after it, so a run's snapshots never meet another's), ``"control:<name>"``
``benchmark/tests/controls_mesh.py <name>`` around the same run, and
``"control:swarm:<name>"`` ``benchmark/tests/controls_swarm.py <name>``
(``"control:safety:<name>"`` likewise; a result of ``correct: false`` is
what each must give).  After ``how``: ``"stop"``
ends the plan when that run did not give what it should, and
``"limit:<seconds>"`` kills the run at that age (rc -9 in the results).
``--within`` is the plan's own limit: a run gets no more than what is
left of it, and is skipped when under a minute is.  Set it under the
call's ``--timeout``: a call that is cut brings nothing back.

Every run's output goes to ``chiprun_out/<tag>.log`` AS IT IS WRITTEN (a
run that is killed leaves what it had said) and its result line to
``chiprun_out/results.jsonl``; what this prints is the short form the
tool's 24,000 bytes of output have room for.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out")
SHOWN = ("stages:", "idle ", "window compiles", "trace:", "trace reduced",
         "setup:", "window:", "fill:", "ROOT", "   ", "capture_small",
         "traced check", "kept snapshot", "mesh:", "enqueued:", "at ",
         "memory", "replay:", "sample:", "window phases", "walk:",
         "window counters", "construct:", "suite:", "witness ",
         "distinct states", "states generated", "diameter", "stop reason",
         "wall seconds", "states/sec", "VIOLATION", "pipeline",
         "setup by", "  marks", "  partition", "  jit", "  compiled",
         "  runs so far", "variant:", "window generated", "host line",
         "served jobs", "a hit", "window by", "benchmark:",
         '{"probe"', "calls", "gc:", "rehash:", "round trip:",
         "slow call:", "stall:", "fault plan", '{"checkout"', '{"what"')


def command(root: str, workload: str, seed: int, trace: int, how: str):
    if how == "ops":
        return [sys.executable, "scripts/ops_per_pass.py", "--workload",
                workload, "--seed", str(seed)]
    if how == "probe":
        return [sys.executable, os.path.join(ROOT, "scripts",
                                             "setup_probe.py"), root]
    if how == "record":
        return [sys.executable, "scripts/record_capture.py"]
    if how == "waitprobe":
        return [sys.executable, "scripts/served_wait_probe.py"]
    if how == "cost":
        return [sys.executable, os.path.join(ROOT, "scripts",
                                             "call_cost.py"), root]
    if how == "d2h":
        return [sys.executable, os.path.join(ROOT, "scripts",
                                             "d2h_probe.py")]
    if how == "check":
        return [sys.executable, "-m", "raft_tla_tpu", "check",
                *workload.split()]
    run = ["--workload", workload, "--seed", str(seed), "--seconds", "20",
           "--trace", str(trace)]
    if how.startswith("control:"):
        script, _, name = how.split(":", 1)[1].rpartition(":")
        return [sys.executable,
                f"benchmark/tests/controls_{script or 'mesh'}.py", name,
                "--", *run]
    if how == "stall":
        return [sys.executable, os.path.join(ROOT, "scripts",
                                             "stall_bench.py"), *run]
    return [sys.executable, "benchmark/run.py", *run]


def last_json(text: str, key: str):
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if key in obj:
                return obj
    return None


def main() -> int:
    argv, within, t_plan = sys.argv[1:], None, time.time()
    if argv[0] == "--within":
        within, argv = float(argv[1]), argv[2:]
    with open(argv[0], encoding="utf-8") as f:
        plans = json.load(f)
    names = argv[1:] or list(plans)
    os.makedirs(OUT, exist_ok=True)
    for tag, root, workload, seed, trace, env, *how in (
            entry for name in names for entry in plans[name]):
        stop = "stop" in how[1:]
        limit = next((float(h.split(":")[1]) for h in how[1:]
                      if h.startswith("limit:")), None)
        how = how[0] if how else "run"
        if within is not None:
            left = within - (time.time() - t_plan)
            limit = min(limit or left, left - 5)
            if limit < 60:
                print(tag, f"skipped: {left:.0f}s of the plan left",
                      flush=True)
                continue
        cwd = os.path.join(ROOT, root)
        environ = dict(os.environ)
        environ.pop("BENCH_RUN", None)
        for key, value in (env or {}).items():
            environ[key] = value.replace("$ROOT", ROOT)
        run_tmp = None
        if "$RUN_TMP" in workload:
            run_tmp = tempfile.mkdtemp(prefix=f"chip_plan_{tag}_")
            workload = workload.replace("$RUN_TMP", run_tmp)
        t0 = time.time()
        log = os.path.join(OUT, tag + ".log")
        environ["PYTHONUNBUFFERED"] = "1"
        with open(log, "w", encoding="utf-8") as f:
            try:
                rc = subprocess.run(
                    command(cwd, workload, seed, trace, how), cwd=cwd,
                    env=environ, stdout=f, stderr=subprocess.STDOUT,
                    timeout=limit).returncode
            except subprocess.TimeoutExpired:
                rc = -9
                f.write(f"\n==== KILLED at its limit of {limit:.0f}s ====\n")
            finally:
                if run_tmp:
                    shutil.rmtree(run_tmp, ignore_errors=True)
        wall = time.time() - t0
        with open(log, encoding="utf-8", errors="replace") as f:
            said = f.read()
        line = last_json(said, "metrics")
        ops = last_json(said, "ops_in_loop")
        with open(os.path.join(OUT, "results.jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps({
                "tag": tag, "rc": rc, "wall_s": round(wall, 1),
                "root": root, "workload": workload, "seed": seed,
                "trace": trace, "how": how, "line": line, "ops": ops}) + "\n")
        metrics = {k: v["value"] for k, v in
                   ((line or {}).get("metrics") or {}).items()}
        print(tag, "rc", rc, f"{wall:.0f}s", "correct",
              (line or {}).get("correct"), metrics, flush=True)
        if ops:
            print("    OPS", {k: v for k, v in ops.items()
                              if k != "ops_by_call"}, flush=True)
        if rc or (how == "run" and line is None):
            print("    ERROR", said[-2500:], flush=True)
        for text in said.splitlines():
            if text.startswith(SHOWN) or text.endswith(" FAIL"):
                print("   ", text[:400], flush=True)
        want = not how.startswith("control:")
        if stop and (line or {}).get("correct") is not want:
            print(f"{tag}: not correct == {want}; the plan stops here",
                  flush=True)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
