#!/usr/bin/env python3
"""Run a plan of benchmark runs one after another, for one ``chiprun`` call.

    chiprun --chips 1 --timeout 3000 -- python3 scripts/chip_plan.py <plan.json> [<name>]

A plan file holds ``{name: [[tag, root, workload, seed, trace, env, how?],
...]}`` (``scripts/chip_plans/``; ``<name>`` picks one list, all run
without it).  Each entry is one process (this one never imports jax, so
the chip is free for each): ``benchmark/run.py`` of ``workload`` with that
``seed`` and ``trace``, from ``root`` (a directory of this checkout: ""
for the checkout itself, or a git-ignored copy of another commit such as
``_archive_check/parent``), with ``env`` added to the environment
(``$ROOT`` stands for this checkout).  ``how`` = ``"ops"`` runs
``scripts/ops_per_pass.py`` instead, ``"probe"`` ``scripts/setup_probe.py``,
``"record"`` ``scripts/record_capture.py``.

Every run's output goes to ``chiprun_out/<tag>.log`` and its result line
to ``chiprun_out/results.jsonl``; what this prints is the short form the
tool's 24,000 bytes of output have room for.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out")
SHOWN = ("stages:", "idle ", "window compiles", "trace:", "trace reduced",
         "setup:", "window:", "fill:", "ROOT", "   ", "capture_small",
         "traced check")


def command(root: str, workload: str, seed: int, trace: int, how: str):
    if how == "ops":
        return [sys.executable, "scripts/ops_per_pass.py", "--workload",
                workload, "--seed", str(seed)]
    if how == "probe":
        return [sys.executable, os.path.join(ROOT, "scripts",
                                             "setup_probe.py"), root]
    if how == "record":
        return [sys.executable, "scripts/record_capture.py"]
    return [sys.executable, "benchmark/run.py", "--workload", workload,
            "--seed", str(seed), "--seconds", "20", "--trace", str(trace)]


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
    with open(sys.argv[1], encoding="utf-8") as f:
        plans = json.load(f)
    names = sys.argv[2:] or list(plans)
    os.makedirs(OUT, exist_ok=True)
    for tag, root, workload, seed, trace, env, *how in (
            entry for name in names for entry in plans[name]):
        how = how[0] if how else "run"
        cwd = os.path.join(ROOT, root)
        environ = dict(os.environ)
        environ.pop("BENCH_RUN", None)
        for key, value in (env or {}).items():
            environ[key] = value.replace("$ROOT", ROOT)
        t0 = time.time()
        p = subprocess.run(command(cwd, workload, seed, trace, how), cwd=cwd,
                           env=environ, capture_output=True, text=True)
        wall = time.time() - t0
        with open(os.path.join(OUT, tag + ".log"), "w",
                  encoding="utf-8") as f:
            f.write(p.stdout + "\n==== STDERR ====\n" + p.stderr[-20000:])
        line = last_json(p.stdout, "metrics")
        ops = last_json(p.stdout, "ops_in_loop")
        with open(os.path.join(OUT, "results.jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps({
                "tag": tag, "rc": p.returncode, "wall_s": round(wall, 1),
                "root": root, "workload": workload, "seed": seed,
                "trace": trace, "how": how, "line": line, "ops": ops}) + "\n")
        metrics = {k: v["value"] for k, v in
                   ((line or {}).get("metrics") or {}).items()}
        print(tag, "rc", p.returncode, f"{wall:.0f}s", "correct",
              (line or {}).get("correct"), metrics, flush=True)
        if ops:
            print("    OPS", {k: v for k, v in ops.items()
                              if k != "ops_by_call"}, flush=True)
        if p.returncode or (how == "run" and line is None):
            print("    ERROR", p.stdout[-600:], p.stderr[-1500:], flush=True)
        for text in p.stdout.splitlines():
            if text.startswith(SHOWN):
                print("   ", text[:400], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
