"""Paths and the rehearsal runner shared by the benchmark's own tests."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def run_cell(root, *argv, script="benchmark/run.py", timeout=900):
    """The command as the driver runs it plus ``--rehearsal``, on the CPU;
    returns
    (exit code, parsed last line or None, stdout)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, script, *argv, "--rehearsal"],
                       cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    last = None
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, last, p.stdout + p.stderr
