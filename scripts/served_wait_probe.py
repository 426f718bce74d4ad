#!/usr/bin/env python3
"""What ``submit --wait`` costs a client of the checker service, by the
path it waits on: the blocking ``result`` (a server whose ``ping`` says
``"wait"``) against the poll of ``status`` every ``--poll-interval`` (a
server that does not; the default interval, one second).

    python3 scripts/served_wait_probe.py [--jobs 5]

The service runs in this process on the device jax finds (it holds the
chip), one client at a time, the job the canary (``configs/
MCraft_noleader.cfg``, ``--trace``, uncached) on a warm engine.  The old
server is the new one with ``"wait"`` taken out of its ``ping`` and out of
the ``result`` requests it is sent.  One JSON line: the walls of the
whole ``submit --wait`` subcommand on either path, the jobs' own
``run_seconds`` by the manager's clock, the device.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--jobs", type=int, default=5)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    from raft_tla_tpu import cli
    from raft_tla_tpu import server as srv_mod
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    dev = jax.devices()[0]
    srv = srv_mod.serve("127.0.0.1", 0,
                        job_dir=tempfile.mkdtemp(prefix="waitprobe_"))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    addr = f"127.0.0.1:{srv.server_address[1]}"
    cmd = ["submit", os.path.join(ROOT, "configs", "MCraft_noleader.cfg"),
           "--server", addr, "--wait", "--trace", "--timeout", "600"]
    orig = srv_mod.handle_request

    def old_server(req, manager=None):
        if req.get("op") == "result":
            req = {k: v for k, v in req.items() if k != "wait"}
        resp = orig(req, manager)
        if req.get("op") == "ping":
            resp.pop("wait", None)
        return resp

    def one() -> float:
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(io.StringIO()), \
                contextlib.redirect_stderr(io.StringIO()):
            rc = cli.main(cmd)
        assert rc == 1, rc      # the canary's violation
        return time.perf_counter() - t0

    try:
        first = one()                       # builds and compiles
        walls = {"blocking": [], "polling": []}
        for _ in range(args.jobs):          # alternating, one client
            srv_mod.handle_request = orig
            walls["blocking"].append(one())
            srv_mod.handle_request = old_server
            walls["polling"].append(one())
        srv_mod.handle_request = orig
        runs = [j["run_seconds"] for j in srv.jobs.jobs_doc()["jobs"]][1:]
    finally:
        srv_mod.handle_request = orig
        srv.shutdown()
        srv.server_close()
    print(json.dumps({
        "probe": "served_wait", "device": [dev.platform, dev.device_kind],
        "first_s": first, "jobs": args.jobs,
        "blocking_s": walls["blocking"], "polling_s": walls["polling"],
        "blocking_median_s": statistics.median(walls["blocking"]),
        "polling_median_s": statistics.median(walls["polling"]),
        "job_run_seconds": runs,
        "job_run_median_s": statistics.median(runs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
