#!/usr/bin/env python3
"""One benchmark run with a fault plan installed: ``benchmark/run.py``'s
own arguments, the plan from ``FAULT_PLAN`` (resilience/faults.py; soft
mode, so nothing dies).

    FAULT_PLAN='stall@phase=wait;call=300;seconds=1.5' \\
        python3 scripts/stall_bench.py --workload mcraft3-noleader --seed 7 \\
        --seconds 20 --trace 1

What PR 52's acceptance run uses: a ``stall`` of 1.5 s in ONE call of the
window (``call`` is the engine's own count of chunk calls, the set-up's
included), which the run's log must name (``slow call: ...``) and
``stall_ms.verdict`` read back.  Nothing of the benchmark is edited: the
plan is in place before ``run.py`` imports the program.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from raft_tla_tpu.resilience import faults  # noqa: E402

faults.install(os.environ["FAULT_PLAN"], hard=False)
print(f"fault plan installed: {os.environ['FAULT_PLAN']}", flush=True)
sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
runpy.run_path(sys.argv[0], run_name="__main__")
