#!/usr/bin/env python3
"""Where a process's set-up goes, by the program's own record.

    python3 scripts/setup_probe.py <checkout>

Builds ``mcraft3``'s engine at the benchmark's sizes in ``<checkout>``
(this one, or a copy of another commit), runs it to diameter 2, and
prints the process record as the next ``run_start`` would carry it
(``raft_tla_tpu/obs/metrics.py ProcessRecord``: marks, jax's trace /
lower / load / compile stages in self time by program, the run's phases
net of jit), in the words of ``benchmark/readers/setup.py``.  Until PR 37
this script summed jax's duration events itself, nested traces counted
twice; a checkout from before has no record, and it says so.
"""

import importlib.util
import os
import sys
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(root, ".jax_cache"))

from raft_tla_tpu.utils.platform import enable_persistent_cache  # noqa: E402
enable_persistent_cache()
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import initial_states, make_engine  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

setup = load_config(os.path.join(root, "configs/MCraft_bounded.cfg"))
t0 = time.time()
eng = make_engine(setup, EngineConfig(
    batch=2048, queue_capacity=2097152, seen_capacity=16777216,
    record_trace=True, max_diameter=2))
t1 = time.time()
res = eng.run(initial_states(setup))
print(f"ROOT {root} make_engine {t1 - t0:.2f} run {time.time() - t1:.2f}",
      {k: round(v, 3) for k, v in res.phases.items()})
try:
    from raft_tla_tpu.obs.metrics import process_record
except ImportError:
    print("    no process record in this checkout (before PR 37)")
    sys.exit(0)
spec = importlib.util.spec_from_file_location(
    "setup_reader", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "..", "benchmark", "readers", "setup.py"))
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)
process = process_record().run_start()
print(reader.describe(process, reader.partition(process), None))
