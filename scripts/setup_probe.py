#!/usr/bin/env python3
"""Where a first run's set-up goes, by jax's own duration events.

    python3 scripts/setup_probe.py <checkout>

Builds ``mcraft3``'s engine at the benchmark's sizes in ``<checkout>``
(this one, or a copy of another commit), runs it to diameter 2, and
prints the seconds jax reports for tracing, lowering, compiling and
loading from the cache, with the programs that took over 50 ms.
"""

import collections
import os
import sys
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(root, ".jax_cache"))

import jax.monitoring  # noqa: E402
from raft_tla_tpu.utils.platform import enable_persistent_cache  # noqa: E402
enable_persistent_cache()
totals = collections.defaultdict(lambda: [0, 0.0])
big = []


def on(event, duration, **kw):
    totals[event][0] += 1
    totals[event][1] += duration
    if duration > 0.05:
        big.append((event.split("/")[-1], round(duration, 3),
                    kw.get("fun_name", "")))


jax.monitoring.register_event_duration_secs_listener(on)
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
for event, (n, seconds) in sorted(totals.items(),
                                  key=lambda kv: -kv[1][1])[:10]:
    print("   ", event, n, round(seconds, 3))
print("    over 50 ms:", big)
