#!/usr/bin/env python3
"""Record the small capture the reader tests keep
(``benchmark/tests/data/capture_small.json``).  Needs a TPU.

    chiprun -- python3 scripts/record_capture.py

One check of ``configs/MCraft_noleader.cfg`` and the replay of what it
finds inside a profiler capture, loaded by ``benchmark/readers/spans.py``
and cut after the fourth chunk call's trace flush: host spans, module
executions, device operations with their scope paths (cut to seven
components), times from the first span.  Written to
``chiprun_out/capture_small.json``, with the stage and idle tables of the
whole capture printed beside it.
"""

import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402
from raft_tla_tpu.utils.platform import enable_persistent_cache  # noqa: E402
enable_persistent_cache()
import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine.check import initial_states, make_engine  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

CALLS, DEPTH = 4, 7
OUT = os.path.join(ROOT, "chiprun_out")
os.makedirs(OUT, exist_ok=True)
print("devices", jax.devices(), flush=True)
setup = load_config(os.path.join(ROOT, "configs/MCraft_noleader.cfg"))
eng = make_engine(setup)
roots = initial_states(setup)
eng.replay(eng.run(roots).violation.fingerprint)     # compiles everything
trace_dir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(trace_dir, profiler_options=opts)
res = eng.run(roots)
steps = eng.replay(res.violation.fingerprint)
jax.profiler.stop_trace()
print("traced check:", res.distinct, "distinct,", len(steps), "steps",
      flush=True)

spans = lib.load_module("readers", "spans")
stages = lib.load_module("readers", "stages")
cap = spans.load(trace_dir)
run = {"_capture": cap, "window_wall_s": 0.1, "chunk_program": "chunk"}
stages.stage_table(run)
spans.idle_table(run)

chunks = [e for e in cap["host"] if e[0] == "chunk"]
flush = next(e for e in cap["host"] if e[0] == "trace_flush"
             and e[1] > chunks[CALLS - 1][1])
cut = flush[1] + flush[2]
lo = min(e[1] for e in cap["host"])
ops = [o for o in cap["ops"].tolist() if o[1] + o[2] <= cut]
used = sorted({o[0] for o in ops})
index = {i: k for k, i in enumerate(used)}
# run and level spans that outlive the cut are clipped to it
host = [[n, s - lo, min(d, cut - s), st] for n, s, d, st in cap["host"]
        if s + d <= cut or (n in ("run", "level") and s < cut)]
small = {"host": host,
         "modules": [[n[:40], s - lo, d] for n, s, d in cap["modules"]
                     if s + d <= cut],
         "ops": [[index[i], s - lo, d] for i, s, d in ops],
         "op_names": [cap["op_names"][i][:32] for i in used],
         "op_paths": ["/".join(cap["op_paths"][i].split("/")[:DEPTH])
                      for i in used]}
path = os.path.join(OUT, "capture_small.json")
with open(path, "w", encoding="utf-8") as f:
    json.dump(small, f, separators=(",", ":"))
tab = stages.table(json.loads(json.dumps(small)))
print("capture_small.json", os.path.getsize(path), "bytes,", len(ops),
      "operations,", len(used), "names; its stage table:",
      tab and {k: v for k, v in tab.items() if k != "by_name"}, flush=True)
