"""raft_tla_tpu — a TPU-native explicit-state model checker.

This package re-implements, TPU-first, the runtime that the reference
TLA+ repository (`lemmy/raft.tla`, mounted at /root/reference) is written
against: TLC's exhaustive breadth-first state-space exploration, randomized
smoke testing, simulation mode, invariant evaluation, state constraints,
deadlock detection, counterexample traces, and checkpoint/resume — for the
Raft consensus specification (/root/reference/raft.tla).

Layout
------
- ``models/``   the Raft transition system itself: state schema (struct-of-
                arrays tensors), the vmap'd action kernels for every ``Next``
                disjunct (raft.tla:421-430), invariant kernels, initial-state
                generators, and a pure-Python reference interpreter used as
                the differential oracle.
- ``ops/``      checker primitives: two-lane 32-bit multiset fingerprinting,
                the sorted fingerprint set (TLC's FPSet equivalent), and
                mask-compaction utilities.
- ``parallel/`` device-mesh sharding: fingerprint-owner partitioned BFS with
                all-to-all dedup over ICI (TLC worker-pool / distributed-TLC
                equivalent).
- ``engine/``   the host-side drivers: level-synchronous BFS, simulation
                mode, trace reconstruction, checkpoint/resume, stats.
- ``utils/``    TLC ``.cfg`` grammar parser, model-value interning, misc.

The reference's ``MCraft.cfg``/``Smokeraft.cfg`` remain the source of truth:
the cfg parser consumes them verbatim (they are *read*, never copied).
"""

import time as _time

# The ``package`` mark of the process record (obs/metrics.py
# ProcessRecord), taken here so that importing the package stays what it
# was: nothing else is imported, and the record itself is made when
# ``obs`` first is.
IMPORT_STAMP = _time.perf_counter()

__version__ = "0.1.0"
