"""Worker for the multi-host simulation test (not a pytest module).

Launched twice by tests/test_multihost.py; each process owns 2 virtual
CPU devices and they form one global 4-device mesh.  Prints one JSON line
with the replicated results — the test asserts both processes report the
SAME violation (the whole point: every host reads identical psum'd
outputs)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tla_tpu.parallel import multihost as mh  # noqa: E402

mh.initialize()    # RAFT_COORDINATOR / RAFT_NUM_PROCESSES / RAFT_PROCESS_ID

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from raft_tla_tpu.models.dims import LEADER, RaftDims  # noqa: E402
from raft_tla_tpu.models.invariants import Bounds, build_constraint  # noqa: E402
from raft_tla_tpu.models.pystate import init_state  # noqa: E402
from raft_tla_tpu.parallel.simulate import MeshSimulator  # noqa: E402


def main():
    assert jax.process_count() == int(os.environ["RAFT_NUM_PROCESSES"])
    dims = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=24)
    sim = MeshSimulator(
        dims,
        invariants={"NoLeader": lambda st: jnp.all(st.role != LEADER)},
        constraint=build_constraint(
            dims, Bounds(max_term=2, max_log_len=1, max_msg_count=1)),
        batch=16, depth=24, chunk=8)
    assert sim.n_dev == len(jax.devices())    # the GLOBAL mesh
    # Root a candidate one vote short of quorum (tests/test_engine.py
    # seeding trick): random walkers reach BecomeLeader within a couple of
    # steps, so the latch + cross-host broadcast path actually fires.
    s0 = init_state(dims).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))  # RVR grant r2->r1
    res = sim.run([s0], num_steps=1 << 16, seed=7)
    print(json.dumps({
        "process": jax.process_index(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "steps": res.steps,
        "traces": res.traces,
        "violation": res.violation_invariant,
        "trace_len": (len(res.violation_trace)
                      if res.violation_trace else None),
    }))


if __name__ == "__main__":
    main()
