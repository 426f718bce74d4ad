"""Worker for the multi-host exhaustive-BFS test (not a pytest module).

Two processes, one global 4-device mesh: the full distributed pipeline —
expand -> fingerprint -> owner-routed all_to_all dedup ACROSS HOSTS ->
sharded FPSet insert -> enqueue, with per-controller spill pools — must
exhaust a bounded 2-server model and report the oracle-pinned counts
(4,779 distinct / diameter 25 / 12,584 generated) identically on every
controller."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tla_tpu.parallel import multihost as mh  # noqa: E402

if os.environ.get("RAFT_COORDINATOR"):
    mh.initialize()    # single-controller mode otherwise (resume test b)

import jax  # noqa: E402

from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.models.dims import RaftDims  # noqa: E402
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,  # noqa: E402
                                            build_type_ok)
from raft_tla_tpu.models.pystate import init_state  # noqa: E402
from raft_tla_tpu.parallel.mesh import MeshBFSEngine  # noqa: E402


def main():
    dims = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    ckpt_dir = os.environ.get("MH_CKPT_DIR")
    max_dia = os.environ.get("MH_MAX_DIAMETER")
    # MH_TRACE=1: record the trace across controllers (per-controller
    # stores + piece-file merge at replay) and hunt a NoLeader violation
    # whose counterexample chain crosses the process boundary.
    trace_on = bool(os.environ.get("MH_TRACE"))
    invariants = {"TypeOK": build_type_ok(dims)}
    if trace_on:
        import jax.numpy as jnp

        from raft_tla_tpu.models.dims import LEADER
        invariants["NoLeader"] = lambda st: jnp.all(st.role != LEADER)
    eng = MeshBFSEngine(
        dims,
        invariants=invariants,
        constraint=build_constraint(
            dims, Bounds(max_term=2, max_log_len=1, max_msg_count=1,
                         max_in_flight=1)),
        config=EngineConfig(batch=32, queue_capacity=1 << 10,
                            seen_capacity=1 << 14, check_deadlock=False,
                            record_trace=trace_on, sync_every=4,
                            checkpoint_dir=ckpt_dir,
                            max_diameter=int(max_dia) if max_dia else None,
                            exit_conditions=(
                                (("queue",
                                  float(os.environ["MH_QUEUE_BUDGET"])),)
                                if os.environ.get("MH_QUEUE_BUDGET")
                                else ())))
    assert eng.n_dev == len(jax.devices())    # the GLOBAL mesh
    if os.environ.get("MH_RESUME"):
        from raft_tla_tpu.engine import checkpoint as ckpt_mod
        path = ckpt_mod.latest(os.environ["MH_RESUME"])
        assert path is not None, "no resumable checkpoint found"
        res = eng.run(None, resume=path)
    else:
        res = eng.run([init_state(dims)])
    out = {
        "process": jax.process_index(),
        "global_devices": len(jax.devices()),
        "distinct": res.distinct,
        "generated": res.generated,
        "diameter": res.diameter,
        "levels": res.levels,
        "stop_reason": res.stop_reason,
        "violation": res.violation.invariant if res.violation else None,
    }
    if trace_on and res.violation is not None:
        steps = eng.replay(res.violation.fingerprint)
        assert steps[-1][1] == res.violation.state
        out["trace_len"] = len(steps)
        out["trace_path"] = [g for g, _s in steps]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
