"""Multi-host support — the DCN half of the distributed backend.

SURVEY §5.8 / §2.4 R7: the reference's implied runtime scales past one
machine with distributed TLC (RMI workers); the TPU-native equivalent is
multi-controller JAX — every host runs the SAME program over a global
``jax.sharding.Mesh`` spanning all processes' devices, and the XLA
collectives that dedup/aggregate across chips ride ICI within a host and
DCN between hosts with no code change in the compiled programs.

The compiled shard_map programs (parallel/mesh.py, parallel/simulate.py)
are already multi-host-clean: everything inside is per-shard compute plus
named-axis collectives.  What this module supplies is the HOST-side
contract that multi-controller execution demands:

- ``initialize()`` — process-group setup (wraps
  ``jax.distributed.initialize``; gloo on CPU, ICI/DCN on TPU pods).
- ``put_global(arr, mesh, spec)`` — build a sharded global array from a
  host value that every process computes identically; each process
  materializes only its addressable shards
  (``jax.make_array_from_callback``), so nothing is shipped cross-host.
  Works unchanged on a single-controller mesh.
- ``put_per_process(value, mesh)`` — a [n_devices] device vector where
  each process's shards carry ITS OWN value — the input to psum-style
  agreement on host-local facts (wall clocks differ per host; a stop
  decision must be collective or the next collective deadlocks).
- ``build_any(mesh)`` — a tiny jitted psum program turning per-process
  flags into one replicated boolean every process reads identically.

Host-loop rules for multi-controller engines (enforced by construction
in parallel/simulate.py):

1. every process executes the same sequence of compiled calls (trip
   counts must match — the programs contain collectives);
2. anything the host READS must be fully replicated output (psum'd in
   the program) — per-shard outputs are only fed back into the next
   call, never inspected;
3. anything the host WRITES into the mesh goes through put_global
   (identical everywhere) or put_per_process (explicitly local);
4. control-flow decisions from host-local state (clocks) go through
   build_any() agreement first.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize(coordinator: str = None, num_processes: int = None,
               process_id: int = None) -> None:
    """Join (or create) the process group.  Arguments default to the
    standard env vars (RAFT_COORDINATOR / RAFT_NUM_PROCESSES /
    RAFT_PROCESS_ID), so a launcher can export three variables and run
    the same command on every host."""
    coordinator = coordinator or os.environ.get("RAFT_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("RAFT_NUM_PROCESSES", "0")) or None
    if process_id is None:
        pid = os.environ.get("RAFT_PROCESS_ID")
        process_id = int(pid) if pid is not None else None
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def put_global(arr: np.ndarray, mesh: Mesh, spec: P):
    """Shard an identically-computed-everywhere host array onto the mesh.
    Each process materializes only the shards its devices own."""
    sh = NamedSharding(mesh, spec)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sh, lambda idx: arr[idx])


def put_per_process(value: int, mesh: Mesh):
    """[n_devices] int32 vector where every device owned by this process
    holds this process's ``value`` (other processes fill their own)."""
    n = mesh.devices.size
    local = np.full((n,), np.int32(value))
    return jax.make_array_from_callback(
        (n,), NamedSharding(mesh, P("x")), lambda idx: local[idx])


def _build_agree(mesh: Mesh, reduce_fn):
    """One compiled psum/pmin-style reduction over per-process int32
    values: the shared plumbing behind every agreement primitive."""

    def agree(vals):
        return reduce_fn(vals[0], "x")

    return jax.jit(jax.shard_map(
        agree, mesh=mesh, in_specs=P("x"), out_specs=P(),
        check_vma=False))


def build_any(mesh: Mesh):
    """Agreement primitive: per-process flags -> one replicated 'did
    anyone flag?' boolean."""
    fn = _build_agree(mesh, jax.lax.psum)

    def any_flag(value: bool) -> bool:
        return bool(np.asarray(fn(put_per_process(int(value), mesh))) > 0)

    return any_flag


def build_min(mesh: Mesh):
    """Agreement primitive for VALUES: every process contributes an int,
    all read back the minimum — e.g. agreeing on a chunk-size budget
    derived from per-host clocks (the conservative choice never overshoots
    a deadline)."""
    fn = _build_agree(mesh, jax.lax.pmin)

    def min_val(value: int) -> int:
        return int(np.asarray(fn(put_per_process(int(value), mesh))))

    return min_val


def build_sum(mesh: Mesh):
    """Agreement primitive summing per-PROCESS ints (each process's value
    counted ONCE, not once per device: only the process's first device
    row carries it) — e.g. totalling the per-controller spill-pool rows
    for a global queue size."""
    fn = _build_agree(mesh, jax.lax.psum)
    n = mesh.devices.size
    me = jax.process_index()
    first = min((i for i, d in enumerate(mesh.devices.flat)
                 if d.process_index == me), default=0)

    # The device agreement runs in int32 (JAX x64 is off) and pool row
    # counts at the spill design scale can exceed it: saturate each
    # process's contribution so the device-side sum cannot wrap.  A
    # saturated total still trips every budget below ~2^31/N rows — it
    # can only over-report, never under-report.
    cap = ((1 << 31) - 1) // max(1, jax.process_count())

    def sum_val(value: int) -> int:
        local = np.zeros((n,), np.int32)
        local[first] = min(int(value), cap)
        arr = jax.make_array_from_callback(
            (n,), NamedSharding(mesh, P("x")),
            lambda idx: local[idx[0].start:idx[0].stop])
        return int(np.asarray(fn(arr)))

    return sum_val


def build_budget_agree(mesh: Mesh):
    """Fused per-chunk budget agreement — ONE cross-host round trip for
    the pair every budgeted chunk needs: (any process over deadline?,
    min of the per-process chunk-size budgets)."""
    n = mesh.devices.size

    def agree(vals):
        v = vals[0]
        return jnp.stack([jax.lax.psum(v[0], "x"),
                          jax.lax.pmin(v[1], "x")])

    fn = jax.jit(jax.shard_map(
        agree, mesh=mesh, in_specs=P("x"), out_specs=P(),
        check_vma=False))

    def budget(over: bool, allowed: int):
        local = np.tile(np.asarray([int(over), int(allowed)], np.int32),
                        (n, 1))
        arr = jax.make_array_from_callback(
            (n, 2), NamedSharding(mesh, P("x")), lambda idx: local[idx])
        out = np.asarray(fn(arr))
        return bool(out[0] > 0), int(out[1])

    return budget


def bcast_lowest_flagged(axis: str, flag, *values):
    """Inside a shard_map'd program: broadcast ``values`` from the
    lowest-axis-indexed shard whose ``flag`` is set, so every shard (and
    hence every controller) reads identical replicated results.  Returns
    (any_flag_set, broadcast_values...)."""
    idx = jax.lax.axis_index(axis)
    far = jnp.int32(1 << 30)
    chosen = jax.lax.pmin(jnp.where(flag, idx, far), axis)
    sel = flag & (idx == chosen)
    out = tuple(
        jax.lax.psum(jnp.where(sel, v, jnp.zeros_like(v)), axis)
        for v in values)
    return (chosen < far,) + out
