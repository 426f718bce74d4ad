"""Mesh-sharded simulation — TLC's ``-simulate`` worker pool on a device
mesh.

Simulation is embarrassingly parallel (SURVEY §3.4: independent random
walkers, no seen-set, no communication), so the mesh version is simply n
independent walker fleets — the same scan'd chunk program as the
single-chip Simulator (engine/simulate.py build_sim_chunk), shard_map'd
over a 1-D mesh with a distinct PRNG key per chip.  Violation latches
are per-chip; the host picks the first latched chip and replays its
(root, action sequence) through the expand kernel exactly like the
single-chip path.  Aggregate throughput scales linearly with chips —
this is the TLC ``-workers N`` analog for simulation mode.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..engine.simulate import SimResult, Simulator, build_sim_chunk
from ..models.dims import RaftDims
from ..models.pystate import PyState


class MeshSimulator:
    """n independent walker fleets of ``batch`` walkers each."""

    def __init__(self, dims: RaftDims,
                 invariants: Optional[Dict[str, Callable]] = None,
                 constraint: Optional[Callable] = None,
                 batch: int = 256, depth: int = 100, chunk: int = 128,
                 devices=None, pipeline: str = "auto", metrics=None):
        self.dims = dims
        self.inv_names = list((invariants or {}).keys())
        inv_fns = list((invariants or {}).values())
        self.batch, self.depth, self.chunk = batch, depth, chunk
        devices = devices if devices is not None else jax.devices()
        self.n_dev = n = len(devices)
        self.mesh = Mesh(np.asarray(devices), ("x",))
        chunk_fn = build_sim_chunk(dims, inv_fns, constraint, batch, depth,
                                   chunk, pipeline=pipeline)

        def sharded(rows, roots, tstep, cur_root, abuf, keys):
            # Leading device axis of size 1 inside shard_map.
            carry = chunk_fn(rows[0], roots, tstep[0], cur_root[0],
                             abuf[0], keys[0])
            rows_o, _roots, tstep_o, cur_root_o, abuf_o, restarts, \
                latch = carry
            vf, vinv, vroot, vlen, vacts, vchoice = latch
            # Everything the host READS is psum-replicated so the loop is
            # multi-controller-safe (parallel/multihost.py rules): the
            # lowest-indexed latched chip's violation wins everywhere.
            from .multihost import bcast_lowest_flagged
            (g_vf, g_vinv, g_vroot, g_vlen, g_vacts,
             g_vchoice) = bcast_lowest_flagged(
                "x", vf, vinv, vroot, vlen, vacts, vchoice)
            return (rows_o[None], tstep_o[None], cur_root_o[None],
                    abuf_o[None], jax.lax.psum(restarts, "x"),
                    g_vf, g_vinv, g_vroot, g_vlen, g_vacts, g_vchoice)

        shard = functools.partial(jax.shard_map, mesh=self.mesh,
                                  check_vma=False)
        sx, rep = P("x"), P()
        self._chunk = jax.jit(shard(
            sharded,
            in_specs=(sx, rep, sx, sx, sx, sx),
            out_specs=(sx, sx, sx, sx) + (rep,) * 7),
            donate_argnums=(0, 4))

        # Root checking + replay reuse the single-chip machinery (its
        # chunk program is jit-lazy and never traced here — only
        # _roots_inv, _reconstruct, and _prepare_roots are used).
        self._single = Simulator(dims, invariants=invariants,
                                 constraint=constraint, batch=batch,
                                 depth=depth, chunk=chunk, metrics=metrics)
        self.metrics = self._single.metrics   # one registry, both paths

    # ------------------------------------------------------------------
    def run(self, roots: List[PyState], num_steps: int, seed: int = 0,
            max_seconds: Optional[float] = None) -> SimResult:
        from . import multihost as mh
        dims, n, B, D = self.dims, self.n_dev, self.batch, self.depth
        res = SimResult()
        t0 = time.time()
        roots_np = self._single._prepare_roots(roots, res, t0)
        if roots_np is None:
            return res
        mesh = self.mesh

        # All inputs are computed identically on every process (same seed)
        # and sharded via put_global — each process materializes only its
        # own shards, so the same code drives one host or a DCN cluster.
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        start = np.asarray(
            jax.random.randint(sub, (n, B), 0, len(roots))).astype(np.int32)
        roots_j = mh.put_global(roots_np, mesh, P())
        rows = mh.put_global(roots_np[start], mesh, P("x"))
        cur_root = mh.put_global(start, mesh, P("x"))
        tstep = mh.put_global(np.zeros((n, B), np.int32), mesh, P("x"))
        abuf = mh.put_global(np.zeros((n, B, D), np.int32), mesh, P("x"))
        res.traces = n * B
        # Wall clocks differ per host: a duration stop must be agreed
        # collectively or the processes' trip counts diverge and the next
        # all_to_all deadlocks (multihost.py rule 4).  The agreement round
        # trip is only paid when it can matter (multi-process AND a
        # duration budget; max_seconds is identical everywhere, so the
        # gate itself is collective-safe).
        any_flag = (mh.build_any(mesh)
                    if mh.is_multiprocess() and max_seconds is not None
                    else None)

        mt = self.metrics
        while res.steps < num_steps:
            key, sub = jax.random.split(key)
            keys = mh.put_global(np.asarray(jax.random.split(sub, n)),
                                 mesh, P("x"))
            with mt.phase_timer("sim_chunk"):
                out = self._chunk(rows, roots_j, tstep, cur_root, abuf,
                                  keys)
            (rows, tstep, cur_root, abuf, g_restarts, g_vf, g_vinv,
             g_vroot, g_vlen, g_vacts, g_vchoice) = out
            res.steps += n * B * self.chunk
            with mt.phase_timer("sim_fetch"):
                res.traces += int(np.asarray(g_restarts))
            mt.counter("sim/steps", n * B * self.chunk)
            mt.gauge("sim/traces", res.traces)
            if bool(np.asarray(g_vf)):
                self._single._reconstruct(
                    res, roots, int(np.asarray(g_vinv)),
                    int(np.asarray(g_vroot)), int(np.asarray(g_vlen)),
                    np.asarray(g_vacts), int(np.asarray(g_vchoice)))
                break
            over = (max_seconds is not None
                    and time.time() - t0 > max_seconds)
            if any_flag is not None:
                over = any_flag(over)
            if over:
                break
        res.wall_seconds = time.time() - t0
        return res
