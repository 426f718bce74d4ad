"""Simulation mode — TLC's ``-simulate`` as a vmap'd random-walk kernel.

TLC simulation generates random traces: from an initial state, repeatedly
pick a *uniformly random enabled* action instance, check invariants along the
way, and restart when the trace reaches the depth bound or cannot be extended
[TLC semantics — external; SURVEY §3.4].  The TPU shape is B independent
walkers advanced in lockstep by one ``lax.scan``:

    states [B] -> vmap(expand) -> enabled [B,G]
               -> masked categorical draw (one PRNG key per step)
               -> tree-gather the chosen successor per walker
               -> invariant ids; constraint/dead-end/depth-bound restarts

Each walker carries its current root index and a [depth] ring of the action
ids taken since its last restart, so the first violation latches a complete
(root, action sequence) pair on device; the host replays it through the
expand kernel into a full counterexample trace — the same replay mechanism
the BFS engine uses.  There is no seen-set — simulation never dedups — so
this mode exercises the pure expansion throughput of the machine.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.actions import build_expand
from ..models.dims import RaftDims
from ..models.invariants import build_inv_id
from ..models.pystate import PyState
from ..obs import MetricsRegistry
from ..models.schema import (StateBatch, build_pack_guard, check_packable,
                             decode_state, encode_state, flatten_state,
                             flatten_states, stack_states, state_width,
                             unflatten_state)

_I32 = jnp.int32


@dataclasses.dataclass
class SimResult:
    steps: int = 0                  # states visited (one per walker-step)
    traces: int = 0                 # traces started (initial B + restarts)
    wall_seconds: float = 0.0
    violation_invariant: Optional[str] = None
    violation_state: Optional[PyState] = None
    violation_trace: Optional[List[Tuple[int, PyState]]] = None

    @property
    def states_per_second(self) -> float:
        return self.steps / self.wall_seconds if self.wall_seconds else 0.0


def build_sim_chunk(dims: RaftDims, inv_fns, constraint, B: int, D: int,
                    chunk: int, pipeline: str = "auto"):
    """Returns ``chunk_fn(rows, roots, tstep, cur_root, abuf, key)`` — the
    scan'd walker advance both the single-chip Simulator and the sharded
    parallel.simulate.MeshSimulator run (each chip is just an independent
    walker fleet with its own PRNG key; simulation never communicates).

    With the v2 pipeline (models/actions2.py; ``pipeline`` as in
    EngineConfig), each walker step computes guard masks only and
    constructs ONE successor — the drawn action — instead of all G
    candidates; masks/choice/successors are bit-identical to the v1
    path, so seeded runs agree across pipelines."""
    expand = build_expand(dims)
    pack_ok = build_pack_guard(dims)
    inv_id = build_inv_id(inv_fns)
    from .bfs import _resolve_pipeline
    v2 = _resolve_pipeline(pipeline, dims)

    def body(carry, key):
        (rows, roots, tstep, cur_root, abuf, restarts, latch) = carry
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        if v2 is None:
            cands, en, ovf = jax.vmap(expand)(states)
            # uint8-row wrap counts as overflow (schema.build_pack_guard):
            # the walker restarts rather than stepping through an aliased
            # row.  Invariants are still checked on the pre-pack candidate.
            ovf = ovf | (en & ~jax.vmap(jax.vmap(pack_ok))(cands))
        else:
            en, ovf = jax.vmap(v2.masks)(states)    # pack guard folded in
        # Uniform choice among enabled instances (masked categorical).
        logits = jnp.where(en, 0.0, -jnp.inf)
        choice = jax.random.categorical(key, logits, axis=-1)    # [B]
        can_step = jnp.any(en, axis=1)
        if v2 is None:
            nxt = jax.tree.map(lambda a: a[jnp.arange(B), choice], cands)
        else:
            ph = jax.vmap(v2.parent_hash)(states)   # DCE'd: hashes unused
            _h, _l, nxt = jax.vmap(v2.lane_out)(states, ph,
                                                choice.astype(_I32))
        nrows = jax.vmap(flatten_state, (0, None))(nxt, dims)

        if inv_fns:
            inv = jax.vmap(inv_id)(nxt)
        else:
            inv = jnp.full((B,), -1, _I32)
        bad = can_step & (inv >= 0)
        vf, vinv, vroot, vlen, vacts, vchoice = latch
        any_new = jnp.any(bad) & ~vf
        w = jnp.argmax(bad)
        latch = (vf | jnp.any(bad),
                 jnp.where(any_new, inv[w], vinv),
                 jnp.where(any_new, cur_root[w], vroot),
                 jnp.where(any_new, tstep[w], vlen),
                 jnp.where(any_new, abuf[w], vacts),
                 jnp.where(any_new, choice[w].astype(_I32), vchoice))

        if constraint is not None:
            cons_ok = jax.vmap(constraint)(nxt)
        else:
            cons_ok = jnp.ones((B,), bool)
        # Record the action taken since the last restart.
        abuf = abuf.at[jnp.arange(B),
                       jnp.clip(tstep, 0, D - 1)].set(
            jnp.where(can_step, choice.astype(_I32), -1))
        # Restart on: dead end, overflow, constraint stop, depth bound.
        restart = (~can_step | jnp.any(ovf, axis=1) | ~cons_ok
                   | (tstep + 1 >= D))
        root_idx = jax.random.randint(jax.random.fold_in(key, 1),
                                      (B,), 0, roots.shape[0])
        rows = jnp.where(restart[:, None], roots[root_idx],
                         jnp.where(can_step[:, None], nrows, rows))
        cur_root = jnp.where(restart, root_idx.astype(_I32), cur_root)
        tstep = jnp.where(restart, 0, tstep + 1)
        restarts = restarts + jnp.sum(restart, dtype=_I32)
        return (rows, roots, tstep, cur_root, abuf, restarts,
                latch), None

    def chunk_fn(rows, roots, tstep, cur_root, abuf, key):
        keys = jax.random.split(key, chunk)
        latch0 = (jnp.bool_(False), jnp.int32(-1), jnp.int32(0),
                  jnp.int32(0), jnp.zeros((D,), _I32), jnp.int32(-1))
        carry0 = (rows, roots, tstep, cur_root, abuf,
                  jnp.int32(0), latch0)
        carry, _ = jax.lax.scan(body, carry0, keys)
        return carry

    return chunk_fn


class Simulator:
    def __init__(self, dims: RaftDims,
                 invariants: Optional[Dict[str, Callable]] = None,
                 constraint: Optional[Callable] = None,
                 batch: int = 256, depth: int = 100, chunk: int = 128,
                 pipeline: str = "auto", metrics=None):
        self.dims = dims
        # Same telemetry spine as the BFS engines (obs/): phase timers
        # around the walker-advance dispatch and the latch fetch, live
        # step/trace counters.
        self.metrics = metrics or MetricsRegistry()
        self.inv_names = list((invariants or {}).keys())
        inv_fns = list((invariants or {}).values())
        self.batch, self.depth, self.chunk = batch, depth, chunk
        self._sw = state_width(dims)
        inv_id = build_inv_id(inv_fns)
        chunk_fn = build_sim_chunk(dims, inv_fns, constraint, batch, depth,
                                   chunk, pipeline=pipeline)

        def roots_inv(batch):
            # Takes the *unpacked* int32 StateBatch, not packed rows: uint8
            # packing wraps out-of-range root values (engine/bfs.py
            # build_root_check), which would mask a root TypeOK violation.
            if inv_fns:
                return jax.vmap(inv_id)(batch)
            return jnp.full(batch.term.shape[:1], -1, _I32)

        self._chunk = jax.jit(chunk_fn, donate_argnums=(0, 4))
        self._roots_inv = jax.jit(roots_inv)
        self._expand1 = jax.jit(build_expand(dims))

    # ------------------------------------------------------------------
    def _prepare_roots(self, roots: List[PyState], res: SimResult, t0):
        """Shared root handling (single-chip and mesh): TLC checks
        invariants on initial states too — a violating root ends the run
        immediately; otherwise reject silently-aliasing roots and return
        the packed root rows."""
        dims = self.dims
        stacked = stack_states([encode_state(s, dims) for s in roots])
        rinv = np.asarray(self._roots_inv(stacked))
        if (rinv >= 0).any():
            idx = int(np.argmax(rinv >= 0))
            res.violation_state = roots[idx]
            res.violation_trace = [(-1, roots[idx])]
            res.violation_invariant = self.inv_names[int(rinv[idx])]
            res.wall_seconds = time.time() - t0
            return None
        check_packable(stacked, dims)
        return flatten_states(stacked, dims)

    def run(self, roots: List[PyState], num_steps: int, seed: int = 0,
            max_seconds: Optional[float] = None) -> SimResult:
        dims, B, D = self.dims, self.batch, self.depth
        res = SimResult()
        t0 = time.time()
        roots_np = self._prepare_roots(roots, res, t0)
        if roots_np is None:
            return res
        roots_j = jnp.asarray(roots_np)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        start = jax.random.randint(sub, (B,), 0, len(roots)).astype(_I32)
        # Initial walker arrays are COMMITTED to the device: the jit
        # cache keys on placement, and uncommitted first-call inputs vs
        # committed carry outputs would recompile the whole scan program
        # on the second call (engine/bfs.py run() rationale).
        dev = jax.devices()[0]
        rows = jax.device_put(roots_j[start], dev)
        cur_root = jax.device_put(start, dev)
        tstep = jax.device_put(jnp.zeros((B,), _I32), dev)
        abuf = jax.device_put(jnp.zeros((B, D), _I32), dev)
        res.traces = B

        mt = self.metrics
        while res.steps < num_steps:
            key, sub = jax.random.split(key)
            with mt.phase_timer("sim_chunk"):
                carry = self._chunk(rows, roots_j, tstep, cur_root, abuf,
                                    sub)
                rows, _roots, tstep, cur_root, abuf, restarts, latch = carry
            res.steps += B * self.chunk
            # int(restarts) below is the blocking device sync of this
            # loop — the "sim_fetch" phase is the walkers' compute time.
            with mt.phase_timer("sim_fetch"):
                res.traces += int(restarts)
                vf, vinv, vroot, vlen, vacts, vchoice = latch
                vf = bool(vf)
            mt.counter("sim/steps", B * self.chunk)
            mt.gauge("sim/traces", res.traces)
            if vf:
                self._reconstruct(res, roots, int(vinv), int(vroot),
                                  int(vlen), np.asarray(vacts),
                                  int(vchoice))
                break
            if max_seconds is not None and time.time() - t0 > max_seconds:
                break
        res.wall_seconds = time.time() - t0
        return res

    # ------------------------------------------------------------------
    def _reconstruct(self, res: SimResult, roots, vinv, vroot, vlen,
                     vacts, vchoice):
        """Replay the latched (root, action sequence) through the kernels.

        The encoded candidate row is threaded through the loop directly:
        re-encoding each decoded PyState would reassign message slots
        (frozenset order), and slot-indexed action ids (Receive /
        Duplicate / Drop) recorded against the walker's slot layout
        would then address the wrong message mid-replay."""
        state = roots[vroot]
        st = encode_state(state, self.dims)
        trace = [(-1, state)]
        for g in list(vacts[:vlen]) + [vchoice]:
            g = int(g)
            cands, en, _ovf = self._expand1(st)
            if g < 0 or not bool(np.asarray(en)[g]):
                break
            row = jax.tree.map(lambda a: np.asarray(a)[g], cands)
            st = StateBatch(*row)
            state = decode_state(st, self.dims)
            trace.append((g, state))
        res.violation_state = state
        res.violation_trace = trace
        res.violation_invariant = (self.inv_names[vinv]
                                   if 0 <= vinv < len(self.inv_names)
                                   else "?")
