"""A trace rebuilt in one device call.

Both trace stores know every action instance of a trace before the first
dispatch: the BFS record holds it (``engine/trace.py`` ``chain``: the
chunk's ``record`` stage wrote ``lane_id`` beside each child's key), the
walker's action record IS it (``engine/swarm.py`` ``vacts``).  So the
steps run ON THE DEVICE: one jitted program takes the root's packed row,
the instances ``g[0..n)`` and ``n``, threads the successor's row from
step to step (never a re-encoded state: re-encoding sorts the message
slots, and a slot-indexed instance would then address another message)
and hands back, in one array, for every step: the successor's packed
row, its key ``(hi, lo)`` and whether ``g[t]`` was enabled on the row it
was applied to.

The trip count is traced (``lax.while_loop`` over ``n``, writing into a
buffer of ``capacity`` steps), so a 9-step trace runs nine bodies and
one executable serves every length; a longer trace takes
``ceil(n / capacity)`` calls, each from the last row of the one before.
The loop ends at the first instance that is negative or not enabled:
what follows it would be steps from a row the spec never reached.

``BFSEngine.replay`` (and the mesh engine's, which is the same) and
``SwarmEngine.replay_actions`` build one :class:`ReplayScan` each, from
their own ``dims``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.actions import build_expand
from ..models.schema import (encode_state, flatten_state, state_width,
                             unflatten_state)
from ..ops.fingerprint import build_fingerprint

_I32 = jnp.int32
_U8 = jnp.uint8

#: Steps one call can rebuild (a longer trace takes more calls).
REPLAY_CAPACITY = 128
#: What follows a step's packed row in the program's result: the key's
#: two halves, four little-endian bytes each, and the enabled flag.
_TAIL = 9


def leading_true(flags) -> int:
    """How many of ``flags`` hold before the first that does not."""
    missed = np.flatnonzero(~np.asarray(flags, bool))
    return int(missed[0]) if len(missed) else len(flags)


def build_replay_step(dims, body: str = "v1"):
    """``(state, g) -> (successor, hi, lo, enabled)`` for one state and
    one action instance, by the v1 kernels (``expand`` of every
    instance, the ``g``-th selected, its ``fingerprint``: what the
    per-step replay has always run, and every variant has) or the v2
    kernels (``masks`` for the guard, ``lane_out`` for the one successor
    and its delta key).  Same successor and key either way, bit for bit
    (``tests/test_replay_scan.py``)."""
    if body == "v1":
        expand = build_expand(dims)
        fingerprint = build_fingerprint(dims)

        def step(st, g):
            cands, en, _ovf = expand(st)
            succ = jax.tree.map(lambda a: a[g], cands)
            hi, lo = fingerprint(succ)
            return succ, hi, lo, en[g]

        return step
    if body != "v2":
        raise ValueError(f"replay body {body!r}: 'v1' or 'v2'")
    from ..models.actions2 import build_v2
    v2 = build_v2(dims)

    def step(st, g):
        en, _ovf = v2.masks(st)
        hi, lo, succ = v2.lane_out(st, v2.parent_hash(st), g)
        return succ, hi, lo, en[g]

    return step


def build_replay_program(dims, *, capacity: int = REPLAY_CAPACITY,
                         body: str = "v1"):
    """The jitted ``replay_scan(row [sw] u8, acts [capacity] i32, n i32)
    -> [capacity, sw + 9] u8``: line ``t`` holds step ``t``'s successor
    row, key and enabled flag; lines past the last step run are zero."""
    sw, G = state_width(dims), dims.n_instances
    step = build_replay_step(dims, body)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8

    def replay_scan(row, acts, n):
        def cond(carry):
            t, _row, _out, alive = carry
            return (t < n) & alive

        def one(carry):
            t, row, out, _alive = carry
            g = acts[t]
            succ, hi, lo, en = step(unflatten_state(row, dims),
                                    jnp.clip(g, 0, G - 1))
            en = en & (g >= 0) & (g < G)
            nrow = flatten_state(succ, dims)
            line = jnp.concatenate([
                nrow, ((hi >> shifts) & 0xFF).astype(_U8),
                ((lo >> shifts) & 0xFF).astype(_U8), en.astype(_U8)[None]])
            out = jax.lax.dynamic_update_slice(out, line[None], (t, 0))
            return t + 1, nrow, out, en

        init = (_I32(0), row, jnp.zeros((capacity, sw + _TAIL), _U8),
                jnp.bool_(True))
        return jax.lax.while_loop(cond, one, init)[2]

    return jax.jit(replay_scan)


class ReplayScan:
    """One engine's fused replay: the program and the host's side of a
    call.  Counts in ``metrics``: ``engine/replay_scans`` calls of the
    program, ``engine/replay_scan_steps`` steps they rebuilt; each call
    is a ``replay_scan`` span."""

    def __init__(self, dims, metrics, *, capacity: int = REPLAY_CAPACITY,
                 body: str = "v1"):
        self.dims, self.metrics, self.capacity = dims, metrics, capacity
        self._sw = state_width(dims)
        self._program = build_replay_program(dims, capacity=capacity,
                                             body=body)

    def __call__(self, root, actions) -> Tuple[np.ndarray, np.ndarray, int]:
        """``(rows [m, sw] u8, keys [m] u64, calls)`` of the first ``m``
        steps from ``root`` (a ``PyState``) through the instance ids
        ``actions``: all of them, or those before the first id that is
        negative or not enabled on the row it meets; ``calls`` of the
        program made them."""
        sw, cap = self._sw, self.capacity
        acts = np.asarray(actions, np.int32).reshape(-1)
        row = flatten_state(encode_state(root, self.dims), self.dims)
        # Seeded with nothing, so that no step at all still concatenates.
        rows, keys = [np.zeros((0, sw), np.uint8)], [np.zeros(0, np.uint64)]
        calls = 0
        for start in range(0, len(acts), cap):
            part = acts[start:start + cap]
            with self.metrics.phase_timer("replay_scan", steps=len(part)):
                out = np.asarray(self._program(
                    row, np.pad(part, (0, cap - len(part))),
                    np.int32(len(part))))
            calls += 1
            done = leading_true(out[:len(part), -1])
            self.metrics.counter("engine/replay_scans")
            self.metrics.counter("engine/replay_scan_steps", done)
            rows.append(out[:done, :sw])
            halves = out[:done, sw:sw + 8].copy().view("<u4")
            keys.append((halves[:, 0].astype(np.uint64) << np.uint64(32))
                        | halves[:, 1].astype(np.uint64))
            if done < len(part):
                break
            row = out[done - 1, :sw]
        return np.concatenate(rows), np.concatenate(keys), calls
