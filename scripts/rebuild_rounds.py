#!/usr/bin/env python3
"""What a seen-set rebuild's probe rounds cost on the device jax finds.

    chiprun --chips 1 -- python3 scripts/rebuild_rounds.py [--slots 25] [--piece 18] [--loads 0,0.07,0.14] [--pieces 19]

``MeshBFSEngine``'s resume and growth and ``fpset.from_host_keys`` rebuild
a table from keys that came out of one, a piece at a time.  This counts,
for a ``2**slots``-slot table at loads 0, 7 and 14 % and pieces of
``2**piece`` fresh distinct keys (one JSON line each, also written to
``chiprun_out/rebuild_rounds.jsonl``):

- ``insert_unique``: the call's wall (``fpset.insert_unique`` itself),
  the rounds its ``while_loop`` ran and the lanes pending after each (an
  instrumented copy of the same loop: the function returns neither);
- ``round``: the wall of R = 0, 1, 2, 8 rounds of that loop at 2^piece,
  2^(piece-2) and 2^(piece-4) lanes against the same table, so that a
  round's cost by its lanes is a difference of two walls (the table's
  copy and the dispatch cancel): R = 1 less R = 0 is a round whose every
  lane probes, the later differences rounds of mostly settled lanes, at
  their spread addresses as ``insert_unique`` has them and staying at
  their slots as ``rebuild_unique`` has them;
- ``compact``: the two ways to bring the pending lanes to the front, at
  the piece's width (one ``lax.sort`` on "not pending" carrying keys and
  step; a prefix sum and three ``unique_indices`` scatters; a prefix sum,
  one such scatter of the lanes' indices and a quarter-width gather);
- ``rebuild_unique`` (where the checkout has it): wall, rounds and
  lane-rounds of the same pieces;
- ``whole``: the mesh cell's rebuild as one chip runs it, 19 pieces into
  an empty table, by either function: wall, rounds, lane-rounds a key.

Walls are host-clock around ``block_until_ready``, the median of five
calls after a warm one; no number here is a benchmark result.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.ops import fpset
from raft_tla_tpu.ops.fingerprint import SENTINEL

_U32, _I32 = jnp.uint32, jnp.int32
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "rebuild_rounds.jsonl")


def say_to(out, **line):
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a", encoding="utf-8") as f:
        f.write(text + "\n")


def wall_ms(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    walls = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(walls), 3)


def keys(rng, n):
    return (jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32)),
            jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32)))


def rounds_of(s, qhi, qlo, rounds=None, stay=False):
    """``insert_unique``'s loop on all-valid lanes: as many rounds as it
    runs (``rounds`` None) or exactly ``rounds``.  With ``stay`` a settled
    lane keeps the (hashed) address of the slot it won, as
    ``rebuild_unique``'s do, where ``insert_unique``'s go to their spread
    address (the lane's number: consecutive).  Returns (table, rounds
    run, lanes pending after each round)."""
    c, kp = s.hi.shape[0], qhi.shape[0]
    h1, h2 = fpset._probe_base(qhi, qlo, c)
    arange = jnp.arange(kp, dtype=_I32)
    spread = arange & (c - 1)
    cm = min(c, fpset.CLAIM_CAP) - 1

    def body(carry):
        hi, lo, claim, step, pending, r, left = carry
        probe = ((h1 + step * h2) & _U32(c - 1)).astype(_I32)
        idx = probe if stay else jnp.where(pending, probe, spread)
        cur_hi, cur_lo = hi[idx], lo[idx]
        match = pending & (cur_hi == qhi) & (cur_lo == qlo)
        pending = pending & ~match
        occupied = pending & ~((cur_hi == SENTINEL) & (cur_lo == SENTINEL))
        attempt = pending & ~occupied
        tag = r * _I32(kp) + arange
        claim = claim.at[idx & cm].max(jnp.where(attempt, tag, -1))
        win = attempt & (claim[idx & cm] == tag)
        hi = hi.at[idx].min(jnp.where(win, qhi, SENTINEL))
        lo = lo.at[idx].min(jnp.where(win, qlo, SENTINEL))
        pending = pending & ~win
        step = step + occupied.astype(_U32)
        left = left.at[r].set(jnp.sum(pending, dtype=_I32))
        return hi, lo, claim, step, pending, r + 1, left

    def cond(carry):
        if rounds is not None:
            return carry[5] < rounds
        return jnp.any(carry[4]) & (carry[5] < fpset.PROBE_ROUNDS)

    hi, lo, _c, _s, _p, r, left = jax.lax.while_loop(
        cond, body,
        (s.hi, s.lo, jnp.full((cm + 1,), -1, _I32), jnp.zeros((kp,), _U32),
         jnp.ones((kp,), bool), _I32(0),
         jnp.zeros((fpset.PROBE_ROUNDS,), _I32)))
    return fpset.FPSet(hi=hi, lo=lo, size=s.size), r, left


def compact_sort(pending, qhi, qlo, step):
    _k, qhi, qlo, step = jax.lax.sort(
        ((~pending).astype(_I32), qhi, qlo, step), num_keys=1)
    return qhi, qlo, step


def compact_scatter(pending, qhi, qlo, step):
    kp = pending.shape[0]
    ahead = jnp.cumsum(pending, dtype=_I32)
    arange = jnp.arange(kp, dtype=_I32)
    dest = jnp.where(pending, ahead - 1, ahead[-1] + arange - ahead)
    return tuple(jnp.zeros((kp,), a.dtype).at[dest].set(
        a, unique_indices=True) for a in (qhi, qlo, step))


def compact_index(pending, qhi, qlo, step):
    """One scatter of the lanes' own indices, then the front quarter
    gathered."""
    kp = pending.shape[0]
    ahead = jnp.cumsum(pending, dtype=_I32)
    arange = jnp.arange(kp, dtype=_I32)
    dest = jnp.where(pending, ahead - 1, ahead[-1] + arange - ahead)
    src = jnp.zeros((kp,), _I32).at[dest].set(
        arange, unique_indices=True)[:kp // 4]
    return qhi[src], qlo[src], step[src]


def filled(rng, slots, load, piece):
    """A table at ``load``, filled a piece at a time."""
    s = fpset.empty(1 << slots)
    ins = jax.jit(fpset.insert_unique, donate_argnums=(0,))
    valid = jnp.ones((piece,), bool)
    fail = False
    for _ in range(round(load * (1 << slots) / piece)):
        s, _new, fail = ins(s, *keys(rng, piece), valid)
    assert not bool(fail)
    return s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=25)
    ap.add_argument("--piece", type=int, default=18)
    ap.add_argument("--loads", default="0,0.07,0.14")
    ap.add_argument("--pieces", type=int, default=19)
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--out", default=OUT, help="the lines, appended")
    args = ap.parse_args()
    say = functools.partial(say_to, args.out)
    dev = jax.devices()[0]
    say(what="device", platform=dev.platform, kind=dev.device_kind,
        slots=args.slots, piece=args.piece)
    rng = np.random.default_rng(args.seed)
    kp = 1 << args.piece
    rebuild = getattr(fpset, "rebuild_unique", None)
    valid = jnp.ones((kp,), bool)

    # One jit a shape, whatever the load.
    insert_fn = jax.jit(fpset.insert_unique)
    count_fn = jax.jit(rounds_of)
    round_fns = {n: jax.jit(lambda s, h, l, n=n: rounds_of(s, h, l, n))
                 for n in (0, 1, 2, 8)}
    stay_fns = {n: jax.jit(lambda s, h, l, n=n: rounds_of(s, h, l, n, True))
                for n in (2, 8)}
    compact_fns = {"sort_ms": jax.jit(compact_sort),
                   "scatter_ms": jax.jit(compact_scatter),
                   "index_ms": jax.jit(compact_index)}
    rebuild_fn = rebuild and jax.jit(rebuild)
    for load in (float(x) for x in args.loads.split(",")):
        s = filled(rng, args.slots, load, kp)
        qhi, qlo = keys(rng, kp)
        _t, r, left = count_fn(s, qhi, qlo)
        r = int(r)
        say(what="insert_unique", load=load, lanes=kp, rounds=r,
            pending_after=np.asarray(left)[:r].tolist(),
            call_ms=wall_ms(insert_fn, s, qhi, qlo, valid))
        for shift in (0, 2, 4):
            w = kp >> shift
            by_rounds = {n: wall_ms(fn, s, qhi[:w], qlo[:w])
                         for n, fn in round_fns.items()}
            staying = {n: wall_ms(fn, s, qhi[:w], qlo[:w])
                       for n, fn in stay_fns.items()}
            say(what="round", load=load, lanes=w, ms_by_rounds=by_rounds,
                first_round_ms=round(by_rounds[1] - by_rounds[0], 3),
                second_round_ms=round(by_rounds[2] - by_rounds[1], 3),
                later_round_ms=round((by_rounds[8] - by_rounds[2]) / 6, 3),
                later_round_staying_ms=round(
                    (staying[8] - staying[2]) / 6, 3))
        pending = jnp.asarray(rng.random(kp) < max(load, 0.02))
        step = jnp.zeros((kp,), _U32)
        say(what="compact", load=load, lanes=kp,
            **{name: wall_ms(fn, pending, qhi, qlo, step)
               for name, fn in compact_fns.items()})
        if rebuild is not None:
            _t, fail, rr, lr = rebuild_fn(s, qhi, qlo, valid)
            say(what="rebuild_unique", load=load, lanes=kp, rounds=int(rr),
                lane_rounds=int(lr), fail=bool(fail),
                call_ms=wall_ms(rebuild_fn, s, qhi, qlo, valid))
        del s

    # The mesh cell's rebuild as one of its chips runs it.
    sets = [keys(rng, kp) for _ in range(args.pieces)]

    def plain_door(s, h, l):        # the function itself: it counts nothing
        return fpset.insert_unique(s, h, l, valid)[0], _I32(0), _I32(0)

    def counted_door(s, h, l):
        s, r, _left = rounds_of(s, h, l)
        return s, r, r * kp

    def rebuild_door(s, h, l):
        s, _fail, r, lane_rounds = rebuild(s, h, l, valid)
        return s, r, lane_rounds

    doors = {"insert_unique itself": plain_door,
             "insert_unique": counted_door}
    if rebuild is not None:
        doors["rebuild_unique"] = rebuild_door
    for name, door in doors.items():
        door, walls = jax.jit(door, donate_argnums=(0,)), []
        for _ in range(4):          # the first compiles
            s, counts = fpset.empty(1 << args.slots), []
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for h, l in sets:
                s, *count = door(s, h, l)
                counts.append(count)
            jax.block_until_ready(s)
            walls.append(round(time.perf_counter() - t0, 4))
        size = int(jnp.sum(~((s.hi == SENTINEL) & (s.lo == SENTINEL))))
        del s
        rounds = [int(r) for r, _lanes in counts]
        lane_rounds = sum(int(lanes) for _r, lanes in counts)
        say(what="whole", door=name, pieces=args.pieces, keys=size,
            walls_s=walls[1:], rounds_by_piece=rounds,
            lane_rounds=lane_rounds,
            lane_rounds_a_key=round(lane_rounds / (args.pieces * kp), 3))


if __name__ == "__main__":
    main()
