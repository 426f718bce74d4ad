"""Candidate-lane compaction — the stage between expand and the FPSet.

The expand kernel emits a [B, G] enabled mask whose true fraction is
typically well under 10% (measured fan-out ~6% of G on MCraft_bounded), so
everything downstream of expand — fingerprint insert, row materialization,
invariant/constraint evaluation, enqueue — runs on K << B*G compacted
lanes.  This module is the single implementation both engines (engine/
bfs.py, parallel/mesh.py) share; its invariants are load-bearing:

- ``K`` is a power of two and ``K >= G``, so one parent's worst-case
  fan-out always fits and a batch always makes progress (``P >= 1``);
- **progress limiting**: only the longest prefix of parents whose total
  fan-out fits K is taken; the caller advances its queue offset by ``P``,
  so a fan-out burst costs extra steps, never dropped states;
- every scatter/gather lane has its own cold address: masked-off lanes
  write to per-lane trash slots in [K, 2K) and unfilled live slots keep a
  spread init, because a shared hot address serializes the op on TPU
  (ops/fpset.py design notes 1+3).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import fpset

_I32 = jnp.int32


def choose_k(B: int, G: int, requested=None) -> int:
    """Compacted-lane count: the requested value (engine config) or the
    16-lanes-per-parent default, rounded to a power of two.

    Floored at ``max(G, B)``: G so one parent's worst-case fan-out fits
    (progress guarantee), and B because the engines' ingest path enqueues
    up to B rows per call against a spill watermark of K — a smaller K
    would let one ingest call run live rows into the scatter-trash region.
    Capped at ``_pow2(B*G)``; more lanes than candidates is pure waste."""
    k = requested
    if k is None:
        k = min(16 * B, B * G)
    return min(fpset._pow2(max(k, G, B)), fpset._pow2(B * G))


def kspread(B: int, G: int, K: int):
    """Hash-spread addresses for dead compacted slots: each its own
    cold address (a shared one serializes the gathers that follow)."""
    return jnp.asarray((np.arange(K) * 2654435761) % (B * G), _I32)


def build_compactor(B: int, G: int, K: int, reduce_p=None):
    """Returns ``compact(en) -> (P, total, lane_id, kvalid)`` for a
    [B, G] enabled mask:

    - ``P``       parents taken this step (advance the offset by this);
    - ``total``   number of live compacted lanes (== sum of en over the
                  first P parents);
    - ``lane_id`` [K] flat candidate-lane index per compacted slot
                  (spread addresses in dead slots).  Disabled lanes write
                  to the K-slot trash region ``K + (lane & (K-1))``; when
                  B*G > K that aliases ~B*G/K lanes per trash slot (~G/16
                  ≈ 8 at the default K = 16·B) — bounded write conflicts,
                  accepted: spreading fully would need a K+B*G-wide
                  scratch target, and an 8-way conflict is noise next to
                  the all-lanes-one-address serialization this avoids;
    - ``kvalid``  [K] liveness mask (arange < total).

    ``reduce_p`` (optional) reduces the locally-computed P before it is
    applied — the mesh engine passes ``lax.pmin`` over the device axis so
    every chip advances its offset identically (the chunk body contains
    collectives, so trip counts must agree).

    The lowering is a B*G-lane scatter of lane indices into the K live +
    K trash slots.  (A ``searchsorted`` inversion of the running count
    was the alternative until PR 31 timed it on the chip: 2.2 to 5.6 %
    slower end to end in both deep cells, PERF.md section 6.)"""
    BG = B * G
    lane_f = jnp.arange(BG, dtype=_I32)
    kspr = kspread(B, G, K)

    def _prefix(en):
        per_parent = jnp.sum(en, axis=1, dtype=_I32)        # [B]
        cum = jnp.cumsum(per_parent)                        # [B]
        P = jnp.sum(cum <= K, dtype=_I32)
        if reduce_p is not None:
            P = reduce_p(P)
        total = jnp.where(P > 0, cum[jnp.clip(P - 1, 0, B - 1)], 0)
        enf = (en & (jnp.arange(B, dtype=_I32) < P)[:, None]).reshape(-1)
        kvalid = jnp.arange(K, dtype=_I32) < total
        return P, total, enf, kvalid

    def compact_scatter(en):
        P, total, enf, kvalid = _prefix(en)
        posk = jnp.cumsum(enf.astype(_I32)) - 1
        pos = jnp.where(enf, posk, K + (lane_f & (K - 1)))
        lane_id = jnp.concatenate([kspr, kspr]) \
            .at[pos].set(lane_f)[:K]
        return P, total, lane_id, kvalid

    return compact_scatter
