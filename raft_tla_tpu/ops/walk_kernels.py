"""Swarm-walk primitives — counter PRNG + per-walk fingerprint rings.

The swarm engine (engine/swarm.py) runs W randomized walks in lockstep
and must stay **partition-invariant**: slicing the W walks into device
batches of 64 or 256 lanes may never change any walk's trajectory.
``jax.random`` key-split chains cannot give that property — a split
sequence threads state through the batch loop, so the stream a walk
sees depends on which slice it landed in.  These kernels instead derive
every per-walk decision from a *counter hash*: pure uint32 avalanche
mixing (the murmur3 fmix32 finalizer already underpinning the state
fingerprints, ops/fingerprint.py) over the tuple ``(seed, walk, step,
stream)``.  Two consequences the engine's contract rests on:

- **replayability** — the i-th decision of walk w under seed s is a
  pure function of (s, w, i); re-running any subset of walks replays
  them bit-identically;
- **partition invariance** — no cross-walk state exists, so the
  visited-fingerprint multiset of a (seed, walks, depth) run is
  independent of the device batch size (tests/test_swarm.py pins it).

The per-walk dedup structure is a fixed-size **fingerprint ring**: the
last R accepted (hi, lo) pairs per walk, probed before every step.
This replaces the exhaustive engines' global sorted FPSet — no host
round-trip, no growth/rehash path, O(R) VPU compares per step — at the
cost of only suppressing short revisit cycles, which is the right
trade for a walker: TLC's ``-simulate`` dedups nothing at all.  The
ring is initialized to the FPSet's reserved all-ones sentinel pair
(ops/fingerprint.py remaps real fingerprints off it), so empty slots
can never alias a real state.

Plain jnp ops throughout; the vmap'd expand kernels the swarm calls
into are already the BLEST-grouped family kernels.
"""

from __future__ import annotations

import jax.numpy as jnp

from .fingerprint import SENTINEL, fmix32

_U32 = jnp.uint32
_I32 = jnp.int32

#: Decision streams: one odd salt per independent per-step draw, so the
#: successor choice and the restart-root choice of the same (walk, step)
#: never correlate.
CHOICE_STREAM = 0x9E3779B1      # which enabled action instance to take
ROOT_STREAM = 0x85EBCA77        # which root to restart onto
INIT_STREAM = 0x27D4EB2F        # the walk's very first root
FAMILY_STREAM = 0x165667B1      # the trace's family-subset mask


def walk_bits(seed, walk_id, step, stream):
    """Counter-hash random bits for one decision: uint32, a pure
    function of ``(seed, walk_id, step, stream)``.  ``walk_id`` and
    ``step`` may be arrays (one draw per lane — the family-mask stream
    keys ``step`` on each lane's trace epoch); ``seed``/``stream`` are
    scalars.  Three chained fmix32 avalanches — each input fully mixed
    before the next is folded in — give the independence the masked
    draw needs (a modulo over correlated low bits would bias toward
    low action indices)."""
    h = fmix32(jnp.asarray(seed).astype(_U32)
               * _U32(0x85EBCA6B) ^ _U32(stream))
    h = fmix32(h ^ (jnp.asarray(walk_id).astype(_U32) * _U32(0xC2B2AE35)))
    return fmix32(h ^ (jnp.asarray(step).astype(_U32) * _U32(0x9E3779B9)))


def masked_choice(bits, enabled):
    """Uniform index draw over the True lanes of ``enabled`` [..., G]
    from counter ``bits`` [...]: rank = bits mod popcount, then the
    rank-th enabled lane via cumulative count.  Rows with no enabled
    lane return lane 0 — callers must gate on ``any(enabled)`` (the
    same dead-walk contract as the simulator's categorical draw).
    The modulo bias at G ≪ 2^32 is ~G/2^32 — irrelevant next to the
    determinism it buys."""
    cnt = jnp.cumsum(enabled.astype(_I32), axis=-1)
    total = cnt[..., -1]
    rank = (bits % jnp.maximum(total, 1).astype(_U32)).astype(_I32)
    return jnp.argmax(cnt > rank[..., None], axis=-1).astype(_I32)


def family_subset(bits, fam):
    """Per-lane action-family keep-mask, expanded to instance lanes:
    instance ``g`` is *preferred* iff bit ``fam[g] mod 32`` of the
    lane's mask word ``bits`` is set, so each of the model's action
    families (models/actions.py family_groups order) is kept with
    probability 1/2 per draw.  This is Holzmann-style swarm
    diversification: a uniform draw over *instances* drowns a hunt in
    whichever family owns the most lanes (raft's three 32-slot message
    families hold 96 of 132 instances), whereas a per-trace family
    subset gives every trace a different sub-model to explore.  ``fam``
    is the static [G] instance->family index; families past 32 share
    mask bits (still diverse, never unsound — the mask only biases)."""
    shift = (fam % 32).astype(_U32)
    return ((bits[..., None] >> shift) & _U32(1)) != 0


def preferred_choice(bits, enabled, preferred):
    """``masked_choice`` over ``enabled & preferred`` when that set is
    non-empty, else over all of ``enabled``: the family bias can never
    stall a walk that still has successors, so reachability (and the
    dead-walk restart contract) is exactly the unbiased kernel's."""
    pref = enabled & preferred
    use = jnp.where(jnp.any(pref, axis=-1, keepdims=True), pref, enabled)
    return masked_choice(bits, use)


def ring_init(lanes: int, capacity: int):
    """Fresh per-walk rings: ``(ring_hi, ring_lo, pos)`` with every slot
    on the reserved sentinel pair (matches no real fingerprint)."""
    return (jnp.full((lanes, capacity), SENTINEL, _U32),
            jnp.full((lanes, capacity), SENTINEL, _U32),
            jnp.zeros((lanes,), _I32))


def ring_probe(ring_hi, ring_lo, hi, lo):
    """Per-lane membership: is (hi, lo) among the lane's last R accepted
    fingerprints?  Dense compare over the ring axis — R is small and
    static, so this stays one fused VPU reduction per step."""
    return jnp.any((ring_hi == hi[:, None]) & (ring_lo == lo[:, None]),
                   axis=1)


def ring_push(ring_hi, ring_lo, pos, hi, lo, do):
    """Append (hi, lo) at each lane's cursor where ``do``; cursors only
    advance on a real push, so a stalled walk never evicts history."""
    lanes = jnp.arange(ring_hi.shape[0])
    slot = pos % ring_hi.shape[1]
    cur_hi, cur_lo = ring_hi[lanes, slot], ring_lo[lanes, slot]
    ring_hi = ring_hi.at[lanes, slot].set(jnp.where(do, hi, cur_hi))
    ring_lo = ring_lo.at[lanes, slot].set(jnp.where(do, lo, cur_lo))
    return ring_hi, ring_lo, pos + do.astype(_I32)


def ring_reset(ring_hi, ring_lo, pos, mask):
    """Clear the rings of lanes in ``mask`` back to sentinel (a restart
    begins a fresh trace: dedup is per-trace, so a new walk may
    legitimately revisit states an earlier trace saw)."""
    ring_hi = jnp.where(mask[:, None], SENTINEL, ring_hi)
    ring_lo = jnp.where(mask[:, None], SENTINEL, ring_lo)
    return ring_hi, ring_lo, jnp.where(mask, 0, pos)


# -- observational Bloom filters (the hunt observatory) -----------------
# The saturation estimator (obs/hunt.py) needs to classify every
# accepted visit as the first / second / later observation of its
# fingerprint WITHOUT reintroducing the global seen-set the swarm
# exists to avoid.  A pair of fixed-size two-probe Bloom filters
# (seen>=1 / seen>=2) gives that: O(1) gathers per step, scatter-max
# updates (idempotent, so duplicate probes within one dispatch are
# harmless), and — critically — the filters feed NOTHING back into the
# walk decisions, so the hunt's verdict and fingerprint multiset stay
# bit-identical with the observatory off (tests/test_swarm.py pins it).
# Cells are uint8 (jnp scatter-max has no bitwise dtype), so a filter
# is cells bytes of device memory; the default 2^20 keeps the two-probe
# collision probability ~load^2 auditable in the hunt report.

def bloom_init(cells: int):
    """One empty filter: ``cells`` uint8 slots, ``cells`` a power of
    two (the probes mask with ``cells - 1``)."""
    if cells & (cells - 1) or cells < 2:
        raise ValueError(f"bloom cells must be a power of two, "
                         f"got {cells}")
    return jnp.zeros((cells,), jnp.uint8)


def bloom_probes(bloom, hi, lo):
    """The two probe indices for fingerprint (hi, lo): the halves are
    already independent avalanche mixes (ops/fingerprint.py), so their
    low bits are the two hash functions for free."""
    m = _U32(bloom.shape[0] - 1)
    return (hi & m).astype(_I32), (lo & m).astype(_I32)


def bloom_probe(bloom, hi, lo):
    """Per-lane membership: True iff BOTH probe cells are set (the
    standard k=2 conjunction; false positives ~load^2, never false
    negatives)."""
    i1, i2 = bloom_probes(bloom, hi, lo)
    return (bloom[i1] > 0) & (bloom[i2] > 0)


def bloom_push(bloom, hi, lo, do):
    """Insert the lanes where ``do`` (scatter-max: racing duplicate
    indices within one dispatch commute, so partition slicing cannot
    change the resulting filter)."""
    i1, i2 = bloom_probes(bloom, hi, lo)
    m = do.astype(jnp.uint8)
    return bloom.at[i1].max(m).at[i2].max(m)
