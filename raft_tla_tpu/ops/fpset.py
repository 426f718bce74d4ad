"""The seen-state set — TLC's FPSet rebuilt as an HBM open-addressing table.

TLC keeps seen-state fingerprints in an in-memory/disk hash set probed one
state at a time [TLC semantics — external].  The first TPU port of this kept
a lex-sorted array merged with a full ``lax.sort`` per step — but an 8M-key
bitonic sort per batch is hundreds of full-array passes and dominated the
whole engine.  This version is the SURVEY §2.4 R3 design proper: a
fixed-capacity **open-addressing hash table resident in HBM** (double
hashing rather than cuckoo eviction — eviction chains serialize badly under
vmap, while bounded double-hash probing is a handful of static gather
rounds), with a *batched parallel insert*:

- each query key probes ``slot_k = (h1 + k*h2) mod C`` for a static number
  of rounds, entirely with gathers/scatters — no data-dependent shapes;
- per round, keys matching an occupied slot resolve as already-present;
  keys over an empty slot stake a **claim** (scatter-max of the query index)
  and exactly the claim winner writes, so concurrent inserts of different
  keys never interleave and the table is deterministic;
- losers re-read the slot after the write (catching same-key duplicates in
  the same batch — the winner's key is now visible) and only then advance
  to their next probe slot.

Insert therefore also performs the *in-batch dedup* that previously needed
a candidate-wide sort: exactly one query per distinct new key reports
``is_new``.  Cost per batch is O(rounds × batch), independent of table
capacity; the old design's O(C log^2 C) sort is gone.

``size`` counts stored keys; a query still unresolved after all probe
rounds sets the ``fail`` flag (table effectively full for that
neighborhood) — the engine raises rather than ever silently dropping a
state.  Keep load below ~0.7 · capacity; the engines' capacity checks
enforce a margin.

Two doors lead into a table.  A chunk program's candidates go through
``insert`` (``insert_windowed`` on the mesh owner): most of them ARE in
the table already, the caller needs ``is_new``, and the benchmark's
key-width controls patch that one function.  A REBUILD (a resume's
checkpointed keys, a growth's rehash: ``from_host_keys``,
``MeshBFSEngine._shards_from_keys``) feeds keys that came out of a
table, pairwise distinct and none of them in the new one, and goes
through ``rebuild_unique``: the same probing and the same chain
invariant, but no match against the table, no ``is_new``, and after the
first probe the rounds run only on the lanes still pending.  The two
stay apart on purpose: a branch inside ``insert_unique`` would change
every cell's chunk program for a gain only a rebuild can show.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from .fingerprint import SENTINEL, fmix32

_U32 = jnp.uint32
_I32 = jnp.int32

# Static probe rounds.  At load factor 0.7 the expected double-hash probe
# count is ~1/(1-0.7) ≈ 3.3; 32 rounds puts the miss probability per query
# around 0.7^32 ≈ 1e-5, and a miss is a *reported error*, never a lost state.
PROBE_ROUNDS = 32

# Claim-table cap (slots).  32 MB of int32 at 2^23; see insert_unique.
CLAIM_CAP = 1 << 23


class FPSet(NamedTuple):
    hi: jnp.ndarray    # [C] uint32 key lane; SENTINEL pair = empty slot
    lo: jnp.ndarray    # [C] uint32
    size: jnp.ndarray  # [] int32 — number of stored keys


def _capacity(requested: int) -> int:
    """Table slots: next power of two >= requested (masked indexing)."""
    c = 1
    while c < requested:
        c <<= 1
    return c


def empty(capacity: int) -> FPSet:
    c = _capacity(capacity)
    return FPSet(hi=jnp.full((c,), SENTINEL, _U32),
                 lo=jnp.full((c,), SENTINEL, _U32),
                 size=jnp.int32(0))


def _probe_base(qhi, qlo, c):
    """(h1, h2) for double hashing; h2 odd => full cycle over power-of-2 C."""
    h1 = fmix32(qhi ^ fmix32(qlo ^ _U32(0x9E3779B9)))
    h2 = fmix32(qlo ^ fmix32(qhi ^ _U32(0x85EBCA6B))) | _U32(1)
    return h1 & _U32(c - 1), h2


# TPU gather/scatter performance is shape-sensitive in three ways this
# module must design around (measured on a v5e, July 2026):
# 1. a gather where a large fraction of lanes reads the SAME address (e.g.
#    every invalid query probing the sentinel key's slot) serializes on the
#    hot address — 0.05ms becomes 300ms;
# 2. non-power-of-two query batches hit a slow lowering (270336 lanes is
#    4000x slower than 262144 for the identical gather);
# 3. the same hot-address serialization applies to SCATTERS — including
#    lanes "masked off" by routing them to one shared out-of-range index
#    with mode="drop".  A scatter with half a million lanes on one
#    (dropped!) index costs ~400ms; four of them made one insert cost
#    1.7 s/batch in round 2.  Masked scatters must therefore be
#    *value-neutral*, not address-neutral: every lane writes to its own
#    (hash-random) address, and inactive lanes contribute the operation's
#    identity element (-1 for the claim's max, SENTINEL for the key
#    table's min) so the write is a no-op wherever it lands.
# Hence: every probing entry point pads its query batch to a power of two,
# inactive lanes GATHER from a per-lane spread address instead of a shared
# one, and every scatter is an identity-element combiner (max/min), never
# a .set behind a shared drop index.  All transformations are semantically
# invisible.

def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _pad_pow2(arrs, fill):
    k = arrs[0].shape[0]
    kp = _pow2(k)
    if kp == k:
        return arrs, k
    return tuple(jnp.concatenate(
        [a, jnp.full((kp - k,), f, a.dtype)]) for a, f in zip(arrs, fill)), k


def dedup_batch(khi, klo, valid):
    """In-batch first-occurrence marking via one (cheap) batch-sized sort.
    Returns ((sorted_hi, sorted_lo), order, first_occ).  Duplicate keys are
    *common* in a BFS batch (many parents generate the same successor), and
    a TPU scatter serializes on colliding indices — so the table insert must
    only ever see unique keys; this pre-pass guarantees that."""
    k = khi.shape[0]
    khi = jnp.where(valid, khi, SENTINEL)
    klo = jnp.where(valid, klo, SENTINEL)
    import jax
    sh, sl, order = jax.lax.sort((khi, klo, jnp.arange(k, dtype=_I32)),
                                 num_keys=2)
    is_sent = (sh == SENTINEL) & (sl == SENTINEL)
    prev_ne = jnp.concatenate([
        jnp.array([True]),
        (sh[1:] != sh[:-1]) | (sl[1:] != sl[:-1])])
    return (sh, sl), order, prev_ne & ~is_sent


def insert_unique(s: FPSet, qhi, qlo, valid) -> Tuple["FPSet", jnp.ndarray,
                                                      jnp.ndarray]:
    """Insert a batch of keys.  Returns ``(table', is_new, fail)``:
    ``is_new[k]`` marks exactly one query per distinct key not previously in
    the table; ``fail`` is True if any valid query exhausted its probes.

    PRECONDITION: valid keys are pairwise distinct (use ``dedup_batch``
    first).  The claim round still resolves the rare *hash* collision of
    distinct keys on one slot deterministically, but heavy same-key batches
    would serialize the claim scatter — that case is the pre-pass's job."""
    c = s.hi.shape[0]
    (qhi, qlo, valid), k = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool)),
        (SENTINEL, SENTINEL, False))
    kp = qhi.shape[0]
    hi, lo = s.hi, s.lo
    h1, h2 = _probe_base(qhi, qlo, c)
    arange = jnp.arange(kp, dtype=_I32)
    spread = (arange & (c - 1)).astype(_I32)   # cold per-lane addresses
    pending = valid
    is_new = jnp.zeros((kp,), bool)
    # The claim table may be smaller than the key table (capped: a 2^28
    # table would need a 1 GB int32 claim).  Two lanes attempting
    # *different* slots that alias in the claim table just means one loses
    # and retries its chain next round — correctness is unaffected, and at
    # 2^23 entries the alias probability per round is ~kp/2^23.
    cm = min(c, CLAIM_CAP) - 1
    # Claim values are round-tagged (r*kp + lane) so a round-r attempt
    # always supersedes any stale entry from an earlier round under the
    # max combiner — no reset scatter, and a claim-cap alias can never
    # eclipse a later round's attempt.  Tags must fit int32:
    assert (PROBE_ROUNDS + 1) * kp < 2**31, "claim tag overflow"
    claim = jnp.full((cm + 1,), -1, _I32)
    # Per-lane probe position.  A lane advances its chain ONLY after
    # observing its current slot occupied by a different key; on a claim
    # loss it retries the same slot next round (the winner's write is
    # visible by then).  This preserves the chain invariant every probing
    # reader depends on — the first empty slot of a key's chain terminates
    # the search — even when a claim-cap alias makes a lane lose a claim
    # on a slot that then stays empty.
    #
    # The rounds run as a while_loop with an any(pending) early exit: at
    # the <=0.55 load the engines maintain, nearly every lane resolves in
    # 2-3 rounds, so the loop runs ~3 iterations instead of a static 32 —
    # the full 32 remain the correctness bound the fail flag reports on.
    import jax

    def round_body(carry):
        hi, lo, claim, step, pending, is_new, r = carry
        probe = ((h1 + step * h2) & _U32(c - 1)).astype(_I32)
        idx = jnp.where(pending, probe, spread)
        cur_hi, cur_lo = hi[idx], lo[idx]
        match = pending & (cur_hi == qhi) & (cur_lo == qlo)
        pending = pending & ~match
        occupied = pending & ~((cur_hi == SENTINEL) & (cur_lo == SENTINEL))
        attempt = pending & ~occupied
        # Every scatter below writes to idx (hash-random, no hot address);
        # inactive lanes write the combiner's identity element instead of
        # being routed to a shared drop index (design note 3 above).
        tag = r * _I32(kp) + arange
        claim = claim.at[idx & cm].max(jnp.where(attempt, tag, -1))
        win = attempt & (claim[idx & cm] == tag)
        hi = hi.at[idx].min(jnp.where(win, qhi, SENTINEL))
        lo = lo.at[idx].min(jnp.where(win, qlo, SENTINEL))
        is_new = is_new | win
        pending = pending & ~win
        step = step + occupied.astype(_U32)
        return hi, lo, claim, step, pending, is_new, r + 1

    def round_cond(carry):
        pending, r = carry[4], carry[6]
        return jnp.any(pending) & (r < PROBE_ROUNDS)

    hi, lo, _claim, _step, pending, is_new, _r = jax.lax.while_loop(
        round_cond, round_body,
        (hi, lo, claim, jnp.zeros((kp,), _U32), pending, is_new,
         _I32(0)))
    return (FPSet(hi=hi, lo=lo,
                  size=s.size + jnp.sum(is_new, dtype=_I32)),
            is_new[:k], jnp.any(pending))


# Lanes of a rebuild's narrower rounds, as divisors of the piece: a
# round costs by the lane, so after the first probe the rounds run on a
# quarter of the piece and then on a thirty-second of it, as soon as the
# lanes still pending fit.  A width under REBUILD_MIN_LANES is not worth
# a compaction of its own.
REBUILD_NARROWINGS = (4, 32)
REBUILD_MIN_LANES = 128


def rebuild_unique(s: FPSet, qhi, qlo, valid):
    """Insert a batch of keys into a table that is being REBUILT.
    Returns ``(table', fail, rounds, lane_rounds)``.

    PRECONDITION: valid keys are pairwise distinct AND none of them is in
    ``s`` (a checkpoint's key dump, a growth's rehash: keys that came out
    of a table).  So a rebuild needs neither ``insert_unique``'s
    ``match`` nor its ``is_new``: every valid lane is new, and a key fed
    twice would be stored twice.

    ``insert_unique`` runs every round on all ``kp`` lanes while any is
    pending, and a settled lane gathers and scatters at its spread
    address like a working one (design note 3).  After one probe the
    pending share is about the table's load, so here the rounds are
    staged over static widths ``kp``, ``kp/4``, ``kp/32``
    (``REBUILD_NARROWINGS``): a stage runs rounds while more lanes are
    pending than the next width holds, then they are brought to the
    front with their keys and chain steps (a prefix sum, one scatter of
    lane numbers, a gather at the next width: a ``lax.sort`` runs 2.5 ms
    a piece faster on a v5e and takes 40 s to compile where this takes
    4), and the next stage runs on that prefix alone.  The width follows
    from the pending count the program itself holds: a rehash into a
    table a quarter full stays wide for as many rounds as it needs, and
    a piece that is mostly padding starts narrow.

    What every probing reader depends on is kept exactly as
    ``insert_unique`` states it: a lane advances its chain only after it
    saw its slot held by another key, a claim loser retries the same
    slot, the first empty slot of a key's chain ends a search.  The one
    claim table is carried through the stages and its tags stay
    monotone across them (round x ``kp`` + the lane's place in the
    current width).  ``fail``: a valid lane still pending after
    ``PROBE_ROUNDS`` rounds in all.  ``rounds`` counts the rounds run,
    ``lane_rounds`` the lanes they ran on (int32 both)."""
    import jax

    c = s.hi.shape[0]
    (qhi, qlo, valid), _k = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool)),
        (SENTINEL, SENTINEL, False))
    kp = qhi.shape[0]
    widths = [kp] + [kp // d for d in REBUILD_NARROWINGS
                     if kp // d >= REBUILD_MIN_LANES]
    cm = min(c, CLAIM_CAP) - 1
    assert (PROBE_ROUNDS + 1) * kp < 2**31, "claim tag overflow"

    hi, lo = s.hi, s.lo
    claim = jnp.full((cm + 1,), -1, _I32)
    step = jnp.zeros((kp,), _U32)
    pending = valid
    qhi, qlo = (jnp.where(valid, q, SENTINEL) for q in (qhi, qlo))
    count = placed = jnp.sum(valid, dtype=_I32)
    r = lane_rounds = _I32(0)
    for w, floor in zip(widths, widths[1:] + [0]):
        if w < qhi.shape[0]:
            # The pending lanes to the front, in their order (at most
            # ``w`` of them unless the rounds ran out, and then ``count``
            # says so): a prefix sum gives every lane its place, the
            # settled ones behind the pending, one scatter of the lanes'
            # own numbers inverts that, and the front ``w`` gather theirs.
            ahead = jnp.cumsum(pending, dtype=_I32)
            lane = jnp.arange(qhi.shape[0], dtype=_I32)
            place = jnp.where(pending, ahead - 1, count + lane - ahead)
            src = jnp.zeros_like(lane).at[place].set(
                lane, unique_indices=True)[:w]
            qhi, qlo, step = qhi[src], qlo[src], step[src]
            pending = jnp.arange(w, dtype=_I32) < count
        h1, h2 = _probe_base(qhi, qlo, c)
        arange = jnp.arange(w, dtype=_I32)
        spread = arange & (c - 1)               # cold per-lane addresses
        # A settled lane stays at the slot that holds its key, an address
        # as scattered as a probing lane's (and a round of lanes at
        # CONSECUTIVE addresses costs a v5e 1.6 times one of lanes at
        # hashed ones); only the lanes without a key, which would all
        # hash alike, go to their spread address.
        keyed = ~((qhi == SENTINEL) & (qlo == SENTINEL))

        def round_body(carry):
            hi, lo, claim, step, pending, _count, r = carry
            probe = ((h1 + step * h2) & _U32(c - 1)).astype(_I32)
            idx = jnp.where(keyed, probe, spread)
            cur_hi, cur_lo = hi[idx], lo[idx]
            occupied = pending & ~((cur_hi == SENTINEL)
                                   & (cur_lo == SENTINEL))
            attempt = pending & ~occupied
            # Identity-element scatters at the lane's own address, as in
            # ``insert_unique``.
            tag = r * _I32(kp) + arange
            claim = claim.at[idx & cm].max(jnp.where(attempt, tag, -1))
            win = attempt & (claim[idx & cm] == tag)
            hi = hi.at[idx].min(jnp.where(win, qhi, SENTINEL))
            lo = lo.at[idx].min(jnp.where(win, qlo, SENTINEL))
            pending = pending & ~win
            step = step + occupied.astype(_U32)
            return (hi, lo, claim, step, pending,
                    jnp.sum(pending, dtype=_I32), r + 1)

        # Rounds on ``w`` lanes while more than ``floor`` are pending.
        r0 = r
        hi, lo, claim, step, pending, count, r = jax.lax.while_loop(
            lambda carry: (carry[5] > floor) & (carry[6] < PROBE_ROUNDS),
            round_body, (hi, lo, claim, step, pending, count, r))
        lane_rounds = lane_rounds + (r - r0) * _I32(w)
    return (FPSet(hi=hi, lo=lo, size=s.size + placed - count),
            count > 0, r, lane_rounds)


def insert(s: FPSet, qhi, qlo, valid) -> Tuple["FPSet", jnp.ndarray,
                                               jnp.ndarray]:
    """Full-batch insert: dedup pre-pass + unique insert.  Returns
    ``(table', is_new, fail)`` with ``is_new`` in the *caller's* (unsorted)
    index domain — exactly one index per distinct new key is marked.
    Pads to a power of two up front so the sort and every probe run on
    fast shapes."""
    (qhi, qlo, valid), k = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool)),
        (SENTINEL, SENTINEL, False))
    kp = qhi.shape[0]
    (sh, sl), order, first = dedup_batch(qhi, qlo, valid)
    s, new_sorted, fail = insert_unique(s, sh, sl, first)
    is_new = jnp.zeros((kp,), bool).at[order].set(new_sorted)
    return s, is_new[:k], fail


def insert_windowed(s: FPSet, qhi, qlo, valid, window: int):
    """``insert`` for a batch that is mostly padding (the mesh owner's
    arrivals: n blocks of ``window`` lanes, each padded with SENTINEL,
    about one block's worth of queries in all).  A gather or a scatter
    costs by the lane whatever the lane holds (design notes 1-3: a masked
    lane does full work at its own spread address), so the probe rounds
    run only on lanes that hold a query.  One stable sort is the
    compaction — SENTINEL sorts last, so the valid queries are the sorted
    prefix, equal keys adjacent with the lowest index first — and
    ``insert`` runs on windows of ``window`` sorted lanes (rounded up to
    a power of two), as many as the valid queries reach: one where they
    fit a block, ``kp / window`` where every lane holds one.  A key on
    both sides of a cut is new in the earlier window, where its lowest
    index lies, and found in the table by the later one.  Each window's
    novelty goes back to the caller's order by a scatter of its own
    lanes.

    Returns ``(table', is_new, fail, windows)``.  ``is_new`` and ``fail``
    are ``insert``'s on the whole batch, lane for lane, and the table
    holds the same key set; only the slot a key lands in may differ
    (claims resolve window by window).  ``windows`` is how many ran.
    Every window goes through ``insert`` itself: the one door by which
    any engine's candidates reach the set."""
    import jax

    (qhi, qlo, valid), k = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool)),
        (SENTINEL, SENTINEL, False))
    kp = qhi.shape[0]
    m = min(_pow2(window), kp)
    (sh, sl), order, _first = dedup_batch(qhi, qlo, valid)
    windows = (jnp.sum(valid, dtype=_I32) + (m - 1)) // m

    def window_body(carry):
        s, is_new, fail, j = carry
        wh, wl, worder = (jax.lax.dynamic_slice_in_dim(a, j * m, m)
                          for a in (sh, sl, order))
        s, wnew, wfail = insert(
            s, wh, wl, ~((wh == SENTINEL) & (wl == SENTINEL)))
        # ``order`` is a permutation: every lane of the window, a padded
        # one too, writes an address of its own.
        is_new = is_new.at[worder].set(wnew, unique_indices=True)
        return s, is_new, fail | wfail, j + 1

    s, is_new, fail, _j = jax.lax.while_loop(
        lambda carry: carry[3] < windows, window_body,
        (s, jnp.zeros((kp,), bool), jnp.bool_(False), _I32(0)))
    return s, is_new[:k], fail, windows


def contains(s: FPSet, qhi, qlo):
    """Membership for a batch of keys.  [K] bool.  Sentinel-keyed (invalid)
    lanes report False."""
    c = s.hi.shape[0]
    (qhi, qlo), k = _pad_pow2((qhi, qlo), (SENTINEL, SENTINEL))
    kp = qhi.shape[0]
    h1, h2 = _probe_base(qhi, qlo, c)
    live = ~((qhi == SENTINEL) & (qlo == SENTINEL))
    spread = (jnp.arange(kp, dtype=_I32) & (c - 1)).astype(_I32)
    import jax

    def round_body(carry):
        found, open_, r = carry
        probe = ((h1 + r.astype(_U32) * h2) & _U32(c - 1)).astype(_I32)
        idx = jnp.where(open_, probe, spread)
        cur_hi, cur_lo = s.hi[idx], s.lo[idx]
        found = found | (open_ & (cur_hi == qhi) & (cur_lo == qlo))
        open_ = open_ & ~((cur_hi == SENTINEL) & (cur_lo == SENTINEL)) \
            & ~found
        return found, open_, r + 1

    found, _open, _r = jax.lax.while_loop(
        lambda c: jnp.any(c[1]) & (c[2] < PROBE_ROUNDS), round_body,
        (jnp.zeros(qhi.shape, bool), live, _I32(0)))
    return found[:k]


def sorted_keys(hi: np.ndarray, lo: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """The keys of a table fetched as it stands (its two arrays, empty
    slots and all), lex-sorted (hi, lo) for a deterministic checkpoint
    layout.  numpy alone."""
    real = ~((hi == SENTINEL) & (lo == SENTINEL))
    hi, lo = hi[real], lo[real]
    order = np.lexsort((lo, hi))
    return hi[order], lo[order]


def to_host_keys(s: FPSet) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize the stored keys host-side, sorted (``sorted_keys``)."""
    return sorted_keys(np.asarray(s.hi), np.asarray(s.lo))


def rebuild_piece(s: FPSet, status, qhi, qlo, valid):
    """One piece of a rebuild and its place in the rebuild's status,
    ``[pieces that failed, rounds, lane_rounds]`` (int32, on the device
    beside the table): ``(table', status')``.  A rebuild carries the
    status through its pieces and reads it once, after the last."""
    s, fail, rounds, lane_rounds = rebuild_unique(s, qhi, qlo, valid)
    return s, status + jnp.stack([fail.astype(_I32), rounds, lane_rounds])


def from_host_keys(keys_hi: np.ndarray, keys_lo: np.ndarray,
                   capacity: int, chunk: int = 1 << 15):
    """Rebuild a table from checkpointed/rehashed keys.  Returns
    ``(table, rounds, lane_rounds)``: the probe rounds the pieces ran and
    the lanes they ran on.

    Every caller feeds keys that are ALREADY pairwise distinct and goes
    into an empty table — they come out of a hash table (growth rehash)
    or a checkpointed key dump (`to_host_keys` output) — so neither the
    per-chunk dedup sort that dominates `insert` nor `insert_unique`'s
    match against the table has anything to find: a rebuild goes through
    `rebuild_unique`, the door of its own whose rounds run, after the
    first, on the lanes still pending.  (The chunk programs keep
    `insert`: their candidates ARE mostly in the table, and they need
    `is_new`.)  The pieces' fail flags and counts stay on the device and
    are read once, after the last piece; an overflow raises then."""
    import jax

    s = empty(capacity)
    status = jnp.zeros((3,), _I32)
    piece = jax.jit(rebuild_piece, donate_argnums=(0, 1))
    n = len(keys_hi)
    for base in range(0, n, chunk):
        h = np.asarray(keys_hi[base:base + chunk], np.uint32)
        l = np.asarray(keys_lo[base:base + chunk], np.uint32)
        pad = chunk - len(h)
        valid = np.arange(chunk) < len(h)
        s, status = piece(
            s, status, jnp.asarray(np.pad(h, (0, pad))),
            jnp.asarray(np.pad(l, (0, pad))), jnp.asarray(valid))
    failed, rounds, lane_rounds = (int(x) for x in np.asarray(status))
    if failed:
        raise RuntimeError(
            f"FPSet rebuild overflow: {n} keys into capacity {capacity}")
    return s, rounds, lane_rounds
