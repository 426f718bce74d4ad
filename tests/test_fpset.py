"""Direct FPSet property tests (the engines exercise it indirectly).

The insert path has two performance-driven subtleties that need their own
regression coverage:

- scatters are value-neutral (identity-element combiners), never routed to
  a shared drop index — see the design notes in ops/fpset.py;
- the claim table may be smaller than the key table (``CLAIM_CAP``), so
  distinct slots can alias one claim entry; claims are round-tagged
  (``r*kp + lane`` under a max combiner), so a round-r attempt always
  supersedes any stale entry from an earlier round — no reset scatter,
  and an alias can never eclipse a later round's attempt (without the
  tags, stale winner ids would starve aliased lanes into spurious
  ``fail``).

The test forces the capped path with a tiny cap and checks exact set
semantics against a Python set under heavy duplication across many batches.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tla_tpu.ops import fpset
import raft_tla_tpu.ops.fpset as fp


@pytest.mark.parametrize("claim_cap", [1 << 10, 1 << 30])
def test_insert_matches_set_semantics(claim_cap, monkeypatch):
    """Exact distinct counting vs a Python set, duplicate-heavy batches,
    load driven past 0.25, both the capped and uncapped claim paths."""
    monkeypatch.setattr(fp, "CLAIM_CAP", claim_cap)
    rng = np.random.RandomState(7)
    s = fpset.empty(1 << 16)
    ins = jax.jit(fp.insert)
    ref = set()
    for it in range(8):
        # keys drawn from a small universe => heavy in-batch duplication
        keys = rng.randint(0, 1 << 14, size=2048).astype(np.uint64)
        hi = jnp.asarray((keys >> 32).astype(np.uint32) | np.uint32(it))
        lo = jnp.asarray(keys.astype(np.uint32))
        valid = jnp.asarray(rng.rand(2048) < 0.7)
        s, new, fail = ins(s, hi, lo, valid)
        assert not bool(fail), f"spurious probe failure at iter {it}"
        pairs = {(int(h) | it, int(l))
                 for h, l, v in zip(keys >> 32, keys, np.asarray(valid))
                 if v}
        fresh = pairs - ref
        assert int(new.sum()) == len(fresh)
        ref |= pairs
        assert int(s.size) == len(ref)
    hi = jnp.asarray(np.array([h for h, _ in ref], np.uint32))
    lo = jnp.asarray(np.array([l for _, l in ref], np.uint32))
    assert bool(fp.contains(s, hi, lo).all())
    # absent keys (drawn far outside the key universe) report False
    assert not bool(fp.contains(
        s, hi | jnp.uint32(1 << 20), lo).any())


def test_insert_reports_fail_when_genuinely_full():
    """Overfilling a tiny table must set fail, never silently drop keys."""
    s = fpset.empty(1 << 8)
    hi = jnp.asarray(np.arange(512, dtype=np.uint32))
    lo = jnp.asarray(np.arange(512, dtype=np.uint32) * 7 + 1)
    s, new, fail = fp.insert(s, hi, lo, jnp.ones((512,), bool))
    assert bool(fail)


# -- the rebuild's own insert (PR 49) ---------------------------------------

def _random_keys(rng, n):
    """``n`` pairwise distinct non-sentinel keys."""
    both = rng.choice(1 << 40, size=n, replace=False).astype(np.uint64)
    return ((both >> np.uint64(20)).astype(np.uint32),
            (both & np.uint64((1 << 20) - 1)).astype(np.uint32) * 4099 + 1)


def _chains(hi, lo, slots):
    """(h1, h2) of the double-hash chain of each key, as numpy."""
    h1, h2 = fp._probe_base(jnp.asarray(hi), jnp.asarray(lo), slots)
    return np.asarray(h1), np.asarray(h2)


def _replay_rebuild(table_hi, table_lo, qhi, qlo, valid):
    """``rebuild_unique`` in numpy, round for round: the stages' widths,
    the stable compaction between them, the claim a slot's highest lane
    wins.  Returns (hi, lo, fail, rounds by width)."""
    hi, lo = table_hi.copy(), table_lo.copy()
    c, kp = len(hi), fp._pow2(len(qhi))
    pad = kp - len(qhi)
    qhi, qlo = (np.pad(a, (0, pad), constant_values=fp.SENTINEL)
                for a in (qhi, qlo))
    pending = np.pad(valid, (0, pad))
    h1, h2 = _chains(qhi, qlo, c)
    step = np.zeros(kp, np.uint32)
    cm = min(c, fp.CLAIM_CAP) - 1
    widths = [kp] + [kp // d for d in fp.REBUILD_NARROWINGS
                     if kp // d >= fp.REBUILD_MIN_LANES]
    count, r, ran = int(pending.sum()), 0, {w: 0 for w in widths}
    for w, floor in zip(widths, widths[1:] + [0]):
        if w < len(qhi):
            front = np.argsort(~pending, kind="stable")[:w]
            qhi, qlo, h1, h2, step = (a[front]
                                      for a in (qhi, qlo, h1, h2, step))
            pending = np.arange(w) < count
        while count > floor and r < fp.PROBE_ROUNDS:
            idx = ((h1 + step * h2) & np.uint32(c - 1)).astype(np.int64)
            empty = (hi[idx] == fp.SENTINEL) & (lo[idx] == fp.SENTINEL)
            lanes = np.flatnonzero(pending & empty)
            best = np.full(cm + 1, -1, np.int64)
            np.maximum.at(best, idx[lanes] & cm, lanes)
            won = lanes[best[idx[lanes] & cm] == lanes]
            hi[idx[won]], lo[idx[won]] = qhi[won], qlo[won]
            step += (pending & ~empty).astype(np.uint32)
            pending[won] = False
            count, r, ran[w] = int(pending.sum()), r + 1, ran[w] + 1
    return hi, lo, count > 0, ran


def _chain_invariant_holds(hi, lo):
    """Every slot of a stored key's chain before its own is occupied:
    what lets a reader stop at the first empty slot."""
    c = len(hi)
    occupied = ~((hi == fp.SENTINEL) & (lo == fp.SENTINEL))
    at = np.flatnonzero(occupied)
    h1, h2 = _chains(hi[at], lo[at], c)
    open_ = np.ones(len(at), bool)
    for k in range(fp.PROBE_ROUNDS):
        slot = (h1 + np.uint32(k) * h2) & np.uint32(c - 1)
        open_ &= slot != at
        if not open_.any():
            return True
        if not occupied[slot[open_]].all():
            return False
    return False


REBUILD_SLOTS = 1 << 16
CLAIMS_APART = fp.CLAIM_CAP      # as the module has it: no two slots alias


@pytest.fixture(scope="module")
def loaded_tables():
    """{load: (FPSet of 2^16 slots at that load, the keys in it)}."""
    rng = np.random.default_rng(49)
    out = {}
    for load in (0.0, 0.15, 0.45):
        hi, lo = _random_keys(rng, int(load * REBUILD_SLOTS))
        s, _new, fail = jax.jit(fp.insert_unique)(
            fp.empty(REBUILD_SLOTS), hi, lo, np.ones(len(hi), bool))
        assert not bool(fail)
        out[load] = (s, set(zip(hi.tolist(), lo.tolist())))
    return out


@pytest.mark.parametrize("claim_cap", [CLAIMS_APART, 1 << 10],
                         ids=["claims_apart", "claims_alias"])
@pytest.mark.parametrize("piece", [1 << 10, 1 << 13, 5000])
@pytest.mark.parametrize("load", [0.0, 0.15, 0.45])
def test_rebuild_unique_is_insert_unique_on_a_rebuilds_keys(
        loaded_tables, monkeypatch, load, piece, claim_cap):
    """Keys pairwise distinct and not in the table, as a rebuild's are:
    the same key set and ``size`` as ``insert_unique``, every key found
    and 1,000 others not, no ``fail``, the chain invariant whole; table,
    ``rounds`` and ``lane_rounds`` equal the numpy replay's.  At loads 0
    and 0.15 under a quarter of the lanes are pending after one probe and
    one wide round runs; at 0.45 (and where claims alias: 2^10 claim
    slots, as many winners a round at most) more are, and the wide stage
    runs on."""
    monkeypatch.setattr(fp, "CLAIM_CAP", claim_cap)
    s, held = loaded_tables[load]
    rng = np.random.default_rng(piece + int(100 * load))
    hi, lo = _random_keys(rng, piece + 1000)
    fresh = [i for i, key in enumerate(zip(hi.tolist(), lo.tolist()))
             if key not in held]
    hi, lo = hi[fresh], lo[fresh]
    (hi, lo), (other_hi, other_lo) = ((hi[:piece], lo[:piece]),
                                      (hi[piece:], lo[piece:]))
    valid = np.ones(piece, bool)
    # A new function each case: ``CLAIM_CAP`` is read when it is traced.
    got, fail, rounds, lane_rounds = jax.jit(
        lambda *a: fp.rebuild_unique(*a))(s, hi, lo, valid)
    want, _new, want_fail = jax.jit(
        lambda *a: fp.insert_unique(*a))(s, hi, lo, valid)
    assert not bool(fail) and not bool(want_fail)
    assert int(got.size) == int(want.size) == len(held) + piece
    for a, b in zip(fp.to_host_keys(got), fp.to_host_keys(want)):
        assert np.array_equal(a, b)
    assert bool(fp.contains(got, hi, lo).all())
    assert not bool(fp.contains(got, other_hi, other_lo).any())
    got_hi, got_lo = np.asarray(got.hi), np.asarray(got.lo)
    assert _chain_invariant_holds(got_hi, got_lo)
    r_hi, r_lo, r_fail, ran = _replay_rebuild(
        np.asarray(s.hi), np.asarray(s.lo), hi, lo, valid)
    assert not r_fail
    assert np.array_equal(got_hi, r_hi) and np.array_equal(got_lo, r_lo)
    assert (int(rounds), int(lane_rounds)) == (
        sum(ran.values()), sum(w * n for w, n in ran.items()))
    kp = fp._pow2(piece)
    assert len(ran) == (2 if piece == 1 << 10 else 3)
    assert ran[kp] >= 1 and int(rounds) > ran[kp]
    if claim_cap == CLAIMS_APART:
        assert (ran[kp] == 1) == (load < 0.45)


def test_rebuild_unique_starts_narrow_on_a_piece_that_is_mostly_padding(
        loaded_tables):
    """A last piece's few keys fit the narrowest width: no wide round."""
    s, _held = loaded_tables[0.15]
    hi, lo = _random_keys(np.random.default_rng(4949), 1 << 13)
    valid = np.arange(1 << 13) < 200
    got, fail, rounds, lane_rounds = jax.jit(fp.rebuild_unique)(
        s, hi, lo, valid)
    assert not bool(fail) and int(got.size) == int(s.size) + 200
    assert bool(fp.contains(got, hi[:200], lo[:200]).all())
    assert not bool(fp.contains(got, hi[200:], lo[200:]).any())
    assert int(lane_rounds) == int(rounds) * ((1 << 13) // 32)


def test_rebuild_unique_reports_fail_where_the_table_is_too_small():
    hi, lo = _random_keys(np.random.default_rng(7), 1 << 10)
    got, fail, rounds, _lane_rounds = jax.jit(fp.rebuild_unique)(
        fp.empty(1 << 9), hi, lo, np.ones(1 << 10, bool))
    assert bool(fail) and int(rounds) == fp.PROBE_ROUNDS
    assert int(got.size) == 1 << 9      # every slot taken, none twice
    assert len(fp.to_host_keys(got)[0]) == 1 << 9


def test_insert_after_a_rebuild_finds_its_keys_and_admits_fresh_ones_once(
        loaded_tables):
    s, held = loaded_tables[0.15]
    hi, lo = _random_keys(np.random.default_rng(11), 3000)
    fresh = [i for i, key in enumerate(zip(hi.tolist(), lo.tolist()))
             if key not in held][:2048]
    hi, lo = hi[fresh], lo[fresh]
    built, fail, _r, _lr = jax.jit(fp.rebuild_unique)(
        s, hi[:1024], lo[:1024], np.ones(1024, bool))
    assert not bool(fail)
    # The rebuilt keys twice over and the fresh ones twice over.
    both_hi, both_lo = np.tile(hi, 2), np.tile(lo, 2)
    after, new, fail = jax.jit(fp.insert)(
        built, both_hi, both_lo, np.ones(4096, bool))
    new = np.asarray(new)
    assert not bool(fail) and int(after.size) == int(built.size) + 1024
    assert not new[:1024].any() and not new[2048:3072].any()
    assert (new[1024:2048] ^ new[3072:]).all()


def test_from_host_keys_reads_its_status_once_and_raises_on_overflow(
        monkeypatch):
    """Twelve pieces into a table that the third overfills: every piece
    is dispatched before the one status read, and the rebuild raises as
    it always has.  A rebuild that fits gives its table and counts."""
    calls = []
    jit = jax.jit

    def counting_jit(fn, **kw):
        compiled = jit(fn, **kw)
        return lambda *a: calls.append(fn.__name__) or compiled(*a)

    monkeypatch.setattr(jax, "jit", counting_jit)
    hi, lo = _random_keys(np.random.default_rng(12), 3000)
    with pytest.raises(RuntimeError, match="FPSet rebuild overflow: 3000 "
                                           "keys into capacity 512"):
        fp.from_host_keys(hi, lo, 512, chunk=256)
    assert calls == ["rebuild_piece"] * 12
    s, rounds, lane_rounds = fp.from_host_keys(hi, lo, 1 << 13, chunk=256)
    assert int(s.size) == 3000 and rounds >= 12
    assert 3000 <= lane_rounds <= rounds * 256
    for a, b in zip(fp.to_host_keys(s), (hi, lo)):
        assert sorted(a.tolist()) == sorted(b.tolist())
