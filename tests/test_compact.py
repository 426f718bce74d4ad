"""ops/compact.py ``build_compactor`` against a plain numpy reference.

The compactor is the stage every later one rests on (construct, insert,
enqueue and record run on its K lanes, and the queue offset advances by
its ``P``), so it is held to a reference here, case by case: nothing
enabled, more enabled than K holds (the progress-limited prefix),
exactly K, and isolated lanes.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.ops.compact import build_compactor


def reference(en: np.ndarray, K: int):
    """(P, total, lane_id, kvalid) as the module's docstring defines them:
    the longest prefix of parents whose enabled lanes fit K, those lanes'
    flat indices in order, the hash-spread address in every dead slot."""
    B, G = en.shape
    cum = np.cumsum(en.sum(axis=1))
    P = int((cum <= K).sum())
    total = int(cum[P - 1]) if P else 0
    live = np.flatnonzero(en[:P].reshape(-1))
    assert len(live) == total
    lane_id = (np.arange(K, dtype=np.int64) * 2654435761) % (B * G)
    lane_id[:total] = live
    return P, total, lane_id, np.arange(K) < total


def check(en: np.ndarray, K: int):
    B, G = en.shape
    P, total, lane_id, kvalid = build_compactor(B, G, K)(jnp.asarray(en))
    want = reference(en, K)
    assert (int(P), int(total)) == want[:2]
    assert (np.asarray(lane_id) == want[2]).all()
    assert (np.asarray(kvalid) == want[3]).all()
    return want


def test_nothing_enabled_takes_every_parent_and_no_lane():
    P, total, _lane_id, kvalid = check(np.zeros((8, 12), bool), 16)
    assert (P, total) == (8, 0) and not kvalid.any()


@pytest.mark.parametrize("B, G, K", [(8, 12, 16), (16, 33, 64), (8, 7, 8)])
def test_everything_enabled_takes_the_prefix_that_fits(B, G, K):
    """Overflow past K: only the parents whose whole fan-out fits are
    taken (K // G of them), none of a later parent's lanes leaks in."""
    P, total, lane_id, _ = check(np.ones((B, G), bool), K)
    assert (P, total) == (K // G, (K // G) * G)
    assert (lane_id[:total] == np.arange(total)).all()


def test_exactly_k_enabled_fills_every_slot():
    rng = np.random.RandomState(5)
    B, G, K = 16, 12, 64
    en = np.zeros((B, G), bool)
    en.reshape(-1)[rng.choice(B * G, K, replace=False)] = True
    P, total, _lane_id, kvalid = check(en, K)
    assert (P, total) == (B, K) and kvalid.all()
    # One lane more, in the last parent: that parent no longer fits.
    last = np.flatnonzero(~en[B - 1])[0]
    en[B - 1, last] = True
    P, total, _lane_id, _ = check(en, K)
    assert P == B - 1 and total == K + 1 - en[B - 1].sum()


def test_isolated_lanes_and_a_burst_in_the_middle():
    """First lane, last lane, one lane a parent; then a parent whose own
    fan-out ends the prefix before it."""
    B, G, K = 8, 12, 16
    for lanes in ([0], [B * G - 1], [g * G + g for g in range(B)]):
        en = np.zeros((B, G), bool)
        en.reshape(-1)[lanes] = True
        P, total, lane_id, _ = check(en, K)
        assert (P, total) == (B, len(lanes))
        assert list(lane_id[:total]) == lanes
    en = np.zeros((B, G), bool)
    en[0, 3] = en[1, 5] = True
    en[2, :] = True             # 2 + 12 = 14 fit
    en[3, :4] = True            # 18 > 16: parent 3 waits for the next pass
    P, total, _lane_id, _ = check(en, K)
    assert (P, total) == (3, 14)
