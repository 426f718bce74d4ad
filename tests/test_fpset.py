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
