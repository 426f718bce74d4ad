"""Pallas probe/insert for the FPSet — the fused-chunk experiment's stage 1.

Motivation: once the v2 delta pipeline removes the
expand/materialize cost, the measured TPU chunk's dominant residue is the
hash insert (5.3 ms, *including* the dedup sort) and the enqueue scatter
(14.5 ms) — and the whole chunk sits ~100× above the HBM bandwidth floor
because it is hundreds of separate XLA kernels.  The decision rule for
attacking that is a single fused Pallas chunk; this
module is its first, independently-testable stage: the table insert as ONE
Pallas kernel.

Design vs the XLA path (`ops/fpset.py`):

- **Sequential insertion replaces sort + claim.**  The XLA insert needs a
  K-lane `lax.sort` pre-pass (in-batch dedup) and a claim/scatter-max
  protocol (concurrent-writer determinism) because all K lanes insert at
  once.  A Pallas TPU grid executes programs *sequentially* on a core
  ("arbitrary" dimension semantics), so this kernel just inserts queries
  in index order: a later duplicate finds the earlier key present — the
  sort AND the claim machinery disappear.
- **Same probe chains.**  `_probe_base` (double hashing, h2 odd) is
  imported from ops/fpset.py, so a key's candidate slot sequence is
  identical in both lowerings.
- **Same observable contract, different physical layout.**  ``is_new``
  marks exactly the first query index holding each distinct new key
  (the XLA path's stable sort marks the same index); ``fail``/``size``
  match; the stored KEY SET matches.  The raw slot assignment may differ
  when two *distinct* keys contend for one empty slot in the same round
  (the XLA claim hands it to the highest lane, sequential order to the
  lowest) — both layouts satisfy the chain invariant every reader
  depends on (a key occupies the first slot of its probe chain that was
  empty at its insert time), so `contains`, checkpointing
  (`to_host_keys` sorts), and every engine result are unaffected.
  Tests compare is_new/size/fail and the sorted key set, and run whole
  engines under both lowerings (bit-identical results).

Table reads/writes go through single-element async copies (the table
lives in HBM; TPU has no vector gather from HBM — XLA's own gather is a
DMA loop underneath).  The kernel is therefore also the *measurement
instrument* for Mosaic's scalar-DMA round-trip cost, the number that
decides whether the fully-fused chunk kernel is viable:
scripts/profile_step.py times it next to the XLA insert on the same
batch.

Bit-identity is proven on CPU via interpret mode (`tests/test_fpset.py`,
`tests/test_engine.py`); `interpret` defaults to automatic (real lowering
on TPU, interpreter elsewhere).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fingerprint import SENTINEL
from .fpset import FPSet, PROBE_ROUNDS, _pad_pow2, _probe_base

_U32 = jnp.uint32
_I32 = jnp.int32

# Queries processed per grid program.  Small enough that the per-program
# VMEM blocks stay tiny; large enough that program-switch overhead
# amortizes.  Must divide the (power-of-two-padded) query count, so keep
# it a power of two.
_BLOCK = 512


def probe_insert_query(hi_ref, lo_ref, scr, sem, qh, ql, pending0,
                       c_mask: int, rounds: int):
    """Sequentially probe/insert ONE key into the table refs — the inner
    chain shared by this module's insert kernel and the fused
    insert+enqueue kernel (ops/fused_tail_pallas.py), so the two
    lowerings can never drift on probe order or claim semantics.
    Returns ``(is_new, still_pending)``; table writes go through the
    refs via single-element async copies."""

    def probe_round(carry):
        r, step, pending, newf, qh, ql, h1, h2 = carry
        idx = ((h1 + step * h2) & _U32(c_mask)).astype(_I32)
        # Fetch the slot (4 B each lane of the key pair).
        rd_hi = pltpu.make_async_copy(
            hi_ref.at[pl.ds(idx, 1)], scr.at[pl.ds(0, 1)], sem.at[0])
        rd_lo = pltpu.make_async_copy(
            lo_ref.at[pl.ds(idx, 1)], scr.at[pl.ds(1, 1)], sem.at[1])
        rd_hi.start()
        rd_lo.start()
        rd_hi.wait()
        rd_lo.wait()
        cur_hi = scr[0]
        cur_lo = scr[1]
        is_match = (cur_hi == qh) & (cur_lo == ql)
        is_empty = (cur_hi == SENTINEL) & (cur_lo == SENTINEL)
        # Branch-free write-back: claim the slot when empty, else rewrite
        # the value just read (a no-op).  Unconditional DMA sidesteps
        # predicated-DMA lowering; sequential grid order makes it race-free.
        scr[0] = jnp.where(is_empty, qh, cur_hi)
        scr[1] = jnp.where(is_empty, ql, cur_lo)
        wr_hi = pltpu.make_async_copy(
            scr.at[pl.ds(0, 1)], hi_ref.at[pl.ds(idx, 1)], sem.at[0])
        wr_lo = pltpu.make_async_copy(
            scr.at[pl.ds(1, 1)], lo_ref.at[pl.ds(idx, 1)], sem.at[1])
        wr_hi.start()
        wr_lo.start()
        wr_hi.wait()
        wr_lo.wait()
        newf = newf | is_empty
        pending = pending & ~(is_match | is_empty)
        # Advance the chain only past a slot occupied by a different key.
        step = step + pending.astype(_U32)
        return r + 1, step, pending, newf, qh, ql, h1, h2

    def probe_cond(carry):
        r, _step, pending, *_ = carry
        return pending & (r < rounds)

    h1, h2 = _probe_base(qh, ql, c_mask + 1)
    _r, _s, pending, newf, *_ = jax.lax.while_loop(
        probe_cond, probe_round,
        (_I32(0), _U32(0), pending0, jnp.bool_(False), qh, ql, h1, h2))
    return newf, pending


def _kernel(qhi_ref, qlo_ref, valid_ref,   # [BLK] SMEM in blocks
            hi_in, lo_in,                  # [C] ANY in (aliased to outputs)
            hi_ref, lo_ref,                # [C] ANY out — the same buffers;
                                           # all reads+writes go through these
            new_ref,                       # [BLK] SMEM out block
            fail_ref,                      # [1] SMEM out, revisited by all
            scr, sem,                      # SMEM (2,) u32 scratch + 2 DMA sems
            *, c_mask: int, rounds: int):
    del hi_in, lo_in
    @pl.when(pl.program_id(0) == 0)
    def _():
        fail_ref[0] = _I32(0)

    def one_query(i, local_fail):
        qh = qhi_ref[i]
        ql = qlo_ref[i]
        pending0 = valid_ref[i] != 0
        newf, pending = probe_insert_query(hi_ref, lo_ref, scr, sem,
                                           qh, ql, pending0, c_mask, rounds)
        new_ref[i] = newf.astype(_I32)
        return local_fail | pending.astype(_I32)

    local_fail = jax.lax.fori_loop(0, qhi_ref.shape[0], one_query, _I32(0))
    fail_ref[0] = fail_ref[0] | local_fail


# No donate_argnums: when called inside the engines' jitted chunk the
# inner jit inlines (donation is moot), and standalone callers (profile
# matrix, tests) re-time the same table object repeatedly — donation
# would invalidate their buffers.  input_output_aliases inside the
# pallas_call already gives the in-place table update.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _insert_padded(s: FPSet, qhi, qlo, valid, interpret: bool):
    c = s.hi.shape[0]
    kp = qhi.shape[0]
    blk = min(_BLOCK, kp)
    grid = kp // blk
    kern = functools.partial(_kernel, c_mask=c - 1, rounds=PROBE_ROUNDS)
    hi, lo, is_new, fail = pl.pallas_call(
        kern,
        grid=(grid,),
        # Every per-query value is read and written as a scalar, so the
        # query blocks, the novelty block and the fail flag live in SMEM
        # (Mosaic stores no scalars to VMEM).
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c,), jnp.uint32),
            jax.ShapeDtypeStruct((c,), jnp.uint32),
            jax.ShapeDtypeStruct((kp,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True),
        interpret=interpret,
    )(qhi, qlo, valid.astype(_I32), s.hi, s.lo)
    is_new = is_new.astype(bool)
    return (FPSet(hi=hi, lo=lo,
                  size=s.size + jnp.sum(is_new, dtype=_I32)),
            is_new, fail[0] > 0)


def insert(s: FPSet, qhi, qlo, valid,
           interpret: bool | None = None) -> Tuple[FPSet, jnp.ndarray,
                                                   jnp.ndarray]:
    """Drop-in replacement for :func:`ops.fpset.insert` (same contract:
    ``(table', is_new, fail)``, is_new marking exactly one query per
    distinct new key).  No dedup pre-pass needed — sequential insertion
    dedups in-table."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    (qhi, qlo, valid), k = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool)),
        (SENTINEL, SENTINEL, False))
    s, is_new, fail = _insert_padded(s, qhi, qlo, valid, interpret)
    return s, is_new[:k], fail
