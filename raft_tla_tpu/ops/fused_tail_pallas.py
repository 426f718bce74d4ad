"""Fused Pallas probe/insert -> enqueue — the v3 pipeline's tail stage.

NORTHSTAR.md §d names the insert+enqueue residue (19.8 ms measured) as
the dominant term once the v2 delta pipeline removes expand/materialize
cost, and the decision rule stages a single fused kernel for it.  This
module is that kernel: the sequential probe/insert chain of
ops/fpset_pallas.py (shared inner loop — the probe order is literally
the same code) extended so that the novelty bit never round-trips to
HBM between the two stages.  The moment a query resolves as new, the
same grid program issues the row's HBM-to-HBM DMA append at the running
enqueue cursor — XLA's separate insert kernel, novelty-mask
materialization, position cumsum, and K-row scatter collapse into one
launch.

Layout contract (bit-identical to the "scatter" enqueue lowering,
engine/chunk.py): live rows land at ``next_count + rank-among-enqueued``
in lane order (sequential grid order IS lane order, so the running
cursor reproduces the cumsum positions exactly), and every non-enqueued
lane writes its row to the per-lane trash slot ``trash_base + lane`` —
the same addresses the scatter path uses, so even the trash region
matches byte-for-byte.  The unconditional DMA (destination select, not
a predicated copy) sidesteps predicated-DMA lowering exactly as the
insert kernel's branch-free write-back does.

``is_new``/``fail``/stored-key-set semantics are ops/fpset_pallas.py's
(same contract as ops/fpset.py insert).  Bit-identity is proven on CPU
via interpret mode (tests/test_fused.py); ``interpret`` defaults to
automatic (real lowering on TPU, interpreter elsewhere).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fingerprint import SENTINEL
from .fpset import FPSet, PROBE_ROUNDS, _pad_pow2
from .fpset_pallas import _BLOCK, probe_insert_query

_U32 = jnp.uint32
_I32 = jnp.int32


def _kernel(qhi_ref, qlo_ref, valid_ref, enq_ref,   # [BLK] SMEM in blocks
            nc_ref,                                 # [1] SMEM: next_count
            hi_in, lo_in,                           # [C] ANY (aliased)
            krows_ref,                              # [KP,SW] ANY in
            q_in,                                   # [QA,SW] ANY (aliased)
            hi_ref, lo_ref,                         # [C] ANY out
            q_ref,                                  # [QA,SW] ANY out
            new_ref,                                # [BLK] SMEM out block
            fail_ref, cnt_ref,                      # [1] SMEM outs, revisited
            scr, sem, rsem,                         # scratch + DMA sems
            *, c_mask: int, rounds: int, blk: int, trash_base: int):
    del hi_in, lo_in, q_in

    @pl.when(pl.program_id(0) == 0)
    def _():
        fail_ref[0] = _I32(0)
        cnt_ref[0] = nc_ref[0]

    gbase = pl.program_id(0) * blk

    def one_query(i, local_fail):
        qh = qhi_ref[i]
        ql = qlo_ref[i]
        pending0 = valid_ref[i] != 0
        newf, pending = probe_insert_query(hi_ref, lo_ref, scr, sem,
                                           qh, ql, pending0, c_mask, rounds)
        new_ref[i] = newf.astype(_I32)
        # Enqueue leg: the row goes out NOW, while the novelty bit is
        # still in a register — at the running cursor when enqueued, to
        # its per-lane trash slot otherwise (the scatter lowering's
        # addresses; destination select keeps the DMA unconditional).
        gidx = gbase + i
        do_enq = newf & (enq_ref[i] != 0)
        dst = jnp.where(do_enq, cnt_ref[0], trash_base + gidx)
        cp = pltpu.make_async_copy(
            krows_ref.at[pl.ds(gidx, 1), :],
            q_ref.at[pl.ds(dst, 1), :], rsem)
        cp.start()
        cp.wait()
        cnt_ref[0] = cnt_ref[0] + do_enq.astype(_I32)
        return local_fail | pending.astype(_I32)

    local_fail = jax.lax.fori_loop(0, qhi_ref.shape[0], one_query, _I32(0))
    fail_ref[0] = fail_ref[0] | local_fail


# No donate_argnums — same rationale as ops/fpset_pallas.py: the inner
# jit inlines inside the engines' chunk, and input_output_aliases already
# provides the in-place table/queue update.
@functools.partial(jax.jit, static_argnames=("trash_base", "interpret"))
def _tail_padded(s: FPSet, qhi, qlo, valid, enq_ok, krows, qnext,
                 next_count, trash_base: int, interpret: bool):
    c = s.hi.shape[0]
    kp = qhi.shape[0]
    blk = min(_BLOCK, kp)
    grid = kp // blk
    kern = functools.partial(_kernel, c_mask=c - 1, rounds=PROBE_ROUNDS,
                             blk=blk, trash_base=trash_base)
    hi, lo, q_out, is_new, fail, _cnt = pl.pallas_call(
        kern,
        grid=(grid,),
        # Per-query values are scalars: SMEM blocks, as in
        # ops/fpset_pallas.py (Mosaic stores no scalars to VMEM).
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c,), jnp.uint32),
            jax.ShapeDtypeStruct((c,), jnp.uint32),
            jax.ShapeDtypeStruct(qnext.shape, qnext.dtype),
            jax.ShapeDtypeStruct((kp,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2,), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={5: 0, 6: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            has_side_effects=True),
        interpret=interpret,
    )(qhi, qlo, valid.astype(_I32), enq_ok.astype(_I32),
      next_count[None].astype(_I32), s.hi, s.lo, krows, qnext)
    is_new = is_new.astype(bool)
    return (FPSet(hi=hi, lo=lo,
                  size=s.size + jnp.sum(is_new, dtype=_I32)),
            is_new, fail[0] > 0, q_out)


def insert_enqueue(s: FPSet, qhi, qlo, valid, krows, enq_ok, qnext,
                   next_count, trash_base: int,
                   interpret: bool | None = None
                   ) -> Tuple[FPSet, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused tail: ``(table', is_new, fail, qnext')``.

    ``is_new`` follows the insert contract (exactly one query per
    distinct new key); rows with ``is_new & enq_ok`` land contiguously
    at ``qnext[next_count + rank]`` in lane order, every other lane's
    row at ``qnext[trash_base + lane]`` — both identical to the XLA
    scatter enqueue.  The caller advances its count by
    ``sum(is_new & enq_ok)`` and must guarantee
    ``qnext.shape[0] >= trash_base + len(qhi)`` (the engines' PAD >= K
    allocation rule)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    k = qhi.shape[0]
    (qhi, qlo, valid, enq_ok), _ = _pad_pow2(
        (qhi, qlo, jnp.asarray(valid, bool), jnp.asarray(enq_ok, bool)),
        (SENTINEL, SENTINEL, False, False))
    kp = qhi.shape[0]
    if qnext.shape[0] < trash_base + kp:
        raise ValueError(
            f"qnext has {qnext.shape[0]} rows; the per-lane trash region "
            f"needs trash_base + {kp} = {trash_base + kp}")
    if kp != k:
        pad = jnp.zeros((kp - k,) + krows.shape[1:], krows.dtype)
        krows = jnp.concatenate([krows, pad])
    s, is_new, fail, q_out = _tail_padded(
        s, qhi, qlo, valid, enq_ok, krows, qnext,
        jnp.asarray(next_count, _I32), trash_base, interpret)
    return s, is_new[:k], fail, q_out
