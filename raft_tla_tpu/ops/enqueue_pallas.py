"""Pallas enqueue — run-coalesced DMA writer for the compacted rows.

The measured TPU chunk's single biggest post-v2 residue is the enqueue
scatter: 14.5 ms to place K 473-byte rows (`artifacts/
profile_step_tpu.txt`; NORTHSTAR.md §c).  The XLA lowerings move every
row through gather/scatter machinery ("scatter": per-row scatter with
K trash writes for masked lanes; "window": K-row searchsorted gather +
one dynamic_update_slice).  But the *destination is contiguous*: the
enq lanes land at [next_count, next_count + new_n) in queue order — an
append, not a scatter.  This kernel exploits that directly:

- OUTSIDE the kernel (vectorized [K] int ops, microseconds): decompose
  the enq mask into maximal runs of consecutive live lanes, quantized
  into fixed-``S``-row copy segments (DMA slice sizes must be static);
  emit per-copy (src_lane, dst_row) arrays with `inv_positions`.
- INSIDE the kernel: one sequential loop issuing an HBM→HBM DMA of S
  rows per segment — no VMEM staging, no per-row scatter, no trash
  writes.  ~new_n/S + runs copies of S·SW ≈ 4 KB each instead of K
  row-scatters.

Overhang rule (what makes quantization safe): a run's last segment may
copy up to S-1 rows past the run's true end — junk rows from disabled
lanes.  Segments are issued in ascending destination order, and the
NEXT run's first segment starts exactly where the previous run's real
rows ended, overwriting the junk; only the final segment's overhang
survives, and it lies in [next_count + new_n, next_count + new_n + S)
— beyond the live region (never read: all readers slice [:count]) and
in-bounds (the batch watermark keeps next_count <= Q - K and the queue
carries PAD >= K extra rows).

Live rows [0, final next_count) are bit-identical to both XLA lowerings
(the "window" method set the precedent that only live rows are compared
— its trash region also differs from "scatter"'s).  Switchable as
``EngineConfig.enqueue_method = "pallas"``; interpret mode off-TPU, and
staged in the profile matrix so a chip run prices it against both XLA
lowerings (the second half of the fused-chunk decision, next to
ops/fpset_pallas.py's insert).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compact import inv_positions

_I32 = jnp.int32

# Rows per DMA segment.  Power of two; 8 rows x ~500 B ~= 4 KB per copy.
SEG = 8


def build_copy_plan(enq, next_count, K: int):
    """Vectorized segment plan: ``(src, dst, n_copies)`` where copy c
    moves ``SEG`` rows ``krows[src[c] : src[c]+SEG]`` to
    ``queue[dst[c] : dst[c]+SEG]``, for c < n_copies, in ascending
    destination order."""
    idx = jnp.arange(K, dtype=_I32)
    enq = jnp.asarray(enq, bool)
    prev = jnp.concatenate([jnp.zeros((1,), bool), enq[:-1]])
    run_start = jax.lax.cummax(jnp.where(enq & ~prev, idx, -1))
    pos_in_run = idx - run_start          # valid on enq lanes only
    copy_flag = enq & (pos_in_run % SEG == 0)
    excl = jnp.cumsum(enq.astype(_I32)) - enq.astype(_I32)
    lane = inv_positions(copy_flag, K)    # c-th copy's source lane
    src = lane
    dst = (next_count + excl)[lane]
    return src.astype(_I32), dst.astype(_I32), jnp.sum(copy_flag,
                                                       dtype=_I32)


def _kernel(src_ref, dst_ref, n_ref, krows_ref, q_in, q_ref, sem):
    del q_in   # aliased with q_ref — all access through the output ref
    n = n_ref[0]

    def body(c, carry):
        cp = pltpu.make_async_copy(
            krows_ref.at[pl.ds(src_ref[c], SEG), :],
            q_ref.at[pl.ds(dst_ref[c], SEG), :],
            sem)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, n, body, _I32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _enqueue_jit(qnext, next_count, krows, enq, interpret: bool):
    K, SW = krows.shape
    src, dst, n_copies = build_copy_plan(enq, next_count, K)
    krows_pad = jnp.concatenate(
        [krows, jnp.zeros((SEG, SW), krows.dtype)])
    (q_out,) = [pl.pallas_call(
        _kernel,
        in_specs=[
            # The copy plan is read one scalar index at a time: SMEM.
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(qnext.shape, qnext.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(src, dst, n_copies[None], krows_pad, qnext)]
    return q_out


def enqueue(qnext, next_count, krows, enq, interpret: bool | None = None):
    """Write ``krows[enq]`` contiguously at ``qnext[next_count:]`` —
    same live rows as the XLA enqueue lowerings (engine/chunk.py)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _enqueue_jit(qnext, next_count, krows, enq, interpret)
