"""Pallas lane compaction — the v3 fused pipeline's compact stage.

The XLA compact lowerings (ops/compact.py) move the [B, G] enabled mask
through either a B*G-lane scatter or ~log2(B*G) searchsorted gather
rounds — each a separate kernel launch with an HBM round trip for the
mask and the index vectors (the TPU profile's 21 ms compact stage;
NORTHSTAR.md §c).  This kernel keeps the whole mask VMEM-resident and
compacts it with ONE sequential in-register scan: per flat candidate
lane, append its index to the next free survivor slot.  No scatter, no
sort, no intermediate HBM traffic — the formulation the fused-chunk
decision rule wants priced next to both XLA lowerings.

Outputs are bit-identical to ``ops.compact.build_compactor`` (both
methods; they agree by construction): ``(P, total, lane_id, kvalid)``
with the same progress-limited parent prefix, the same ascending
survivor order, and the same hash-spread addresses in dead slots.

The sequential scan is priced for TPU VMEM residency; in interpret mode
(CPU) it emulates at Python-traced-loop speed, so the v3 plan
(ops/pipeline_v3.py) only selects it off-TPU when a test forces it —
the platform policy keeps CPU runs on the XLA compactor.

``reduce_p`` (the mesh engine's pmin hook) is deliberately NOT
supported: a cross-chip collective cannot live inside a Pallas stage,
which is exactly why the mesh plan falls back to XLA for this stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compact import kspread

_I32 = jnp.int32


def _kernel(en_ref, kspread_ref,            # [B,G] i32, [K] i32 (VMEM)
            p_ref, total_ref,               # [1] i32 outs
            lane_ref, kvalid_ref,           # [K] i32 outs
            *, B: int, G: int, K: int):
    en = en_ref[...] != 0                               # [B, G]
    per_parent = jnp.sum(en.astype(_I32), axis=1)       # [B]
    cum = jnp.cumsum(per_parent)
    # Progress limiting (ops/compact.py invariant): longest parent
    # prefix whose fan-out fits K.
    P = jnp.sum((cum <= K).astype(_I32))
    total = jnp.where(P > 0, cum[jnp.clip(P - 1, 0, B - 1)], _I32(0))
    p_ref[0] = P
    total_ref[0] = total
    kvalid_ref[...] = (jnp.arange(K, dtype=_I32) < total).astype(_I32)
    # Dead slots keep the same hash-spread init as both XLA methods.
    lane_ref[...] = kspread_ref[...]
    enf = (en & (jnp.arange(B, dtype=_I32) < P)[:, None]).reshape(-1)

    def body(f, slot):
        take = enf[f]

        @pl.when(take)
        def _():
            lane_ref[pl.ds(slot, 1)] = jnp.full((1,), f, _I32)

        return slot + take.astype(_I32)

    jax.lax.fori_loop(0, B * G, body, _I32(0))


@functools.partial(jax.jit, static_argnames=("K", "interpret"))
def _compact_jit(en, kspread, K: int, interpret: bool):
    B, G = en.shape
    kern = functools.partial(_kernel, B=B, G=G, K=K)
    p, total, lane_id, kvalid = pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1,), _I32),
            jax.ShapeDtypeStruct((1,), _I32),
            jax.ShapeDtypeStruct((K,), _I32),
            jax.ShapeDtypeStruct((K,), _I32),
        ],
        interpret=interpret,
    )(en.astype(_I32), kspread)
    return p[0], total[0], lane_id, kvalid.astype(bool)


def build_compactor(B: int, G: int, K: int, interpret: bool | None = None):
    """Drop-in replacement for ``ops.compact.build_compactor`` (same
    ``compact(en) -> (P, total, lane_id, kvalid)`` contract, identical
    outputs).  No ``reduce_p`` hook — see module docstring."""
    # Shared with ops/compact.py: dead-slot bit-identity across every
    # lowering hangs on all of them using the one kspread definition.
    kspr = kspread(B, G, K)

    def compact(en):
        ipt = interpret
        if ipt is None:
            ipt = jax.devices()[0].platform != "tpu"
        return _compact_jit(en, kspr, K, ipt)

    return compact
