"""Pallas whole-chunk FRONT megakernel — the v4 pipeline's fused
masks -> POR -> compact -> delta-fingerprint stage group.

The v3 pipeline (ops/pipeline_v3.py) retired the chunk's tail into one
Pallas kernel but left the front as three separate XLA stages, each
round-tripping the [B, G] mask and the parent-struct window through HBM
(NORTHSTAR.md §c: the masks + compact + fingerprint stages are the bulk
of the remaining per-batch device ops).  This kernel moves the whole
front inward: the B-row parent window is loaded into VMEM ONCE and the
guards-only enabled/overflow masks, the optional partial-order
reduction, the sequential compaction scan (the ops/compact_pallas.py
formulation, inlined), the delta fingerprints + sparse successor rows,
the state constraint, the invariant dispatch, and the parent
fingerprints all run in a single launch.  Together with the fused tail
(ops/fused_tail_pallas.py) the chunk body becomes two Pallas launches
per batch — the "one kernel launch per chunk" step ROADMAP item 1
records as PR 7's successor.

Mechanically, the kernel body cannot CLOSE OVER the model's baked-in
arrays (fingerprint salts, zeta tables — Pallas rejects captured
constants), so the two pure-math halves of the front — masks+POR before
the scan, fingerprints/constraint/invariants after it — are
``jax.closure_convert``-ed at build time and their hoisted constants
ride in as ordinary VMEM operands.  The sequential lane-assembly scan
between them stays a ref-mutation ``fori_loop`` (the compact_pallas
formulation, already proven to lower on TPU Mosaic).

Bit-identity: the converted bodies ARE the jaxprs of the same jnp model
functions the XLA path runs (models/actions2.py masks/lane_out,
models/schema.py flatten/unflatten, models/invariants.py dispatch) on
the same values.  In interpret mode (CPU) executing them is executing
those ops, so v4-vs-v2 engine differentials hold exactly.  On the TPU
Mosaic rejects the gather-heavy body today (models/actions2.py
``last_term``: "Shape mismatch in input, indices and output";
tests/test_chip_compile.py pins it), and the v4 build fails there with
that message (ops/pipeline_v4.py build-and-probe).

Outputs mirror engine/chunk.py's front section exactly: the
post-progress-limit enabled/overflow masks, the pre-progress-limit POR
pruned mask, (P, total, lane_id, kvalid) from compaction, the K-lane
fingerprints/rows/constraint/invariant results, and the per-lane parent
fingerprints the trace recorder consumes.  The parent fingerprints are
computed unconditionally (trace-off runs pay a few extra VMEM ops
rather than a second kernel variant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.invariants import build_inv_id
from ..models.schema import flatten_state, state_width, unflatten_state
from .compact import kspread

_I32 = jnp.int32
_U32 = jnp.uint32
_U8 = jnp.uint8

_N_OUT = 14


def _pack_consts(consts):
    """Constants hoisted by closure_convert, massaged into VMEM-legal
    operands: 0-d arrays become (1,), bools become int32.  Returns
    (operands, restore) with ``restore`` mapping the in-kernel ref
    loads back to the original shapes/dtypes."""
    ops, meta = [], []
    for c in consts:
        c = jnp.asarray(c)
        scalar = c.ndim == 0
        isbool = c.dtype == jnp.bool_
        out = c.reshape((1,)) if scalar else c
        if isbool:
            out = out.astype(_I32)
        ops.append(out)
        meta.append((scalar, isbool))

    def restore(vals):
        res = []
        for v, (scalar, isbool) in zip(vals, meta):
            if isbool:
                v = v != 0
            res.append(v[0] if scalar else v)
        return res

    return ops, restore


def _front_kernel(*refs, math1, math2, rest1, rest2, n1, por,
                  B, G, K):
    """One grid-less program computing the whole chunk front in VMEM.

    ``refs`` = rows, valid, kspread, (por_mask, por_priority)?, the n1
    hoisted constants of the masks half, the hoisted constants of the
    fingerprint half, then the 14 output refs."""
    base = 5 if por else 3
    rows = refs[0][...]                                 # [B, sw] u8
    valid = refs[1][...] != 0                           # [B]
    kspread_v = refs[2][...]
    por_args = ()
    if por:
        por_args = (refs[3][...] != 0, refs[4][...])
    split = len(refs) - _N_OUT
    c1 = rest1([r[...] for r in refs[base:base + n1]])
    c2 = rest2([r[...] for r in refs[base + n1:split]])
    (en_ref, ovf_ref, pruned_ref, p_ref, total_ref, lane_ref,
     kvalid_ref, kh_ref, kl_ref, krows_ref, cons_ref, inv_ref,
     phi_ref, plo_ref) = refs[split:]

    # -- masks + POR (closure-converted pure half #1) ------------------
    en, ovf, pruned = math1(rows, valid, *por_args, *c1)

    # -- compaction (ops/compact_pallas.py scan, inlined) --------------
    per_parent = jnp.sum(en.astype(_I32), axis=1)       # [B]
    cum = jnp.cumsum(per_parent)
    P = jnp.sum((cum <= K).astype(_I32))
    total = jnp.where(P > 0, cum[jnp.clip(P - 1, 0, B - 1)], _I32(0))
    p_ref[0] = P
    total_ref[0] = total
    kvalid_ref[...] = (jnp.arange(K, dtype=_I32) < total).astype(_I32)
    lane_ref[...] = kspread_v           # dead slots: shared hash spread
    ptaken = jnp.arange(B, dtype=_I32) < P
    enf = (en & ptaken[:, None]).reshape(-1)

    def body(f, slot):
        take = enf[f]

        @pl.when(take)
        def _():
            lane_ref[pl.ds(slot, 1)] = jnp.full((1,), f, _I32)

        return slot + take.astype(_I32)

    jax.lax.fori_loop(0, B * G, body, _I32(0))

    # Progress-limited masks out; pruned stays pre-limit (the chunk body
    # applies "& ptaken" when accounting fam_pruned, like the XLA path).
    en_ref[...] = (en & ptaken[:, None]).astype(_I32)
    ovf_ref[...] = (ovf & ptaken[:, None]).astype(_I32)
    pruned_ref[...] = pruned.astype(_I32)

    # -- fingerprints + constraint/invariants (pure half #2) -----------
    lane_id = lane_ref[...]             # read-back: the scan is done
    kh, kl, krows, cons, inv, phi, plo = math2(rows, lane_id, *c2)
    kh_ref[...] = kh
    kl_ref[...] = kl
    krows_ref[...] = krows
    cons_ref[...] = cons.astype(_I32)
    inv_ref[...] = inv
    phi_ref[...] = phi
    plo_ref[...] = plo


def build_front(*, dims, v2, constraint, inv_fns, B: int, G: int,
                K: int, por_mask=None, por_priority=None,
                interpret: bool | None = None):
    """Build the fused front: ``front(rows, valid) -> (en, ovf, pruned,
    P, total, lane_id, kvalid, kh, kl, krows, cons_ok, inv, parent_hi,
    parent_lo)`` with the same dtypes/semantics as engine/chunk.py's
    split front.  ``v2`` is models/actions2.build_v2's pipeline (v4
    shares v2's delta kernels); ``inv_fns`` the run's invariant
    predicate list (may be empty/None)."""
    sw = state_width(dims)
    inv_id = build_inv_id(list(inv_fns)) if inv_fns else None
    por = por_mask is not None
    kspr = kspread(B, G, K)
    pm = jnp.asarray(por_mask) if por else None
    pp = jnp.asarray(por_priority) if por else None

    def _math1(rows, valid, *por_args):
        """Masks + POR: the exact engine/chunk.py v2 front."""
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        en, ovf = jax.vmap(v2.masks)(states)
        en = en & valid[:, None]
        ovf = ovf & valid[:, None]
        if por:
            pmask, ppri = por_args
            amp = en & pmask[None, :]
            any_amp = jnp.any(amp, axis=1)
            pri = jnp.where(amp, ppri[None, :], jnp.int32(2147483647))
            sel = jnp.argmin(pri, axis=1)
            keep = jnp.where(
                any_amp[:, None],
                jnp.arange(G, dtype=_I32)[None, :] == sel[:, None],
                jnp.ones((B, G), bool))
            pruned = en & ~keep
            en = en & keep
            ovf = ovf & keep
        else:
            pruned = jnp.zeros((B, G), bool)
        return en, ovf, pruned

    def _math2(rows, lane_id):
        """Delta fingerprints + sparse successors + constraint/
        invariant dispatch + per-lane parent fps, on the K lanes."""
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        ph = jax.vmap(v2.parent_hash)(states)
        pidx = lane_id // G
        kparents = jax.tree.map(lambda a: a[pidx], states)
        kph = jax.tree.map(lambda a: a[pidx], ph)
        kh, kl, kstates = jax.vmap(v2.lane_out)(
            kparents, kph, lane_id % G)
        krows = jax.vmap(flatten_state, (0, None))(kstates, dims)
        if constraint is not None:
            cons = jax.vmap(constraint)(kstates)
        else:
            cons = jnp.ones((K,), bool)
        if inv_id is not None:
            inv = jax.vmap(inv_id)(kstates)
        else:
            inv = jnp.full((K,), -1, _I32)
        php, plp = jax.vmap(v2.parent_fp)(ph)
        return kh, kl, krows, cons, inv, php[pidx], plp[pidx]

    # The kernel body may not close over arrays (Pallas rejects captured
    # constants), so hoist each half's baked-in model arrays (salt/zeta
    # tables, family grids) into explicit operands.  jax.closure_convert
    # would only hoist AD-perturbable tracers, so do it directly: trace
    # each half to a jaxpr and re-play it in-kernel with the jaxpr
    # consts passed as VMEM refs.
    rows_av = jax.ShapeDtypeStruct((B, sw), _U8)
    valid_av = jax.ShapeDtypeStruct((B,), jnp.bool_)
    lane_av = jax.ShapeDtypeStruct((K,), _I32)
    por_avs = ((jax.ShapeDtypeStruct(pm.shape, jnp.bool_),
                jax.ShapeDtypeStruct(pp.shape, pp.dtype)) if por else ())
    closed1 = jax.make_jaxpr(_math1)(rows_av, valid_av, *por_avs)
    closed2 = jax.make_jaxpr(_math2)(rows_av, lane_av)

    def _replay(closed):
        def run(*args_then_consts):
            n = len(closed.jaxpr.invars)
            args = args_then_consts[:n]
            consts = args_then_consts[n:]
            return jax.core.eval_jaxpr(closed.jaxpr, consts, *args)
        return run

    math1, math2 = _replay(closed1), _replay(closed2)
    ops1, rest1 = _pack_consts(closed1.consts)
    ops2, rest2 = _pack_consts(closed2.consts)

    kern = functools.partial(
        _front_kernel, math1=math1, math2=math2, rest1=rest1,
        rest2=rest2, n1=len(ops1), por=por, B=B, G=G, K=K)
    n_in = (5 if por else 3) + len(ops1) + len(ops2)
    out_shape = [
        jax.ShapeDtypeStruct((B, G), _I32),     # en (post progress limit)
        jax.ShapeDtypeStruct((B, G), _I32),     # ovf
        jax.ShapeDtypeStruct((B, G), _I32),     # pruned (pre limit)
        jax.ShapeDtypeStruct((1,), _I32),       # P
        jax.ShapeDtypeStruct((1,), _I32),       # total
        jax.ShapeDtypeStruct((K,), _I32),       # lane_id
        jax.ShapeDtypeStruct((K,), _I32),       # kvalid
        jax.ShapeDtypeStruct((K,), _U32),       # kh
        jax.ShapeDtypeStruct((K,), _U32),       # kl
        jax.ShapeDtypeStruct((K, sw), _U8),     # krows
        jax.ShapeDtypeStruct((K,), _I32),       # cons_ok
        jax.ShapeDtypeStruct((K,), _I32),       # inv
        jax.ShapeDtypeStruct((K,), _U32),       # parent_hi
        jax.ShapeDtypeStruct((K,), _U32),       # parent_lo
    ]
    call = pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n_in,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * _N_OUT,
        out_shape=out_shape,
        interpret=(jax.devices()[0].platform != "tpu"
                   if interpret is None else interpret),
    )

    def front(rows, valid):
        args = [rows, valid.astype(_I32), kspr]
        if por:
            args += [pm.astype(_I32), pp]
        args += list(ops1) + list(ops2)
        (en, ovf, pruned, p, total, lane_id, kvalid, kh, kl, krows,
         cons, inv, phi, plo) = call(*args)
        return (en != 0, ovf != 0, pruned != 0, p[0], total[0],
                lane_id, kvalid != 0, kh, kl, krows, cons != 0, inv,
                phi, plo)

    return front
