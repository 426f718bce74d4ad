"""The per-batch BFS pipeline body, shared by both engines.

One batch = slice B rows off the level queue -> expand all G action
instances -> fingerprint -> compact enabled lanes to K slots
(ops/compact.py) -> hash-insert the K keys -> materialize rows, evaluate
invariants + the state constraint, enqueue, record trace rows — all on the
K compacted lanes.  engine/bfs.py (single chip) and parallel/mesh.py
(sharded) run the IDENTICAL body; they differ only in

- ``insert_fn``: the single-chip FPSet insert vs the mesh's owner-routed
  all_to_all insert (mesh.py route_insert), and
- the loop wrapper around the body (plain while_loop vs shard_map with
  psum-replicated stop conditions), which stays in each engine.

Each stage of the body runs under a named scope (``STAGES``,
``named_stage`` below), so every device operation of a pass says which
stage it belongs to.

Keeping the body in one place is load-bearing: the two engines must stay
bit-identical per batch (same candidate order, same compaction, same
trace layout) for checkpoints to be portable across engines and for the
differential tests to mean anything.

The carry tuple layout (22 fields) is:
    (offset, steps, qnext, next_count, seen, tbuf, tcount,
     gen, newc, ovfc, dead_any, drow, viol_any, vinv, vrow, vhi, vlo,
     fail_any, fam_counts, fam_new, expanded, fam_pruned)

``fam_counts`` [n_families] accumulates enabled-successor counts per
action family (TLC's per-action statistics; SURVEY §5.1) — a handful of
static-slice reduces per batch.  ``fam_new`` [n_families] accumulates
per-family NOVEL-state counts (the insert's novelty mask attributed to
the compacted lane's action family — TLC coverage's "distinct"),
``expanded`` counts parents actually advanced past (valid, inside the
taken prefix) — the exact base for host-side disabled-guard counts
(``expanded * family_size - generated - pruned``) — and ``fam_pruned``
counts enabled lanes the partial-order reduction masked out before
fingerprinting (zero with POR off; the reduced-vs-full accounting
obs/coverage.py renders).  All ride the same packed stats vector;
obs/coverage.py is the host-side consumer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from ..models.invariants import build_inv_id
from ..models.schema import flatten_state, unflatten_state

_I32 = jnp.int32

# The stages of one pass, in order, as they are named in the compiled
# program (a component ``<stage>`` of every operation's ``op_name``, so in
# a profiler capture and in ``--xla_dump_to`` text).  The engines put
# ``prologue``/``epilogue`` around what sits outside the ``while``.
STAGES = ("slice", "masks", "compact", "construct", "insert", "enqueue",
          "record", "stats")

# Scope names are debug info, which jax strips before it hashes a module
# for the persistent compile cache (``cache_key._canonicalize_ir``): a
# cache filled before a name was added or changed hands back an
# executable whose operations carry the old names.  The tag is in what
# is hashed (``tag_stages``).  Change it with the names: the chunk and
# ingest programs then compile once more.
STAGES_TAG = "s3"

# The parts of ``construct``, as scopes nested in the stage's own
# (``.../construct/<part>/...``): the parents' hash sums and the K-lane
# gather of the parents, the successors' construction, the state
# constraint, the rows' packing, and the invariants (each predicate
# under its cfg name where ``engine/check.py`` resolved it).  The
# parents' fingerprints for the trace records stay under the stage's
# name alone.
CONSTRUCT_PARTS = ("parents", "lane_out", "constraint", "flatten",
                   "invariants")


def named_stage(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``: ``.../<name>/...``
    in the ``op_name`` of every operation it emits, and nothing else."""
    @functools.wraps(fn)
    def call(*args):
        with jax.named_scope(name):
            return fn(*args)
    return call


def tag_stages(count):
    """``count``, an integer a program takes, through an ``add 0`` that
    carries ``STAGES_TAG`` as a frontend attribute: an attribute of the
    module jax hashes, on an operation XLA folds away.  (A named call
    would do too, and costs 1.4 s of tracing and lowering per process on
    the chip's host; a new name for the program itself is what others
    know it by.)"""
    with set_xla_metadata(stages_tag=STAGES_TAG):
        return count + 0


def build_chunk_body(*, dims, expand, fingerprint, pack_ok, inv_fns,
                     constraint, B, G, K, Q, TQ, record_static, compactor,
                     insert_fn, v2=None, por_mask=None,
                     por_priority=None):
    """Returns ``chunk_body(qcur, cur_count, carry) -> carry'``.

    ``Q`` is the live next-queue capacity (per chip for the mesh); masked
    enqueue lanes write trash slots [Q, Q+K), masked trace lanes write
    [TQ, TQ+K) — the caller allocates the padding (engine/bfs.py capacity
    comment).

    ``v2`` (models/actions2.build_v2 result, or None) selects the delta
    pipeline: guards-only masks over the B*G lanes, then delta
    fingerprints + sparse successor construction on the K compacted lanes
    only.  Bit-identical to the v1 path in every carry field (enabled/
    overflow masks, fingerprints, successor rows, per-family stats) —
    property-tested in tests/test_actions2.py — so the two paths share
    checkpoints and differential baselines freely.

    ``por_mask``/``por_priority`` ([G] bool / [G] int32 device arrays,
    or both None = off) enable the statically-certified partial-order
    reduction (analysis/por.py): when a state's enabled set contains a
    certified ample instance, every OTHER expansion of that state is
    masked out before fingerprinting — the lowest-priority-value
    certified enabled lane is the one kept.  Deadlock detection is
    unaffected (masking only fires on non-empty enabled sets), and
    masked lanes' overflow flags are dropped with them (a pruned
    successor is never materialized, so its capacity overflow cannot
    abort the reduced run)."""
    if (por_mask is None) != (por_priority is None):
        raise ValueError("por_mask and por_priority must be given together")
    if por_mask is not None:
        # Last-line admission re-check at the compilation boundary: a
        # reduction mask that does not cover the instance grid exactly
        # (or a non-bool mask, which jnp.where would happily treat as
        # weights) must fail HERE, not silently mis-mask lanes.  The
        # table-level checks (fingerprint, model signature, predicate
        # coverage, encoding version) live in analysis/por.check_table;
        # this guards the raw arrays actually baked into the program.
        if tuple(por_mask.shape) != (G,) \
                or tuple(por_priority.shape) != (G,):
            raise ValueError(
                f"POR mask/priority must be [{G}] (the action-instance "
                f"grid), got {tuple(por_mask.shape)} / "
                f"{tuple(por_priority.shape)}")
        if por_mask.dtype != jnp.bool_ \
                or por_priority.dtype != jnp.int32:
            raise ValueError(
                f"POR mask/priority must be bool/int32, got "
                f"{por_mask.dtype} / {por_priority.dtype}")
    BG = B * G
    inv_id = build_inv_id(inv_fns) if inv_fns else None

    fam_slices = tuple(zip(dims.family_offsets, dims.family_sizes))

    @functools.partial(named_stage, "slice")
    def slice_(qcur, offset, cur_count):
        rows = jax.lax.dynamic_slice_in_dim(qcur, offset, B, axis=0)
        valid = (offset + jnp.arange(B, dtype=_I32)) < cur_count
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        return rows, valid, states

    @functools.partial(named_stage, "masks")
    def masks(states, valid):
        if v2 is None:
            cands, en, ovf = jax.vmap(expand)(states)
            en = en & valid[:, None]
            # A successor whose term/bag count outgrew the uint8 row
            # is an overflow too (schema.build_pack_guard): stop,
            # never alias.
            ovf = (ovf | (en & ~jax.vmap(jax.vmap(pack_ok))(cands))) \
                & valid[:, None]
        else:
            # Masks fold the pack guard in at the same lanes
            # (actions2).
            cands = None
            en, ovf = jax.vmap(v2.masks)(states)
            en = en & valid[:, None]
            ovf = ovf & valid[:, None]

        if por_mask is not None:
            # Partial-order reduction (analysis/por.py table): keep
            # ONE certified ample lane per state that has any,
            # masking its siblings before compaction/fingerprinting
            # — the reduction the coverage tables account as
            # "pruned".  Rows with no certified enabled instance are
            # untouched, so a state with an empty enabled set still
            # reads as a deadlock.
            amp = en & por_mask[None, :]
            any_amp = jnp.any(amp, axis=1)
            pri = jnp.where(amp, por_priority[None, :],
                            jnp.int32(2147483647))
            sel = jnp.argmin(pri, axis=1)
            keep = jnp.where(
                any_amp[:, None],
                jnp.arange(G, dtype=_I32)[None, :] == sel[:, None],
                jnp.ones((B, G), bool))
            pruned = en & ~keep
            en = en & keep
            ovf = ovf & keep
        else:
            pruned = None
        return cands, en, ovf, pruned

    @functools.partial(named_stage, "compact")
    def compact(en, ovf):
        # Progress limiting + lane compaction (ops/compact.py): take
        # the longest parent prefix whose fan-out fits K, compact
        # the enabled lanes to K slots — nothing is ever dropped, a
        # fan-out burst just advances fewer parents this step.
        P, total, lane_id, kvalid = compactor(en)
        ptaken = jnp.arange(B, dtype=_I32) < P
        en = en & ptaken[:, None]
        ovf = ovf & ptaken[:, None]
        return en, ovf, P, total, lane_id, kvalid, ptaken

    @functools.partial(named_stage, "construct")
    def construct(states, cands, lane_id):
        # Everything from here on — fingerprinting included — runs on
        # the K compacted lanes only: gather the candidate structs
        # first, hash after (identical to hashing the packed rows
        # whenever pack_ok holds, and any overflow aborts the run).
        # Hashing before compaction would read every field of all
        # B*G lanes for the ~94% that are disabled.  Its parts run
        # under scopes of their own (``CONSTRUCT_PARTS``).
        if v2 is None:
            with jax.named_scope("parents"):
                cflat = jax.tree.map(
                    lambda a: a.reshape((BG,) + a.shape[2:]), cands)
                kstates = jax.tree.map(lambda a: a[lane_id], cflat)
            kh, kl = jax.vmap(fingerprint)(kstates)     # [K]
        else:
            # Gather K parent structs (from B parents, not B*G
            # candidate lanes) and construct only those successors,
            # with their fingerprints coming from the parents' hash
            # sums + per-lane deltas (models/actions2.py).
            with jax.named_scope("parents"):
                ph = jax.vmap(v2.parent_hash)(states)
                pidx = lane_id // G
                kparents = jax.tree.map(lambda a: a[pidx], states)
                kph = jax.tree.map(lambda a: a[pidx], ph)
            with jax.named_scope("lane_out"):
                kh, kl, kstates = jax.vmap(v2.lane_out)(
                    kparents, kph, lane_id % G)

        if constraint is not None:
            with jax.named_scope("constraint"):
                cons_ok = jax.vmap(constraint)(kstates)
        else:
            cons_ok = jnp.ones((K,), bool)
        with jax.named_scope("flatten"):
            krows = jax.vmap(flatten_state, (0, None))(kstates, dims)
        # Invariant dispatch depends only on the candidates, so it
        # sits before the insert: every invariant on all K lanes of
        # the pass, duplicates and empty lanes included (the engines
        # count them, ``inv_lanes``).
        if inv_id is not None:
            with jax.named_scope("invariants"):
                inv = jax.vmap(inv_id)(kstates)
        else:
            inv = jnp.full((K,), -1, _I32)
        parent_hi = parent_lo = None
        if record_static:
            if v2 is None:
                php, plp = jax.vmap(fingerprint)(states)  # [B]
            else:
                php, plp = jax.vmap(v2.parent_fp)(ph)
            parent_hi = php[lane_id // G]
            parent_lo = plp[lane_id // G]
        return kh, kl, krows, cons_ok, inv, parent_hi, parent_lo

    insert = named_stage("insert", insert_fn)

    @functools.partial(named_stage, "enqueue")
    def enqueue(qnext, next_count, krows, new, cons_ok):
        # Each row at its cumsum position; a masked lane writes its own
        # trash slot past Q.  (Rebuilding a K-row window at next_count
        # with a searchsorted gather and one dynamic_update_slice was
        # the alternative until PR 31 timed it on the chip: 5.9 to 8.4 %
        # slower end to end, PERF.md section 6.)
        enq = new & cons_ok
        epos = next_count + jnp.cumsum(enq.astype(_I32)) - 1
        epos = jnp.where(enq, epos, Q + jnp.arange(K, dtype=_I32))
        qnext = qnext.at[epos].set(krows)
        return qnext, next_count + jnp.sum(enq, dtype=_I32)

    @functools.partial(named_stage, "record")
    def record(tbuf, tcount, new, kh, kl, parent_hi, parent_lo, lane_id):
        actions = lane_id % G
        tpos = jnp.where(
            new, tcount + jnp.cumsum(new.astype(_I32)) - 1,
            TQ + jnp.arange(K, dtype=_I32))
        tbuf = tuple(
            buf.at[tpos].set(col)
            for buf, col in zip(
                tbuf, (kh, kl, parent_hi, parent_lo, actions)))
        return tbuf, tcount + jnp.sum(new, dtype=_I32)

    @functools.partial(named_stage, "stats")
    def stats(counters, P, rows, valid, ptaken, en, ovf, pruned, total,
              lane_id, new, fail, inv, krows, kh, kl):
        (offset, steps, gen, newc, ovfc, dead_any, drow, viol_any, vinv,
         vrow, vhi, vlo, fail_any, fam_counts, fam_new, expanded,
         fam_pruned) = counters
        dead_b = valid & ptaken & ~jnp.any(en, axis=1) \
            & ~jnp.any(ovf, axis=1)
        dead_any_b = jnp.any(dead_b)
        drow_b = rows[jnp.argmax(dead_b)]
        viol = new & (inv >= 0)
        viol_any_b = jnp.any(viol)
        vpos = jnp.argmax(viol)
        take_v = ~viol_any & viol_any_b
        vinv = jnp.where(take_v, inv[vpos], vinv)
        vrow = jnp.where(take_v, krows[vpos], vrow)
        vhi = jnp.where(take_v, kh[vpos], vhi)
        vlo = jnp.where(take_v, kl[vpos], vlo)
        drow = jnp.where(dead_any | ~dead_any_b, drow, drow_b)
        fam_counts = fam_counts + jnp.stack(
            [jnp.sum(en[:, off:off + sz], dtype=_I32)
             for off, sz in fam_slices])
        # Per-family novelty (coverage "distinct"): attribute each novel
        # compacted lane to the family of the action that produced it.
        kact = lane_id % G
        fam_new = fam_new + jnp.stack(
            [jnp.sum(new & (kact >= off) & (kact < off + sz), dtype=_I32)
             for off, sz in fam_slices])
        expanded = expanded + jnp.sum(valid & ptaken, dtype=_I32)
        if pruned is not None:
            # Reduced-vs-full accounting (obs/coverage.py): enabled lanes
            # the POR mask dropped, counted only for parents this step
            # actually advanced past (same base as ``expanded``).
            ptr = pruned & ptaken[:, None]
            fam_pruned = fam_pruned + jnp.stack(
                [jnp.sum(ptr[:, off:off + sz], dtype=_I32)
                 for off, sz in fam_slices])
        return (offset + P, steps + 1, gen + total,
                newc + jnp.sum(new, dtype=_I32),
                ovfc + jnp.sum(ovf, dtype=_I32),
                dead_any | dead_any_b, drow,
                viol_any | viol_any_b, vinv, vrow, vhi, vlo,
                fail_any | fail, fam_counts, fam_new, expanded,
                fam_pruned)

    def chunk_body(qcur, cur_count, carry):
        (offset, steps, qnext, next_count, seen, tbuf, tcount,
         *counters) = carry
        rows, valid, states = slice_(qcur, offset, cur_count)
        cands, en, ovf, pruned = masks(states, valid)
        en, ovf, P, total, lane_id, kvalid, ptaken = compact(en, ovf)
        (kh, kl, krows, cons_ok, inv, parent_hi,
         parent_lo) = construct(states, cands, lane_id)
        seen, new, fail = insert(seen, kh, kl, kvalid)
        qnext, next_count = enqueue(qnext, next_count, krows, new, cons_ok)
        if record_static:
            tbuf, tcount = record(tbuf, tcount, new, kh, kl, parent_hi,
                                  parent_lo, lane_id)
        offset, steps, *counters = stats(
            (offset, steps, *counters), P, rows, valid, ptaken, en, ovf,
            pruned, total, lane_id, new, fail, inv, krows, kh, kl)
        return (offset, steps, qnext, next_count, seen, tbuf, tcount,
                *counters)

    return chunk_body
