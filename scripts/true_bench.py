"""Device timings that amortize dispatch.

Every measurement here loops the op N times inside ONE jitted
``lax.fori_loop`` (data-chained so iterations can't collapse) and ends
with a host fetch of a scalar — a true barrier.  Reported per-iteration time subtracts nothing;
with N=8 the dispatch+RTT overhead is amortized to noise.

``TB_JSON=path`` additionally writes the measurements as one JSON
object in the bench.py dialect — ``ms`` (this script's fori-loop
numbers), ``chunk_stages`` (the shared obs/profile.py staged
decomposition over the same warm frontier), and ``coverage`` (the
warm run's TLC-style per-action object) — so scripts/bench_diff.py
can gate these trajectories exactly like bench.py ones.
"""

import json
import os
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.models.actions import build_expand
from raft_tla_tpu.models.schema import flatten_state, unflatten_state
from raft_tla_tpu.ops import fpset
from raft_tla_tpu.ops.fingerprint import SENTINEL, build_fingerprint
from raft_tla_tpu.utils.cfg import load_config

N = 4

#: name -> ms/iter, what TB_JSON serializes.
RESULTS = {}


def timed(name, jitted, *args):
    out = jitted(*args)
    _ = float(np.asarray(jax.tree.leaves(out)[0]).ravel()[0])  # barrier
    t0 = time.time()
    out = jitted(*args)
    _ = float(np.asarray(jax.tree.leaves(out)[0]).ravel()[0])  # barrier
    dt = (time.time() - t0) / N * 1e3
    print(f"{name:46s} {dt:9.2f} ms/iter")
    RESULTS[name] = round(dt, 3)
    return dt


def main():
    print("platform:", jax.devices()[0].platform, " N =", N)
    setup = load_config("configs/MCraft_bounded.cfg")
    dims = setup.dims
    B = int(os.environ.get("TB_BATCH", 2048))
    G = dims.n_instances
    K = B * G
    # Workload generated in-process (runs from a fresh clone): a few real
    # BFS levels supply a representative mid-level frontier, and one
    # expand+fingerprint pass over it supplies real candidate keys.
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import initial_states, make_engine
    # The warm-up run doubles as the telemetry-regression gate (same
    # contract as bench.py): its event log must exist and parse, or the
    # whole measurement exits nonzero — microbenchmark numbers from an
    # unobservable engine are not trustworthy evidence.
    import tempfile
    scratch_dir = tempfile.mkdtemp(prefix="tb_obs_")
    warm = make_engine(setup, EngineConfig(
        batch=B, queue_capacity=1 << 20, seen_capacity=1 << 23,
        record_trace=False, check_deadlock=False, max_diameter=4,
        events_out=os.path.join(scratch_dir, "events.jsonl")))
    wres = warm.run(initial_states(setup))
    # Engine-resolved path + cleanup-on-both-outcomes, shared with
    # bench.py (obs.validate_and_cleanup).
    from raft_tla_tpu.obs import validate_and_cleanup
    try:
        validate_and_cleanup(warm._events_path(), scratch_dir)
    except (OSError, ValueError) as e:
        print(f"true_bench: telemetry regression — event log invalid: {e}",
              file=sys.stderr)
        sys.exit(1)
    wrows = warm._last_frontier
    rows = jnp.asarray(np.tile(wrows, (-(-B // len(wrows)), 1))[:B])
    expand = build_expand(dims)
    fingerprint = build_fingerprint(dims)

    @jax.jit
    def mkkeys(rows):
        states = jax.vmap(unflatten_state, (0, None))(rows, dims)
        cands, en, _ovf = jax.vmap(expand)(states)
        cflat = jax.tree.map(lambda a: a.reshape((K,) + a.shape[2:]), cands)
        crows = jax.vmap(flatten_state, (0, None))(cflat, dims)
        st2 = jax.vmap(unflatten_state, (0, None))(crows, dims)
        fh, fl = jax.vmap(fingerprint)(st2)
        return fh, fl, en.reshape(-1)

    fph, fpl, enf = mkkeys(rows)
    C = 1 << 23

    @jax.jit
    def loop_insert(fph, fpl, enf):
        s = fpset.empty(C)

        def body(i, carry):
            s, acc = carry
            s2, new, fail = fpset.insert(s, fph ^ i.astype(jnp.uint32),
                                         fpl, enf)
            return s2, acc + jnp.sum(new, dtype=jnp.int32)

        s, acc = jax.lax.fori_loop(0, N, body, (s, jnp.int32(0)))
        return acc

    timed("insert 270k real keys", loop_insert, fph, fpl, enf)

    @jax.jit
    def loop_dedup(fph, fpl, enf):
        def body(i, acc):
            (sh, sl), order, first = fpset.dedup_batch(
                fph ^ i.astype(jnp.uint32), fpl, enf)
            return acc + jnp.sum(first, dtype=jnp.int32)

        return jax.lax.fori_loop(0, N, body, jnp.int32(0))

    timed("dedup_batch (sort 270k)", loop_dedup, fph, fpl, enf)

    @jax.jit
    def loop_bigsort(fph):
        base = jnp.full((C,), SENTINEL, jnp.uint32)

        def body(i, acc):
            ch = jnp.concatenate([base, fph ^ i.astype(jnp.uint32)])
            sh, _sl = jax.lax.sort((ch, ch), num_keys=2)
            return acc + sh[0].astype(jnp.int32)

        return jax.lax.fori_loop(0, N, body, jnp.int32(0))

    timed("merge-sort 8M+270k (old FPSet)", loop_bigsort, fph)

    @jax.jit
    def loop_expand(rows):
        def body(i, acc):
            states = jax.vmap(unflatten_state, (0, None))(
                rows.at[0, 0].add(i.astype(rows.dtype)), dims)
            cands, en, ovf = jax.vmap(expand)(states)
            cflat = jax.tree.map(
                lambda a: a.reshape((K,) + a.shape[2:]), cands)
            crows = jax.vmap(flatten_state, (0, None))(cflat, dims)
            return acc + jnp.sum(crows[:, 0], dtype=jnp.int32) \
                + jnp.sum(en, dtype=jnp.int32)

        return jax.lax.fori_loop(0, N, body, jnp.int32(0))

    timed("expand+flatten 2048 states", loop_expand, rows)

    @jax.jit
    def loop_fp(rows):
        def body(i, acc):
            states = jax.vmap(unflatten_state, (0, None))(
                rows.at[0, 0].add(i.astype(rows.dtype)), dims)
            cands, en, ovf = jax.vmap(expand)(states)
            cflat = jax.tree.map(
                lambda a: a.reshape((K,) + a.shape[2:]), cands)
            crows = jax.vmap(flatten_state, (0, None))(cflat, dims)
            st2 = jax.vmap(unflatten_state, (0, None))(crows, dims)
            fh, fl = jax.vmap(fingerprint)(st2)
            return acc + jnp.sum(fh, dtype=jnp.uint32).astype(jnp.int32)

        return jax.lax.fori_loop(0, N, body, jnp.int32(0))

    t_fp = timed("expand+flatten+fingerprint", loop_fp, rows)

    Q = 1 << 20
    crows = jnp.zeros((K, 473), jnp.uint8)

    @jax.jit
    def loop_enqueue(crows, enf):
        qnext = jnp.zeros((Q, 473), jnp.uint8)

        def body(i, carry):
            qnext, acc = carry
            enq = enf
            pos = jnp.cumsum(enq.astype(jnp.int32)) - 1
            pos = jnp.where(enq, pos + i, Q)
            qnext = qnext.at[pos].set(crows, mode="drop")
            return qnext, acc + qnext[0, 0].astype(jnp.int32)

        qnext, acc = jax.lax.fori_loop(0, N, body, (qnext, jnp.int32(0)))
        return acc

    timed("enqueue row-scatter 270k->1M", loop_enqueue, crows, enf)

    @jax.jit
    def loop_gather_rows(crows, enf):
        order = jnp.argsort(~enf)           # enabled rows first

        def body(i, acc):
            sel = crows[order + i - i]      # row gather 270k x 473
            return acc + sel[0, 0].astype(jnp.int32)

        return jax.lax.fori_loop(0, N, body, jnp.int32(0))

    timed("row-gather 270k x 473", loop_gather_rows, crows, enf)

    out_path = os.environ.get("TB_JSON")
    if out_path:
        # bench.py-dialect JSON: chunk_stages + coverage are the two
        # axes scripts/bench_diff.py gates on; "ms" carries this
        # script's own fori-loop numbers for eyeballing.
        from raft_tla_tpu.obs.profile import profile_stages
        stage_means = profile_stages(
            dims, np.asarray(rows), seen_capacity=1 << 23, n=max(N, 2))
        doc = {
            "metric": "true_bench_ms",
            "value": RESULTS.get("expand+flatten+fingerprint", 0.0),
            "unit": "ms/iter",
            "platform": jax.devices()[0].platform,
            "batch": B,
            "n_iters": N,
            "ms": RESULTS,
            "chunk_stages": {k: round(v, 6)
                             for k, v in stage_means.items()},
            "coverage": wres.coverage,
            "distinct_states": wres.distinct,
            "generated_states": wres.generated,
        }
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"true_bench: wrote {out_path}")


if __name__ == "__main__":
    main()
