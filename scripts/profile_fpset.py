"""Microbenchmark of FPSet primitive costs on the ambient platform.

Times, per call: one big scatter; one big gather; the hash-insert (static
rounds vs while_loop); the old sorted-merge (full lax.sort) and
binary-search probe — to decide which dedup design the TPU actually wants.

These are per-call host-clock timings around ``block_until_ready``; for
device timings read a profiler capture of an end-to-end engine run
(``--xla-profile``, ``benchmark/run.py --trace 1``).
"""

import sys
import os
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.ops import fpset
from raft_tla_tpu.ops.fingerprint import SENTINEL

C = int(os.environ.get("FPSET_C", 1 << 23))   # table capacity
K = int(os.environ.get("FPSET_K", 1 << 18))   # keys per insert


def timeit(name, fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name:40s} {(time.time() - t0) / n * 1e3:9.2f} ms")


def main():
    print("platform:", jax.devices()[0].platform)
    rng = np.random.RandomState(0)
    qhi = jnp.asarray(rng.randint(0, 1 << 32, K, np.uint64).astype(np.uint32))
    qlo = jnp.asarray(rng.randint(0, 1 << 32, K, np.uint64).astype(np.uint32))
    valid = jnp.ones((K,), bool)
    idx = jnp.asarray(rng.randint(0, C, K, np.int64).astype(np.int32))
    big = jnp.zeros((C,), jnp.uint32)
    upd = qhi

    timeit("scatter 256k -> 8M", jax.jit(
        lambda b, i, u: b.at[i].set(u, mode="drop")), big, idx, upd)
    timeit("scatter-max 256k -> 8M", jax.jit(
        lambda b, i, u: b.at[i].max(u, mode="drop")), big, idx, upd)
    timeit("gather 256k <- 8M", jax.jit(lambda b, i: b[i]), big, idx)
    timeit("sort 256k (3 lanes)", jax.jit(
        lambda a, b: jax.lax.sort((a, b, jnp.arange(K, dtype=jnp.int32)),
                                  num_keys=2)), qhi, qlo)
    bighi = jnp.full((C,), SENTINEL, jnp.uint32)
    timeit("sort 8M+256k (2 lanes, old merge)", jax.jit(
        lambda bh, nh: jax.lax.sort(
            (jnp.concatenate([bh, nh]), jnp.concatenate([bh, nh])),
            num_keys=2)), bighi, qhi)

    s = fpset.empty(C)
    ins = jax.jit(fpset.insert)
    timeit("hash insert 256k -> empty 8M", ins, s, qhi, qlo, valid)
    # Table at ~50% load.
    s50 = fpset.empty(C)
    half = C // 2
    fill_hi = jnp.asarray(
        rng.randint(0, 1 << 32, half, np.uint64).astype(np.uint32))
    fill_lo = jnp.asarray(
        rng.randint(0, 1 << 32, half, np.uint64).astype(np.uint32))
    ins_d = jax.jit(fpset.insert, donate_argnums=(0,))
    for b in range(0, half, K):
        s50, _, _ = ins_d(s50, fill_hi[b:b + K], fill_lo[b:b + K], valid)
    timeit("hash insert 256k -> 50%-load 8M", ins, s50, qhi, qlo, valid)
    timeit("hash contains 256k in 50%-load 8M", jax.jit(fpset.contains),
           s50, qhi, qlo)


if __name__ == "__main__":
    main()
