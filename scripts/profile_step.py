"""Decompose one BFS batch into its device kernels and time each on the
ambient platform (the TPU; the CPU under JAX_PLATFORMS=cpu).  This is the
instrument for the round-3 performance work: run it before and after any
engine change and commit the numbers.

The staged decomposition (expand / fingerprint / dedup_insert /
enqueue, fenced between stages) comes from the shared
``obs.profile`` API — the same programs ``--profile-chunks`` samples
inside a live engine run — so this script's numbers and an engine
run's ``chunk_profile`` event are the same instrument.  On top of
that, this script times what the in-engine profiler can't:

  CHUNK                  the engine's real fused chunk program
  CHUNK x8               ditto, 8 batches per call (sync_every)
  CHUNK v2 / v2 x8       the delta pipeline (what ``auto`` runs)

Run:  python scripts/profile_step.py [batch]

Cross-check any surprising number against scripts/true_bench.py
(fori_loop-chained, host-fetch barrier) and against an end-to-end engine
run before acting on it.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.engine.bfs import EngineConfig
from raft_tla_tpu.engine.check import initial_states, make_engine
from raft_tla_tpu.ops import fpset
from raft_tla_tpu.utils.cfg import load_config


def bench(label, fn, *args, n=10, **kw):
    out = fn(*args, **kw)          # compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    ms = (time.time() - t0) / n * 1e3
    print(f"{label:42s} {ms:9.2f} ms")
    return ms, out


def main():
    print("platform:", jax.devices()[0].platform)
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    enable_persistent_cache()
    setup = load_config("configs/MCraft_bounded.cfg")
    dims = setup.dims
    # The per-stage parts below instrument the v1 pipeline's components;
    # the fused-CHUNK section at the end times BOTH pipelines (v1 expand
    # vs the actions2 delta path) on the same warm frontier.
    cfg = EngineConfig(batch=B, queue_capacity=1 << 20,
                       seen_capacity=1 << 23, record_trace=False,
                       check_deadlock=False, pipeline="v1")
    eng = make_engine(setup, cfg)
    G, SW, Q, K = eng._G, eng._sw, eng._Q, eng._K
    QA = Q + eng._PAD
    BG = B * G
    print(f"dims: {dims}  B={B} G={G} SW={SW} B*G={BG} K={K}")

    # A realistic frontier: run the engine for a few levels and snapshot a
    # mid-level frontier, so the benchmarked batch has representative
    # duplication/occupancy (tiled roots would collapse to ~G distinct
    # candidates and flatter the dedup path).
    warm = make_engine(setup, EngineConfig(
        batch=B, queue_capacity=1 << 20, seen_capacity=1 << 23,
        record_trace=False, check_deadlock=False, max_diameter=4))
    wres = warm.run(initial_states(setup))
    wrows = warm._last_frontier
    print(f"warm-up frontier: {len(wrows)} states at diameter "
          f"{wres.diameter} ({wres.distinct} distinct seen)")
    reps = -(-QA // len(wrows))
    qcur = jnp.asarray(np.tile(wrows, (reps, 1))[:QA])

    # The staged decomposition — the SAME programs --profile-chunks runs
    # inside a live engine (obs/profile.py), so a number printed here
    # and a chunk_profile event disagree only if the hardware does.
    from raft_tla_tpu.obs.profile import STAGES, profile_stages
    rows = qcur[:B]
    means = profile_stages(dims, np.asarray(rows), lanes=K,
                           seen_capacity=cfg.seen_capacity, n=10)
    for s in STAGES:
        print(f"{s + ' (staged, fenced)':42s} {means[s] * 1e3:9.2f} ms")
    staged_sum = sum(means[s] for s in STAGES)
    print(f"{'sum(stages)':42s} {staged_sum * 1e3:9.2f} ms")
    print(f"{'staged total (one jit, non-donating)':42s} "
          f"{means['total'] * 1e3:9.2f} ms")

    seen = fpset.empty(cfg.seen_capacity)
    qnext = jnp.zeros((QA, SW), jnp.uint8)

    # The engine's own fused chunk program (qnext/seen/tbuf are donated:
    # thread the outputs back through).
    tbuf = tuple(jnp.zeros((eng._TA,), d) for d in
                 (jnp.uint32, jnp.uint32, jnp.uint32, jnp.uint32, jnp.int32))

    def chunk_once(qnext, seen, tbuf):
        return eng._chunk(qcur, jnp.int32(B), jnp.int32(0), qnext,
                          jnp.int32(0), seen, tbuf, jnp.int32(0),
                          jnp.int32(1))

    out = chunk_once(qnext, seen, tbuf)     # compile + warm
    jax.block_until_ready(out)
    n = 10
    t0 = time.time()
    for _ in range(n):
        out = chunk_once(out[0], out[1], out[2])
    jax.block_until_ready(out)
    print(f"{'CHUNK (1 batch, fused program)':42s} "
          f"{(time.time() - t0) / n * 1e3:9.2f} ms")
    st = np.asarray(out[3])
    print(f"  chunk stats: offset={st[0]} steps={st[1]} next={st[2]} "
          f"seen={st[3]} gen={st[5]} new={st[6]}")

    def chunk8(qnext, seen, tbuf):
        return eng._chunk(qcur, jnp.int32(8 * B), jnp.int32(0), qnext,
                          jnp.int32(0), seen, tbuf, jnp.int32(0),
                          jnp.int32(8))

    out = chunk8(out[0], out[1], out[2])    # warm (same compiled program)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = chunk8(out[0], out[1], out[2])
    jax.block_until_ready(out)
    print(f"{'CHUNK x8 (8 batches per call)':42s} "
          f"{(time.time() - t0) / n / 8 * 1e3:9.2f} ms/batch")

    # The same fused chunk, v2 (delta) pipeline — models/actions2.py.
    eng2 = make_engine(setup, EngineConfig(
        batch=B, queue_capacity=1 << 20, seen_capacity=1 << 23,
        record_trace=False, check_deadlock=False, pipeline="v2"))
    qnext2 = jnp.zeros((QA, SW), jnp.uint8)
    seen2 = fpset.empty(cfg.seen_capacity)
    tbuf2 = tuple(jnp.zeros((eng2._TA,), d) for d in
                  (jnp.uint32, jnp.uint32, jnp.uint32, jnp.uint32,
                   jnp.int32))
    out2 = eng2._chunk(qcur, jnp.int32(B), jnp.int32(0), qnext2,
                       jnp.int32(0), seen2, tbuf2, jnp.int32(0),
                       jnp.int32(1))
    jax.block_until_ready(out2)
    t0 = time.time()
    for _ in range(n):
        out2 = eng2._chunk(qcur, jnp.int32(B), jnp.int32(0), out2[0],
                           jnp.int32(0), out2[1], out2[2], jnp.int32(0),
                           jnp.int32(1))
    jax.block_until_ready(out2)
    print(f"{'CHUNK v2 (1 batch, delta pipeline)':42s} "
          f"{(time.time() - t0) / n * 1e3:9.2f} ms")

    def chunk8_v2(qnext, seen, tbuf):
        return eng2._chunk(qcur, jnp.int32(8 * B), jnp.int32(0), qnext,
                           jnp.int32(0), seen, tbuf, jnp.int32(0),
                           jnp.int32(8))

    out2 = chunk8_v2(out2[0], out2[1], out2[2])
    jax.block_until_ready(out2)
    t0 = time.time()
    for _ in range(n):
        out2 = chunk8_v2(out2[0], out2[1], out2[2])
    jax.block_until_ready(out2)
    print(f"{'CHUNK v2 x8 (8 batches per call)':42s} "
          f"{(time.time() - t0) / n / 8 * 1e3:9.2f} ms/batch")

if __name__ == "__main__":
    main()
