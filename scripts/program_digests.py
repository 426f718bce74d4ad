#!/usr/bin/env python3
"""Digests of the lowered text of the programs the chip runs, on the CPU.

    python3 scripts/program_digests.py [<checkout>]

Lowers ``BFSEngine``'s ``chunk`` and ``ingest`` for seven cfgs
(``MCraft_safety`` and ``reconfig3_safety`` are the ones that trace
``models/safety.py``, the second under ``ReconfigDims``; ``Smokeraft`` is
the smoke tier's, 1,103-byte rows; a cfg a checkout lacks is left out),
``MeshBFSEngine``'s two programs over four virtual devices (trace
recording on and off) and ``SwarmEngine``'s walk chunk at a small batch,
and prints sha256 of each ``.lower(...).as_text()``.  jax's
persistent-cache key strips debug metadata, so where two checkouts print
the same digests the cache hands the second the first one's executables:
a PR that must not change the program (ISSUE 31), or only one cfg's
(ISSUE 36), shows it by running this on its parent and on itself.
"""

import functools
import hashlib
import os
import sys

ROOT = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else
                       os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import (make_engine,  # noqa: E402
                                       make_swarm_engine)
from raft_tla_tpu.parallel.mesh import MeshBFSEngine  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

S = jax.ShapeDtypeStruct


def digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 14,
                        seen_capacity=1 << 17, **kw)


def main() -> None:
    i32 = S((), jnp.int32)
    for cfg in ("MCraft_bounded", "TPUraft", "MCraft_noleader",
                "MCraft_safety", "reconfig3", "reconfig3_safety",
                "Smokeraft"):
        if not os.path.exists(os.path.join(ROOT, f"configs/{cfg}.cfg")):
            continue
        eng = make_engine(
            load_config(os.path.join(ROOT, f"configs/{cfg}.cfg")), small())
        av = eng.chunk_avals()
        print(f"chunk  {cfg:16s} {digest(eng._chunk.lower(*av))}")
        print(f"ingest {cfg:16s} " + digest(eng._ingest.lower(
            S((eng._B, eng._sw), jnp.uint8), S((eng._B,), jnp.bool_),
            av[3], i32, av[5])))
    setup = load_config(os.path.join(ROOT, "configs/MCraft_bounded.cfg"))
    for trace in (True, False):
        eng = make_engine(setup, small(record_trace=trace),
                          engine_cls=functools.partial(
                              MeshBFSEngine, devices=jax.devices()[:4]))

        def over_mesh(a, eng=eng):
            return S(a.shape, a.dtype, sharding=NamedSharding(
                eng.mesh, P("x") if a.ndim else P()))

        av = jax.tree.map(over_mesh, eng.chunk_avals())
        qav, counts, _, _, _, keys, _, _, tbuf, _, _ = av
        print(f"mesh chunk  MCraft_bounded trace={trace} "
              f"{digest(eng._chunk.lower(*av))}")
        n, B, sw = eng.n_dev, eng._B, eng._sw
        print(f"mesh ingest MCraft_bounded trace={trace} " + digest(
            eng._ingest.lower(over_mesh(S((n, B, sw), jnp.uint8)),
                              over_mesh(S((n, B), jnp.bool_)), qav, counts,
                              keys, keys, counts, tbuf, counts)))
    eng = make_swarm_engine(
        load_config(os.path.join(ROOT, "configs/MCraft_noleader.cfg")),
        walks=256, max_depth=64, batch=256)
    print("walk chunk  MCraft_noleader "
          + digest(eng._chunk.lower(*eng.chunk_avals(1))))


if __name__ == "__main__":
    main()
