"""The controls: the program made to compute less, without a switch in
the program.  Each breaks one guarantee the configurations state, and a
run under either has to report ``correct: false``.

    python3 benchmark/tests/controls.py fp32   -- <run.py arguments>
    python3 benchmark/tests/controls.py family -- <run.py arguments>

``fp<bits>`` keeps only the low ``bits`` bits of every fingerprint where
the seen-set is keyed (the 64-bit guarantee); ``family`` masks the
DropMessage action family out of every expansion (exhaustiveness).  On
the chip the command runs a cell at its own size; the tests here run it
with ``--rehearsal`` on the CPU, on a throw-away cell.
"""

from __future__ import annotations

import contextlib
import os
import sys

MASKED_FAMILY = "DropMessage"


@contextlib.contextmanager
def truncated_fingerprints(bits: int):
    """Key the seen-set by the low ``bits`` of each 64-bit fingerprint."""
    import jax.numpy as jnp
    from raft_tla_tpu.ops import fpset
    orig = fpset.insert
    lo_mask = jnp.uint32((1 << min(bits, 32)) - 1)
    hi_mask = jnp.uint32((1 << max(bits - 32, 0)) - 1)

    def insert(seen, fph, fpl, en):
        return orig(seen, fph & hi_mask, fpl & lo_mask, en)

    fpset.insert = insert
    try:
        yield
    finally:
        fpset.insert = orig


@contextlib.contextmanager
def masked_family(name: str = MASKED_FAMILY):
    """Never enable any instance of one action family."""
    import jax.numpy as jnp
    from raft_tla_tpu.engine import bfs
    orig = bfs._resolve_pipeline

    def resolve(requested, dims):
        v2 = orig(requested, dims)
        fam = dims.family_names.index(name)
        off, size = dims.family_offsets[fam], dims.family_sizes[fam]
        keep = (jnp.arange(dims.n_instances) < off) | (
            jnp.arange(dims.n_instances) >= off + size)

        def masks(state):
            en, ovf = v2.masks(state)
            return en & keep, ovf & keep

        return v2._replace(masks=masks)

    bfs._resolve_pipeline = resolve
    try:
        yield
    finally:
        bfs._resolve_pipeline = orig


def control(name: str):
    if name == "family":
        return masked_family()
    if name.startswith("fp") and name[2:].isdigit():
        return truncated_fingerprints(int(name[2:]))
    raise SystemExit(f"unknown control {name!r} (fp<bits> or family)")


def main(argv) -> int:
    if len(argv) < 2 or argv[1] != "--":
        raise SystemExit(__doc__)
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, bench)
    sys.path.insert(0, os.path.dirname(bench))
    # As run.py does, and before anything imports jax.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(bench), ".jax_cache"))
    import run
    with control(argv[0]):
        return run.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
