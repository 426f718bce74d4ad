"""The safety cell's controls: the program made to evaluate less of the
suite ``benchmark/configs/mcraft3-safety.json`` names, without a switch in
the program.  A run under either has to report ``correct: false``.

    python3 benchmark/tests/controls_safety.py predicate -- <run.py arguments>
    python3 benchmark/tests/controls_safety.py dispatch  -- <run.py arguments>

``predicate`` makes one predicate of ``models/safety.py`` (``LogMatching``)
constant ``True`` where the engine resolves the cfg's names; ``dispatch``
makes the chunk's first-failing dispatch (``build_inv_id``) answer -1, "all
hold", on every lane.  Neither moves a count (the suite holds on this
space), so only the witnesses see them.  On the chip the command runs the
cell at its own size; the tests here run it with ``--rehearsal`` on the
CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys

SKIPPED_PREDICATE = "LogMatching"


@contextlib.contextmanager
def constant_predicate(name: str = SKIPPED_PREDICATE):
    """``resolve_invariants`` finds a builder of ``lambda state: True``
    under ``name``."""
    import jax.numpy as jnp
    from raft_tla_tpu.engine import check
    orig = check.INVARIANT_REGISTRY[name]
    check.INVARIANT_REGISTRY[name] = lambda dims: (
        lambda st: jnp.bool_(True))
    try:
        yield
    finally:
        check.INVARIANT_REGISTRY[name] = orig


@contextlib.contextmanager
def silent_dispatch():
    """The chunk body's ``inv_id`` answers -1 on every lane."""
    import jax.numpy as jnp
    from raft_tla_tpu.engine import chunk
    orig = chunk.build_inv_id
    chunk.build_inv_id = lambda inv_fns: (lambda st: jnp.int32(-1))
    try:
        yield
    finally:
        chunk.build_inv_id = orig


def control(name: str):
    if name == "predicate":
        return constant_predicate()
    if name == "dispatch":
        return silent_dispatch()
    raise SystemExit(f"unknown control {name!r} (predicate or dispatch)")


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
