"""The swarm cell's controls: the program made to keep fewer of the
guarantees ``benchmark/configs/mcraft3-swarm.json`` states, without a
switch in the program.  A run under either has to report ``correct:
false``.

    python3 benchmark/tests/controls_swarm.py constraint -- <run.py arguments>
    python3 benchmark/tests/controls_swarm.py choice     -- <run.py arguments>

``constraint`` drops the CONSTRAINT from the walk chunk (walkers step
through states outside ``BoundedSpace``); ``choice`` makes the successor
draw the same for every walker (the choice is no longer a function of
(seed, walk, step)).  On the chip the command runs the cell at its own
size; the tests here run it with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def dropped_constraint():
    """``make_swarm_engine`` finds no constraint to hand the walk chunk."""
    from raft_tla_tpu.engine import check
    orig = check.resolve_constraint
    check.resolve_constraint = lambda setup: None
    try:
        yield
    finally:
        check.resolve_constraint = orig


@contextlib.contextmanager
def constant_choice():
    """Every walker draws the successor choice of walker 0."""
    from raft_tla_tpu.engine import swarm
    orig = swarm.walk_bits

    def walk_bits(seed, walk_id, step, stream):
        if stream == swarm.CHOICE_STREAM:
            walk_id = walk_id * 0
        return orig(seed, walk_id, step, stream)

    swarm.walk_bits = walk_bits
    try:
        yield
    finally:
        swarm.walk_bits = orig


def control(name: str):
    if name == "constraint":
        return dropped_constraint()
    if name == "choice":
        return constant_choice()
    raise SystemExit(f"unknown control {name!r} (constraint or choice)")


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
