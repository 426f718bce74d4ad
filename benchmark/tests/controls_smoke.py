"""The smoke cell's controls: the program made to do less than
``benchmark/configs/smokeraft.json`` guarantees, without a switch in the
program.  A run under any of them has to report ``correct: false``.

    python3 benchmark/tests/controls_smoke.py init_override_dropped -- <run.py arguments>
    python3 benchmark/tests/controls_smoke.py family                -- <run.py arguments>
    python3 benchmark/tests/controls_smoke.py bag_per_root          -- <run.py arguments>

``init_override_dropped`` makes ``Init <- SmokeInit`` a dead letter: the
cfg still loads as a smoke setup, and every check starts from the spec's
one ``Init`` state.  ``family`` masks one action family
(``controls.py masked_family``: ``DropMessage``, a quarter of what a
smoke root generates) out of every expansion.  ``bag_per_root`` gives
each root a bag of its own (its first message left out of every second
root), where ``SmokeInit`` has one bag shared by all.

What catches each (``benchmark/traffic/smoke_loop.py``'s letters):
``init_override_dropped``: one root enqueued, not 512 (c); level 0 is not
the pin's (d); the roots are no SmokeInit set and not the pinned draw's
product (b).  ``family``: (a) and (b) hold, the roots are what they were;
level 1's generated and ``DropMessage``'s count differ from the pin in
every check (d), and from the reference's in the sample (e).
``bag_per_root``: 512 roots still, level 0's three counts equal the
pin's; the recogniser finds two bags and the set is not the pinned
draw's product (b); level 1 differs from the pin (d).

What the cell is blind to: a level past the pinned ones is held only by
the seeded sample and the replays, a few hundred states of millions; any
draw of ``SmokeInit`` but the 16 of the root seeds; a domain misread
alike in ``models/smoke.py`` and in ``benchmark/reference/smoke.py``
(both were written from ``SURVEY.md``'s account, not from upstream's
file).

On the chip the command runs the cell at its own size; the tests here run
it with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def patched_roots(rewrite):
    """``models/smoke.py smoke_init_states`` with ``rewrite(states, dims)``
    applied to what it returns."""
    from raft_tla_tpu.models import smoke
    orig = smoke.smoke_init_states

    def smoke_init_states(dims, k=2, seed=0):
        return rewrite(orig(dims, k=k, seed=seed), dims)

    smoke.smoke_init_states = smoke_init_states
    try:
        yield
    finally:
        smoke.smoke_init_states = orig


def init_override_dropped():
    from raft_tla_tpu.models.pystate import init_state
    return patched_roots(lambda states, dims: [init_state(dims)])


def bag_per_root():
    import dataclasses

    def own_bags(states, dims):
        first = min(states[0].messages)
        return [dataclasses.replace(s, messages=s.messages - {first})
                if i % 2 else s for i, s in enumerate(states)]

    return patched_roots(own_bags)


def family():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import controls
    return controls.masked_family()


CONTROLS = {"init_override_dropped": init_override_dropped,
            "family": family, "bag_per_root": bag_per_root}


def main(argv) -> int:
    if len(argv) < 2 or argv[1] != "--" or argv[0] not in CONTROLS:
        raise SystemExit(__doc__)
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, bench)
    sys.path.insert(0, os.path.dirname(bench))
    # As run.py does, and before anything imports jax.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(bench), ".jax_cache"))
    import run
    with CONTROLS[argv[0]]():
        return run.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
