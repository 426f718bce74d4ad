"""The reconfiguration cell's controls: the program made to do less than
``benchmark/configs/reconfig3.json`` guarantees, without a switch in the
program.  A run under any of them has to report ``correct: false``.

    python3 benchmark/tests/controls_reconfig.py simple_majority -- <run.py arguments>
    python3 benchmark/tests/controls_reconfig.py no_extra        -- <run.py arguments>
    python3 benchmark/tests/controls_reconfig.py one_byte_values -- <run.py arguments>

``simple_majority`` gives ``ReconfigDims`` the base model's quorum rule
(``2 * |votes| > N``, whatever configuration the deciding server's log
holds): a leader outside C_new commits its joint entry with one
follower.  ``no_extra`` masks the variant's nine extra lanes
(``InitiateReconfig``, ``FinalizeReconfig``) in the guards of the v2
masks pass: no membership change is ever started.  ``one_byte_values``
packs log values into one byte again (``value_bytes`` 1, the lane audit
told the values fit): the wrap the program's ``models/reconfig.py``
records, a configuration entry aliasing to a client value in every queue
row.

What catches each (``benchmark/traffic/rooted_window.py``'s letters):
``simple_majority`` moves no shape and no root, so (a) and (b) hold; from
the roots the two rules first part at level 6 (``E_2``'s joint entry
acknowledged by one follower), so the walk's levels 6, 7 and 8 and every
boundary the window crosses differ from the pin (c).  ``no_extra``: the
two families count 0 from level 1 on (c), in the window (d) and in the
sample (f).  ``one_byte_values``: the row is 369 bytes, not 474 (a); the
six roots that hold a configuration entry do not decode from their rows
(b); and the engine's own ``TypeOK`` stops the walk at the roots, whose
wrapped entries are no value of the domain, so there is no start level
and the line says that.

On the chip the command runs the cell at its own size; the tests here
run it with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def patched(cls, **attrs):
    """``attrs`` set on a class (or a module) for the block."""
    saved = {k: cls.__dict__[k] for k in attrs}
    for k, v in attrs.items():
        setattr(cls, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(cls, k, v)


def simple_majority():
    from raft_tla_tpu.models.dims import RaftDims
    from raft_tla_tpu.models.reconfig import ReconfigDims
    return patched(ReconfigDims, build_quorum=RaftDims.build_quorum)


def no_extra():
    """The guards-only mask kernels answer "not enabled" on every extra
    lane (and no overflow)."""
    import jax.numpy as jnp
    from raft_tla_tpu.models.reconfig import ReconfigDims
    orig = ReconfigDims.build_extra_masks_v2

    def masked(self):
        def off(fn):
            def kernel(*args):
                en, ovf = fn(*args)
                return jnp.zeros_like(en), jnp.zeros_like(ovf)
            return kernel
        return [off(fn) for fn in orig(self)]

    return patched(ReconfigDims, build_extra_masks_v2=masked)


@contextlib.contextmanager
def one_byte_values():
    """The program guards this three times: the lane audit when the dims
    are built, ``check_packable`` on the roots, and 2-byte lanes.  All
    three are told the values fit one byte; what is left is the wrap in
    ``flatten_state``, which nothing on the device guards."""
    from raft_tla_tpu.engine import bfs
    from raft_tla_tpu.models.reconfig import ReconfigDims
    with patched(bfs, check_packable=lambda st, dims: None), \
            patched(ReconfigDims,
                    value_bytes=property(lambda self: 1),
                    max_log_value=property(lambda self: self.n_values)):
        yield


CONTROLS = {"simple_majority": simple_majority, "no_extra": no_extra,
            "one_byte_values": one_byte_values}


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
