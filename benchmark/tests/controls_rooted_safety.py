"""The rooted safety cells' controls (``reconfig3-safety``,
``leader-rich``): the program made to check less than their configurations
guarantee, without a switch in the program.  A run under any of them has
to report ``correct: false``.

    python3 benchmark/tests/controls_rooted_safety.py low_byte_entry_eq -- <run.py arguments>
    python3 benchmark/tests/controls_rooted_safety.py log_matching_true -- <run.py arguments>
    python3 benchmark/tests/controls_rooted_safety.py inv_id_minus_one  -- <run.py arguments>
    python3 benchmark/tests/controls_rooted_safety.py leader_family     -- <run.py arguments>

``low_byte_entry_eq`` makes ``models/safety.py _entry_eq`` compare the
values of two records mod 256: the wrap ``models/reconfig.py`` records
(``joint_value(7, 3)`` and ``final_value(3)`` both end in byte 3), moved
from the queue's rows into the six predicates that compare whole records.
``log_matching_true`` makes ``LogMatching`` constant ``True`` where the
engine resolves the cfg's names and ``inv_id_minus_one`` makes the chunk's
first-failing dispatch answer -1 on every lane (``controls_safety.py``'s
two).  ``leader_family`` masks ``AppendEntries`` out of every expansion
(``controls.py``'s mask, on a family only a leader enables).

What catches each (``benchmark/traffic/rooted_safety_window.py``'s and
``rooted_window.py``'s letters).  None of the first three moves a count:
the suite holds on both spaces, so (a)-(g) pass and only the witnesses of
(s3) see them.  ``low_byte_entry_eq``: the high-byte witness alone is sure
to (``witness_log_matching_high_byte``: the resumed frontier does not stop
under ``LogMatching``); ``LogMatching``'s own four witnesses draw two of
five values and read ``correct: true`` unless all four pairs share a low
byte, and no other maker builds two records that differ in the high byte
alone, so in ``leader-rich`` (one-byte values) the control changes
nothing and is not run.  ``log_matching_true``: both ``LogMatching``
makers' frontiers stop under a later name or not at all.
``inv_id_minus_one``: every maker's frontier runs its level out without a
violation.  ``leader_family``: (c) from level 1 on (generated and the
family's count against the pin), (d) ``window generated AppendEntries``,
(f) the sample's counts.

On the chip the command runs a cell at its own size; the tests here run it
with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys

MASKED_FAMILY = "AppendEntries"


@contextlib.contextmanager
def low_byte_entry_eq():
    """``_entry_eq`` with the values' low bytes compared."""
    from raft_tla_tpu.models import safety
    orig = safety._entry_eq

    def entry_eq(st):
        te = st.log_term[:, None, :] == st.log_term[None, :, :]
        lo = st.log_val & 0xFF
        return te & (lo[:, None, :] == lo[None, :, :])

    safety._entry_eq = entry_eq
    try:
        yield
    finally:
        safety._entry_eq = orig


def control(name: str):
    if name == "low_byte_entry_eq":
        return low_byte_entry_eq()
    if name == "log_matching_true":
        import controls_safety
        return controls_safety.constant_predicate("LogMatching")
    if name == "inv_id_minus_one":
        import controls_safety
        return controls_safety.silent_dispatch()
    if name == "leader_family":
        import controls
        return controls.masked_family(MASKED_FAMILY)
    raise SystemExit(f"unknown control {name!r}")


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
