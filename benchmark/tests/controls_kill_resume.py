"""The kill-and-resume cell's controls: the program made to keep less
than ``benchmark/configs/mcraft3-supervised.json`` guarantees, without a
switch in the program.  A run under any of them has to report ``correct:
false``.

    python3 benchmark/tests/controls_kill_resume.py restore_every_other_key -- <run.py arguments>
    python3 benchmark/tests/controls_kill_resume.py latest_oldest           -- <run.py arguments>
    python3 benchmark/tests/controls_kill_resume.py drop_last_level_records -- <run.py arguments>

``restore_every_other_key`` hands the restore's table rebuild
(``ops/fpset.from_host_keys``) every other key of the snapshot: a resumed
run meets half of what it had seen as new.  ``latest_oldest`` makes
``checkpoint.latest`` answer the OLDEST intact snapshot of a directory:
the recovery starts a level too early and does that level's work again,
unasked.  ``drop_last_level_records`` makes ``checkpoint.save`` leave
out the trace records that are new since the snapshot before it, the last
level's: the file's frontier and keys are whole, and a state admitted
after a recovery from it has no path back to ``Init``.

What catches each (``benchmark/traffic/kill_resume.py``):
``restore_every_other_key``: the killed run itself resumes the start
level's file, so the first boundary it crosses differs from the pin, and
so does every later one.  ``latest_oldest``: ``latest()`` after the kill
is not the kill level's file, ``run_start`` names another path, and the
recovered run crosses (and snapshots) the kill level's boundary a second
time.  ``drop_last_level_records``: no count moves; the plain reader
finds fewer records than keys in both files on disk, and the replays of
states admitted after the recovery do not reach a root.

On the chip the command runs the cell at its own size; the tests here
run it with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

import numpy as np


@contextlib.contextmanager
def patched(module, **attrs):
    """``attrs`` set on a module for the block."""
    saved = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(module, k, v)


def restore_every_other_key():
    from raft_tla_tpu.ops import fpset
    orig = fpset.from_host_keys

    def half(hi, lo, *args, **kw):
        return orig(hi[::2], lo[::2], *args, **kw)

    return patched(fpset, from_host_keys=half)


def latest_oldest():
    from raft_tla_tpu.engine import checkpoint

    def oldest(checkpoint_dir):
        if not os.path.isdir(checkpoint_dir):
            return None
        for _lvl, names in sorted(checkpoint._list_snapshots(checkpoint_dir)):
            if checkpoint._group_is_intact(checkpoint_dir, names):
                return os.path.join(checkpoint_dir, names[0])
        return None

    return patched(checkpoint, latest=oldest)


def drop_last_level_records():
    from raft_tla_tpu.engine import checkpoint
    orig = checkpoint.save
    before = {"fps": np.empty(0, np.uint64)}    # the last save's, sorted

    def save(path, ckpt, *args, **kw):
        fps = np.asarray(ckpt.trace_fps, np.uint64)
        keep = np.isin(fps, before["fps"], assume_unique=True)
        before["fps"] = np.sort(fps)
        return orig(path, dataclasses.replace(
            ckpt, trace_fps=fps[keep],
            trace_parents=np.asarray(ckpt.trace_parents)[keep],
            trace_actions=np.asarray(ckpt.trace_actions)[keep]),
            *args, **kw)

    return patched(checkpoint, save=save)


CONTROLS = {"restore_every_other_key": restore_every_other_key,
            "latest_oldest": latest_oldest,
            "drop_last_level_records": drop_last_level_records}


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
