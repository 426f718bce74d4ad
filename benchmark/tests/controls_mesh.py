"""The controls of a ``mesh_window`` cell: ``controls.py``'s two patches
from outside, with the snapshot a run under a control makes kept apart
from the checkout's own.

    python3 benchmark/tests/controls_mesh.py fp32   -- <run.py arguments>
    python3 benchmark/tests/controls_mesh.py family -- <run.py arguments>

A control run keeps nothing of the checkout's: it looks for its snapshot
under ``.bench_kept.control/``, finds none, and so MAKES it under the
control (``fp32``: 19.8 M keys in 2^32 collide in the tens of thousands
by level 12, and the walk's counts leave the pin; ``family``: level 3
already does), which has to read ``correct: false`` and leave no copy
behind.  The directory is removed afterwards either way.
"""

from __future__ import annotations

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONTROL_DIRNAME = ".bench_kept.control"


def main(argv) -> int:
    if len(argv) < 2 or argv[1] != "--":
        raise SystemExit(__doc__)
    for p in (HERE, BENCH, os.path.dirname(BENCH)):
        sys.path.insert(0, p)
    # As run.py does, and before anything imports jax.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
        os.path.dirname(BENCH), ".jax_cache"))
    import bench_lib as lib
    import controls
    import run
    kind = lib.load_module("traffic", "mesh_window")
    kind.KEPT_DIRNAME = CONTROL_DIRNAME
    kept = os.path.join(lib.ROOT, CONTROL_DIRNAME)
    shutil.rmtree(kept, ignore_errors=True)
    try:
        with controls.control(argv[0]):
            return run.main(argv[2:])
    finally:
        left = os.listdir(kept) if os.path.isdir(kept) else []
        print(f"control {argv[0]}: copies left under {CONTROL_DIRNAME}: "
              f"{left}", file=sys.stderr, flush=True)
        shutil.rmtree(kept, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
