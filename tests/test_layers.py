"""The package's boxes and which way their arrows point.

Read from the files' ASTs (lazy imports inside functions included), so
nothing is imported and no edge hides behind a call.  A box is a
directory of ``raft_tla_tpu/``; ``cli.py`` and ``server.py`` are the
entry points above all of them and nothing below imports them.

``MAY_IMPORT`` is the picture with every arrow pointing down (the
boxes are listed bottom-up).  ``BACK_EDGES`` are the arrows that point
up today, each with the import that draws it: a named debt (ROADMAP.md
C14), written down so that a NEW one fails here.
"""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "raft_tla_tpu")

MAY_IMPORT = {
    # utils/ imports obs/, and everything imports utils/ or obs/: a leaf.
    "obs": set(),
    "native": set(),
    "models": set(),
    "ops": {"models"},
    "analysis": {"models", "ops"},
    "utils": {"obs", "models"},
    "resilience": {"obs"},
    "engine": {"obs", "native", "models", "ops", "analysis", "utils",
               "resilience"},
    "parallel": {"obs", "models", "ops", "resilience", "engine"},
    "serving": {"obs"},
}

BACK_EDGES = {
    "models": {"ops",        # actions2.py: fingerprint.SENTINEL, fmix32
               "analysis"},  # schema.py: analysis.lane_map (lazy)
    "analysis": {"engine"},  # lint.py: engine.chunk.build_chunk_body (lazy)
    "resilience": {"engine"},  # supervisor.py: engine.checkpoint (lazy)
    "engine": {"parallel"},  # check.py: mesh; bfs.py: multihost (lazy)
}


def imported_boxes(box: str) -> set:
    """The top-level names of ``raft_tla_tpu`` that the files of ``box``
    import, ``box`` itself left out."""
    found = set()
    root = os.path.join(PKG, box)
    for d, _dirs, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            # The package a relative import of this file counts from.
            here = ["raft_tla_tpu"] + os.path.relpath(d, PKG).split(os.sep)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name.split(".") for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level:
                    base = here[:len(here) - node.level + 1]
                    mod = base + (node.module.split(".")
                                  if node.module else [])
                    # ``from .. import x``: x may be a box or a name of
                    # the package's own ``__init__`` (IMPORT_STAMP).
                    mods = ([mod + [a.name] for a in node.names]
                            if len(mod) == 1 else [mod])
                elif isinstance(node, ast.ImportFrom):
                    mods = [(node.module or "").split(".")]
                else:
                    continue
                for m in mods:
                    if m[0] == "raft_tla_tpu" and len(m) > 1:
                        found.add(m[1])
    boxes = {n[:-3] if n.endswith(".py") else n for n in os.listdir(PKG)}
    return (found & boxes) - {box}


def test_the_table_names_every_box():
    boxes = {n for n in os.listdir(PKG)
             if os.path.isfile(os.path.join(PKG, n, "__init__.py"))}
    assert boxes == set(MAY_IMPORT)


@pytest.mark.parametrize("box", sorted(MAY_IMPORT))
def test_a_box_imports_only_what_it_may(box):
    found = imported_boxes(box)
    assert not found & {"cli", "server", "__main__"}, (
        f"{box}/ imports an entry point")
    extra = found - MAY_IMPORT[box] - BACK_EDGES.get(box, set())
    assert not extra, (
        f"{box}/ imports {sorted(extra)}: a new arrow, and if it points "
        f"up, a new cycle (tests/test_layers.py)")
    # The debt list stays true: a back edge that is gone leaves it.
    gone = BACK_EDGES.get(box, set()) - found
    assert not gone, f"{box}/ no longer imports {sorted(gone)}: drop it " \
                     f"from BACK_EDGES"
