"""Reader ``variant``: what a spec variant's hooks cost a pass, and what
share of a window's successors its families (and a leader's) generate.

``raft_tla_tpu/models/actions2.py`` runs the quorum rule
(``dims.build_quorum()``: ``BecomeLeader``'s guard in stage ``masks``,
``AdvanceCommitIndex``'s rule in ``construct/lane_out``) under a scope
``quorum`` and a variant's extra action families (their guards in
``masks``, their lanes' deltas and successors in ``lane_out``) under a
scope ``extra``, for every dims.  An operation goes to a scope when its
first stage (``readers/stages.py stage_of``) is ``masks``, or is
``construct`` with ``lane_out`` the first part its path names
(``readers/construct.py part_of``), and the path names the scope after
that; a scope entered inside a ``vmap`` is written ``vmap(quorum)``
(``construct.unwrapped``).  Self times over the whole chunk calls
``stages.whole_calls`` takes, per pass, ``masks`` and ``lane_out``
together; the traced run's log prints the two apart.  By the FUSED
operation's own path, as ``construct`` reads its parts: a fusion across
two scopes carries one name.  ``stage_ms.*`` and ``construct_ms.*`` read
what they read: neither name is a stage or a part.

A program without the scopes (the parent of the PR that added them)
names neither, and nothing is reported; so with the counter, which is
``run_end.generated_by_family``: the window's own successors by action
family, from the packed statistics every chunk call returns.

Modes of ``read``:
  scope_ms      self time of the operations under ``scope`` in ms a pass
  family_share  100 x the window's generated by the families named /
                its generated by all
"""

from __future__ import annotations

import numpy as np

import bench_lib as lib

SCOPES = ("quorum", "extra")
SITES = ("masks", "lane_out")


def scope_of(path: str):
    """(site, scope) of an operation under ``masks`` or under
    ``construct/lane_out``; scope None where the path names neither of
    ``SCOPES`` there.  None for any other operation."""
    st = lib.load_module("readers", "stages")
    con = lib.load_module("readers", "construct")
    stage = st.stage_of(path)
    if stage == "masks":
        site = "masks"
    elif stage == "construct" and (con.part_of(path) or (None,))[0] \
            == "lane_out":
        site = "lane_out"
    else:
        return None
    parts = [con.unwrapped(c) for c in path.split("/")[:-1]]
    after = parts[parts.index(site) + 1:]
    return site, next((c for c in after if c in SCOPES), None)


def split(run: dict):
    """{"passes", "ns": {(site, scope or None): ns}} over the whole chunk
    calls of the capture, computed and printed once; None where ``stages``
    reports nothing or the program names neither scope."""
    if "_variant_split" in run:
        return run["_variant_split"]
    run["_variant_split"] = None
    st = lib.load_module("readers", "stages")
    if st.stage_table(run) is None:
        return None
    cap = st.spans_reader().capture(run)
    of = [scope_of(p) for p in cap["op_paths"]]
    ops = np.asarray(cap["ops"], np.int64).reshape(-1, 3)
    ops = ops[np.lexsort((-ops[:, 2], ops[:, 1]))]   # as stages.table
    passes, ns_of = 0, {}
    for lo, hi, n in st.whole_calls(cap, run.get("chunk_program", "chunk")):
        inside = ops[np.searchsorted(ops[:, 1], lo):
                     np.searchsorted(ops[:, 1], hi)].tolist()
        selfs, _leaves = st.self_times(inside)
        if sum(ns for _i, ns in selfs) < st.WHOLE * (hi - lo):
            continue
        passes += n
        for i, ns in selfs:
            if of[i] is not None:
                ns_of[of[i]] = ns_of.get(of[i], 0) + ns
    if not passes or not any(scope for _site, scope in ns_of):
        print("variant: no operation under 'masks' or 'lane_out' names "
              "'quorum' or 'extra' (a program from before the scopes); "
              "nothing reported", flush=True)
        return None
    print("variant: " + "; ".join(
        f"{site}: " + ", ".join(
            f"{scope or '(rest)'} {ns_of.get((site, scope), 0) / 1e6 / passes:.3f}"
            for scope in SCOPES + (None,)) for site in SITES)
        + f" ms a pass over {passes} passes", flush=True)
    run["_variant_split"] = {"passes": passes, "ns": ns_of}
    return run["_variant_split"]


def read(run: dict, mode: str, scope: str = "", families=()):
    if mode == "family_share":
        ends = [e for e in run.get("events") or []
                if e.get("event") == "run_end"]
        own = ends[-1].get("generated_by_family") if ends else None
        if not own or not sum(own.values()):
            return None
        return 100.0 * sum(own.get(f, 0) for f in families) \
            / sum(own.values())
    if mode != "scope_ms":
        raise ValueError(f"variant reader: unknown mode {mode!r}")
    if scope not in SCOPES:
        raise ValueError(f"variant reader: unknown scope {scope!r}")
    tab = split(run)
    if tab is None:
        return None
    return sum(tab["ns"].get((site, scope), 0) for site in SITES) \
        / 1e6 / tab["passes"]
