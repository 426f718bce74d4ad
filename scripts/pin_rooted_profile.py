#!/usr/bin/env python3
"""Make ``benchmark/pinned/<pinned>.jsonl`` of a configuration whose cells
start from roots other than ``Init`` (traffic kind ``rooted_window``): the
plain reference's level profile FROM THOSE ROOTS.

    python3 scripts/pin_rooted_profile.py reconfig3 window-reconfig-l8 --levels 10

Breadth-first search with TLC's constraint semantics (a state outside the
CONSTRAINT is generated and counted, never expanded) by
``benchmark/reference`` alone: nothing of the program is imported.  One
line a level: ``frontier``, cumulative ``distinct`` and ``generated`` as the
other pins have them, and ``generated_by_family``, the cumulative count of
successors by action family (what the engine's ``action_counts`` are held
to).  Level 0 is the roots.

The seen-set keeps a 16-byte blake2b digest of each state's canonical
form (the bag sorted), not the state: 11 M states of level 10 are 1 GB so
and 25 GB whole.  Levels 0-9 were held equal to ``oracle.bfs``'s, which
keeps the states (ISSUE 39's numbers; ``--whole`` runs that way here).
About 20 minutes for ``reconfig3`` through level 10 on one core.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)


def digest(s) -> bytes:
    # repr, not pickle: pickle writes an object it has met before as a
    # reference, so equal states would give different bytes.
    return hashlib.blake2b(repr((
        s.current_term, s.role, s.voted_for, s.log, s.commit_index,
        s.votes_responded, s.votes_granted, s.next_index, s.match_index,
        sorted(s.messages))).encode(), digest_size=16).digest()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("mix")
    ap.add_argument("--levels", type=int, default=10)
    ap.add_argument("--whole", action="store_true",
                    help="keep whole states in the seen-set, not digests")
    ap.add_argument("--out", help="write here, not under benchmark/pinned")
    args = ap.parse_args(argv)

    from reference import dims as rd
    from reference import oracle
    with open(os.path.join(BENCH, "configs", args.config + ".json"),
              encoding="utf-8") as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", args.mix + ".json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    modname, fn = mix["roots"].split(":")
    mod = importlib.import_module(modname)
    dims = mod.reference_dims(config)
    constraint = rd.constraint_py(mod.reference_bounds(config))
    key = (lambda s: s) if args.whole else digest

    frontier = []
    seen = set()
    for root in getattr(mod, fn)(dims):
        k = key(root.state)
        if k not in seen:
            seen.add(k)
            if constraint(root.state, dims):
                frontier.append(root.state)
    generated = 0
    by_family = dict.fromkeys(mod.FAMILY_NAMES, 0)
    out = args.out or os.path.join(BENCH, "pinned",
                                   config["pinned"] + ".jsonl")
    t0 = time.time()
    with open(out, "w", encoding="utf-8") as f:
        for level in range(args.levels + 1):
            line = {"config": config["name"], "level": level,
                    "frontier": len(frontier), "distinct": len(seen),
                    "generated": generated,
                    "generated_by_family": dict(by_family)}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(f"{time.time() - t0:8.1f}s {line}", flush=True)
            if level == args.levels:
                break
            nxt = []
            for s in frontier:
                for (family, _params), t in oracle.successors(s, dims):
                    generated += 1
                    by_family[mod.FAMILY_NAMES[family]] += 1
                    k = key(t)
                    if k not in seen:
                        seen.add(k)
                        if constraint(t, dims):
                            nxt.append(t)
            frontier = nxt
    return 0


if __name__ == "__main__":
    sys.exit(main())
