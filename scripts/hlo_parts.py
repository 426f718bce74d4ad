#!/usr/bin/env python3
"""A cfg's chunk compiled for a described v5e, read by part: no chip needed.

    python3 scripts/hlo_parts.py <cfg name> [--batch 64] [--root <checkout>]
                                 [--part invariants] [--scope extra]
                                 [--top 10] [--out f.txt]

Builds ``BFSEngine`` for ``configs/<cfg name>.cfg`` as ``make_engine``
resolves it (at ``--batch 2048`` with the deep cells' pools: a minute of
compile; 64: a quarter), compiles its chunk program for one chip of a
described ``v5e:2x2`` (on-chip-measurement guide, section 2) and prints,
for each part of ``construct`` (``engine/chunk.py CONSTRUCT_PARTS``; by
the FUSED operation's own ``op_name``, as ``benchmark/readers/
construct.py`` attributes device time), the operations, the sum of XLA's
own ``estimated_cycles``, and how much of both lies in operations that
write a tensor of the K lanes with another axis minor-most (``{2,1,0}``:
a row a lane, an axis of N or L padded to a 128-wide vector).  Then the
``--top`` operations of ``--part``.  With ``--scope <name>``, the same
three numbers for the operations of ``--part`` whose path names that
scope after the part (``models/actions2.py``'s ``quorum`` and ``extra``,
as ``benchmark/readers/variant.py scope_of`` reads them; a scope entered
inside a ``vmap`` is written ``vmap(extra)``), and ``--top`` lists those
alone: what a variant's hooks cost ``lane_out``, before and after a
change, in one command each (``--root`` for the other checkout).

**A compiler's estimate, not a time**: the two fusions that were
``TypeOK``'s 3.9 ms a pass until PR 38 read 6.29 M cycles here (1.7x
high at 940 MHz).  What it is good for is the ORDER of operations and
their layouts before a chip run is spent (PERF.md section 6, PR 38).
"""

import argparse
import collections
import os
import re
import sys

PARTS = ("parents", "lane_out", "constraint", "flatten", "invariants")


def unwrapped(component: str) -> str:
    """``vmap(extra)`` -> ``extra`` (``benchmark/readers/construct.py``)."""
    while component.startswith("vmap("):
        component = component[5:]
    return component.rstrip(")")


def scopes_under(rest: str, part: str) -> list:
    """The scope components an operation's path (below ``/construct/``)
    names after ``part``; the last component is the operation's own."""
    comps = [unwrapped(c) for c in rest.split("/")[:-1]]
    return comps[comps.index(part) + 1:] if part in comps else []


def compiled_text(root: str, cfg: str, batch: int) -> tuple:
    sys.path.insert(0, root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import make_engine
    from raft_tla_tpu.utils.cfg import load_config

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    big = batch >= 1024
    eng = make_engine(
        load_config(os.path.join(root, f"configs/{cfg}.cfg")),
        EngineConfig(batch=batch,
                     queue_capacity=1 << (21 if big else 14),
                     seen_capacity=1 << (24 if big else 17)))
    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng.chunk_avals())
    return jax.jit(eng._chunk).lower(*placed).compile().as_text(), eng._K


def tally(text: str, lanes: int, part: str, scope: str = None) -> tuple:
    """``(ops, cycles, rows)`` of an optimised chunk's text: two counters
    keyed ``(part of construct or "rest" or "scope", "all" or
    "lanes-major")``, ``"scope"`` being the operations of ``part`` under
    ``scope``; and a row for each of those (of ``part``'s, with no
    ``scope``): ``(cycles, name, "lanes-major" or "", result type, path
    below construct)``."""
    ops, cycles = collections.Counter(), collections.Counter()
    rows = []
    for line in text.splitlines():
        head = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                        r"([\w\-]+)\(", line)
        cost = re.search(r'"estimated_cycles":"(\d+)"', line)
        path = re.search(r'op_name="([^"]*/construct/[^"]*)"', line)
        if not (head and cost and path):
            continue
        rest = path.group(1).split("/construct/", 1)[1]
        of = next((p for p in rest.split("/") if p in PARTS), "rest")
        scoped = of == part and scope in scopes_under(rest, of)
        major = any(minor != "0" for minor in re.findall(
            rf"\[{lanes},\d+(?:,\d+)*\]\{{(\d+)", head.group(2)))
        for layout in ("all", "lanes-major")[:1 + major]:
            for key in (of, "scope")[:1 + scoped]:
                ops[key, layout] += 1
                cycles[key, layout] += int(cost.group(1))
        if scoped if scope else of == part:
            rows.append((int(cost.group(1)), head.group(1),
                         "lanes-major" if major else "",
                         head.group(2)[:120], rest))
    return ops, cycles, rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cfg")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--root", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    ap.add_argument("--part", default="invariants")
    ap.add_argument("--scope", help="a scope under --part, read apart")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--out", help="keep the optimised text here")
    args = ap.parse_args()
    text, lanes = compiled_text(os.path.abspath(args.root), args.cfg,
                                args.batch)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
    ops, cycles, shown = tally(text, lanes, args.part, args.scope)
    print(f"{args.cfg} batch {args.batch} K {lanes}: construct "
          f"{sum(cycles[p, 'all'] for p in (*PARTS, 'rest'))} "
          f"estimated cycles")
    for part in (*PARTS, "rest"):
        print(f"  {part:11s} {ops[part, 'all']:4d} operations "
              f"{cycles[part, 'all']:10d} cycles; writing lanes-major "
              f"{ops[part, 'lanes-major']:3d} and "
              f"{cycles[part, 'lanes-major']:10d}")
    if args.scope:
        print(f"  {args.part}/{args.scope}: {ops['scope', 'all']} operations "
              f"{cycles['scope', 'all']} cycles; writing lanes-major "
              f"{ops['scope', 'lanes-major']} and "
              f"{cycles['scope', 'lanes-major']}")
    for row in sorted(shown, reverse=True)[:args.top]:
        print("   ", *row)


if __name__ == "__main__":
    main()
