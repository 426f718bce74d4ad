"""Plain reference of the swarm tier: one random walker over the spec, and
the rules a walk the program reports is held to.

TLC's simulation mode (``tlc -simulate``) as the program's swarm runs it,
written straightforwardly over ``oracle.successors`` and Python's
``random``; it imports nothing of the program.  A trace starts at the
initial state and draws a random subset of the ten action families
(each kept with probability 1/2).  A step takes, uniformly, one of the
enabled action instances of the kept families, or of all families where
none of the kept ones is enabled; the invariant is evaluated on every
chosen successor; the trace ends (and the walker restarts at the initial
state with a new subset) on a dead end, on a successor outside the
constraint, on a successor that is among the last ``RING`` states the
trace accepted (the root is not among them until it is reached again),
and when it has ``depth`` accepted steps.

It does NOT reproduce the program's bits.  The program draws from a
counter hash of (seed, walk, step) over its own numbering of the action
instances, which for the three message families are indices of message
SLOTS, a layout of the program's; this walker draws from ``random`` over
the oracle's own list.  What the two share is the rules, so what is
compared is (a) every walk the program reports, step by step, against
them (``check_transcript``), and (b) distributions over many traces
(``tests/test_swarm_deployment.py``).  Packing overflow, which the
program also restarts on, cannot occur inside the constraint of the
configurations that use this and is not modelled.
"""

from __future__ import annotations

import random
from typing import Callable, List, Optional, Tuple

from . import oracle
from .dims import RaftDims
from .pystate import PyState, init_state

RING = 16
N_FAMILIES = 10
# Why a trace ended, in the order the rules are applied to a step.
REASONS = ("deadend", "violation", "constraint", "revisit", "depth")


def walk_trace(dims: RaftDims, rng: random.Random, *, depth: int,
               constraint: Optional[Callable] = None,
               invariant: Optional[Callable] = None, ring: int = RING
               ) -> Tuple[List[PyState], List[int], str]:
    """One trace: (states from the initial one, the family of each step
    taken, why it ended).  A step that ends the trace without being
    accepted (``violation``, ``constraint``, ``revisit``) is the last of
    both lists."""
    keep = rng.getrandbits(N_FAMILIES)
    states, families = [init_state(dims)], []
    while True:
        succ = oracle.successors(states[-1], dims)
        if not succ:
            return states, families, "deadend"
        kept = [x for x in succ if keep >> x[0][0] & 1]
        (fam, _params), nxt = rng.choice(kept or succ)
        recent = states[1:][-ring:]
        states.append(nxt)
        families.append(fam)
        if invariant is not None and not invariant(nxt, dims):
            return states, families, "violation"
        if constraint is not None and not constraint(nxt, dims):
            return states, families, "constraint"
        if nxt in recent:
            return states, families, "revisit"
        if len(families) >= depth:
            return states, families, "depth"


def census(dims: RaftDims, seed: int, traces: int, **rules) -> dict:
    """{reason: traces that ended so, "steps": steps taken} over
    ``traces`` traces of one seeded walker."""
    rng = random.Random(seed)
    out = dict.fromkeys(REASONS, 0)
    out["steps"] = 0
    for _ in range(traces):
        _states, families, why = walk_trace(dims, rng, **rules)
        out[why] += 1
        # A dead end takes a lockstep step too, and chooses nothing.
        out["steps"] += len(families) + (why == "deadend")
    return out


def check_transcript(root: PyState, actions: List[int],
                     states: List[PyState], *, dims: RaftDims, depth: int,
                     constraint: Optional[Callable] = None,
                     ring: int = RING, whole: bool = True) -> List[str]:
    """The faults of one walk the program reports (none: it keeps the
    rules).  ``root`` is where its current trace started, ``actions`` the
    family of each step since, ``states`` what each step led to.
    ``whole``: every step was accepted (a walker read back between two
    steps); else the last step is the one that ended the trace (a
    reported violation), and only the steps before it are held to the
    constraint and the ring."""
    faults = []
    if root != init_state(dims):
        faults.append("the trace does not start at the initial state")
    if len(actions) != len(states):
        return faults + [f"{len(actions)} actions for {len(states)} states"]
    accepted = len(states) if whole else len(states) - 1
    if accepted >= depth if whole else accepted > depth - 1:
        faults.append(f"{accepted} accepted steps, the depth bound is "
                      f"{depth}")
    path = [root] + list(states)
    for i, (fam, prev, nxt) in enumerate(zip(actions, path, path[1:])):
        if not any(a[0] == fam and t == nxt
                   for a, t in oracle.successors(prev, dims)):
            faults.append(f"step {i + 1}: no enabled instance of family "
                          f"{fam} leads there")
        if i >= accepted:
            continue
        if constraint is not None and not constraint(nxt, dims):
            faults.append(f"step {i + 1}: accepted outside the constraint")
        if nxt in path[1:i + 1][-ring:]:
            faults.append(f"step {i + 1}: accepted though among the last "
                          f"{ring} states of the trace")
    return faults
