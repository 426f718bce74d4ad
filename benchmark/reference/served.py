"""The plain reference of the served deployment (``served3``): what a
checker service with ONE executor, a fingerprint-keyed result cache and
the rule "least-recently-served tenant first, FIFO within a tenant" must
do with a given record of submits, and what each of its answers must
hold.  Imports nothing of the program.

(a) The schedule.  ``schedule(log)`` replays the service's record in the
order it was written.  An entry is a dict:

  {"ev": "submit", "job": id, "tenant": t, "key": k}   acknowledged; ``k``
        is the request's fingerprint where it may be answered from the
        cache (``cache: true``), None where it may not
  {"ev": "cancel", "job": id}      a queued job taken back
  {"ev": "pick"}                   the executor took its next job
  {"ev": "end", "job": id, "ok": b}   the job the executor held ended

and the answer is ``{"starts": [ids in the order the executor must have
started them], "hits": {ids the cache must have answered without a
run}}``.  The rule, as ``serving/manager.py``'s docstring states it: of
the tenants with a job queued, the one served longest ago goes first (a
tenant never served goes before every tenant that was, the one that
joined first before the others); within a tenant the oldest job.  A hit
takes its tenant's turn like any job.  The cache stores the answer of a
job that carried a key, was run, and ended ok; a later job with that key
is a hit.  (The service's cache is bounded, 128 keys, least recently
used out first: a record with more distinct keys than that is outside
this reference.)  A ``pick`` with nothing queued is a fault of the
record and raises.

(b) The answers.  ``levels_of(doc)`` reads an exhaustive answer's
per-level table as ``{level: (frontier, distinct, generated)}``, the
shape of ``benchmark/pinned/<name>.jsonl``; ``trace_faults(trace, ...)``
holds a counterexample to the interpreter beside this file: it starts at
the reference's initial state and every step is a transition
``oracle.successors`` allows, compared in the rendering both sides
share (``pystate.format_state``), every state but the last holds no
leader and passes the constraint, the last holds a leader.
"""

from __future__ import annotations


def schedule(log) -> dict:
    queues = {}             # tenant -> [job ids], oldest first
    rank = {}               # tenant -> (served, joined)
    tenant_of, key_of = {}, {}
    served = joined = 0
    stored, running = set(), None
    starts, hits = [], set()
    for n, e in enumerate(log):
        ev = e["ev"]
        if ev == "submit":
            t = e["tenant"]
            tenant_of[e["job"]], key_of[e["job"]] = t, e.get("key")
            queues.setdefault(t, []).append(e["job"])
            if t not in rank:
                joined += 1
                rank[t] = (0, joined)
        elif ev == "cancel":
            q = queues.get(tenant_of.get(e["job"]), [])
            if e["job"] in q:
                q.remove(e["job"])
        elif ev == "pick":
            waiting = [t for t, q in queues.items() if q]
            if not waiting:
                raise ValueError(f"entry {n}: a pick with nothing queued")
            t = min(waiting, key=lambda t: rank[t])
            job = queues[t].pop(0)
            served += 1
            rank[t] = (served, rank[t][1])
            starts.append(job)
            running = job
            if key_of[job] is not None and key_of[job] in stored:
                hits.add(job)
        elif ev == "end":
            job = e["job"]
            if (e.get("ok") and job == running and job not in hits
                    and key_of.get(job) is not None):
                stored.add(key_of[job])
            running = None
        else:
            raise ValueError(f"entry {n}: unknown event {ev!r}")
    return {"starts": starts, "hits": hits}


def levels_of(doc: dict) -> dict:
    """{level: (frontier, distinct, generated)} of an exhaustive answer's
    own per-level table (``report.levels``)."""
    return {int(r["level"]): (int(r["frontier"]), int(r["distinct"]),
                              int(r["generated"]))
            for r in (doc.get("report") or {}).get("levels") or []}


def trace_faults(trace: list, dims, constraint, oracle, pystate,
                 no_leader) -> list:
    """Faults of one counterexample ``[{"action": .., "state": <text>},
    ..]`` against the interpreter; [] where it is a legal election."""
    if not trace:
        return ["no trace"]
    cur = pystate.init_state(dims)
    if trace[0].get("action") != "Init" \
            or pystate.format_state(cur, dims) != trace[0].get("state"):
        return ["the trace does not start at the reference's initial state"]
    faults = []
    for n, step in enumerate(trace[1:], 1):
        if not (no_leader(cur, dims) and constraint(cur, dims)):
            faults.append(f"state {n - 1} holds a leader or lies outside "
                          f"the constraint, and was expanded")
        nxt = [s for _a, s in oracle.successors(cur, dims)
               if pystate.format_state(s, dims) == step.get("state")]
        if not nxt:
            return faults + [f"step {n} ({step.get('action')}) is no "
                             f"transition of the reference"]
        cur = nxt[0]
    if no_leader(cur, dims):
        faults.append("the last state holds no leader")
    return faults
