"""The served cell's controls: the service made to do less than
``benchmark/configs/served3.json`` guarantees, without a switch in the
program.  A run under any of them has to report ``correct: false``.

    python3 benchmark/tests/controls_served.py pick_newest    -- <run.py arguments>
    python3 benchmark/tests/controls_served.py cache_fresh    -- <run.py arguments>
    python3 benchmark/tests/controls_served.py drop_unjournaled -- <run.py arguments>

``pick_newest`` makes the manager's pick newest-first: of the tenants
with a job queued the one served LAST goes first.  ``cache_fresh`` makes
the result cache answer a fresh request: every submit is keyed as if it
had said ``cache: true``.  ``drop_unjournaled`` drops one acknowledged
job (the ninth submit the manager sees: the sixth of the window) before
it is journaled: the client holds an ``ok: true`` and a job id the
service never heard of.

What catches each (``benchmark/traffic/served_loop.py``): ``pick_newest``:
the journal's order of starts is not the reference's (the answers are
all sound).  ``cache_fresh``: jobs the reference calls fresh come back
``cached: true`` (a second ``deep``, a fresh canary), and
``jobs/executed`` falls short of the fresh jobs.  ``drop_unjournaled``:
the client's ``result`` is refused (unknown job), the job never reached
a terminal state, and the journal lacks its lines.

What the cell is blind to: a pick out of turn among tenants that are
never queued together (with three tenants in a closed loop at most two
wait at a pick); a cache that answers across DIFFERENT requests whose
answers happen to be equal; a journal line written and lost after the
window (the run replays the journal once, at its end, from the page
cache: no crash, no ``fsync``; that is ``kill-resume``'s).

On the chip the command runs the cell at its own size; the tests here run
it with ``--rehearsal`` on the CPU.
"""

from __future__ import annotations

import contextlib
import os
import sys


@contextlib.contextmanager
def patched(owner, name: str, value):
    orig = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, orig)


def pick_newest():
    from raft_tla_tpu.serving.manager import JobManager

    def _pick_locked(self):
        """``JobManager._pick_locked`` with ``max`` where it says
        ``min``."""
        while True:
            candidates = [t for t, q in self._queues.items() if q]
            if not candidates:
                return None
            t = max(candidates, key=lambda t: self._tenant_rank[t])
            q = self._queues[t]
            job = None
            while q:
                job = self._jobs.get(q.popleft())
                if job is not None and job["state"] == "queued":
                    break
                job = None
            if not q:
                del self._queues[t]
            if job is not None:
                self._served_seq += 1
                self._tenant_rank[t] = (self._served_seq,
                                        self._tenant_rank[t][1])
                return job

    return patched(JobManager, "_pick_locked", _pick_locked)


def cache_fresh():
    from raft_tla_tpu import server
    orig = server._cache_key_for
    return patched(server, "_cache_key_for",
                   lambda req, inner: orig(dict(req, cache=True), inner))


def drop_unjournaled(nth: int = 9):
    from raft_tla_tpu.serving import jobs as jobs_mod
    from raft_tla_tpu.serving.manager import JobManager
    orig = JobManager.submit
    seen = []

    def submit(self, request, tenant=None, **kw):
        seen.append(1)
        if len(seen) != nth:
            return orig(self, request, tenant, **kw)
        job = jobs_mod.new_job(f"j{nth:06d}-dropped", str(tenant), request)
        return jobs_mod.summarize(job)

    return patched(JobManager, "submit", submit)


CONTROLS = {"pick_newest": pick_newest, "cache_fresh": cache_fresh,
            "drop_unjournaled": drop_unjournaled}


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
