"""Reader ``setup``: ``setup_s`` in the eight parts the program itself
recorded, from the ``process`` field of the FIRST ``run_start`` of the
window's own run-event log (``run["events"]``).

The program keeps one record a process from the package's import on
(``raft_tla_tpu/obs/metrics.py ProcessRecord``): marks in seconds since
the process started, the seconds of every closed span by name, and jax's
trace / lower / compile-or-cache-load events in self time by program.
Every ``run_start`` carries it as it stands.  The harness stops the
``setup_s`` clock on the line before the call that emits the window's
first ``run_start``, so ``process.age_s`` there is the program's own
reading of ``setup_s``, and the eight parts are a partition of it:

  ready_s        mark ``engine_begin`` (entry of the first
                 ``make_engine`` / ``make_swarm_engine``), less the jit
                 seconds before it: the interpreter, the imports of jax
                 and the package, the chip's start-up, the cfg
  make_engine_s  seconds of every ``make_engine`` span so far, net of jit
  trace_s        ``jit.trace`` seconds: jaxprs traced (self time)
  lower_s        ``jit.lower`` seconds: jaxprs lowered to modules
  cache_load_s   ``jit.load`` seconds: executables read back from the
                 persistent compile cache
  compile_s      ``jit.compile`` seconds: backend compiles; in a warm
                 process the programs the cache never keeps
  runs_s         seconds of every closed ``run`` scope so far (the walk,
                 the warm resume, the first hunt), net of jit
  outside_s      ``age_s`` less the seven above: what the process did
                 between the program's spans (the harness's snapshot
                 load, digests, replayed sample; a traced run's
                 ``start_trace``)

The first computation prints the marks, the partition beside the
harness's own ``setup_s`` of the same run, the programs with most jit
seconds and the phases of the earlier runs.  A program without the
record (the parent of the PR that added it) leaves nothing to read and
every mode returns None.
"""

from __future__ import annotations

PARTS = ("ready_s", "make_engine_s", "trace_s", "lower_s", "cache_load_s",
         "compile_s", "runs_s", "outside_s")


def process_of(run: dict):
    """The ``process`` record of the window's first ``run_start``."""
    for e in run.get("events") or []:
        if e.get("event") == "run_start":
            return e.get("process")
    return None


def partition(process: dict) -> dict:
    """{part: seconds} of ``PARTS``; they sum to ``process["age_s"]``."""
    jit, runs = process["jit"], process["runs"]
    begin = process["marks"].get("engine_begin")
    parts = {
        "ready_s": (0.0 if begin is None else
                    max(begin - process.get("jit_before_engine_s", 0.0),
                        0.0)),
        "make_engine_s": runs["make_engine_s"],
        "trace_s": jit["trace"][1], "lower_s": jit["lower"][1],
        "cache_load_s": jit["load"][1], "compile_s": jit["compile"][1],
        "runs_s": runs["run_s"]}
    parts["outside_s"] = process["age_s"] - sum(parts.values())
    return parts


def describe(process: dict, parts: dict, harness_setup_s) -> str:
    jit, runs = process["jit"], process["runs"]
    marks = sorted(process["marks"].items(), key=lambda kv: kv[1])
    lines = [
        f"setup by the program's own record: age {process['age_s']:.3f}s "
        f"at the window's run_start; the harness's setup_s "
        + (f"{harness_setup_s:.3f}s" if harness_setup_s is not None
           else "not given"),
        "  marks (s since the process started): "
        + ", ".join(f"{k} {v:.3f}" for k, v in marks),
        "  partition: " + ", ".join(
            f"{k[:-2]} {parts[k]:.3f}" for k in PARTS),
        "  jit (events, self seconds): " + ", ".join(
            f"{s} {n} {sec:.3f}" for s, (n, sec) in jit.items())
        + f"; before engine_begin "
          f"{process.get('jit_before_engine_s', 0.0):.3f}; of load, the "
          f"cache's own read {process['cache']['retrieval_s']:.3f}; "
          f"compiles the cache kept {process['cache']['stored']}",
        "  compiled, not loaded (program, compiles, seconds): " + (
            ", ".join(f"{n} {k} {s:.3f}"
                      for n, k, s in process.get("compiled", []))
            or "none"),
        f"  runs so far: {runs['count']} in {runs['run_s']:.3f}s net of "
        f"jit; phases: " + ", ".join(
            f"{k} {v:.3f}" for k, v in runs["phases"].items()),
        "  programs by jit seconds (trace, lower, backend, cache, last "
        "span):"]
    for p in process["programs"]:
        lines.append(f"    {p['name']}: {p['trace_s']:.3f} "
                     f"{p['lower_s']:.3f} {p['backend_s']:.3f} "
                     f"{p['cache']} {p.get('span')}")
    return "\n".join(lines)


def read(run: dict, part: str):
    if part not in PARTS:
        raise ValueError(f"setup reader: unknown part {part!r}")
    parts = run.get("_setup_parts")
    if parts is None:
        process = process_of(run)
        if not process:
            return None
        parts = run["_setup_parts"] = partition(process)
        print(describe(process, parts,
                       (run.get("end_to_end") or {}).get("setup_s")),
              flush=True)
    return parts[part]
