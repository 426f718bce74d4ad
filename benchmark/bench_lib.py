"""What every traffic kind and reader of the benchmark shares: file lookup
by name, the pinned level profiles, the comparison ledger that decides
``correct``, the compile watch, and the bridge from a configuration file's
numbers to the plain reference.

Nothing here measures time; the clocks are in ``run.py`` and the traffic
kinds.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Context:
    """What ``run.py`` hands a traffic kind."""

    args: object            # --workload --seed --seconds --trace
    cell: dict              # the manifest entry + benchmark/traffic/<mix>.json
    config: dict            # benchmark/configs/<config>.json
    tmp: str                # scratch directory, removed at exit
    ledger: "Ledger"
    t_start: float          # perf_counter at process start
    compiles: "CompileWatch"
    trace_dir: str | None   # where a --trace 1 run puts the profile


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module, found by name — how a
    traffic kind or a reader that a later PR adds is picked up without an
    edit here."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"nothing named {name!r} in benchmark/{folder}: expected "
            f"{os.path.relpath(path, ROOT)}")
    modname = f"bench_{folder}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_pinned(name: str) -> dict:
    """``benchmark/pinned/<name>.jsonl`` -> {level: (frontier, distinct,
    generated)}: the plain reference's own level-by-level record."""
    rows = {}
    with open(os.path.join(BENCH_DIR, "pinned", name + ".jsonl"),
              encoding="utf-8") as f:
        for line in f:
            if line.strip():
                r = json.loads(line)
                rows[int(r["level"])] = (int(r["frontier"]),
                                         int(r["distinct"]),
                                         int(r["generated"]))
    return rows


def read_events(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def level_rows(events: list) -> dict:
    """{level: (frontier, distinct, generated)} of one run's
    ``level_complete`` events."""
    return {int(e["level"]): (int(e["frontier_rows"]), int(e["distinct"]),
                              int(e["generated"]))
            for e in events if e["event"] == "level_complete"}


class Ledger:
    """Every number compared, beside its limit; ``correct`` is that none
    failed.  ``attempted``/``failed`` count the comparisons."""

    def __init__(self):
        self.attempted = 0
        self.failed = 0

    def exact(self, what: str, got, want) -> bool:
        """An exact comparison: the limit on the difference is 0."""
        ok = got == want
        self._line(what, got, f"== {want}", ok)
        return ok

    def at_most(self, what: str, got, limit) -> bool:
        ok = got is not None and got <= limit
        self._line(what, got, f"<= {limit}", ok)
        return ok

    def true(self, what: str, cond: bool, detail: str = "") -> bool:
        self._line(what, bool(cond), "is True" + (f" ({detail})"
                                                  if detail else ""),
                   bool(cond))
        return bool(cond)

    def _line(self, what, got, limit, ok) -> None:
        self.attempted += 1
        self.failed += 0 if ok else 1
        print(f"compare {what}: got {got} limit {limit} "
              f"{'ok' if ok else 'FAIL'}", flush=True)

    @property
    def correct(self) -> bool:
        return self.attempted > 0 and self.failed == 0


def compare_levels(ledger: Ledger, got: dict, pinned: dict, levels,
                   what: str) -> None:
    """Each of ``levels`` must be present in ``got`` and equal the pinned
    (frontier, distinct, generated) triple."""
    for lv in levels:
        ledger.exact(f"{what} level {lv} (frontier, distinct, generated)",
                     got.get(lv), pinned.get(lv))


class CompileWatch:
    """Records XLA compiles (and loads from the persistent cache, which
    take the same path) through jax's own monitoring hook, each with the
    name of the jitted function, so a window can show that none of the
    programs it drives compiled inside it.  The engine's trace flush
    slices a device buffer at a different length after every chunk call,
    and each new length is a small compile of its own (``dynamic_slice``);
    those are the program's behaviour, and are counted and reported."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.records = []           # (perf_counter at end, seconds, name)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self._EVENT:
            self.records.append((time.perf_counter(), float(duration),
                                 str(kw.get("fun_name", ""))))

    def between(self, t0: float, t1: float, programs=()) -> dict:
        """Compiles that ended inside [t0, t1]: how many, their seconds,
        and those of the named ``programs`` among them."""
        inside = [(d, n) for (t, d, n) in self.records if t0 <= t <= t1]
        return {"count": len(inside), "seconds": sum(d for d, _n in inside),
                "of_programs": sorted(n for _d, n in inside
                                      if n in programs)}


# The jitted programs a window drives (BFSEngine._chunk, ._ingest): none
# may compile, or load from the cache, inside it.
ENGINE_PROGRAMS = ("chunk", "ingest")


@contextlib.contextmanager
def traced(ctx: Context):
    """Bracket a ``--trace 1`` window in the profiler (Python tracer off);
    a no-op for ``--trace 0``.  The whole window, always: a capture of a
    part of it from a timer thread was tried on ``raft5-deep`` (PR 26) and
    took the profiler longer to hand over than the whole."""
    if not ctx.trace_dir:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def check_window_log(ctx: Context, events: list, t0: float, t1: float) -> dict:
    """What every window's own record has to show: the batch asked for, none
    of the mix's forbidden events, no compile of the engine's programs."""
    ledger = ctx.ledger
    ledger.exact("window ran at the batch asked for",
                 sorted({e["batch"] for e in events
                         if e["event"] == "run_start"}),
                 [ctx.config["batch"]])
    for bad in ctx.cell["forbidden_events"]:
        ledger.exact(f"'{bad}' events in the window",
                     sum(e["event"] == bad for e in events), 0)
    comp = ctx.compiles.between(t0, t1, programs=ENGINE_PROGRAMS)
    print(f"window compiles: {comp['count']} taking {comp['seconds']:.3f}s "
          f"in all (the trace flush's per-call slices)", flush=True)
    ledger.exact("compiles of the engine's programs inside the window",
                 comp["of_programs"], [])
    return comp


# -- the plain reference ------------------------------------------------------

def reference(config: dict):
    """``benchmark/reference`` set up from the numbers the configuration
    file states: ``.dims``, ``.constraint`` and the modules ``.rd``
    (dims), ``.oracle``, ``.pystate``."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    from reference import dims as rd
    from reference import oracle, pystate
    c = config["constants"]
    bounds = rd.Bounds(max_term=c.get("MaxTerm"),
                       max_log_len=c.get("MaxLogLen"),
                       max_msg_count=c.get("MaxMsgCount"))
    return types.SimpleNamespace(
        dims=rd.RaftDims(n_servers=len(c["Server"]),
                         n_values=len(c["Value"])),
        constraint=rd.constraint_py(bounds), rd=rd, oracle=oracle,
        pystate=pystate)


def to_reference_state(s, pystate_mod):
    """A state the program decoded, as the reference's own ``PyState``:
    plain tuples and frozensets copied field by field."""
    return pystate_mod.PyState(**{
        f.name: getattr(s, f.name)
        for f in dataclasses.fields(pystate_mod.PyState)})


def write_cfg(config: dict, directory: str) -> str:
    """The configuration's TLC ``.cfg`` text, as the file states it,
    written where ``utils.cfg.load_config`` can read it."""
    path = os.path.join(directory, config["cfg_name"])
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(config["cfg_text"]) + "\n")
    return path
