"""Front-end: resolved cfg -> engine run (the ``tlc <cfg> <module>`` path).

Maps a ``CheckSetup`` (utils/cfg.py) onto the BFS engine: invariant names
resolve through the registry below (TypeOK today; the raft.tla dead-region
safety suite registers here as it lands), constraint names resolve to
predicate builders (``BoundedSpace`` reads the MaxTerm/MaxLogLen/MaxMsgCount
constants), ``Init <- SmokeInit`` selects the randomized smoke roots
(Smokeraft.cfg:43-44), and StopAfter budgets land in EngineConfig.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..models import smoke
from ..models.dims import RaftDims
from ..models.invariants import (Bounds, build_constraint,
                                 invariant_registry)
from ..models.pystate import PyState, init_state
from ..obs.metrics import process_span
from ..utils.cfg import CheckSetup, load_config
from .bfs import BFSEngine, EngineConfig, EngineResult
from .chunk import named_stage

# name -> builder(dims) -> kernel(state)->bool.  TypeOK (raft.tla:482-492)
# plus the whole dead-region safety suite (raft.tla:896-1180; SURVEY §2.3),
# checkable by naming them as INVARIANT in any cfg.  The registry itself
# lives in models/invariants.py (invariant_registry) so the analyzer's
# POR visibility condition and this cfg resolution can never drift.
INVARIANT_REGISTRY: Dict[str, Callable[[RaftDims], Callable]] = \
    invariant_registry()

CONSTRAINT_REGISTRY: Dict[str, Callable[[RaftDims, Bounds], Callable]] = {
    "BoundedSpace": build_constraint,
}


def resolve_invariants(setup: CheckSetup) -> Dict[str, Callable]:
    """The cfg's INVARIANTS in cfg order, each kernel traced under a
    scope of its own name (a capture then says which predicate an
    operation of any engine's program belongs to)."""
    invs = {}
    for name in setup.invariants:
        if name not in INVARIANT_REGISTRY:
            raise ValueError(
                f"unknown INVARIANT {name!r}; registered: "
                f"{sorted(INVARIANT_REGISTRY)}")
        invs[name] = named_stage(name, INVARIANT_REGISTRY[name](setup.dims))
    return invs


def resolve_constraint(setup: CheckSetup) -> Optional[Callable]:
    constraint = None
    for name in setup.constraints:
        if name not in CONSTRAINT_REGISTRY:
            raise ValueError(
                f"unknown CONSTRAINT {name!r}; registered: "
                f"{sorted(CONSTRAINT_REGISTRY)}")
        if constraint is not None:
            raise ValueError("multiple constraints not yet supported")
        constraint = CONSTRAINT_REGISTRY[name](setup.dims, setup.bounds)
    return constraint


def engine_config_from_backend(setup: CheckSetup) -> EngineConfig:
    """EngineConfig seeded from the cfg's ``\\* TPU:`` backend directives
    (utils/cfg.py).  Used whenever the caller does not supply an explicit
    EngineConfig, so the precedence chain (caller > cfg directive >
    built-in default) holds for the API entry points, not just the CLI."""
    be = setup.backend
    return EngineConfig(
        batch=be.get("BATCH", EngineConfig.batch),
        queue_capacity=be.get("QUEUE_CAPACITY", EngineConfig.queue_capacity),
        seen_capacity=be.get("SEEN_CAPACITY", EngineConfig.seen_capacity),
        checkpoint_dir=be.get("CHECKPOINT_DIR"),
        checkpoint_every=be.get("CHECKPOINT_EVERY",
                                EngineConfig.checkpoint_every),
        checkpoint_interval_seconds=float(
            be.get("CHECKPOINT_INTERVAL",
                   EngineConfig.checkpoint_interval_seconds)),
        keep_checkpoints=be.get("KEEP_CHECKPOINTS"),
        spill_dir=be.get("SPILL_DIR"),
        trace_dir=be.get("TRACE_DIR"),
        events_out=be.get("EVENTS_OUT"),
        trace_out=be.get("TRACE_OUT"),
        xla_profile_chunks=be.get("XLA_PROFILE"),
        pipeline=be.get("PIPELINE", EngineConfig.pipeline),
        por=bool(be.get("POR", False)),
        por_table=be.get("POR_TABLE"),
        statespace_report=bool(be.get("REPORT", True)),
        counterexample_dir=be.get("COUNTEREXAMPLE_DIR"))


@process_span("make_engine", "engine_begin", "engine_built", kind="bfs")
def make_engine(setup: CheckSetup,
                engine_config: Optional[EngineConfig] = None,
                engine_cls=None):
    """Build a checker engine with the cfg-file fallbacks applied
    (CHECK_DEADLOCK, StopAfter budgets).  ``engine_cls`` selects the
    implementation — BFSEngine (default), parallel.mesh.MeshBFSEngine,
    or the string ``"auto"`` (mesh iff running on more than one
    accelerator device, e.g. a v5e-8 slice) — so every entry point
    resolves the engine and config identically."""
    import dataclasses as _dc
    if engine_cls == "auto":
        import jax
        devs = jax.devices()
        if len(devs) > 1 and devs[0].platform != "cpu":
            from ..parallel.mesh import MeshBFSEngine
            engine_cls = MeshBFSEngine
        else:
            engine_cls = None
    base = engine_config or engine_config_from_backend(setup)
    cfg = _dc.replace(          # never mutate the caller's config
        base,
        check_deadlock=(base.check_deadlock
                        if base.check_deadlock is not None
                        else setup.check_deadlock),
        max_seconds=(base.max_seconds if base.max_seconds is not None
                     else setup.max_seconds),
        max_diameter=(base.max_diameter if base.max_diameter is not None
                      else setup.max_diameter),
        exit_conditions=(base.exit_conditions or setup.exit_conditions))
    cls = engine_cls or BFSEngine
    return cls(setup.dims, invariants=resolve_invariants(setup),
               constraint=resolve_constraint(setup), config=cfg)


def swarm_slice_width(walks: int, hunt: bool = True) -> int:
    """Lanes of one dispatch of the walk chunk where neither the caller
    nor the cfg's ``BATCH`` says: all ``walks`` in one slice, but with
    the hunt observatory on no more than keeps its (lanes x lanes)
    same-fingerprint prior, four bytes a cell with its temporaries,
    inside a sixteenth of the device's memory (16,384 lanes on a 16 GB
    chip).  A power of two, so the last slice is the only narrow one."""
    import jax
    if not hunt:
        return walks
    stats = jax.devices()[0].memory_stats() or {}
    budget = int(stats.get("bytes_limit", 16 << 30)) // 16
    lanes = 1 << (max(int((budget // 4) ** 0.5), 1).bit_length() - 1)
    return min(walks, lanes)


@process_span("make_engine", "engine_begin", "engine_built", kind="swarm")
def make_swarm_engine(setup: CheckSetup, *, walks: Optional[int] = None,
                      max_depth: Optional[int] = None,
                      batch: Optional[int] = None,
                      pipeline: Optional[str] = None, **engine_kwargs):
    """Build the swarm tier's engine (engine/swarm.py) with the cfg-file
    fallbacks applied, so every entry point — ``check --mode swarm``,
    the server's swarm branch, ``bench.py``'s ``BENCH_MODE=swarm``, the
    benchmark — resolves it identically: each of ``walks`` (else the
    cfg's ``WALKS``, else 1024), ``max_depth`` (else the companion
    module's depth budget, else 128), ``batch`` (the slice width: else
    the cfg's ``BATCH``, else ``swarm_slice_width``) and ``pipeline``
    (else ``PIPELINE``, else auto) is the caller's where given.
    ``engine_kwargs`` go to ``SwarmEngine`` as they are."""
    from .swarm import SwarmEngine
    be = setup.backend
    walks = int(walks if walks is not None else be.get("WALKS", 1024))
    if batch is None:
        batch = be.get("BATCH")
    if batch is None:
        batch = swarm_slice_width(walks, engine_kwargs.get("hunt", True))
    return SwarmEngine(
        setup.dims, invariants=resolve_invariants(setup),
        constraint=resolve_constraint(setup), walks=walks,
        max_depth=int(max_depth or setup.max_diameter or 128),
        batch=min(int(batch), walks),
        pipeline=pipeline or be.get("PIPELINE", "auto"), **engine_kwargs)


def initial_states(setup: CheckSetup, seed: int = 0) -> List[PyState]:
    if setup.smoke:
        # The draw of the nine k-subsets and the bag, and their product:
        # what a smoke check pays on the host before its first state.
        with process_span("smoke_roots", k=setup.smoke_k, seed=seed):
            return smoke.smoke_init_states(setup.dims, k=setup.smoke_k,
                                           seed=seed)
    return [init_state(setup.dims)]


def path_to_state(dims: RaftDims, target: PyState,
                  constraint: Optional[Callable] = None,
                  init_states: Optional[List[PyState]] = None,
                  config: Optional[EngineConfig] = None):
    """Minimal action path from Init to ``target`` — the counterexample
    extractor for runs that had no trace store (multi-host runs record no
    traces; their Violation still carries the concrete state).  Runs a
    single-host BFS with an injected "never reaches target" invariant and
    replays the hit: BFS order makes the result a minimal-depth path.

    Returns ``[(grid_index, PyState), ...]`` (root first, grid_index -1
    for the root) — pretty-print actions with ``dims.describe_instance``.
    Raises if ``target`` is unreachable inside the constraint bounds."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from ..models.schema import encode_state
    from ..ops.fingerprint import build_fingerprint
    fingerprint = build_fingerprint(dims)
    thi, tlo = (int(x) for x in fingerprint(encode_state(target, dims)))

    roots = init_states or [init_state(dims)]
    if target in roots:
        return [(-1, target)]           # trivial path: target IS a root

    def not_target(st):
        h, l = fingerprint(st)
        return ~((h == jnp.uint32(thi)) & (l == jnp.uint32(tlo)))

    # The extractor needs its own trace store regardless of how the
    # original (possibly trace-less multi-host) run was configured, and
    # only cares about reachability — a reachable dead-end state at a
    # shallower level must not abort the search.
    cfg = _dc.replace(config or EngineConfig(),
                      record_trace=True, check_deadlock=False)
    eng = BFSEngine(dims, invariants={"__NotTarget": not_target},
                    constraint=constraint, config=cfg)
    res = eng.run(roots)
    if res.violation is None:
        raise ValueError(
            f"target state unreachable within the explored space "
            f"({res.distinct} states, stop: {res.stop_reason})")
    assert res.violation.state == target, \
        "fingerprint collision: matched state differs from target"
    return eng.replay(res.violation.fingerprint)


def run_check(cfg_path: str, engine_config: Optional[EngineConfig] = None,
              seed: int = 0, max_log: Optional[int] = None,
              n_msg_slots: Optional[int] = None) -> EngineResult:
    """One-call path: parse cfg, build engine, run.  The reference configs
    (/root/reference/MCraft.cfg, Smokeraft.cfg) run unmodified."""
    setup = load_config(cfg_path, max_log=max_log, n_msg_slots=n_msg_slots)
    engine = make_engine(setup, engine_config)
    res = engine.run(initial_states(setup, seed=seed))
    res.engine = engine
    return res


def format_result(res: EngineResult) -> str:
    lines = [
        f"distinct states    {res.distinct}",
        f"states generated   {res.generated}",
        f"diameter           {res.diameter}",
        f"stop reason        {res.stop_reason}",
        f"wall seconds       {res.wall_seconds:.2f}",
        f"states/sec         {res.states_per_second:.0f}",
    ]
    if res.report:
        col = res.report["collision"]
        lines.append(
            f"fp collision prob  {col['calculated']:.2e} calculated "
            f"(optimistic); {col['observed_dual_key']} observed")
        peak = res.report.get("frontier_peak")
        if peak:
            lines.append(f"widest level       {peak['level']} "
                         f"({peak['frontier']:,} states)")
    if res.pipeline:
        lines.append(f"pipeline           {res.pipeline}")
    if res.action_counts:
        lines.append("generated by action family:")
        for name, c in sorted(res.action_counts.items(),
                              key=lambda kv: -kv[1]):
            lines.append(f"  {name:22s} {c}")
    if res.growth_stalls:
        total = sum(s for _c, s in res.growth_stalls)
        lines.append(
            f"seen-set growths   {len(res.growth_stalls)} "
            f"(off-clock stalls {total:.1f}s: "
            + ", ".join(f"{c}@{s}s" for c, s in res.growth_stalls) + ")")
    if res.violation is not None:
        lines.append(f"VIOLATION          {res.violation.invariant} "
                     f"(fp {res.violation.fingerprint:#018x})")
    if res.deadlock is not None:
        lines.append("DEADLOCK reached")
    return "\n".join(lines)
