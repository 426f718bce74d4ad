"""TLC ``.cfg`` configuration parsing and model resolution.

The reference harness configs (/root/reference/MCraft.cfg,
/root/reference/Smokeraft.cfg) remain the source of truth (SURVEY §5.6/H1-H2):
this module parses the TLC cfg grammar subset they use —

    CONSTANT/CONSTANTS blocks with ``name = modelvalue``,
    ``name = {set literal}``, ``name = number``, and ``name <- definition``
    substitutions; SPECIFICATION; INVARIANT(S); CONSTRAINT(S);
    CHECK_DEADLOCK; ``\\*`` comments

— and resolves them against the spec's known definition names.  Instead of a
full TLA+ parser, the companion ``.tla`` harness module (MCraft.tla /
Smokeraft.tla, looked up next to the cfg) is scanned for the three shapes the
harnesses actually use:

- model-value set definitions ``name == {v1, v2}`` (MCraft.tla:15-21),
- the smoke subset size ``k == 2`` (Smokeraft.tla:17-19),
- StopAfter budgets ``TLCGet("duration") > 1`` / ``TLCGet("diameter") > 100``
  (Smokeraft.tla:88-92).

Bounded exhaustive configs (the BASELINE.json runs) use ordinary cfg constants
``MaxTerm/MaxLogLen/MaxMsgCount`` consumed by the built-in ``BoundedSpace``
constraint — standard TLC practice, no grammar extension required.

**TPU backend keys** (the ``TPUraft.cfg`` mechanism from the BASELINE.json
north star): engine parameters ride in the cfg as ``\\* TPU: KEY = VALUE``
comment directives, e.g. ``\\* TPU: BATCH = 8192``.  Because they are TLC
comments, a backend-annotated cfg still parses and runs under stock TLC
unchanged — the cfg stays the single source of truth for both engines.
Recognized keys: BATCH, QUEUE_CAPACITY, SEEN_CAPACITY, N_MSG_SLOTS,
MAX_LOG, PLATFORM, CHECKPOINT_DIR, CHECKPOINT_EVERY, CHECKPOINT_INTERVAL,
SPILL_DIR, TRACE_DIR, PROGRESS_SECONDS, EVENTS_OUT, KEEP_CHECKPOINTS,
TRACE_OUT (Chrome-trace span file), POR (statically-certified
partial-order reduction),
POR_TABLE (pre-certified reduction-table artifact path), PIPELINE
(successor pipeline: auto / v1 / v2, ``PIPELINES`` below; engine/bfs.py
EngineConfig.pipeline), XLA_PROFILE (device-profiler
capture: trace the first N chunk calls through jax.profiler,
obs/profile.py XlaProfileCapture), METRICS_PORT (serve /metrics
Prometheus exposition + /flight live snapshots over HTTP for the run,
obs/expose.py), REPORT (the TLC-parity statespace run report,
obs/report.py; TRUE by default — FALSE drops every report surface),
COUNTEREXAMPLE_DIR (where a traced violation's rendered counterexample
lands, engine/explain.py; defaults next to CHECKPOINT_DIR), HISTORY
(append one run-history ledger entry per run to this JSONL file,
obs/history.py), MODE (checking engine tier: ``exhaustive``
(default) or ``swarm`` — the vmap'd randomized-walk engine,
engine/swarm.py), WALKS (swarm mode: concurrent walks per device).
Precedence everywhere: CLI flag > cfg backend key > built-in default.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional, Tuple

from ..models.dims import RaftDims
from ..models.invariants import Bounds
from ..obs.metrics import process_span

_KEYWORDS = {
    "CONSTANT", "CONSTANTS", "SPECIFICATION", "INVARIANT", "INVARIANTS",
    "CONSTRAINT", "CONSTRAINTS", "ACTION_CONSTRAINT", "INIT", "NEXT",
    "SYMMETRY", "VIEW", "CHECK_DEADLOCK", "PROPERTY", "PROPERTIES",
}


@dataclasses.dataclass
class ParsedCfg:
    assignments: Dict[str, object] = dataclasses.field(default_factory=dict)
    substitutions: Dict[str, str] = dataclasses.field(default_factory=dict)
    specification: Optional[str] = None
    init: Optional[str] = None
    next: Optional[str] = None
    invariants: List[str] = dataclasses.field(default_factory=list)
    constraints: List[str] = dataclasses.field(default_factory=list)
    action_constraints: List[str] = dataclasses.field(default_factory=list)
    properties: List[str] = dataclasses.field(default_factory=list)
    symmetry: Optional[str] = None
    view: Optional[str] = None
    check_deadlock: bool = True        # TLC default
    backend: Dict[str, object] = dataclasses.field(default_factory=dict)


def _tokenize(text: str) -> List[str]:
    text = re.sub(r"\\\*[^\n]*", " ", text)          # \* line comments
    text = re.sub(r"\(\*.*?\*\)", " ", text, flags=re.S)  # (* block *)
    # Split keeping braces/commas/operators as tokens.
    return re.findall(r"<-|=|\{|\}|,|[^\s{},=]+", text)


_BACKEND_KEYS = {
    "BATCH", "QUEUE_CAPACITY", "SEEN_CAPACITY", "N_MSG_SLOTS", "MAX_LOG",
    "PLATFORM", "CHECKPOINT_DIR", "CHECKPOINT_EVERY", "CHECKPOINT_INTERVAL",
    "SPILL_DIR", "TRACE_DIR", "PROGRESS_SECONDS", "EVENTS_OUT",
    "KEEP_CHECKPOINTS", "TRACE_OUT", "POR", "POR_TABLE",
    "PIPELINE", "XLA_PROFILE", "METRICS_PORT", "REPORT",
    "COUNTEREXAMPLE_DIR", "HISTORY", "MODE", "WALKS",
}


#: The successor pipelines an engine can be asked for (engine/bfs.py
#: EngineConfig.pipeline): ``auto`` is v2 wherever the spec variant has v2
#: kernels, v1 elsewhere.
PIPELINES = ("auto", "v1", "v2")


def check_pipeline(value) -> None:
    """Reject a pipeline name no engine builds — from a flag, a
    directive, a request or a caller's EngineConfig alike."""
    if value not in PIPELINES:
        raise ValueError(f"pipeline must be one of "
                         f"{'/'.join(PIPELINES)}, got {value!r}")


def parse_backend_directives(text: str) -> Dict[str, object]:
    """``\\* TPU: KEY = VALUE`` comment directives (see module docstring)."""
    out: Dict[str, object] = {}
    for m in re.finditer(r"^\s*\\\*\s*TPU:\s*(\w+)\s*=\s*(\S+)",
                         text, flags=re.M | re.I):
        key, raw = m.group(1).upper(), m.group(2)
        if key not in _BACKEND_KEYS:
            raise ValueError(f"unknown TPU backend key {key!r}; "
                             f"recognized: {sorted(_BACKEND_KEYS)}")
        if re.fullmatch(r"-?\d+", raw):
            out[key] = int(raw)
        elif re.fullmatch(r"-?\d+\.\d*", raw):
            out[key] = float(raw)
        elif raw.upper() in ("TRUE", "FALSE"):
            # Case-insensitive like the keys: boolean directives (POR)
            # must not silently truthy-string their way to enabled when
            # written ``= false``.
            out[key] = raw.upper() == "TRUE"
        else:
            out[key] = raw
    if "PIPELINE" in out:
        check_pipeline(out["PIPELINE"])
    return out


def parse_cfg(text: str) -> ParsedCfg:
    toks = _tokenize(text)
    cfg = ParsedCfg()
    cfg.backend = parse_backend_directives(text)
    i, n = 0, len(toks)

    def parse_value(j: int) -> Tuple[object, int]:
        if toks[j] == "{":
            vals, j = [], j + 1
            while toks[j] != "}":
                if toks[j] != ",":
                    vals.append(toks[j])
                j += 1
            return tuple(vals), j + 1
        v = toks[j]
        if re.fullmatch(r"-?\d+", v):
            return int(v), j + 1
        if v in ("TRUE", "FALSE"):
            return v == "TRUE", j + 1
        return v, j + 1

    mode = None
    while i < n:
        t = toks[i]
        if t in _KEYWORDS:
            mode = t
            i += 1
            if t == "CHECK_DEADLOCK":
                cfg.check_deadlock = toks[i] == "TRUE"
                i += 1
                mode = None
            continue
        if mode in ("CONSTANT", "CONSTANTS", "INIT", "NEXT"):
            # INIT/NEXT in cfg name an operator; `Init <- SmokeInit` appears
            # inside a CONSTANT block in Smokeraft.cfg:43-44 — both accepted.
            name = t
            if i + 1 < n and toks[i + 1] == "=":
                val, i2 = parse_value(i + 2)
                cfg.assignments[name] = val
                i = i2
            elif i + 1 < n and toks[i + 1] == "<-":
                cfg.substitutions[name] = toks[i + 2]
                i += 3
            elif mode in ("INIT", "NEXT"):
                setattr(cfg, mode.lower(), name)
                i += 1
                mode = None
            else:
                i += 1
        elif mode == "SPECIFICATION":
            cfg.specification = t
            i += 1
            mode = None
        elif mode in ("INVARIANT", "INVARIANTS"):
            cfg.invariants.append(t)
            i += 1
        elif mode in ("CONSTRAINT", "CONSTRAINTS"):
            cfg.constraints.append(t)
            i += 1
        elif mode == "ACTION_CONSTRAINT":
            # TLC action constraints range over transitions (primed and
            # unprimed state) — different semantics from state constraints;
            # rejected explicitly rather than silently misinterpreted.
            cfg.action_constraints.append(t)
            i += 1
        elif mode in ("PROPERTY", "PROPERTIES"):
            cfg.properties.append(t)
            i += 1
        elif mode in ("SYMMETRY", "VIEW"):
            # Captured so load_config can reject them loudly (below); the
            # reference cfgs use neither (MCraft.cfg:1-39 has "No SYMMETRY,
            # no VIEW" per SURVEY §1 L5), so rejection — not implementation
            # — is the required behavior: silently dropping either would
            # report non-TLC state counts with no warning.
            setattr(cfg, mode.lower(), t)
            i += 1
            mode = None
        else:
            i += 1
    return cfg


# ---------------------------------------------------------------------------
# Companion-module scanning (the three shapes the reference harnesses use).

def scan_module_definitions(text: str) -> Dict[str, object]:
    """Extract ``name == <set literal | int>`` definitions from a harness
    module (handles the newline between name and body, MCraft.tla:15-21)."""
    out: Dict[str, object] = {}
    for m in re.finditer(
            r"^\s*(\w+)\s*==\s*\n?\s*(\{[^}]*\}|-?\d+)\s*$",
            re.sub(r"\\\*[^\n]*", "", text), flags=re.M):
        name, body = m.group(1), m.group(2).strip()
        if body.startswith("{"):
            out[name] = tuple(x.strip() for x in body[1:-1].split(",")
                              if x.strip())
        else:
            out[name] = int(body)
    return out


# Engine counters a TLCGet-consulting constraint may read — the live values
# TLC exposes through its control channel (SURVEY §5.5).  duration/diameter
# map onto the engines' native budget machinery; the rest are checked
# against live result counters after every chunk of work.
EXIT_COUNTERS = ("duration", "diameter", "distinct", "generated", "queue")

_TLCSET_EXIT = r'TLCSet\(\s*"exit"\s*,\s*TLCGet\("(\w+)"\)\s*>\s*(\d+)\s*\)'


@dataclasses.dataclass(frozen=True)
class ExitOp:
    """One operator of the StopAfter shape found in a companion module."""
    conds: Tuple[Tuple[str, float], ...]
    # True iff the body is NOTHING but TLCSet exit conjuncts — only then may
    # the operator be consumed as a pure budget; a mixed budget+predicate
    # CONSTRAINT is rejected at load (dropping the predicate half would
    # silently change state counts).
    pure: bool


def scan_exit_operators(text: str) -> Dict[str, ExitOp]:
    """Find operators of the Smokeraft StopAfter shape (Smokeraft.tla:88-92)

        Name ==
            /\\ TLCSet("exit", TLCGet("<counter>") > <n>)
            ...

    and return {operator name: ExitOp}.  This is the general TLCGet/TLCSet
    metrics-control coupling: any such PURE operator named as CONSTRAINT in
    a cfg becomes a budget consulting live engine counters — no code changes
    needed for e.g. ``TLCGet("distinct") > 1000000``.  Validation (unknown
    counters, impure bodies) happens in load_config, and only for operators
    a cfg actually names — an unused helper must not poison the module."""
    out: Dict[str, ExitOp] = {}
    clean = re.sub(r"\(\*.*?\*\)", "", text, flags=re.S)   # (* block *)
    clean = re.sub(r"\\\*[^\n]*", "", clean)               # \* line
    defs = list(re.finditer(r"^\s*(\w+)\s*(\([^)]*\))?\s*==", clean,
                            flags=re.M))
    for k, m in enumerate(defs):
        end = defs[k + 1].start() if k + 1 < len(defs) else len(clean)
        body = clean[m.end():end]
        conds = re.findall(_TLCSET_EXIT, body)
        if not conds:
            continue
        # Residue after removing the exit conjuncts: only /\ , \/ glue and
        # the module terminator's ='s may remain for the body to be pure.
        residue = re.sub(_TLCSET_EXIT, "", body)
        pure = re.fullmatch(r"[\s/\\=-]*", residue) is not None
        out[m.group(1)] = ExitOp(
            conds=tuple((c, float(n)) for c, n in conds), pure=pure)
    return out


# ---------------------------------------------------------------------------
# Resolution into a runnable setup.

@dataclasses.dataclass
class CheckSetup:
    """Everything the engine needs, resolved from one cfg."""

    dims: RaftDims
    bounds: Bounds
    invariants: List[str]
    constraints: List[str]
    check_deadlock: bool
    smoke: bool = False                 # Init <- SmokeInit override
    smoke_k: int = 2
    max_seconds: Optional[float] = None
    max_diameter: Optional[int] = None
    # Further TLCGet-consulting budgets (counter, threshold) beyond the two
    # with native engine machinery: distinct / generated / queue.
    exit_conditions: Tuple[Tuple[str, float], ...] = ()
    server_names: Tuple[str, ...] = ()
    value_names: Tuple[str, ...] = ()
    cfg: Optional[ParsedCfg] = None
    backend: Dict[str, object] = dataclasses.field(default_factory=dict)


@process_span("load_config", end="cfg_loaded")
def load_config(cfg_path: str, max_log: Optional[int] = None,
                n_msg_slots: Optional[int] = None) -> CheckSetup:
    """Parse cfg + companion module, intern model values, derive dims.
    ``max_log``/``n_msg_slots`` arguments (CLI flags) override the cfg's
    ``\\* TPU:`` backend directives, which override built-in defaults."""
    with open(cfg_path) as f:
        cfg = parse_cfg(f.read())
    if max_log is None:
        max_log = cfg.backend.get("MAX_LOG")
    if n_msg_slots is None:
        n_msg_slots = cfg.backend.get("N_MSG_SLOTS", 32)
    moddefs: Dict[str, object] = {}
    exit_ops: Dict[str, ExitOp] = {}
    # Scan the companion module and its EXTENDS chain (Smokeraft EXTENDS
    # MCraft — Smokeraft.tla:2 — whose const_* definitions the cfg names).
    mod_dir = os.path.dirname(os.path.abspath(cfg_path))
    pending = [os.path.splitext(os.path.basename(cfg_path))[0]]
    seen_mods = set()
    while pending:
        mod = pending.pop()
        if mod in seen_mods:
            continue
        seen_mods.add(mod)
        cand = os.path.join(mod_dir, mod + ".tla")
        if not os.path.exists(cand):
            continue
        with open(cand) as f:
            text = f.read()
        moddefs.update(scan_module_definitions(text))
        for name, conds in scan_exit_operators(text).items():
            exit_ops.setdefault(name, conds)
        ext = re.search(r"^\s*EXTENDS\s+([^\n]+)", text, flags=re.M)
        if ext:
            pending.extend(x.strip() for x in ext.group(1).split(","))

    def resolve_set(name: str) -> Tuple[str, ...]:
        if name in cfg.assignments and isinstance(cfg.assignments[name],
                                                  tuple):
            return cfg.assignments[name]
        if name in cfg.substitutions:
            target = cfg.substitutions[name]
            if target in moddefs and isinstance(moddefs[target], tuple):
                return moddefs[target]
            raise ValueError(
                f"cannot resolve {name} <- {target}: definition not found "
                f"in companion module of {cfg_path}")
        raise ValueError(f"no binding for constant {name} in {cfg_path}")

    servers = resolve_set("Server")
    values = resolve_set("Value")

    def int_const(name: str) -> Optional[int]:
        v = cfg.assignments.get(name)
        return v if isinstance(v, int) else None

    bounds = Bounds(max_term=int_const("MaxTerm"),
                    max_log_len=int_const("MaxLogLen"),
                    max_msg_count=int_const("MaxMsgCount"),
                    max_in_flight=int_const("MaxInFlight"))

    if cfg.action_constraints:
        raise NotImplementedError(
            f"ACTION_CONSTRAINT {cfg.action_constraints} not supported: "
            "action constraints range over transitions, not states")

    if cfg.symmetry is not None:
        raise NotImplementedError(
            f"SYMMETRY {cfg.symmetry} not supported: symmetry reduction "
            "quotients the state space and changes distinct-state counts; "
            "running without it would silently disagree with TLC")

    if cfg.view is not None:
        raise NotImplementedError(
            f"VIEW {cfg.view} not supported: a view changes which states "
            "are considered distinct; fingerprints here cover the full "
            "canonical state only")

    if cfg.properties:
        # Temporal properties (PROPERTY/PROPERTIES) need liveness checking
        # (fairness, SCC search over the behavior graph) — a different
        # algorithm from safety BFS.  Rejected loudly: dropping them would
        # let a cfg 'pass' a property that was never checked.
        raise NotImplementedError(
            f"PROPERTY {cfg.properties} not supported: temporal/liveness "
            "checking is not implemented; this engine checks INVARIANT "
            "(safety) properties only")

    smoke = cfg.substitutions.get("Init") == "SmokeInit" \
        or cfg.init == "SmokeInit"
    smoke_k = moddefs.get("k", 2) if smoke else 2

    if max_log is None:
        if bounds.max_log_len is not None:
            # Expanded states have len <= MaxLogLen; their successors can
            # exceed the bound by one appended entry (counted, not expanded).
            max_log = bounds.max_log_len + 1
        elif smoke:
            max_log = 12    # init logs <= 3 (Smokeraft.tla:70) + headroom
        else:
            max_log = 8

    # Any CONSTRAINT whose companion-module definition is a TLCSet("exit",
    # TLCGet(...) > n) conjunction is a budget, not a state predicate —
    # Smokeraft's StopAfter is simply the reference instance of the shape.
    max_seconds = max_diameter = None
    exit_conditions: List[Tuple[str, float]] = []
    budget_names = [c for c in cfg.constraints if c in exit_ops]
    for name in budget_names:
        op = exit_ops[name]
        if not op.pure:
            raise NotImplementedError(
                f"CONSTRAINT {name} mixes TLCSet exit budgets with other "
                "conjuncts; dropping the non-budget half would silently "
                "change state counts — split the operator into a pure "
                "budget and a pure state predicate")
        for counter, threshold in op.conds:
            if counter not in EXIT_COUNTERS:
                raise NotImplementedError(
                    f'TLCGet("{counter}") in CONSTRAINT {name} not '
                    f"supported; available engine counters: {EXIT_COUNTERS}")
            # TLC exits when ANY TLCSet("exit", ...) trips, so when the
            # same counter is bounded twice the SMALLEST threshold wins.
            if counter == "duration":
                max_seconds = threshold if max_seconds is None \
                    else min(max_seconds, threshold)
            elif counter == "diameter":
                max_diameter = int(threshold) if max_diameter is None \
                    else min(max_diameter, int(threshold))
            else:
                exit_conditions.append((counter, threshold))

    # TargetConfigs (a set of membership bitmasks over the interned server
    # order) selects the joint-consensus reconfiguration variant
    # (models/reconfig.py) — the BASELINE.json configs[4] state space.
    if "TargetConfigs" in cfg.assignments:
        from ..models.reconfig import ReconfigDims
        raw = cfg.assignments["TargetConfigs"]
        if not isinstance(raw, tuple):
            raw = (raw,)
        targets = tuple(sorted(int(x) for x in raw))
        dims = ReconfigDims(n_servers=len(servers), n_values=len(values),
                            max_log=max_log, n_msg_slots=n_msg_slots,
                            targets=targets)
    else:
        dims = RaftDims(n_servers=len(servers), n_values=len(values),
                        max_log=max_log, n_msg_slots=n_msg_slots)

    return CheckSetup(
        dims=dims,
        bounds=bounds,
        invariants=list(cfg.invariants),
        constraints=[c for c in cfg.constraints if c not in budget_names],
        check_deadlock=cfg.check_deadlock,
        smoke=smoke, smoke_k=smoke_k,
        max_seconds=max_seconds, max_diameter=max_diameter,
        exit_conditions=tuple(exit_conditions),
        server_names=servers, value_names=values, cfg=cfg,
        backend=dict(cfg.backend))
