"""Hot-loop lint: TPU-throughput hazards in the compiled step + host loop.

Three halves, one pass:

- **Jaxpr lint**: trace the BFS chunk body (the per-batch pipeline the
  engines run thousands of times per second — both the v1 expand path
  and the v2 delta path), the fingerprint kernel, and the FPSet insert,
  then walk every equation (recursing into pjit / while / cond / scan
  sub-jaxprs) for ops that silently wreck device throughput: host
  callbacks and infeed/outfeed (ERROR — a host round-trip per batch),
  dynamic shapes (ERROR — recompilation per shape), non-deterministic
  floating-point reductions (WARNING — the engines' bit-identical
  cross-engine contract assumes integer determinism), and
  dtype-narrowing converts (intentional uint8 row packing is an INFO
  count; any *other* integer narrowing is a WARNING, because that is
  exactly how a lane silently loses bits).

- **Host-loop AST lint**: the steady-state loop (``engine/chunk.py``,
  and ``_run_impl`` with its ``_run_levels`` in ``engine/bfs.py``) must
  fetch device data only at sanctioned sync points; any other blocking
  device read
  (``np.asarray`` / ``jax.device_get`` / ``block_until_ready``) inside
  a loop serializes the dispatch pipeline.
  Sanctioned means: under a ``with <registry>.phase_timer(...)`` block
  (the engines' audited sync points — the telemetry contract makes
  every sync visible in the phase breakdown), or inside a branch that
  exits the loop (violation / deadlock reporting runs once, off the
  steady state).

- **Read-set self-check**: analyzer-vs-analyzer consistency — any state
  lane a kernel jaxpr demonstrably reads (consumed by a non-identity
  primitive on the way to the outputs) must be inside the read set the
  effects pass reports for that family.  A mismatch means the taint
  interpreter dropped a dependency, which would make downstream
  consumers (the POR certificates) unsound — ERROR.

Everything here is trace/parse-time only: no device execution, no
compilation — safe to run in CI on a CPU-only runner.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .report import ERROR, Finding, INFO, WARNING

PASS = "lint"

#: Primitive names (exact or substring "callback") that move data or
#: control to the host from inside a compiled program.
_HOST_PRIMS = ("infeed", "outfeed", "host_local_array_to_global_array")
#: Reductions whose result depends on accumulation order for floats.
_ORDER_SENSITIVE = ("reduce_sum", "reduce_prod", "dot_general", "add_any",
                    "cumsum", "cumprod")


# ---------------------------------------------------------------------------
# Jaxpr lint


def _sub_jaxprs(params) -> Iterable:
    """Every jaxpr nested in an eqn's params (pjit/while/cond/scan...)."""
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr") or hasattr(x, "eqns"):
                yield x


def _walk_eqns(jaxpr):
    closed = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in closed.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def lint_jaxpr(closed, kernel: str) -> Tuple[dict, List[Finding]]:
    """Lint one traced kernel.  Returns (summary, findings)."""
    findings: List[Finding] = []
    n_eqns = 0
    pack_narrows = 0
    narrow_prims: Dict[str, int] = {}
    seen_codes = set()

    def once(code, qual, sev, msg, **kw):
        key = (code, qual)
        if key in seen_codes:
            return
        seen_codes.add(key)
        findings.append(Finding(PASS, sev, code, field=kernel,
                                message=msg, **kw))

    for eqn in _walk_eqns(closed):
        n_eqns += 1
        name = eqn.primitive.name
        if "callback" in name or name in _HOST_PRIMS:
            once("host-callback", name, ERROR,
                 f"compiled kernel {kernel!r} contains host-transfer "
                 f"primitive {name!r} — a host round-trip inside the "
                 "device loop throttles every batch")
        elif name == "debug_print":
            once("debug-print", name, WARNING,
                 f"compiled kernel {kernel!r} contains debug_print — "
                 "host formatting inside the device loop")
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if any(not isinstance(d, int) for d in shape):
                once("dynamic-shape", name, ERROR,
                     f"kernel {kernel!r}: primitive {name!r} has a "
                     f"dynamically-shaped output {shape} — every new "
                     "shape recompiles the step")
        if name in _ORDER_SENSITIVE:
            in_dt = np.dtype(eqn.invars[0].aval.dtype)
            if in_dt.kind == "f":
                once("nondet-reduction", name, WARNING,
                     f"kernel {kernel!r}: float {name} — accumulation "
                     "order is backend-dependent, breaking the engines' "
                     "bit-identical cross-engine contract")
        if name == "convert_element_type":
            in_dt = np.dtype(eqn.invars[0].aval.dtype)
            out_dt = np.dtype(eqn.outvars[0].aval.dtype)
            if (in_dt.kind in "iu" and out_dt.kind in "iu"
                    and out_dt.itemsize < in_dt.itemsize):
                if out_dt == np.uint8:
                    pack_narrows += 1       # the row packing, by design
                else:
                    narrow_prims[f"{in_dt}->{out_dt}"] = \
                        narrow_prims.get(f"{in_dt}->{out_dt}", 0) + 1
    for conv, cnt in sorted(narrow_prims.items()):
        findings.append(Finding(
            PASS, WARNING, "narrowing-convert", field=kernel,
            message=f"kernel {kernel!r}: {cnt} integer-narrowing "
                    f"convert(s) {conv} outside the uint8 row packing — "
                    "a lane silently loses bits if the value can exceed "
                    "the target width",
            details={"convert": conv, "count": cnt}))
    if pack_narrows:
        findings.append(Finding(
            PASS, INFO, "packing-converts", field=kernel,
            message=f"kernel {kernel!r}: {pack_narrows} intentional "
                    "uint8 row-packing convert(s) (pack-guarded)",
            details={"count": pack_narrows}))
    return {"eqns": n_eqns, "packing_converts": pack_narrows}, findings


def _trace_engine_kernels(dims, batch: int = 4):
    """Trace the kernels the single-chip engine actually runs, with tiny
    capacities (tracing only — nothing executes).  Yields
    (kernel name, ClosedJaxpr)."""
    import jax
    import jax.numpy as jnp

    from ..engine.chunk import build_chunk_body
    from ..models.actions import build_expand
    from ..models.invariants import build_type_ok
    from ..models.schema import StateBatch, build_pack_guard, state_width
    from ..ops import compact as compact_mod
    from ..ops import fpset
    from ..ops.fingerprint import build_fingerprint
    from . import lane_map

    expand = build_expand(dims)
    fingerprint = build_fingerprint(dims)
    pack_ok = build_pack_guard(dims)
    inv_fns = [build_type_ok(dims)]
    sw = state_width(dims)
    B, G = batch, dims.n_instances
    K = compact_mod.choose_k(B, G, None)
    Q = max(B, K)
    QA = Q + max(B, K)
    TQ = Q + K

    shapes = lane_map.field_shapes(dims)
    state1 = [jax.ShapeDtypeStruct(shapes[f], jnp.int32)
              for f in lane_map.FIELDS]
    yield "fingerprint", jax.make_jaxpr(
        lambda *a: fingerprint(StateBatch(*a)))(*state1)

    seen = fpset.empty(1024)
    keys = jax.ShapeDtypeStruct((K,), jnp.uint32)
    valid = jax.ShapeDtypeStruct((K,), jnp.bool_)
    yield "fpset_insert", jax.make_jaxpr(fpset.insert)(
        seen, keys, keys, valid)

    def carry(seen):
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        return (
            i32, i32,
            jax.ShapeDtypeStruct((QA, sw), jnp.uint8), i32, seen,
            tuple(jax.ShapeDtypeStruct((TQ + K,), dt) for dt in
                  (jnp.uint32, jnp.uint32, jnp.uint32, jnp.uint32,
                   jnp.int32)),
            i32, i32, i32, i32,
            jax.ShapeDtypeStruct((), jnp.bool_),
            jax.ShapeDtypeStruct((sw,), jnp.uint8),
            jax.ShapeDtypeStruct((), jnp.bool_), i32,
            jax.ShapeDtypeStruct((sw,), jnp.uint8),
            jax.ShapeDtypeStruct((), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.bool_),
            # fam_counts, fam_new (coverage), expanded, fam_pruned (POR)
            # — the 22-field carry (engine/chunk.py layout).
            jax.ShapeDtypeStruct((len(dims.family_sizes),), jnp.int32),
            jax.ShapeDtypeStruct((len(dims.family_sizes),), jnp.int32),
            i32,
            jax.ShapeDtypeStruct((len(dims.family_sizes),), jnp.int32))

    qcur = jax.ShapeDtypeStruct((QA, sw), jnp.uint8)
    cnt = jax.ShapeDtypeStruct((), jnp.int32)

    def step_jaxpr(v2):
        body = build_chunk_body(
            dims=dims, expand=expand, fingerprint=fingerprint,
            pack_ok=pack_ok, inv_fns=inv_fns, constraint=None,
            B=B, G=G, K=K, Q=Q, TQ=TQ, record_static=True,
            compactor=compact_mod.build_compactor(B, G, K),
            insert_fn=fpset.insert, v2=v2)
        return jax.make_jaxpr(body)(qcur, cnt, carry(seen))

    yield "bfs_step_v1", step_jaxpr(None)
    from ..models.actions2 import V2Unavailable, build_v2
    try:
        v2 = build_v2(dims)
    except V2Unavailable:
        v2 = None
    if v2 is not None:
        yield "bfs_step_v2", step_jaxpr(v2)


# ---------------------------------------------------------------------------
# Analyzer-vs-analyzer read-set self-check
#
# The effects pass's read sets feed the POR certificates, so a taint
# dependency silently dropped by the interpreter would turn into an
# unsound reduction.  This check re-derives a SYNTACTIC read set per
# action family — every state invar consumed by at least one
# non-value-preserving primitive on the way to the outputs — and flags
# any lane the jaxpr demonstrably reads that the effects pass does not
# report.  Pure pass-through (an unchanged successor field flowing
# identically to an outvar) is not a read; that is exactly the
# distinction the taint domain draws, so the two analyzers must agree.

#: Primitives that move values without consuming them (reshape-like).
_IDENTITY_PRIMS = frozenset({
    "copy", "reshape", "squeeze", "expand_dims", "transpose", "rev",
    "broadcast_in_dim", "convert_element_type", "stop_gradient", "slice",
})

_CALL_PRIMS = ("jit", "closed_call", "core_call", "remat", "checkpoint",
               "custom_jvp_call", "custom_vjp_call")


def syntactic_real_reads(closed, n_state: int) -> set:
    """Indices (0..n_state-1) of state invars consumed by a non-identity
    primitive anywhere in the jaxpr (recursing into call sub-jaxprs)."""
    reads: set = set()

    from jax.extend.core import Literal

    def walk(jaxpr, env):
        for eqn in jaxpr.eqns:
            srcs = [env.get(v, frozenset()) for v in eqn.invars
                    if not isinstance(v, Literal)]
            union = frozenset().union(*srcs) if srcs else frozenset()
            name = eqn.primitive.name
            if name in _CALL_PRIMS:
                inner = eqn.params.get("jaxpr") or \
                    eqn.params.get("call_jaxpr")
                if inner is not None:
                    ij = getattr(inner, "jaxpr", inner)
                    sub_env = {}
                    live = [v for v in eqn.invars
                            if not isinstance(v, Literal)]
                    for var, outer in zip(ij.invars, live):
                        sub_env[var] = env.get(outer, frozenset())
                    walk(ij, sub_env)
                    for outv, innerv in zip(eqn.outvars, ij.outvars):
                        if not isinstance(innerv, Literal):
                            env[outv] = sub_env.get(innerv, frozenset())
                    continue
            if name in _IDENTITY_PRIMS:
                for outv in eqn.outvars:
                    env[outv] = union
            else:
                reads.update(union)
                for outv in eqn.outvars:
                    env[outv] = union

    jaxpr = closed.jaxpr
    env = {v: frozenset([k]) for k, v in enumerate(jaxpr.invars[:n_state])}
    walk(jaxpr, env)
    return reads


def read_set_check(dims, family_reads=None,
                   effect_summary=None) -> List[Finding]:
    """Flag any action kernel whose jaxpr reads a packed lane outside
    the read set the effects pass reports for it.  ``family_reads``
    overrides the effects-derived ``{family: fields}`` map (tests plant
    a missing field there to prove the check fires).

    Element granularity: the effects pass now reports per-element
    masks, so a FIELD the pass claims to read with an all-empty mask
    would slip past a set-membership comparison — membership here is
    therefore derived from the per-instance masks (``.any()``), and two
    mask-level invariants of the extraction are re-checked per
    instance: the guard's read mask is contained in the full read mask,
    and every reported mask has the field's declared shape (a
    wrong-shaped mask would make every element-wise intersection
    downstream silently wrong)."""
    from . import lane_map
    from .interp import traced_kernels
    findings: List[Finding] = []
    if family_reads is None:
        if effect_summary is None:
            from . import effects
            effect_summary, _f = effects.analyze(dims)
        shapes = lane_map.field_shapes(dims)
        family_reads = {}
        for inst in effect_summary.instances:
            fam = family_reads.setdefault(inst.family, set())
            fam.update(f for f, m in inst.reads.items() if m.any())
            fam.update(f for f, m in inst.guard_reads.items() if m.any())
            bad_shape = sorted(
                f for masks in (inst.reads, inst.writes, inst.guard_reads)
                for f, m in masks.items() if m.shape != shapes[f])
            if bad_shape:
                findings.append(Finding(
                    PASS, ERROR, "footprint-shape-mismatch",
                    field=inst.family, witness=inst.label,
                    message=f"{inst.label}: footprint mask(s) for "
                            f"{', '.join(bad_shape)} do not match the "
                            "declared field shape — element-wise "
                            "intersections downstream would be wrong"))
            leaked = sorted(
                f for f, m in inst.guard_reads.items()
                if bool((m & ~inst.reads.get(f, np.zeros_like(m))).any()))
            if leaked:
                findings.append(Finding(
                    PASS, ERROR, "guard-read-leak",
                    field=inst.family, witness=inst.label,
                    message=f"{inst.label}: guard reads element(s) of "
                            f"{', '.join(leaked)} missing from the "
                            "full read mask — the dependence matrix "
                            "under-approximates (POR certificates "
                            "would be unsound)"))
    n_state = len(lane_map.FIELDS)
    for name, closed, _params in traced_kernels(dims):
        syn = {lane_map.FIELDS[k]
               for k in syntactic_real_reads(closed, n_state)}
        extra = sorted(syn - set(family_reads.get(name, frozenset())))
        if extra:
            findings.append(Finding(
                PASS, ERROR, "read-set-mismatch", field=name,
                message=f"kernel {name!r} syntactically reads state "
                        f"field(s) {', '.join(extra)} that the effects "
                        "pass does not report — the taint interpreter "
                        "dropped a dependency (POR certificates would "
                        "be unsound)",
                details={"extra_reads": extra}))
    return findings


# ---------------------------------------------------------------------------
# Host-loop AST lint


def _is_blocking_read(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Attribute):
        base = f.value.id if isinstance(f.value, ast.Name) else None
        if f.attr == "block_until_ready":
            return "block_until_ready()"
        if base in ("np", "numpy") and f.attr in ("asarray", "array"):
            return f"np.{f.attr}"
        if base == "jax" and f.attr == "device_get":
            return "jax.device_get"
    elif isinstance(f, ast.Name) and f.id == "device_get":
        return "device_get"
    return None


def _is_phase_timer_with(node: ast.With) -> bool:
    for item in node.items:
        ctx = item.context_expr
        if isinstance(ctx, ast.Call):
            f = ctx.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                (f.id if isinstance(f, ast.Name) else None)
            if name == "phase_timer":
                return True
    return False


def _branch_exits(stmts: Sequence[ast.stmt]) -> bool:
    """Does this if-branch leave the loop (break/return/raise anywhere
    in its subtree)?  Conservative: a nested loop's break also counts —
    acceptable, these are one-shot reporting branches either way."""
    for st in stmts:
        for n in ast.walk(st):
            if isinstance(n, (ast.Break, ast.Return, ast.Raise)):
                return True
    return False


def _scan_block(stmts, in_loop: bool, sanctioned: bool, hits: list):
    for st in stmts:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # A nested def's loops are scanned in their own right (the
            # engines' nested helpers run inside the hot loop).
            _scan_block(st.body, in_loop, sanctioned, hits)
        elif isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
            for sub in ast.walk(st.test if isinstance(st, ast.While)
                                else st.iter):
                if isinstance(sub, ast.Call):
                    kind = _is_blocking_read(sub)
                    if kind and not sanctioned:
                        hits.append((sub.lineno, kind))
            _scan_block(st.body, True, sanctioned, hits)
            _scan_block(st.orelse, in_loop, sanctioned, hits)
        elif isinstance(st, ast.With):
            _scan_block(st.body, in_loop,
                        sanctioned or _is_phase_timer_with(st), hits)
        elif isinstance(st, ast.If):
            _scan_block(st.body, in_loop,
                        sanctioned or (in_loop and _branch_exits(st.body)),
                        hits)
            _scan_block(st.orelse, in_loop,
                        sanctioned or (in_loop
                                       and _branch_exits(st.orelse)),
                        hits)
        elif isinstance(st, ast.Try):
            for blk in (st.body, st.orelse, st.finalbody):
                _scan_block(blk, in_loop, sanctioned, hits)
            for h in st.handlers:
                _scan_block(h.body, in_loop, sanctioned, hits)
        else:
            if in_loop and not sanctioned:
                for n in ast.walk(st):
                    if isinstance(n, ast.Call):
                        kind = _is_blocking_read(n)
                        if kind:
                            hits.append((n.lineno, kind))


def scan_host_loops(path: str, scope: Optional[Sequence[str]] = None
                    ) -> List[Finding]:
    """AST lint one file for blocking device reads inside loops outside
    sanctioned sync points.  ``scope`` restricts the scan to the named
    function defs (at any nesting depth); None scans the whole module."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    roots: List[Sequence[ast.stmt]] = []
    if scope is None:
        roots.append(tree.body)
    else:
        want = set(scope)
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n.name in want:
                roots.append(n.body)
    hits: List[Tuple[int, str]] = []
    for body in roots:
        _scan_block(body, in_loop=False, sanctioned=False, hits=hits)
    rel = os.path.relpath(path, start=os.getcwd()) \
        if os.path.isabs(path) else path
    return [Finding(
        PASS, ERROR, "blocking-read-in-loop", field=f"{rel}:{ln}",
        message=f"{rel}:{ln}: {kind} inside the hot loop outside a "
                "sanctioned sync point (phase_timer block or loop-exit "
                "branch) — serializes the dispatch pipeline")
        for ln, kind in hits]


#: (file, scope) pairs the default scan covers: the whole shared chunk
#: body module plus the single-chip engine's steady-state loop.
def _default_targets() -> List[Tuple[str, Optional[Tuple[str, ...]]]]:
    eng = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "engine")
    return [(os.path.join(eng, "chunk.py"), None),
            (os.path.join(eng, "bfs.py"), ("_run_impl", "_run_levels"))]


# ---------------------------------------------------------------------------
# The pass


def analyze(dims, targets=None,
            effect_summary=None) -> Tuple[dict, List[Finding]]:
    """Run all lint halves.  ``targets`` overrides the host-loop file
    list (``[(path, scope-or-None), ...]``; tests plant fixtures here);
    ``effect_summary`` reuses the effects pass's result for the read-set
    self-check when both passes run in one invocation."""
    findings: List[Finding] = []
    kernels: Dict[str, dict] = {}
    for kernel, closed in _trace_engine_kernels(dims):
        summ, fs = lint_jaxpr(closed, kernel)
        kernels[kernel] = summ
        findings.extend(fs)
    scanned = []
    for path, scope in (_default_targets() if targets is None else targets):
        findings.extend(scan_host_loops(path, scope))
        scanned.append(os.path.basename(path))
    rs = read_set_check(dims, effect_summary=effect_summary)
    findings.extend(rs)
    return {"kernels": kernels, "host_files": scanned,
            "read_set_mismatches": len(rs)}, findings
