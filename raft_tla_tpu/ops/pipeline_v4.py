"""v4 whole-chunk stage plan — glue between EngineConfig.pipeline="v4"
and the two chunk megakernels.

v4 is the v2 delta pipeline with BOTH halves of the chunk body fused:

    masks        \
    compact       }  ops/chunk_front_pallas.py   [one Pallas launch]
    fingerprint  /
    insert       \\   ops/fused_tail_pallas.py    [one Pallas launch]
    enqueue      /

The front trio is ONE stage group: the megakernel exists precisely so
the [B, G] mask and the parent-struct window never leave VMEM between
masks, compaction, and the delta fingerprints, so its members degrade
together — forcing (or failing to build) ANY of masks/compact/
fingerprint splits the group back to the v3-style arrangement, where
masks+fingerprint are the XLA jaxpr programs and compact resolves per
the v3 platform policy.  The tail pair is the same fused group v3
ships.  Every kernel is build-and-probe verified at plan time at the
real per-program shapes.  On the TPU a kernel the compiler refuses
fails the build with the compiler's message (pipeline_v3
``_raise_on_chip``); off the chip, in interpret mode, a stage that
will not build degrades with a recorded reason (``V4Plan.stages`` /
``reasons`` -> ``EngineResult.fused_stages``).

Per-stage forcing comes from ``EngineConfig.v4_force_stages`` and the
``RAFT_V4_FORCE`` environment variable ("masks=xla,insert=xla" — env
entries win over config), which is how the fallback-lattice tests pin
each stage to its XLA lowering without plumbing test-only config.

Platform policy:

- TPU single chip: front=fused, tail=fused — two launches per batch.
- CPU single chip: both kernels run in interpret mode.  Unlike v3's
  compact-only scan (pure emulation overhead on CPU), the front
  megakernel's body IS the traced XLA front, so interpreting it costs
  nothing extra while collapsing the chunk jaxpr to ~two launch sites —
  which is exactly what the CI launch pin measures.
- mesh: no front (compact's P is pmin-replicated across chips, and
  owner-routed dedup needs the all_to_all — both collectives), so the
  mesh plan matches v3's: compact/insert=xla, enqueue=pallas.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional

from .pipeline_v3 import _probe_enqueue, _probe_tail, _raise_on_chip

STAGES = ("masks", "compact", "fingerprint", "insert", "enqueue")
FRONT_STAGES = ("masks", "compact", "fingerprint")

ENV_FORCE = "RAFT_V4_FORCE"


class V4Plan(NamedTuple):
    stages: Dict[str, str]       # stage -> "fused" | "pallas" | "xla"
    reasons: Dict[str, str]      # stage -> why it is not fused
    front: Optional[Callable]    # fused masks+compact+fingerprint, or None
    compactor: Optional[Callable]   # split-front Pallas compactor
    tail: Optional[Callable]     # fused insert+enqueue, or None = split
    enqueue_method: str          # chunk-body enqueue when tail is None
    # Expected kernel launches per stage per batch — same contract as
    # V3Plan.launches: a fused group is ONE kernel billed to its first
    # member (compact/fingerprint are 0 when the front is fused, like
    # enqueue under the fused tail), an XLA stage is None (the launch
    # model derives its op count from the traced jaxpr).  Default None,
    # not {}: NamedTuple defaults are class-level, a dict would be
    # shared across instances.
    launches: Optional[Dict[str, Optional[int]]] = None


def describe(plan: V4Plan) -> str:
    """One-line stage map for logs/results: "masks=fused compact=fused ..."."""
    return " ".join(f"{s}={plan.stages[s]}" for s in STAGES)


def _merged_force(force: Optional[Dict[str, str]]) -> Dict[str, str]:
    """Config force merged with RAFT_V4_FORCE ("a=xla,b=xla"; env wins).
    Malformed entries raise — a typo'd override must not silently run
    the fused kernel the test meant to disable."""
    out = dict(force or {})
    raw = os.environ.get(ENV_FORCE, "").strip()
    if raw:
        for item in raw.split(","):
            item = item.strip()
            if not item:
                continue
            if "=" not in item:
                raise ValueError(
                    f"{ENV_FORCE}: expected stage=impl, got {item!r}")
            stage, impl = item.split("=", 1)
            out[stage.strip()] = impl.strip()
    return out


def resolve_plan(B: int, G: int, K: int, *, Q: int, sw: int = 8,
                 mesh: bool = False, enqueue_method: str = "scatter",
                 force: Optional[Dict[str, str]] = None,
                 interpret: Optional[bool] = None,
                 front_ctx: Optional[Dict[str, Any]] = None) -> V4Plan:
    """Resolve the v4 per-stage lowering for one engine build.

    ``front_ctx`` carries what the front megakernel closes over beyond
    shapes: {"dims", "v2", "constraint", "inv_fns", "por_mask",
    "por_priority"} from the engine build (None degrades the front with
    a recorded reason — the profiler's shape-only probes pass one).
    ``Q``/``sw`` as in pipeline_v3.resolve_plan; ``force`` merges with
    the RAFT_V4_FORCE env var (env wins per stage).  Forcing any front
    member away from "fused" degrades the WHOLE front group — the
    megakernel has no partial configuration — after which "compact"
    may still independently resolve to the v3 Pallas scan."""
    import jax
    force = _merged_force(force)
    _VALID = {"masks": ("fused", "xla"),
              "compact": ("fused", "pallas", "xla"),
              "fingerprint": ("fused", "xla"),
              "insert": ("fused", "xla"),
              "enqueue": ("fused", "pallas", "xla")}
    for stage, impl in force.items():
        if stage not in _VALID or impl not in _VALID[stage]:
            raise ValueError(
                f"v4_force_stages: unknown {stage!r}={impl!r}; valid: "
                + ", ".join(f"{s}∈{v}" for s, v in _VALID.items()))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    stages = {s: "xla" for s in STAGES}
    reasons: Dict[str, str] = {}
    front = None
    compactor = None
    tail = None

    # -- front group: masks + compact + fingerprint --------------------
    degraded = None
    if mesh:
        degraded = ("the mesh chunk's compact P is pmin-replicated and "
                    "its dedup is an all_to_all; collectives cannot "
                    "live inside the front kernel")
    else:
        for s in FRONT_STAGES:
            impl = force.get(s)
            if impl is not None and impl != "fused":
                degraded = f"front group degraded: {s} forced to {impl}"
                break
    if degraded is None and front_ctx is None:
        degraded = "no front build context (shape-only plan resolve)"
    if degraded is None:
        try:
            from . import chunk_front_pallas
            import jax.numpy as jnp
            cand = chunk_front_pallas.build_front(
                dims=front_ctx["dims"], v2=front_ctx["v2"],
                constraint=front_ctx.get("constraint"),
                inv_fns=front_ctx.get("inv_fns"),
                B=B, G=G, K=K,
                por_mask=front_ctx.get("por_mask"),
                por_priority=front_ctx.get("por_priority"),
                interpret=interpret)
            jax.block_until_ready(cand(
                jnp.zeros((B, sw), jnp.uint8), jnp.zeros((B,), bool)))
            front = cand
            for s in FRONT_STAGES:
                stages[s] = "fused"
        except Exception as e:  # noqa: BLE001 — interpret-mode fallback
            _raise_on_chip(interpret)
            degraded = (f"front kernel failed to build/probe: "
                        f"{type(e).__name__}: {str(e)[:160]}")
    if front is None:
        for s in FRONT_STAGES:
            reasons[s] = degraded

    # -- split compact when the front is not fused ---------------------
    if front is None:
        want_compact = force.get("compact")
        if mesh:
            want_compact = "xla"   # pmin collective; not forceable
        if want_compact in (None, "fused"):
            want_compact = "xla" if interpret else "pallas"
            if interpret:
                reasons["compact"] = (
                    reasons.get("compact", "") +
                    "; sequential B*G scan is priced for TPU VMEM "
                    "residency, xla on cpu").lstrip("; ")
        if want_compact == "pallas":
            try:
                from . import compact_pallas
                import jax.numpy as jnp
                cand = compact_pallas.build_compactor(B, G, K,
                                                      interpret=interpret)
                jax.block_until_ready(cand(jnp.zeros((B, G), bool)))
                compactor = cand
                stages["compact"] = "pallas"
            except Exception as e:  # noqa: BLE001 — interpret-mode fallback
                _raise_on_chip(interpret)
                reasons["compact"] = (
                    f"pallas compact failed to build/probe: "
                    f"{type(e).__name__}: {str(e)[:160]}")

    # -- insert + enqueue (fused tail) — v3 semantics ------------------
    if mesh:
        want_tail = "xla"
        reasons["insert"] = ("owner-routed all_to_all dedup is a "
                             "collective; cannot fuse on the mesh")
    else:
        want_tail = force.get("insert", force.get("enqueue"))
        if want_tail is None:
            want_tail = "fused"
    if want_tail == "fused":
        try:
            from . import fused_tail_pallas

            def cand_tail(seen, kh, kl, kvalid, krows, cons_ok,
                          next_count, qnext):
                return fused_tail_pallas.insert_enqueue(
                    seen, kh, kl, kvalid, krows, cons_ok, qnext,
                    next_count, Q, interpret=interpret)

            _probe_tail(K, sw, interpret)
            tail = cand_tail
            stages["insert"] = stages["enqueue"] = "fused"
        except Exception as e:  # noqa: BLE001 — interpret-mode fallback
            _raise_on_chip(interpret)
            reasons["insert"] = (f"fused tail failed to build/probe: "
                                 f"{type(e).__name__}: {str(e)[:160]}")
    if tail is None and "insert" not in reasons:
        reasons["insert"] = "forced to xla"

    # -- split enqueue when the tail is not fused ----------------------
    enq = enqueue_method
    if tail is None:
        want_enq = force.get("enqueue")
        if want_enq in ("pallas", "xla"):
            enq = "scatter" if want_enq == "xla" else "pallas"
        elif mesh:
            enq = "pallas"   # enqueue_pallas inside shard_map
        if enq == "pallas":
            try:
                _probe_enqueue(K, sw, interpret)
                stages["enqueue"] = "pallas"
            except Exception as e:  # noqa: BLE001 — interpret-mode fallback
                _raise_on_chip(interpret)
                reasons["enqueue"] = (f"pallas enqueue failed to "
                                      f"build/probe: {type(e).__name__}: "
                                      f"{str(e)[:160]}")
                enq = enqueue_method

    launches: Dict[str, Optional[int]] = {s: None for s in STAGES}
    if front is not None:
        launches["masks"] = 1
        launches["compact"] = launches["fingerprint"] = 0
    elif stages["compact"] == "pallas":
        launches["compact"] = 1
    if stages["insert"] == "fused":
        launches["insert"], launches["enqueue"] = 1, 0
    elif stages["enqueue"] == "pallas":
        launches["enqueue"] = 1
    return V4Plan(stages=stages, reasons=reasons, front=front,
                  compactor=compactor, tail=tail, enqueue_method=enq,
                  launches=launches)
