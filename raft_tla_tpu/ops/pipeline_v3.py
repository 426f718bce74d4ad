"""v3 fused-chunk stage plan — glue between EngineConfig.pipeline="v3"
and the Pallas stage kernels.

The v3 pipeline is the v2 delta pipeline (models/actions2.py semantics,
bit-identical by construction) with the chunk's stages progressively
moved into Pallas kernels so the K-lane survivor window stops
round-tripping to HBM between stages:

    masks        guards-only enabled/overflow masks      [always XLA]
    compact      ops/compact_pallas.py sequential scan   [Pallas]
    fingerprint  v2 delta fingerprints + sparse rows     [always XLA]
    insert       ops/fused_tail_pallas.py                [Pallas, fused
    enqueue        probe/insert -> DMA append             with insert]

Two stages are XLA by design, not by fallback: the masks stage is the
whole model's guard alphabet (a jaxpr program XLA already fuses into
one kernel — a Pallas port would re-implement the spec), and the delta
fingerprint is sparse gather arithmetic over the parent struct that
only wins in Pallas once the struct itself is VMEM-resident (the
staged next step).  The other stages resolve per platform/engine.  On
the TPU a Pallas kernel the compiler refuses fails the engine build
with the compiler's message: a v3 run there either runs its kernels or
does not run.  Off the chip (interpret mode) a stage whose kernel
cannot be built or probed degrades to the XLA lowering and records why
(``V3Plan.stages`` / ``reasons``, surfaced on
``EngineResult.fused_stages``).

Platform policy (overridable per stage with ``force`` for tests):

- TPU single chip: compact=pallas, insert+enqueue=fused.
- CPU single chip: compact=xla (the sequential B*G scan is priced for
  VMEM residency; interpret-mode emulation would dominate the chunk),
  insert+enqueue=fused in interpret mode — the correctness-bearing
  fused tail runs everywhere.
- mesh: compact=xla (P is pmin-replicated across chips — a collective
  cannot live inside a Pallas stage), insert=xla (owner-routed
  all_to_all dedup is a collective), enqueue=pallas
  (ops/enqueue_pallas.py rides inside shard_map).
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

STAGES = ("masks", "compact", "fingerprint", "insert", "enqueue")


class V3Plan(NamedTuple):
    stages: Dict[str, str]       # stage -> "xla" | "pallas" | "fused"
    reasons: Dict[str, str]      # stage -> why it is not Pallas/fused
    compactor: Optional[Callable]   # Pallas compactor, or None = XLA
    tail: Optional[Callable]     # fused insert+enqueue, or None = split
    enqueue_method: str          # chunk-body enqueue when tail is None
    # Expected kernel launches per stage, per batch: a Pallas/fused
    # stage is exactly ONE kernel (the fused insert+enqueue pair share
    # it); an XLA stage is None here — its pre-fusion device-op count
    # comes from the launch model's jaxpr walk (obs/perf.py), which
    # this plan cannot know without the model's kernels.  Makes the
    # fused-vs-unfused launch delta first-class on EngineResult.perf.
    # Default None, not {}: a NamedTuple field default is CLASS-level,
    # so a dict here would be shared (and mutable) across instances.
    launches: Optional[Dict[str, Optional[int]]] = None


def describe(plan: V3Plan) -> str:
    """One-line stage map for logs/results: "masks=xla compact=pallas ..."."""
    return " ".join(f"{s}={plan.stages[s]}" for s in STAGES)


def resolve_plan(B: int, G: int, K: int, *, Q: int, sw: int = 8,
                 mesh: bool = False, enqueue_method: str = "scatter",
                 force: Optional[Dict[str, str]] = None,
                 interpret: Optional[bool] = None) -> V3Plan:
    """Resolve the per-stage lowering for one engine build.

    ``Q`` is the live next-queue capacity (the fused tail's trash base);
    ``sw`` the packed state-row width (the tail probe's row shape).
    ``force`` overrides the platform policy per stage ({"compact":
    "pallas", ...}); "insert"/"enqueue" accept "fused" jointly — except
    on the mesh, whose collective-coupled stages are not forceable.
    Every Pallas choice is build-and-probe verified here at the REAL
    per-program shapes (the full [B, G] mask; the tail's real K-query
    grid and sw-byte rows, over small HBM extents).  On the TPU a kernel
    the compiler refuses fails the build HERE with the compiler's error
    (see :func:`_raise_on_chip`); in interpret mode, off the chip, it
    falls back with a recorded reason.  Residual risk: a
    lowering failure keyed to the total HBM extent (table/queue length)
    would still surface at the first chunk compile — extents are the
    one thing the probe shrinks."""
    import jax
    force = dict(force or {})
    # Validate up front: a typo'd stage name or value must not silently
    # degrade to the platform policy (a "forced full-Pallas" test would
    # then compare XLA against XLA and pass vacuously).
    _VALID = {"masks": ("xla",), "compact": ("pallas", "xla"),
              "fingerprint": ("xla",), "insert": ("fused", "xla"),
              "enqueue": ("fused", "pallas", "xla")}
    for stage, impl in force.items():
        if stage not in _VALID or impl not in _VALID[stage]:
            raise ValueError(
                f"v3_force_stages: unknown {stage!r}={impl!r}; valid: "
                + ", ".join(f"{s}∈{v}" for s, v in _VALID.items()))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    stages = {s: "xla" for s in STAGES}
    reasons = {
        "masks": "model guard alphabet; XLA fuses the guards-only pass",
        "fingerprint": "delta arithmetic over the parent struct; Pallas "
                       "win needs the VMEM-resident struct window "
                       "(staged next)",
    }
    compactor = None
    tail = None

    # -- compact stage -------------------------------------------------
    if mesh:
        # Not overridable by force: the mesh compactor's P reduction is
        # a pmin collective (and the engine would ignore a forced
        # Pallas compactor anyway) — honoring the force here would make
        # fused_stages claim a lowering that never runs.
        want_compact = "xla"
        reasons["compact"] = ("P is pmin-replicated across chips; a "
                              "collective cannot live inside a "
                              "Pallas stage")
    else:
        want_compact = force.get("compact")
    if want_compact is None:
        if interpret:
            want_compact = "xla"
            reasons["compact"] = ("sequential B*G scan is priced for TPU "
                                  "VMEM residency; interpret-mode "
                                  "emulation would dominate the CPU chunk")
        else:
            want_compact = "pallas"
    if want_compact == "pallas":
        try:
            from . import compact_pallas
            cand = compact_pallas.build_compactor(B, G, K,
                                                  interpret=interpret)
            import jax.numpy as jnp
            jax.block_until_ready(cand(jnp.zeros((B, G), bool)))
            compactor = cand
            stages["compact"] = "pallas"
            reasons.pop("compact", None)
        except Exception as e:  # noqa: BLE001 — interpret-mode fallback
            _raise_on_chip(interpret)
            reasons["compact"] = (f"pallas compact failed to build/probe: "
                                  f"{type(e).__name__}: {str(e)[:160]}")
    elif "compact" not in reasons:
        reasons["compact"] = "forced to xla"

    # -- insert + enqueue (fused tail) ---------------------------------
    if mesh:
        # Not overridable by force: the mesh insert IS the owner-routed
        # all_to_all dedup — a per-chip fused tail would dedup locally
        # and silently double-count cross-chip duplicates.
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
    # Expected launches per stage (obs/perf.py consumes this): each
    # resolved Pallas kernel is exactly one launch; the fused tail is
    # ONE kernel covering insert+enqueue (so enqueue's own count is 0
    # when fused — summing the dict never double-prices the pair); XLA
    # stages are None (their pre-fusion op count is the launch model's
    # to derive from the traced jaxpr).
    launches: Dict[str, Optional[int]] = {s: None for s in STAGES}
    if stages["compact"] == "pallas":
        launches["compact"] = 1
    if stages["insert"] == "fused":
        launches["insert"], launches["enqueue"] = 1, 0
    elif stages["enqueue"] == "pallas":
        launches["enqueue"] = 1
    return V3Plan(stages=stages, reasons=reasons, compactor=compactor,
                  tail=tail, enqueue_method=enq, launches=launches)


def _raise_on_chip(interpret: bool) -> None:
    """Called from an ``except`` block around a Pallas build/probe.  A
    real Mosaic lowering (``interpret`` False: the platform is the TPU)
    that was asked for and refused is an error carrying the compiler's
    message — never a substituted XLA stage.  Only interpret mode (off
    the chip) degrades the stage with a recorded reason."""
    if not interpret:
        raise


def _probe_enqueue(K: int, sw: int, interpret: bool) -> None:
    """Compile-and-run the run-coalesced Pallas enqueue once at the real
    per-copy shapes (K rows of sw bytes, empty mask) so lowering errors
    degrade the stage at plan time.  The probe runs outside shard_map —
    the kernel contains no collectives, so a per-chip lowering that
    compiles solo compiles identically inside the mesh program."""
    import jax
    import jax.numpy as jnp

    from . import enqueue_pallas
    out = enqueue_pallas.enqueue(
        jnp.zeros((2 * K, sw), jnp.uint8), jnp.int32(0),
        jnp.zeros((K, sw), jnp.uint8), jnp.zeros((K,), bool),
        interpret=interpret)
    jax.block_until_ready(out)


def _probe_tail(K: int, sw: int, interpret: bool) -> None:
    """Compile-and-run the fused tail once at the REAL per-program
    shapes — K queries (the real block size and grid), sw-byte rows —
    over small HBM extents (a 256-slot table, a K-row queue with
    trash_base=0), so per-block Mosaic lowering errors surface at plan
    time, not at the first chunk.  Only the total table/queue extents
    (and the trash-base constant) differ from the engine's call."""
    import jax
    import jax.numpy as jnp

    from . import fpset, fused_tail_pallas
    seen = fpset.empty(256)
    out = fused_tail_pallas.insert_enqueue(
        seen,
        jnp.arange(K, dtype=jnp.uint32),
        jnp.arange(K, dtype=jnp.uint32),
        jnp.zeros((K,), bool),          # all-invalid: no probe walking,
        jnp.zeros((K, sw), jnp.uint8),  # the run is trash-copies only
        jnp.zeros((K,), bool),
        jnp.zeros((K, sw), jnp.uint8),
        jnp.int32(0),
        0, interpret=interpret)
    jax.block_until_ready(out)
