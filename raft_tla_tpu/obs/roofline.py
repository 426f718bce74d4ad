"""Static roofline model — per-stage HBM-traffic floors from traced
jaxprs, priced against device bandwidth, with a fusion advisor.

NORTHSTAR §c argues from a *bandwidth floor*: the chunk's per-batch data
is small (tens of MB), so at HBM bandwidth the movement floor is
~0.1-0.3 ms/batch while the measured chunk is 89.45 ms — the gap is
kernel granularity, not physics.  Until now that floor was a hand
calculation in a markdown file.  This module derives it mechanically,
per stage, from the SAME stage programs the ChunkProfiler times
(obs/profile.py build_stage_programs), so the model rows and the
measured rows share keys and can be joined into achieved-bandwidth
fractions.

The byte model is a **traffic floor**: every stage INPUT is read once
(or, when it is only ever accessed through gather / dynamic_slice
windows, only the windows are read), every stage OUTPUT is written once
(scatter / dynamic_update_slice outputs count only their update
windows), and intermediates are free — the perfectly-fused ideal.  Loop
bodies (the FPSet probe chain) are counted once: the floor of a
data-dependent walk.  The walk rides :func:`analysis.interp.eval_jaxpr`
with a provenance domain (which stage input does this value alias?) —
the same shared evaluator the effects/bounds passes use, no new tracer.

``achieved fraction = (floor bytes / measured stage seconds) / peak``;
``headroom = measured - floor_time`` is the stage's time above the
bandwidth floor — what fusion can reclaim.  The **fusion advisor**
(:func:`advise`) ranks stages by ``launch_count x per-launch overhead +
headroom`` and names the top candidate: the measurement-driven answer
to "what do we fuse next" that ROADMAP item 1 asks for, replacing
hand-reading NORTHSTAR §c.

Peak bandwidth comes from a device-kind table (TPU generations; a
deliberately conservative DDR figure off-accelerator) overridable with
``RAFT_PEAK_GBPS`` — the ``source`` field always says which was used,
so a fraction computed against an assumed CPU figure can never be
mistaken for a hardware measurement.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

#: Peak HBM bandwidth by device-kind substring (bytes/s).  Datasheet
#: numbers; matched case-insensitively against ``jax.devices()[0]
#: .device_kind``.  Override with RAFT_PEAK_GBPS (GB/s) for hardware
#: not listed here.
PEAK_BW_TABLE = (
    ("v5p", 2765e9),
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v6 lite", 1638e9), ("v6e", 1638e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

#: Off-accelerator fallback: dual-channel DDR4-3200 class (~51 GB/s).
#: The point of a CPU row is shape, not absolutes — the source field
#: marks it "assumed".
CPU_ASSUMED_BW = 51.2e9

_VIEW_PRIMS = frozenset(("reshape", "squeeze", "expand_dims",
                         "broadcast_in_dim"))
_ALIAS_PRIMS = frozenset(("reshape", "squeeze", "expand_dims"))
_WINDOW_READ = frozenset(("gather", "dynamic_slice"))
#: operand-position-0 read-modify-write primitives: traffic is the
#: update window, and the output aliases the operand.
_WINDOW_RMW = frozenset(("scatter", "scatter-add", "scatter_add",
                         "dynamic_update_slice"))


def peak_bandwidth() -> Dict[str, object]:
    """{"bytes_per_sec", "source"} for the first visible device.
    RAFT_PEAK_GBPS (GB/s) overrides; an accelerator whose
    ``device_kind`` is not in :data:`PEAK_BW_TABLE` raises — the CPU
    figure is never lent to a device it does not describe."""
    env = os.environ.get("RAFT_PEAK_GBPS")
    if env:
        # A malformed override falls through to detection.
        try:
            return {"bytes_per_sec": float(env) * 1e9,
                    "source": "RAFT_PEAK_GBPS override"}
        except ValueError:
            import sys
            print(f"perf: ignoring malformed RAFT_PEAK_GBPS={env!r} "
                  f"(want GB/s as a number)", file=sys.stderr)
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return {"bytes_per_sec": CPU_ASSUMED_BW,
                "source": "assumed (cpu ddr-class)"}
    return {"bytes_per_sec": peak_bandwidth_for_kind(dev.device_kind),
            "source": f"datasheet ({dev.device_kind})"}


def peak_bandwidth_for_kind(device_kind: str) -> float:
    """Datasheet peak HBM bytes/s for an accelerator's ``device_kind``;
    raises for a kind :data:`PEAK_BW_TABLE` does not list."""
    kind = (device_kind or "").lower()
    for sub, bw in PEAK_BW_TABLE:
        if sub in kind:
            return bw
    raise ValueError(
        f"no peak bandwidth known for accelerator device_kind "
        f"{device_kind!r}: add it to obs/roofline.py PEAK_BW_TABLE or "
        f"set RAFT_PEAK_GBPS")


# ---------------------------------------------------------------------------
# Provenance traffic walk (analysis/interp.py eval_jaxpr domain)


class _Src:
    """Provenance of one value: the stage-input index it aliases (via
    shape-preserving view prims and loop carries), or None."""

    __slots__ = ("root",)

    def __init__(self, root=None):
        self.root = root


def _aval_bytes(aval) -> int:
    import numpy as np
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * np.dtype(aval.dtype).itemsize


class TrafficDomain:
    """Domain for :func:`analysis.interp.eval_jaxpr` accumulating the
    traffic-floor facts: which stage inputs are read fully vs only
    through windows, window bytes written back into passed-through
    inputs, and the device-op tally (obs/perf.py shares the counting
    rules through :data:`_VIEW_PRIMS`)."""

    def __init__(self):
        self.full_read = set()            # roots read at full extent
        self.win_read: Dict[int, int] = {}    # root -> window bytes
        self.win_written: Dict[int, int] = {}  # root -> window bytes
        self.launches = 0                 # device ops (view prims free)
        self.while_launches = 0           # ...of which inside loop bodies
        self.collectives = 0
        self.collectives_in_loop = 0
        self._in_while = 0
        self.notes = set()
        # Deferred import: perf and roofline lazily import each other
        # (perf consumes the walk, the walk tags perf's collectives).
        from .perf import COLLECTIVE_PRIMS
        self._collective_prims = COLLECTIVE_PRIMS

    # -- domain protocol ----------------------------------------------
    def lift(self, x):
        return x if isinstance(x, _Src) else _Src(None)

    def unknown(self, aval, invals, why):
        for v in invals:
            self._read_full(v)
        self.notes.add(f"opaque call: {why}")
        return _Src(None)

    # -- accumulators --------------------------------------------------
    def _read_full(self, v):
        if isinstance(v, _Src) and v.root is not None:
            self.full_read.add(v.root)

    def _read_win(self, v, nbytes):
        if isinstance(v, _Src) and v.root is not None:
            self.win_read[v.root] = self.win_read.get(v.root, 0) + nbytes

    def _write_win(self, v, nbytes):
        if isinstance(v, _Src) and v.root is not None:
            self.win_written[v.root] = (self.win_written.get(v.root, 0)
                                        + nbytes)

    def _launch(self, name=None):
        self.launches += 1
        if self._in_while:
            self.while_launches += 1
        if name in self._collective_prims:
            self.collectives += 1
            if self._in_while:
                self.collectives_in_loop += 1

    # -- primitive rules -----------------------------------------------
    def apply(self, name, eqn, invals):
        nouts = len(eqn.outvars)
        if name == "while":
            return self._p_while(eqn, invals)
        if name == "cond":
            return self._p_cond(eqn, invals)
        if name == "scan":
            return self._p_scan(eqn, invals)
        if name == "shard_map":
            return self._p_shard_map(eqn, invals)
        if name in _WINDOW_READ:
            self._read_win(invals[0], _aval_bytes(eqn.outvars[0].aval))
            for v in invals[1:]:
                self._read_full(v)
            self._launch(name)
            return [_Src(None) for _ in range(nouts)]
        if name in _WINDOW_RMW:
            upd = (eqn.invars[1].aval if name == "dynamic_update_slice"
                   else eqn.invars[2].aval)
            nb = _aval_bytes(upd)
            self._read_win(invals[0], nb)
            self._write_win(invals[0], nb)
            for v in invals[1:]:
                self._read_full(v)
            self._launch(name)
            out = (_Src(invals[0].root)
                   if isinstance(invals[0], _Src) else _Src(None))
            return [out] + [_Src(None)] * (nouts - 1)
        if name in _ALIAS_PRIMS:
            return [_Src(invals[0].root
                         if isinstance(invals[0], _Src) else None)]
        if name in _VIEW_PRIMS:        # broadcast: splat, fused for free
            for v in invals:
                self._read_full(v)
            return [_Src(None) for _ in range(nouts)]
        for v in invals:
            self._read_full(v)
        self._launch(name)
        return [_Src(None) for _ in range(nouts)]

    # -- control flow ---------------------------------------------------
    def _p_while(self, eqn, invals):
        from ..analysis.interp import eval_jaxpr
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_c = invals[:cn]
        body_c = invals[cn:cn + bn]
        carry = invals[cn + bn:]
        self._in_while += 1
        eval_jaxpr(p["cond_jaxpr"], cond_c + carry, self)
        outs = eval_jaxpr(p["body_jaxpr"], body_c + carry, self)
        self._in_while -= 1
        self.notes.add("loop bodies counted once (traffic/launch floor)")
        joined = []
        for init, out in zip(carry, outs):
            r0 = init.root if isinstance(init, _Src) else None
            r1 = out.root if isinstance(out, _Src) else None
            joined.append(_Src(r0 if r0 == r1 else None))
        return joined

    def _p_cond(self, eqn, invals):
        from ..analysis.interp import eval_jaxpr
        pred, ops = invals[0], invals[1:]
        self._read_full(pred)
        base = (self.launches, self.while_launches, self.collectives,
                self.collectives_in_loop)
        best = base
        outs_all = []
        for br in eqn.params["branches"]:
            (self.launches, self.while_launches, self.collectives,
             self.collectives_in_loop) = base
            outs_all.append(eval_jaxpr(br, list(ops), self))
            now = (self.launches, self.while_launches, self.collectives,
                   self.collectives_in_loop)
            # One branch executes: price each counter at its own branch
            # max (element-wise — tuple max would be lexicographic and
            # drop a cheaper-launch branch's larger collective count).
            best = tuple(max(b, n) for b, n in zip(best, now))
        (self.launches, self.while_launches, self.collectives,
         self.collectives_in_loop) = best
        joined = []
        for i in range(len(eqn.outvars)):
            roots = {o[i].root if isinstance(o[i], _Src) else None
                     for o in outs_all}
            joined.append(_Src(roots.pop() if len(roots) == 1 else None))
        return joined

    def _p_scan(self, eqn, invals):
        from ..analysis.interp import eval_jaxpr
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        consts, carry = invals[:nc], invals[nc:nc + ncar]
        xs = invals[nc + ncar:]
        for v in xs:                    # all iterations read everything
            self._read_full(v)
        self._in_while += 1
        eval_jaxpr(p["jaxpr"], consts + carry + [_Src(None)] * len(xs),
                   self)
        self._in_while -= 1
        self.notes.add("scan body counted once (floor)")
        return [_Src(None) for _ in eqn.outvars]

    def _p_shard_map(self, eqn, invals):
        from ..analysis.interp import eval_jaxpr
        inner = eqn.params.get("jaxpr")
        if inner is not None and not hasattr(inner, "consts"):
            # shard_map carries an OPEN jaxpr; close it for the shared
            # evaluator (per-shard avals: traffic is per-chip).
            try:
                from jax.extend.core import ClosedJaxpr
            except ImportError:
                from jax.core import ClosedJaxpr
            inner = ClosedJaxpr(inner, ())
        if inner is None or len(inner.jaxpr.invars) != len(invals):
            return [self.unknown(v.aval, invals, "shard_map")
                    for v in eqn.outvars]
        outs = eval_jaxpr(inner, list(invals), self)
        self.notes.add("shard_map traffic/launches are per-chip")
        return [o if isinstance(o, _Src) else _Src(None) for o in outs]


def jaxpr_traffic(closed, arg_avals) -> dict:
    """Traffic floor of one traced program: {"bytes_read",
    "bytes_written", "launches", "while_launches", "collectives",
    "collectives_in_loop", "notes"}.  ``arg_avals`` are the FLAT input
    avals in invar order (what the caller traced with)."""
    from ..analysis.interp import eval_jaxpr
    dom = TrafficDomain()
    outs = eval_jaxpr(closed, [_Src(i) for i in range(len(arg_avals))],
                      dom)
    bytes_read = 0
    for i, aval in enumerate(arg_avals):
        full = _aval_bytes(aval)
        if i in dom.full_read:
            bytes_read += full
        elif i in dom.win_read:
            bytes_read += min(full, dom.win_read[i])
    bytes_written = 0
    for o, var in zip(outs, closed.jaxpr.outvars):
        r = o.root if isinstance(o, _Src) else None
        if r is not None:
            if r in dom.win_written:    # carry-through, window-updated
                bytes_written += min(_aval_bytes(var.aval),
                                     dom.win_written[r])
            # unchanged passthrough of an input: nothing written
        else:
            bytes_written += _aval_bytes(var.aval)
    return {"bytes_read": bytes_read, "bytes_written": bytes_written,
            "launches": dom.launches,
            "while_launches": dom.while_launches,
            "collectives": dom.collectives,
            "collectives_in_loop": dom.collectives_in_loop,
            "notes": sorted(dom.notes)}


# ---------------------------------------------------------------------------
# Per-stage traffic over the shared profiler stage programs


def stage_traffic(dims, B: int, K: int, *, pipeline: str = "v1",
                  seen_capacity: int = 1 << 14, ring: int = 16,
                  swarm_pipeline: str = "v1") -> Dict[str, dict]:
    """{stage: traffic dict} for the ChunkProfiler's stage programs —
    v1 granularity (expand/fingerprint/dedup_insert/enqueue) or the
    swarm walk-kernel granularity
    (expand/choose/latch/ring_probe; ``ring``/``swarm_pipeline``
    mirror the swarm engine's dedup capacity and resolved expand
    pipeline) — matching ``chunk_stages`` keys so measured means and
    modeled floors join by name.  Trace-only (eval_shape chains the
    stage signatures); nothing executes or compiles.

    ``seen_capacity`` shapes the probe table aval; it never enters the
    byte model (the insert touches probe WINDOWS, counted per round) —
    any small power of two gives identical results."""
    import jax
    import jax.tree_util as jtu

    from . import profile as profile_mod
    from ..ops import fpset

    if pipeline == "swarm":
        progs = profile_mod.build_stage_programs_swarm(
            dims, B, ring, pipeline=swarm_pipeline)
    else:
        progs = profile_mod.build_stage_programs(dims, B, K)

    def traced(fn, *args):
        closed = jax.make_jaxpr(fn)(*args)
        flat, _ = jtu.tree_flatten(args)
        return jaxpr_traffic(closed, flat)

    import jax.numpy as jnp
    from ..models.schema import state_width
    sw = state_width(dims)
    rows = jax.ShapeDtypeStruct((B, sw), jnp.uint8)
    valid = jax.ShapeDtypeStruct((B,), jnp.bool_)
    out: Dict[str, dict] = {}
    if pipeline == "swarm":
        k = jax.ShapeDtypeStruct((), jnp.int32)
        rh = jax.ShapeDtypeStruct((B, ring), jnp.uint32)
        rp = jax.ShapeDtypeStruct((B,), jnp.int32)
        packed, en, ovf = jax.eval_shape(progs["expand"], rows, valid)
        out["expand"] = traced(progs["expand"], rows, valid)
        choice = jax.eval_shape(progs["choose"], en, k)
        out["choose"] = traced(progs["choose"], en, k)
        _nrows, fp_hi, fp_lo = jax.eval_shape(progs["latch"], packed,
                                              choice)
        out["latch"] = traced(progs["latch"], packed, choice)
        out["ring_probe"] = traced(progs["ring_probe"], rh, rh, rp,
                                   fp_hi, fp_lo, en, ovf)
        for t in out.values():
            t["bytes_total"] = t["bytes_read"] + t["bytes_written"]
        return out
    seen = jax.eval_shape(lambda: fpset.empty(seen_capacity))
    qnext = jax.ShapeDtypeStruct((progs["queue_rows"], sw), jnp.uint8)
    cflat, lane_id, kvalid = jax.eval_shape(progs["expand"], rows,
                                            valid)
    out["expand"] = traced(progs["expand"], rows, valid)
    kstates, kh, kl = jax.eval_shape(progs["fingerprint"], cflat,
                                     lane_id)
    out["fingerprint"] = traced(progs["fingerprint"], cflat, lane_id)
    out["dedup_insert"] = traced(progs["dedup_insert"], seen, kh, kl,
                                 kvalid)
    out["enqueue"] = traced(progs["enqueue"], qnext, kstates, kvalid)
    for t in out.values():
        t["bytes_total"] = t["bytes_read"] + t["bytes_written"]
    return out


# ---------------------------------------------------------------------------
# Roofline rows + fusion advisor


def build_roofline(traffic: Dict[str, dict],
                   stage_means: Optional[Dict[str, float]],
                   peak: Dict[str, object]) -> Dict[str, dict]:
    """Join the modeled floors with the ChunkProfiler's measured stage
    means into roofline rows.  Rows without a measurement (profiler off,
    mesh) keep floors + launches with null achieved fields — the model
    half still renders, it just cannot claim a fraction."""
    bw = float(peak["bytes_per_sec"])
    means = stage_means or {}
    rows: Dict[str, dict] = {}
    for stage, t in traffic.items():
        floor_s = t["bytes_total"] / bw if bw else None
        mean_s = means.get(stage)
        row = {
            "bytes_read": t["bytes_read"],
            "bytes_written": t["bytes_written"],
            "bytes_total": t["bytes_total"],
            "launches": t["launches"],
            "floor_seconds": round(floor_s, 9) if floor_s else floor_s,
            "mean_seconds": (round(mean_s, 6) if mean_s is not None
                             else None),
            "achieved_gbps": None,
            "bandwidth_fraction": None,
            "headroom_seconds": None,
            "notes": t.get("notes", []),
        }
        if mean_s:
            achieved = t["bytes_total"] / mean_s
            row["achieved_gbps"] = round(achieved / 1e9, 3)
            row["bandwidth_fraction"] = round(achieved / bw, 6) if bw \
                else None
            row["headroom_seconds"] = round(
                max(0.0, mean_s - (floor_s or 0.0)), 6)
        rows[stage] = row
    return rows


def advise(rows: Dict[str, dict], overhead_seconds: float) -> dict:
    """Rank the stages by reclaimable time — ``launches x per-launch
    overhead + bandwidth headroom`` — and name the top fusion candidate.
    Stages without a measured mean score on the launch tax alone (their
    headroom is unknowable statically), so the advisor still answers on
    a profiler-less run, just with less evidence; ``basis`` says which
    case each row is."""
    ranking = []
    for stage, row in rows.items():
        tax = row["launches"] * overhead_seconds
        headroom = row["headroom_seconds"]
        score = tax + (headroom or 0.0)
        ranking.append({
            "stage": stage,
            "score_seconds": round(score, 6),
            "launch_tax_seconds": round(tax, 6),
            "headroom_seconds": headroom,
            "launches": row["launches"],
            "bandwidth_fraction": row["bandwidth_fraction"],
            "basis": ("measured+model" if headroom is not None
                      else "launch-model-only"),
        })
    ranking.sort(key=lambda r: (-r["score_seconds"], r["stage"]))
    if not ranking:
        return {"ranking": [], "top": None, "verdict": "no stages"}
    top = ranking[0]
    frac = top["bandwidth_fraction"]
    verdict = (
        f"fuse '{top['stage']}' next: {top['launches']} device ops/batch "
        f"(~{top['launch_tax_seconds'] * 1e3:.2f} ms launch tax)"
        + (f", {top['headroom_seconds'] * 1e3:.2f} ms above the "
           f"bandwidth floor"
           f" ({frac:.1%} of peak achieved)" if top["headroom_seconds"]
           is not None and frac is not None else ", unmeasured headroom"))
    return {"ranking": ranking, "top": top["stage"], "verdict": verdict}
