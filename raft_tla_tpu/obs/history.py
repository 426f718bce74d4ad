"""Cross-run history ledger — the durable trajectory of measurements.

Every prior surface (bench JSON, run events, reports) is per-run; the
trajectory across runs lived in hand-curated ``BENCH_rNN.json`` files
and round notes — which is exactly how the PR 7 trap happened (an
absolute rate silently compared across a ~4x slower container, because
nothing recorded which host produced which number).  This module is the
append-only JSONL ledger closing that gap: one line per run, recording

- identity: ``cfg_fingerprint`` (sha256 of the cfg text) +
  ``model_fingerprint`` (sha256 of ``repr(dims)``) + the full
  ``host_fingerprint`` (obs/flight.py) and its short ``host_key``;
- outcome: verdict / stop_reason, distinct / generated / diameter /
  wall seconds, headline rates;
- how it ran: the resolved pipeline;
- the ``statespace`` report summary (obs/report.py ``summarize``);
- for bench runs, the full bench JSON (``bench``) — which is what lets
  ``scripts/bench_diff.py --history`` resolve its baseline from the
  ledger (newest same-host-key bench entry) instead of a hand-picked
  file.

Writers: ``check --history PATH`` / the ``HISTORY`` cfg directive
(cli.py) and ``BENCH_HISTORY`` (bench.py).  Readers:
``scripts/bench_history.py`` (trajectory table, ``--import-legacy``
seeding from the committed BENCH_r*/MULTICHIP_r* files) and
``scripts/bench_diff.py`` (baseline auto-resolution).  Zero-dep and
jax-free, like the rest of ``obs/``.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import List, Optional

ENTRY_VERSION = 1

#: host_fingerprint keys that decide comparability — hostname alone is
#: NOT identity (same container class, new pod).  THE single
#: definition: scripts/bench_diff.py imports this for its cross-host
#: WARNING, so the ledger's host_key and the diff's warning can never
#: disagree about what "same host" means.
HOST_KEYS = ("cpu_model", "device_kind", "device_count", "platform",
             "jax", "jaxlib")


def host_key(fp: Optional[dict]) -> Optional[str]:
    """Short stable digest of the comparability-deciding fingerprint
    fields; None for a missing/empty fingerprint (legacy imports) — an
    unknown host must render as unknown, never as a real key."""
    if not fp or not any(fp.get(k) for k in HOST_KEYS):
        return None
    blob = json.dumps([fp.get(k) for k in HOST_KEYS])
    return hashlib.sha256(blob.encode()).hexdigest()[:10]


def fingerprint_text(text) -> str:
    if isinstance(text, str):
        text = text.encode()
    return hashlib.sha256(text).hexdigest()


def make_entry(kind: str, *, label: Optional[str] = None,
               cfg_text: Optional[str] = None,
               dims=None, host_fingerprint: Optional[dict] = None,
               verdict: Optional[str] = None,
               stop_reason: Optional[str] = None,
               distinct: Optional[int] = None,
               generated: Optional[int] = None,
               diameter: Optional[int] = None,
               wall_seconds: Optional[float] = None,
               distinct_per_sec: Optional[float] = None,
               generated_per_sec: Optional[float] = None,
               pipeline: Optional[str] = None,
               report_summary: Optional[dict] = None,
               bench: Optional[dict] = None,
               ts: Optional[float] = None,
               extra: Optional[dict] = None) -> dict:
    """One ledger line.  ``kind`` is ``check`` / ``bench`` / ``server``
    (the checker service's executed-job entries, which carry ``job_id``
    and ``tenant`` via ``extra``) / whatever a legacy import labels;
    unknown fields stay None rather than absent so every line has the
    same shape.  ``extra`` keys are merged last (they may not shadow
    the schema: a colliding key raises)."""
    out = {
        "v": ENTRY_VERSION,
        "ts": round(time.time() if ts is None else ts, 3),
        "kind": kind,
        "label": label,
        "cfg_fingerprint": (fingerprint_text(cfg_text)
                            if cfg_text is not None else None),
        "model_fingerprint": (fingerprint_text(repr(dims))
                              if dims is not None else None),
        "host_fingerprint": dict(host_fingerprint or {}) or None,
        "host_key": host_key(host_fingerprint),
        "verdict": verdict,
        "stop_reason": stop_reason,
        "distinct": distinct,
        "generated": generated,
        "diameter": diameter,
        "wall_seconds": wall_seconds,
        "distinct_per_sec": distinct_per_sec,
        "generated_per_sec": generated_per_sec,
        "pipeline": pipeline,
        "report": dict(report_summary or {}) or None,
        "bench": bench,
    }
    for k, v in (extra or {}).items():
        if k in out:
            raise ValueError(f"extra key {k!r} shadows a ledger field")
        out[k] = v
    return out


def entry_from_result(kind: str, res, *, cfg_text=None, dims=None,
                      host_fingerprint=None, label=None,
                      extra=None) -> dict:
    """Ledger entry from a finished ``EngineResult`` (the ``check
    --history`` writer).  Lazy import of report.summarize keeps this
    module's import graph flat."""
    from .report import summarize
    wall = float(getattr(res, "wall_seconds", 0.0) or 0.0)
    verdict = ("violation" if getattr(res, "violation", None) is not None
               else "deadlock" if getattr(res, "deadlock", None)
               is not None else "ok")
    return make_entry(
        kind, label=label, cfg_text=cfg_text, dims=dims,
        host_fingerprint=host_fingerprint,
        verdict=verdict, stop_reason=res.stop_reason,
        distinct=res.distinct, generated=res.generated,
        diameter=res.diameter, wall_seconds=round(wall, 3),
        distinct_per_sec=round(res.distinct / wall, 1) if wall else None,
        generated_per_sec=round(res.generated / wall, 1) if wall else None,
        pipeline=res.pipeline or None,
        report_summary=summarize(getattr(res, "report", None)),
        extra=extra)


def entry_from_bench(doc: dict, *, label=None, kind="bench",
                     ts=None) -> dict:
    """Ledger entry from one bench.py JSON object (raw form)."""
    from .report import summarize
    return make_entry(
        kind, label=label, ts=ts,
        host_fingerprint=doc.get("host_fingerprint"),
        verdict="ok" if doc.get("stop_reason") != "violation" else
        "violation",
        stop_reason=doc.get("stop_reason"),
        distinct=doc.get("distinct_states"),
        generated=doc.get("generated_states"),
        diameter=doc.get("diameter"),
        wall_seconds=doc.get("wall_s"),
        distinct_per_sec=doc.get("value"),
        generated_per_sec=doc.get("generated_per_sec"),
        pipeline=doc.get("pipeline"),
        report_summary=summarize(doc.get("report")),
        bench=doc)


def append_entry(path: str, entry: dict, default=None) -> None:
    """Append one JSONL line (O_APPEND single write — concurrent
    appenders on a local filesystem interleave at line granularity).
    ONE definition of the append idiom: the serving job journal
    (serving/jobs.py) writes through here too (with ``default=str``
    for its richer records), so a future durability change — fsync,
    line-length guard — lands in every append-only log at once."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(entry, sort_keys=True, default=default)
                + "\n")


def read_history(path: str) -> List[dict]:
    """Parse the ledger; raises FileNotFoundError/ValueError on a
    missing or corrupt file (the bench_diff gate convention: a gate
    that cannot read its evidence fails loudly)."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"run-history ledger missing: {path}")
    out = []
    with open(path, encoding="utf-8") as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{ln}: malformed ledger line "
                                 f"({e})")
            if not isinstance(rec, dict) or "kind" not in rec:
                raise ValueError(f"{path}:{ln}: not a ledger entry: "
                                 f"{line[:120]}")
            out.append(rec)
    return out


def resolve_baseline(path: str, host_fp: Optional[dict],
                     kind: str = "bench",
                     exclude_bench: Optional[dict] = None
                     ) -> Optional[dict]:
    """The newest ledger entry of ``kind`` whose host_key matches
    ``host_fp``'s AND which carries an embedded bench object — the
    auto-resolved bench_diff baseline.  None when no same-host entry
    exists (cross-host baselines must be picked deliberately, never
    silently — the whole point of the ledger).

    ``exclude_bench``: the CANDIDATE's bench object.  The documented
    workflow records a run with BENCH_HISTORY and then gates its own
    stdout JSON with ``bench_diff --history`` — without this exclusion
    the newest same-host entry would be the candidate's own ledger
    line, and the gate would vacuously self-compare (0% change hides a
    real regression).  Identity: matching ``run_id`` (bench.py stamps
    one into both the printed JSON and the ledger copy — robust to the
    captured file being annotated or reformatted later), falling back
    to whole-document equality for run_id-less docs."""
    key = host_key(host_fp)
    if key is None:
        return None

    def is_candidate(bench: dict) -> bool:
        if exclude_bench is None:
            return False
        rid, crid = bench.get("run_id"), exclude_bench.get("run_id")
        if rid is not None and crid is not None:
            return rid == crid
        return bench == exclude_bench

    for rec in reversed(read_history(path)):
        if rec.get("kind") == kind and rec.get("host_key") == key \
                and rec.get("bench") \
                and not is_candidate(rec["bench"]):
            return rec
    return None


def hunt_columns(entry: dict):
    """(saturation, novel rate, time-to-violation seconds) from a swarm
    entry's hunt summary (obs/hunt.py summarize) — carried either as
    the entry's own ``hunt`` extra (``check --mode swarm --history``,
    the server's swarm leg) or inside the embedded bench doc
    (BENCH_MODE=swarm).  (None, None, None) for exhaustive rows and
    hunt-less swarm rows, so the trajectory renders '--'."""
    hunt = entry.get("hunt")
    if not isinstance(hunt, dict):
        hunt = (entry.get("bench") or {}).get("hunt")
    if not isinstance(hunt, dict):
        return None, None, None
    return (hunt.get("saturation"), hunt.get("novel_rate"),
            hunt.get("time_to_violation_seconds"))


def render_table(entries: List[dict], hunt: bool = False) -> str:
    """The trajectory table (scripts/bench_history.py): one row per
    entry, host-key column + explicit flags where adjacent entries are
    NOT rate-comparable (different or unknown host) — the r05 trap,
    rendered impossible to miss.  ``hunt=True`` adds the
    hunt-observatory columns (coverage saturation + novelty rate +
    time-to-violation from obs/hunt.py summaries) so a swarm
    trajectory answers "is each round's hunt saturating sooner /
    latching faster" at a glance."""
    hcols = (f" {'satur':>7s} {'novel':>7s} {'t-viol':>7s}") if hunt \
        else ""
    lines = [f"{'#':>3s} {'label':20s} {'kind':9s} {'host':10s} "
             f"{'distinct/s':>12s} {'distinct':>12s} {'diam':>5s} "
             f"{'verdict':10s}{hcols} flags"]
    first = object()
    prev_key = first              # sentinel: first row never flags
    warnings = []
    for i, e in enumerate(entries):
        key = e.get("host_key")
        flags = []
        if key is None:
            flags.append("host?")
        if prev_key is not first and key != prev_key:
            flags.append("HOST-CHANGE")
            warnings.append(
                f"entry {i} ({e.get('label') or e.get('ts')}): host "
                f"changed ({prev_key or 'unknown'} -> "
                f"{key or 'unknown'}) — rates before/after are not "
                f"comparable")
        rate = e.get("distinct_per_sec")
        # Swarm-dialect rows (kind=swarm, from check --mode swarm or
        # BENCH_MODE=swarm): the rate column carries the tier's steps/s
        # headline, flagged as such — a walker's rate sitting in an
        # exhaustive distinct/s trajectory must read as a different
        # dialect, not as a host anomaly or a throughput jump.  These
        # rows carry a real host_fingerprint, so the host?/HOST-CHANGE
        # flags stay what they mean.
        sw = e.get("swarm")
        if sw is None and isinstance(e.get("bench"), dict) \
                and e["bench"].get("mode") == "swarm":
            sw = e["bench"]
        if isinstance(sw, dict):
            rate = sw.get("steps_per_sec", rate)
            flags.append("steps/s")
        d, dia = e.get("distinct"), e.get("diameter")
        row = (f"{i:3d} {str(e.get('label') or '-'):20s} "
               f"{str(e.get('kind') or '-'):9s} {str(key or '?'):10s} "
               + (f"{rate:12,.1f}" if isinstance(rate, (int, float))
                  else f"{'--':>12s}")
               + (f" {d:12,d}" if isinstance(d, int)
                  else f" {'--':>12s}")
               + (f" {dia:5d}" if isinstance(dia, int)
                  else f" {'--':>5s}")
               + f" {str(e.get('verdict') or '?'):10s}")
        if hunt:
            sat, novel, ttv = hunt_columns(e)
            row += ((f" {sat:7.1%}" if isinstance(sat, (int, float))
                     else f" {'--':>7s}")
                    + (f" {novel:7.1%}"
                       if isinstance(novel, (int, float))
                       else f" {'--':>7s}")
                    + (f" {ttv:6.1f}s"
                       if isinstance(ttv, (int, float))
                       else f" {'--':>7s}"))
        row += " " + (",".join(flags) if flags else "-")
        lines.append(row)
        prev_key = key
    for w in warnings:
        lines.append(f"WARNING: {w}")
    return "\n".join(lines)
