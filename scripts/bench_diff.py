"""Diff two bench JSONs and gate on regressions — the enforceable form
of the BENCH_r* trajectory.

``bench.py`` (and the round-note harness that wraps it into
``BENCH_rNN.json``) emits one JSON object per run: headline
states/s, per-phase host seconds, and the TLC-style ``coverage``
object (obs/coverage.py).  This script compares OLD vs NEW along all
three axes and exits nonzero when NEW regresses past the thresholds —
so CI (and a human mid-perf-PR) gets a yes/no instead of two JSON
blobs to eyeball.

    python scripts/bench_diff.py BENCH_r04.json BENCH_r05.json
    python scripts/bench_diff.py old.json new.json --max-regress 0.05

Input forms accepted: the raw bench.py object, or the ``BENCH_rNN``
wrapper ``{"cmd", "rc", "tail", "parsed": {...}}`` (the parsed object
is used; a null ``parsed`` — a bench run that never emitted JSON — is
malformed input, exit 2).

Comparison rules (each axis only when BOTH runs carry it — early
BENCH_r04/r05 files predate coverage and still diff):

- headline ``value`` (distinct states/s) and ``generated_per_sec``:
  regression when NEW < OLD * (1 - max_regress).
- per-phase seconds: normalized to seconds per million distinct states
  (budget-length independence), compared per phase when the OLD phase
  is at least ``--phase-floor`` of total phase time (noise floor for
  sub-percent phases); threshold ``--phase-max-regress``.
- POR pruned fraction (``pruned / (pruned + generated)`` from the
  coverage object): compared whenever either side pruned anything; a
  candidate whose fraction falls more than ``--pruned-drift`` points
  below the baseline regresses — a certified reduction collapsing back
  to full expansion must fail loudly.
- coverage mix: per-action share of total generated; an action whose
  share moves more than ``--coverage-drift`` (absolute percentage
  points) is flagged.  This is a semantics drift detector, not a perf
  number — identical models must produce identical mixes up to
  duration-budget truncation — so it defaults loose (5 pts).

- swarm dialect (``BENCH_MODE=swarm`` documents, ``mode: "swarm"``):
  when BOTH sides are swarm, the steps/s headline plus walks/s,
  visited/s, and the time-to-first-counterexample are gated; when the
  two sides speak DIFFERENT dialects, the diff folds to a note with
  both headlines reported and nothing gated — an exhaustive distinct/s
  number and a swarm steps/s number measure different things.  When
  both sides also embed a hunt summary (obs/hunt.py), the coverage
  saturation and per-bucket novelty trajectory are gated under
  ``--hunt-drift``: a novelty curve that moved means the walks are
  exploring differently, which is a semantics change, not a perf one.

Additionally, when both runs embed a ``host_fingerprint`` (bench.py,
BENCH_r06+), mismatched hardware/stack identity prints a loud
cross-host WARNING note — absolute rates measured on different hosts
must never be silently read as a trajectory.

Improvements are reported but never fail.  Exit codes: 0 pass, 1 at
least one regression, 2 malformed input/usage (consistent with the
validate_run_events convention: a gate that cannot read its evidence
fails loudly, not silently green).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_bench(path: str) -> dict:
    """Load a bench JSON in either accepted form; raise ValueError on
    anything that is not a bench result object."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"{path}: cannot load bench JSON: {e}")
    if isinstance(data, dict) and "parsed" in data:
        data = data["parsed"]           # BENCH_rNN wrapper
    if not isinstance(data, dict) or "value" not in data:
        raise ValueError(
            f"{path}: not a bench result (no 'value' field; a "
            f"BENCH_r* wrapper whose run emitted no JSON has "
            f"parsed=null)")
    return data


def _ratio_regress(old: float, new: float, thresh: float) -> bool:
    """True when NEW is worse than OLD by more than ``thresh`` (rates:
    lower is worse — callers flip sign for costs)."""
    return old > 0 and new < old * (1.0 - thresh)


class Diff:
    """Accumulates findings; renders the report and the exit code."""

    def __init__(self):
        self.regressions = []
        self.notes = []

    def regress(self, msg: str) -> None:
        self.regressions.append(msg)

    def note(self, msg: str) -> None:
        self.notes.append(msg)

    def render(self, stream=sys.stdout) -> int:
        for n in self.notes:
            print(f"  {n}", file=stream)
        for r in self.regressions:
            print(f"  REGRESSION: {r}", file=stream)
        verdict = ("FAIL" if self.regressions else "PASS")
        print(f"bench_diff: {verdict} "
              f"({len(self.regressions)} regression(s))", file=stream)
        return 1 if self.regressions else 0


#: host_fingerprint keys that make absolute rates incomparable when
#: they differ (hostname alone does not: same container class, new pod).
#: ONE definition, shared with the run ledger's host_key
#: (obs/history.py) — the cross-host WARNING here and resolve_baseline's
#: same-host matching must never disagree about what "same host" means.
from raft_tla_tpu.obs.history import HOST_KEYS as _FINGERPRINT_KEYS  # noqa: E402


def diff_host(old: dict, new: dict, d: Diff):
    """Cross-host guard: when both benches carry a host_fingerprint
    (bench.py, obs/flight.py) and they disagree on hardware/stack
    identity, say so LOUDLY in the notes — the BENCH_r05 trap was an
    absolute number silently compared across a ~4x slower container.
    A note, not a regression: cross-host diffs are sometimes exactly
    what the operator wants (e.g. CPU vs TPU), they just must never be
    read as a regression gate."""
    of, nf = old.get("host_fingerprint"), new.get("host_fingerprint")
    if not of or not nf:
        return
    diffs = [k for k in _FINGERPRINT_KEYS if of.get(k) != nf.get(k)]
    if diffs:
        d.note("WARNING: benches ran on DIFFERENT hosts/stacks — "
               "absolute rates are not comparable; fields: "
               + ", ".join(f"{k}: {of.get(k)!r} -> {nf.get(k)!r}"
                           for k in diffs))
    else:
        d.note("host fingerprints match "
               f"({of.get('cpu_model') or 'unknown cpu'}, "
               f"{of.get('device_kind') or of.get('platform')})")


def diff_headline(old: dict, new: dict, d: Diff, max_regress: float):
    unit = old.get("unit", "states/s")
    for key, label in (("value", f"headline ({unit})"),
                       ("generated_per_sec", "generated states/s")):
        ov, nv = old.get(key), new.get(key)
        if ov is None or nv is None:
            continue
        pct = (nv - ov) / ov * 100.0 if ov else 0.0
        d.note(f"{label}: {ov:,.1f} -> {nv:,.1f} ({pct:+.1f}%)")
        if _ratio_regress(ov, nv, max_regress):
            d.regress(f"{label} moved {pct:+.1f}% "
                      f"(> {max_regress:.0%} allowed): {ov:,.1f} -> "
                      f"{nv:,.1f}")


def bench_mode(doc: dict) -> str:
    """Which bench dialect a document speaks: ``swarm`` (bench.py
    BENCH_MODE=swarm — steps/s headline, walks/visited rates,
    violation_at_seconds) or ``exhaustive`` (the classic distinct/s
    headline; legacy files predate the key)."""
    return doc.get("mode", "exhaustive")


def diff_swarm(old: dict, new: dict, d: Diff, max_regress: float):
    """Swarm-dialect axes (both sides BENCH_MODE=swarm): the walk and
    visit rates regress like the headline, and the time-to-first-
    counterexample regresses when the candidate finds its violation
    slower than allowed — or stops finding one the baseline found."""
    for key, label in (("walks_per_sec", "walks/s"),
                       ("visited_per_sec", "visited states/s")):
        ov, nv = old.get(key), new.get(key)
        if ov is None or nv is None:
            continue
        pct = (nv - ov) / ov * 100.0 if ov else 0.0
        d.note(f"swarm {label}: {ov:,.1f} -> {nv:,.1f} ({pct:+.1f}%)")
        if _ratio_regress(ov, nv, max_regress):
            d.regress(f"swarm {label} moved {pct:+.1f}% "
                      f"(> {max_regress:.0%} allowed): {ov:,.1f} -> "
                      f"{nv:,.1f}")
    ov, nv = old.get("violation_at_seconds"), new.get("violation_at_seconds")
    if ov is None and nv is None:
        return
    d.note(f"violation found at: "
           f"{'-' if ov is None else f'{ov:.2f}s'} -> "
           f"{'-' if nv is None else f'{nv:.2f}s'}")
    if ov is not None and nv is None:
        d.regress(f"baseline found its violation at {ov:.2f}s; the "
                  f"candidate found none in its budget")
    elif ov is not None and nv is not None \
            and ov > 0 and nv > ov * (1.0 + max_regress):
        d.regress(f"time-to-violation rose "
                  f"{(nv - ov) / ov * 100.0:.1f}% "
                  f"(> {max_regress:.0%} allowed): {ov:.2f}s -> "
                  f"{nv:.2f}s")


def diff_hunt(old: dict, new: dict, d: Diff, drift: float):
    """Hunt-observatory axes (both sides swarm with a ``hunt`` summary
    — obs/hunt.py summarize): coverage saturation and the novelty rate
    are reported, and the novelty CURVE is drift-gated — same seed and
    budget should trace the same novelty trajectory, so any bucket of
    the refolded curve moving more than ``--hunt-drift`` (absolute
    novel-rate points) flags a behavioral change in the walk decisions
    (diversification, ring, PRNG), not mere throughput noise.  A
    saturation estimate falling more than the same drift regresses
    too: the candidate's hunt is measurably further from done."""
    oh, nh = old.get("hunt"), new.get("hunt")
    if not isinstance(oh, dict) or not isinstance(nh, dict):
        if isinstance(oh, dict) or isinstance(nh, dict):
            d.note("hunt summary present on one side only "
                   "(observatory toggled?) — hunt axes skipped")
        return
    for key, label, pct in (("saturation", "hunt saturation", True),
                            ("novel_rate", "hunt novel rate", True),
                            ("distinct_observed",
                             "hunt distinct observed", False)):
        ov, nv = oh.get(key), nh.get(key)
        if ov is None or nv is None:
            continue
        if pct:
            d.note(f"{label}: {ov:.1%} -> {nv:.1%}")
        else:
            d.note(f"{label}: {ov:,} -> {nv:,}")
    ov, nv = oh.get("saturation"), nh.get("saturation")
    if ov is not None and nv is not None and ov - nv > drift:
        d.regress(f"hunt saturation fell {ov - nv:.2f} "
                  f"(> {drift:g} allowed): {ov:.1%} -> {nv:.1%} — "
                  f"the candidate's hunt is further from saturated "
                  f"on the same budget")
    oc = {int(k): r for k, r in (oh.get("novelty_curve") or [])}
    nc = {int(k): r for k, r in (nh.get("novelty_curve") or [])}
    worst = None
    for k in sorted(set(oc) & set(nc)):
        delta = abs(nc[k] - oc[k])
        if worst is None or delta > worst[1]:
            worst = (k, delta)
        if delta > drift:
            d.regress(f"novelty curve drift at step {k}: novel rate "
                      f"{oc[k]:.1%} -> {nc[k]:.1%} (|delta| "
                      f"{delta:.2f} > {drift:g} allowed) — the walks "
                      f"are exploring differently, not just "
                      f"slower/faster")
    if worst is not None:
        d.note(f"novelty curve: {len(set(oc) & set(nc))} comparable "
               f"buckets, worst drift {worst[1]:.3f} at step "
               f"{worst[0]}")


def diff_phases(old: dict, new: dict, d: Diff, max_regress: float,
                floor: float):
    op, np_ = old.get("phases") or {}, new.get("phases") or {}
    od, nd = old.get("distinct_states"), new.get("distinct_states")
    if not op or not np_ or not od or not nd:
        return
    ototal = sum(op.values()) or 1.0
    for phase in sorted(set(op) & set(np_)):
        if op[phase] / ototal < floor:
            continue        # sub-floor phases are timer noise
        # Seconds per 1M distinct states: compares runs of different
        # duration budgets on the same model.
        oc = op[phase] / od * 1e6
        nc = np_[phase] / nd * 1e6
        pct = (nc - oc) / oc * 100.0 if oc else 0.0
        d.note(f"phase {phase}: {oc:.2f} -> {nc:.2f} s/M-distinct "
               f"({pct:+.1f}%)")
        if oc > 0 and nc > oc * (1.0 + max_regress):
            d.regress(f"phase '{phase}' cost rose {pct:.1f}% "
                      f"(> {max_regress:.0%} allowed): {oc:.2f} -> "
                      f"{nc:.2f} s/M-distinct")


def pruned_fraction(cov: dict):
    """(pruned count, pruned share of attempted expansions in %) from a
    coverage object — the POR reduction's first-class metric."""
    pr = sum(v.get("pruned", 0) for v in cov.values())
    gen = sum(v.get("generated", 0) for v in cov.values())
    total = pr + gen
    return pr, (pr / total * 100.0) if total else 0.0


def diff_pruned(old: dict, new: dict, d: Diff, drift_pts: float):
    """POR reduced-vs-full accounting as a first-class compared metric:
    the pruned FRACTION (pruned / (pruned + generated) expansions).  A
    candidate whose fraction falls more than ``--pruned-drift``
    percentage points below the baseline regresses — a certified
    reduction that silently collapsed back to full expansion must fail
    the gate, not hide inside an unchanged headline.  Gains are noted
    (the distinct/s gates stay the arbiter of whether pruning pays)."""
    ocov = old.get("coverage") or {}
    ncov = new.get("coverage") or {}
    op, of = pruned_fraction(ocov)
    np_, nf = pruned_fraction(ncov)
    if not op and not np_:
        return
    if not ocov or not ncov:
        # Legacy bench without a coverage object on one side: the
        # fraction cannot be compared, but a pruning run diffed against
        # (or serving as) a legacy baseline still reports the number.
        side = "baseline" if not ocov else "candidate"
        d.note(f"POR pruned expansions: {op:,} ({of:.2f}%) -> "
               f"{np_:,} ({nf:.2f}%) — {side} has no coverage object, "
               "fraction not gated")
        return
    d.note(f"POR pruned expansions: {op:,} ({of:.2f}%) -> "
           f"{np_:,} ({nf:.2f}%)")
    if of - nf > drift_pts:
        d.regress(f"POR pruned fraction fell {of - nf:.2f} pts "
                  f"({of:.2f}% -> {nf:.2f}%, > {drift_pts:g} pts "
                  "allowed) — the reduction collapsed toward full "
                  "expansion")


def diff_coverage(old: dict, new: dict, d: Diff, drift_pts: float):
    # generated_by_action predates the coverage object and carries the
    # same generated series — accept either so old BENCH files diff.
    ocov = old.get("coverage") or {}
    ncov = new.get("coverage") or {}
    og = ({a: v["generated"] for a, v in ocov.items()} if ocov
          else old.get("generated_by_action") or {})
    ng = ({a: v["generated"] for a, v in ncov.items()} if ncov
          else new.get("generated_by_action") or {})
    if not og or not ng:
        return
    ot, nt = sum(og.values()), sum(ng.values())
    if not ot or not nt:
        return
    for action in sorted(set(og) | set(ng)):
        oshare = og.get(action, 0) / ot * 100.0
        nshare = ng.get(action, 0) / nt * 100.0
        delta = nshare - oshare
        if abs(delta) >= drift_pts:
            d.regress(f"coverage mix drift: '{action}' share moved "
                      f"{delta:+.1f} pts ({oshare:.1f}% -> {nshare:.1f}%"
                      f", > {drift_pts:g} pts allowed) — same-model "
                      f"runs should agree; different model/bounds means "
                      f"the two benches are not comparable")
        elif delta:
            d.note(f"coverage {action}: {oshare:.1f}% -> {nshare:.1f}% "
                   f"of generated")


def resolve_history_baseline(ledger: str, new: dict):
    """``--history``: the baseline is the newest ledger entry whose
    host key matches the candidate's host fingerprint (obs/history.py
    resolve_baseline) — never a cross-host number.  Returns (bench
    dict, describing label); raises ValueError when it cannot resolve
    (no fingerprint on the candidate, no same-host entry, unreadable
    ledger) — exit 2, the cannot-read-evidence convention."""
    from raft_tla_tpu.obs import history as history_mod
    fp = new.get("host_fingerprint")
    if not history_mod.host_key(fp):
        raise ValueError(
            "--history needs the candidate bench to embed a "
            "host_fingerprint (bench.py emits one; legacy files do "
            "not) — without it a same-host baseline cannot be chosen")
    try:
        # exclude_bench=new: the candidate's own ledger line (the
        # documented record-then-gate workflow appends it first) must
        # never be chosen — a self-compare gate is vacuously green.
        entry = history_mod.resolve_baseline(ledger, fp,
                                             exclude_bench=new)
    except (OSError, ValueError) as e:
        raise ValueError(f"cannot read ledger {ledger}: {e}")
    if entry is None:
        raise ValueError(
            f"{ledger}: no bench entry with host key "
            f"{history_mod.host_key(fp)} other than the candidate "
            f"itself — run a bench with BENCH_HISTORY on this host "
            f"first (cross-host baselines must be picked explicitly, "
            f"never auto-resolved)")
    label = entry.get("label") or f"ts {entry.get('ts')}"
    return entry["bench"], f"history:{label}"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench JSONs; nonzero exit on regression")
    p.add_argument("old", nargs="?", default=None,
                   help="baseline bench JSON (raw or BENCH_r* wrapper); "
                        "omit with --history to auto-resolve it from "
                        "the run ledger")
    p.add_argument("new", nargs="?", default=None,
                   help="candidate bench JSON")
    p.add_argument("--history", default=None, metavar="LEDGER",
                   help="resolve the baseline from this run-history "
                        "ledger (obs/history.py): the newest bench "
                        "entry with the SAME host fingerprint as the "
                        "candidate.  Usage: bench_diff.py --history "
                        "LEDGER new.json")
    p.add_argument("--max-regress", type=float, default=0.10,
                   help="allowed fractional drop in headline rates "
                        "(default 0.10 = 10%%)")
    p.add_argument("--phase-max-regress", type=float, default=0.35,
                   help="allowed fractional rise in per-phase "
                        "s/M-distinct (noisier than the headline; "
                        "default 0.35)")
    p.add_argument("--phase-floor", type=float, default=0.02,
                   help="ignore phases below this fraction of total "
                        "phase time in the baseline (default 0.02)")
    p.add_argument("--coverage-drift", type=float, default=5.0,
                   help="allowed absolute drift (percentage points) in "
                        "any action's share of generated states "
                        "(default 5.0)")
    p.add_argument("--pruned-drift", type=float, default=1.0,
                   help="allowed drop (percentage points) in the POR "
                        "pruned fraction (pruned/(pruned+generated)) "
                        "vs the baseline — a collapsed reduction fails "
                        "(default 1.0; only checked when either side "
                        "pruned anything)")
    p.add_argument("--hunt-drift", type=float, default=0.25,
                   help="(swarm) allowed absolute drift in each "
                        "refolded novelty-curve bucket's novel rate "
                        "and in the saturation estimate vs the "
                        "baseline (default 0.25) — same seed and "
                        "budget tracing a different novelty "
                        "trajectory means the walk DECISIONS changed, "
                        "not just the throughput")
    args = p.parse_args(argv)

    try:
        if args.history is not None:
            # One positional: the candidate (argparse fills `old`
            # first, so accept either slot).
            new_path = args.new or args.old
            if new_path is None or (args.new and args.old):
                raise ValueError(
                    "--history takes exactly one bench JSON (the "
                    "candidate); the baseline comes from the ledger")
            new = load_bench(new_path)
            old, old_label = resolve_history_baseline(args.history, new)
        else:
            if args.old is None or args.new is None:
                raise ValueError("need OLD and NEW bench JSONs "
                                 "(or --history LEDGER NEW)")
            old, new = load_bench(args.old), load_bench(args.new)
            old_label, new_path = args.old, args.new
    except ValueError as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2

    print(f"bench_diff: {old_label} -> {new_path}")
    if args.history is not None:
        print(f"  baseline auto-resolved from history ledger "
              f"{args.history} ({old_label})")
    d = Diff()
    diff_host(old, new, d)
    om, nm = bench_mode(old), bench_mode(new)
    if om != nm:
        # Cross-dialect diff: an exhaustive distinct/s headline and a
        # swarm steps/s headline measure different things — folding
        # them into one regression ratio would gate noise.  The diff
        # stays a diff (both headlines reported, host guard above still
        # live), nothing is gated.
        d.note(f"bench modes differ (baseline: {om}, candidate: {nm}) "
               f"— dialect rates are not comparable; reported, not "
               f"gated")
        for side, doc in (("baseline", old), ("candidate", new)):
            val = doc.get("value")
            if val is not None:
                d.note(f"  {side} [{bench_mode(doc)}]: {val:,.1f} "
                       f"{doc.get('unit', '?')}")
        return d.render()
    diff_headline(old, new, d, args.max_regress)
    diff_phases(old, new, d, args.phase_max_regress, args.phase_floor)
    if om == "swarm":
        # Swarm-dialect axes; the exhaustive pruned/coverage axes
        # have no meaning for a walker.
        diff_swarm(old, new, d, args.max_regress)
        diff_hunt(old, new, d, args.hunt_drift)
        return d.render()
    diff_pruned(old, new, d, args.pruned_drift)
    diff_coverage(old, new, d, args.coverage_drift)
    return d.render()


if __name__ == "__main__":
    sys.exit(main())
