#!/usr/bin/env python3
"""Render (and seed) the run-history ledger — the bench trajectory tool.

The ledger (obs/history.py; written by ``BENCH_HISTORY`` / ``check
--history`` / the ``HISTORY`` directive) is an append-only JSONL file of
per-run entries: cfg/model/host fingerprints, verdict, counts, headline
rates, pipeline plan, report summary, and (for bench runs) the embedded
bench JSON that lets ``bench_diff.py --history`` auto-resolve baselines.

    python scripts/bench_history.py LEDGER.jsonl
        render the trajectory table: one row per entry with its host
        key, plus explicit HOST-CHANGE / unknown-host flags — the
        BENCH_r05 trap (an absolute rate silently compared across a
        ~4x slower container) rendered impossible to miss.  Swarm-tier
        rows (kind=swarm — ``check --mode swarm`` / BENCH_MODE=swarm)
        render their steps/s headline with a ``steps/s`` dialect flag;
        they carry real host fingerprints, so they never read as host
        anomalies.

    python scripts/bench_history.py LEDGER.jsonl --import-legacy [DIR]
        one-time seeding from the committed BENCH_r04/r05 /
        MULTICHIP_r01..r05 round files (DIR defaults to the repo root)
        so the trajectory is non-empty from day one.  Legacy files
        predate host fingerprints, so every imported entry carries
        host_key null — rendered as ``host?``/not-comparable, which IS
        the honest statement about those numbers.  Idempotent: a label
        already in the ledger is skipped.

Exit codes: 0 ok, 2 unreadable/malformed ledger (the bench_diff
convention — a tool that cannot read its evidence fails loudly).
No jax; runs from a fresh clone.
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tla_tpu.obs import history as history_mod  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_legacy(ledger: str, repo: str) -> int:
    """Seed the ledger from the committed round files; returns the
    number of entries appended."""
    have = set()
    if os.path.exists(ledger):
        have = {e.get("label") for e in history_mod.read_history(ledger)}
    added = 0
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        label = os.path.splitext(os.path.basename(path))[0]
        if label in have:
            continue
        with open(path, encoding="utf-8") as f:
            wrapper = json.load(f)
        parsed = wrapper.get("parsed")
        if parsed:
            entry = history_mod.entry_from_bench(parsed, label=label)
        else:
            # A round whose bench never emitted JSON: recorded as a
            # failed run, not silently dropped — the trajectory should
            # show the crash too.
            entry = history_mod.make_entry(
                "bench", label=label,
                verdict=f"no-json (rc {wrapper.get('rc')})")
        history_mod.append_entry(ledger, entry)
        added += 1
    for path in sorted(glob.glob(os.path.join(repo, "MULTICHIP_r*.json"))):
        label = os.path.splitext(os.path.basename(path))[0]
        if label in have:
            continue
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        verdict = ("ok" if doc.get("ok")
                   else "skipped" if doc.get("skipped")
                   else f"failed (rc {doc.get('rc')})")
        history_mod.append_entry(ledger, history_mod.make_entry(
            "multichip", label=label, verdict=verdict))
        added += 1
    return added


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="render / seed the run-history ledger")
    p.add_argument("ledger", help="JSONL ledger file (obs/history.py)")
    p.add_argument("--import-legacy", nargs="?", const=REPO, default=None,
                   metavar="DIR",
                   help="seed from the committed BENCH_r*/MULTICHIP_r* "
                        "files in DIR (default: repo root) before "
                        "rendering; idempotent by label")
    p.add_argument("--hunt", action="store_true",
                   help="add the hunt-observatory columns (coverage "
                        "saturation + novelty rate + time-to-violation "
                        "from each swarm entry's hunt summary, "
                        "obs/hunt.py) — the trajectory view of whether "
                        "successive hunts are saturating sooner or "
                        "latching faster; exhaustive rows render '--'")
    args = p.parse_args(argv)

    if args.import_legacy is not None:
        repo = args.import_legacy
        try:
            added = import_legacy(args.ledger, repo)
        except (OSError, ValueError) as e:
            print(f"bench_history: {e}", file=sys.stderr)
            return 2
        print(f"bench_history: imported {added} legacy entr"
              f"{'y' if added == 1 else 'ies'} from {repo}")

    try:
        entries = history_mod.read_history(args.ledger)
    except (OSError, ValueError) as e:
        print(f"bench_history: {e}", file=sys.stderr)
        return 2
    print(history_mod.render_table(entries, hunt=args.hunt))
    return 0


if __name__ == "__main__":
    sys.exit(main())
