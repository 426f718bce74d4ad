#!/usr/bin/env python3
"""Summarize ``jax.profiler`` capture artifacts into the perf JSON
dialect — the XPlane ingestion leg of the performance observatory.

``--xla-profile`` (PR 9) lands device-
profiler artifacts under ``<logdir>/plugins/profile/<run>/``: an XPlane
proto plus a Perfetto/Chrome-trace JSON of the ACTUAL kernels the
hardware ran.  Those artifacts are the launch-count truth the static
model in ``obs/perf.py`` can only bound — but until now they were
profiler screenshots: nothing machine-readable entered the ledger.

This script parses the capture's Chrome-trace JSON (the zero-dep half
of the artifact pair; the ``.xplane.pb`` proto needs the tensorboard
profile plugin and is deliberately not required) and emits ONE JSON
object in the bench/perf dialect:

- kernel events on device tracks, bucketed by the ``chunk`` step
  annotation both engines bracket their dispatches with (obs/profile.py
  XlaProfileCapture — the shared span name is the correlation
  contract), giving **measured** ``launches_per_chunk``;
- total device time + the top kernels by accumulated duration — what
  NORTHSTAR §d's launch-bound-vs-bandwidth-bound question reads.

Because the ``perf`` block shape matches ``bench.py``'s,
``scripts/bench_diff.py`` gates these summaries with ``--launch-drift``
like any bench pair, and ``--history`` appends the summary to the run
ledger (kind ``xplane``) so a chip capture lands directly
in the trajectory ``scripts/bench_history.py --perf`` renders.

    python scripts/xplane_summary.py artifacts/xla_profile_v3
    python scripts/xplane_summary.py artifacts/xla_profile_v3 \\
        --out v3.json --history artifacts/history.jsonl --label xplane_v3

Exit codes: 0 ok, 2 unreadable/empty capture (the bench_diff
convention: a tool that cannot read its evidence fails loudly).
"""

import argparse
import bisect
import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Track/process names that mark DEVICE timelines in jax profiler
#: traces ("/device:TPU:0 ...", "TPU:0", "GPU:0", "XLA Op" lanes); host
#: python/TSL tracks never match.
DEVICE_RE = re.compile(r"device|tpu|gpu|xla", re.IGNORECASE)

#: Event names that are annotations/steps, not kernels, on any track.
_NOT_KERNEL = re.compile(r"^(chunk|\$|Steps?$|step\b)", re.IGNORECASE)


def find_trace_file(logdir: str):
    """The newest ``*.trace.json(.gz)`` under ``logdir`` (searched
    directly and under the ``plugins/profile/<run>/`` layout
    jax.profiler writes).  None when the capture left no trace JSON."""
    pats = ("*.trace.json.gz", "*.trace.json")
    cands = []
    for pat in pats:
        cands += glob.glob(os.path.join(logdir, pat))
        cands += glob.glob(os.path.join(logdir, "plugins", "profile",
                                        "*", pat))
        cands += glob.glob(os.path.join(logdir, "*", pat))
    if not cands:
        return None
    return max(cands, key=os.path.getmtime)


def load_trace(path: str) -> list:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt", encoding="utf-8") as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        return doc.get("traceEvents") or []
    return doc if isinstance(doc, list) else []


def summarize_events(events: list) -> dict:
    """Chrome-trace events -> the measured launch summary.  Device
    tracks are found via process/thread metadata names; with none
    matching (a host-only CPU capture) EVERY complete event counts,
    with a note — shape over silence."""
    pid_names, tid_names = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e.get("pid")] = (e.get("args") or {}).get("name", "")
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = \
                (e.get("args") or {}).get("name", "")
    device_pids = {p for p, n in pid_names.items() if DEVICE_RE.search(n)}
    device_tids = {pt for pt, n in tid_names.items()
                   if DEVICE_RE.search(n)}
    notes = []
    if not device_pids and not device_tids:
        notes.append("no device track metadata; counting every "
                     "complete event (host-only capture?)")

    def on_device(e):
        if not device_pids and not device_tids:
            return True
        return (e.get("pid") in device_pids
                or (e.get("pid"), e.get("tid")) in device_tids)

    # Chunk steps counted PER TRACK, then the busiest track taken:
    # captures mirror the StepTraceAnnotation onto both the host thread
    # and a device Steps lane, and counting the union would double the
    # denominator (halving launches_per_chunk — a deflated ledger
    # baseline would then flag the next correct capture as a launch
    # regression).
    chunk_tracks = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name") or ""
        if name == "chunk" or name.startswith("chunk "):
            key = (e.get("pid"), e.get("tid"))
            chunk_tracks.setdefault(key, []).append(
                (float(e.get("ts") or 0.0), float(e.get("dur") or 0.0)))
    steps = (max(chunk_tracks.values(), key=len) if chunk_tracks
             else [])
    chunks = len(steps)
    if not chunks:
        notes.append("no 'chunk' step annotations found; "
                     "launches_per_chunk unavailable (raw kernel count "
                     "reported)")
    # Kernels are bucketed by midpoint-in-chunk-window, so non-chunk
    # device work the capture window also recorded (per-level ingest,
    # profiler stage re-executions, oracle kernels) cannot inflate
    # launches_per_chunk and flip --launch-drift on interleave alone.
    intervals = []
    for ts, dur in sorted(s for s in steps if s[1] > 0):
        if intervals and ts <= intervals[-1][1]:
            intervals[-1][1] = max(intervals[-1][1], ts + dur)
        else:
            intervals.append([ts, ts + dur])
    if chunks and not intervals:
        notes.append("chunk steps carry no duration; counting every "
                     "device event")
    starts = [iv[0] for iv in intervals]

    def in_chunk_window(ts, dur):
        if not intervals:
            return True        # no usable windows: count everything
        mid = ts + dur / 2.0
        i = bisect.bisect_right(starts, mid) - 1
        return i >= 0 and mid <= intervals[i][1]

    kernels = 0
    outside = 0
    device_us = 0.0
    by_name = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name") or ""
        if name == "chunk" or name.startswith("chunk "):
            continue
        if not on_device(e) or _NOT_KERNEL.match(name):
            continue
        ts = float(e.get("ts") or 0.0)
        dur = float(e.get("dur") or 0.0)
        if not in_chunk_window(ts, dur):
            outside += 1
            continue
        kernels += 1
        device_us += dur
        agg = by_name.setdefault(name, [0, 0.0])
        agg[0] += 1
        agg[1] += dur
    if outside:
        notes.append(f"{outside} device events outside the chunk step "
                     f"windows excluded")
    top = sorted(((n, c, round(us / 1e3, 3))
                  for n, (c, us) in by_name.items()),
                 key=lambda t: -t[2])[:10]
    lpc = round(kernels / chunks, 1) if chunks else None
    return {
        "chunks": chunks, "kernel_events": kernels,
        "launches_per_chunk": lpc,
        "device_time_ms": round(device_us / 1e3, 3),
        "top_kernels": [{"name": n, "count": c, "total_ms": ms}
                        for n, c, ms in top],
        "notes": notes,
    }


def build_doc(logdir: str, trace_path: str, summary: dict) -> dict:
    """The perf-dialect JSON object: same ``perf.launch`` shape as
    bench.py's block (bench_diff's --launch-drift gate reads it
    identically), with ``model`` marking these as MEASURED launches."""
    try:
        from raft_tla_tpu.obs import host_fingerprint
        fp = host_fingerprint()
    except Exception:
        fp = None
    return {
        "metric": "xplane_summary",
        "source": os.path.relpath(trace_path),
        "logdir": logdir,
        "host_fingerprint": fp,
        "perf": {
            "pipeline": None,
            "launch": {
                "model": "xplane device events (measured)",
                "launches_per_chunk": summary["launches_per_chunk"],
                "chunk_calls": summary["chunks"],
                "kernel_events": summary["kernel_events"],
                "device_time_ms": summary["device_time_ms"],
                "notes": summary["notes"],
            },
            "roofline": {"stages": {}},
            "advisor": {"ranking": [], "top": None,
                        "verdict": "measured capture (no static model)"},
        },
        "top_kernels": summary["top_kernels"],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="summarize jax.profiler artifacts into perf JSON")
    p.add_argument("logdir", help="--xla-profile directory (or any dir "
                                  "containing *.trace.json[.gz])")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the JSON here (default: stdout)")
    p.add_argument("--history", default=None, metavar="LEDGER",
                   help="append a kind='xplane' entry embedding this "
                        "summary to the run-history ledger "
                        "(obs/history.py)")
    p.add_argument("--label", default=None,
                   help="ledger entry label (e.g. xplane_v3)")
    args = p.parse_args(argv)

    trace_path = find_trace_file(args.logdir)
    if trace_path is None:
        print(f"xplane_summary: no *.trace.json[.gz] under "
              f"{args.logdir!r} — did the capture run? (the XPlane "
              f".pb alone is not parseable without the tensorboard "
              f"profile plugin)", file=sys.stderr)
        return 2
    try:
        events = load_trace(trace_path)
    except (OSError, json.JSONDecodeError, EOFError) as e:
        print(f"xplane_summary: cannot parse {trace_path}: {e}",
              file=sys.stderr)
        return 2
    if not events:
        print(f"xplane_summary: {trace_path} holds no trace events",
              file=sys.stderr)
        return 2
    doc = build_doc(args.logdir, trace_path, summarize_events(events))
    blob = json.dumps(doc)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(blob + "\n")
        print(f"xplane_summary: {doc['perf']['launch']['kernel_events']}"
              f" kernel events, launches/chunk="
              f"{doc['perf']['launch']['launches_per_chunk']} "
              f"-> {args.out}", file=sys.stderr)
    else:
        print(blob)
    if args.history:
        from raft_tla_tpu.obs import history as history_mod
        history_mod.append_entry(args.history, history_mod.make_entry(
            "xplane", label=args.label,
            host_fingerprint=doc.get("host_fingerprint"),
            verdict="ok", bench=doc))
        print(f"xplane_summary: ledger entry appended to {args.history}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
