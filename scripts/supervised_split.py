#!/usr/bin/env python3
"""What a real restart cost, from a supervised run's own event log.

    python3 scripts/supervised_split.py <events.jsonl>

The log is the one ``check ... --checkpoint-dir d --supervise N`` writes
(``--events-out``, else ``<d>/events.jsonl``): the supervisor's ``restart``
lines between the children's.  For every restart: the seconds from the
killed child's last event to the ``restart`` event (the rest of its life: a hard kill
writes no line of its own; then the death seen, the postmortem found,
``latest()``), the backoff the supervisor slept, the
restarted child up to its ``run_start`` (the interpreter, ``import jax``,
the chip's start-up, ``make_engine``: ``run_start.process`` has its
marks), and that run up to its first ``level_complete`` by the phases its
own record gives (``checkpoint_load``, ``run_init``, ``warmup``,
``restore``, then the level's calls).  One JSON line a restart, and one
for the final ``run_end``'s counts.
"""

from __future__ import annotations

import json
import sys


def main(path: str) -> int:
    with open(path, encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    for i, e in enumerate(events):
        if e["event"] != "restart":
            continue
        # The killed child's own last line: the supervisor's
        # ``postmortem`` lines stand between it and the ``restart``.
        before = next((x for x in reversed(events[:i])
                       if "attempt" not in x), e)
        start = next((x for x in events[i:] if x["event"] == "run_start"),
                     None)
        level = next((x for x in events[i:]
                      if x["event"] == "level_complete"), None)
        if start is None or level is None:
            continue
        phases = level.get("phase_seconds") or {}
        print(json.dumps({
            "restart": e.get("attempt"), "exit_code": e.get("exit_code"),
            "resume_from": e.get("resume_from"),
            "last_event_before": before["event"],
            "death_to_restart_event_s": round(e["ts"] - before["ts"], 3),
            "backoff_s": e.get("backoff_seconds"),
            "restart_event_to_run_start_s": round(start["ts"] - e["ts"], 3),
            "child_marks": (start.get("process") or {}).get("marks"),
            "run_start_to_level_complete_s": round(
                level["ts"] - start["ts"], 3),
            "restart_event_to_level_complete_s": round(
                level["ts"] - e["ts"], 3),
            "level": level.get("level"),
            "level_phases_s": {k: round(v, 3) for k, v in sorted(
                phases.items(), key=lambda kv: -kv[1])}}))
    ends = [e for e in events if e["event"] == "run_end"]
    if ends:
        end = ends[-1]
        print(json.dumps({k: end.get(k) for k in (
            "stop_reason", "distinct", "generated", "diameter", "levels",
            "wall_seconds", "checkpoints_written",
            "checkpoint_bytes_written")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
