"""Reader ``counters``: a count the traffic kind took from the program's
own gauges and run events when the window closed (``run["counters"]``),
as it stands."""

from __future__ import annotations


def read(run: dict, key: str):
    return (run.get("counters") or {}).get(key)
