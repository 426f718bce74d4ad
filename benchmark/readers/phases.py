"""Reader ``phases``: arithmetic over the engine's own host-clock spans
(``EngineResult.phases``, fed by ``MetricsRegistry.phase_timer``) and the
harness's spans around the calls into each layer.

``stats_fetch`` is the host blocked on the device finishing a chunk call;
``chunk`` is the dispatch.  Modes:
  host_share   100 * (1 - stats_fetch / window wall): the share of the
               window in which the host was not simply waiting for the chip
  per_batch_ms (chunk + stats_fetch) * 1000 / (parents expanded / batch):
               wall per batch of parents, device and dispatch together
  span_sum     the sum of the named set-up spans, in seconds
"""

from __future__ import annotations


def host_share(stats_fetch_s: float, wall_s: float) -> float:
    return 100.0 * (1.0 - stats_fetch_s / wall_s)


def per_batch_ms(chunk_s: float, stats_fetch_s: float, parents: int,
                 batch: int) -> float:
    return (chunk_s + stats_fetch_s) * 1000.0 / (parents / batch)


def read(run: dict, mode: str, spans=()):
    phases = run.get("phases") or {}
    if mode == "span_sum":
        have = run.get("spans") or {}
        if not all(s in have for s in spans):
            return None
        return sum(have[s] for s in spans)
    if "stats_fetch" not in phases or not run.get("window_wall_s"):
        return None
    if mode == "host_share":
        return host_share(phases["stats_fetch"], run["window_wall_s"])
    if mode == "per_batch_ms":
        if not run.get("parents_expanded"):
            return None
        return per_batch_ms(phases.get("chunk", 0.0), phases["stats_fetch"],
                            run["parents_expanded"], run["batch"])
    raise ValueError(f"phases reader: unknown mode {mode!r}")
