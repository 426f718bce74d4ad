"""Reader ``recovery``: what durability cost a window that holds snapshot
writes, a kill and a recovery (traffic kind ``kill_resume``).

A save is the engine's ``checkpoint`` phase (``raft.checkpoint`` in a
capture), one a snapshot, and ends in a ``checkpoint`` event: the
acknowledgement, written after the file is fsynced, renamed and its
directory fsynced.  Since the PR that named a save's parts the event also
says ``seconds`` (the phase's), ``bytes_raw`` (the arrays as they lay in
memory), ``bytes_written`` (the file) and ``parts``: seconds of the spans
inside the phase, ``ckpt_export`` (the trace store's copy), ``ckpt_keys``
(the seen-set fetched whole, masked, sorted), ``ckpt_frontier`` (the
level's rows brought over), ``ckpt_deflate``, ``ckpt_write`` (the file,
both fsyncs, the rename) and ``ckpt_gc`` (retention).  A program from
before it leaves those fields out, and the modes that read them return
None.

Modes of ``read`` (``run`` is what the kind returned):
  save_stall_s  seconds of the ``checkpoint`` phase over the window's runs
                (``run_end.phase_seconds``, summed by the kind) / the
                window's ``checkpoint`` events: what one snapshot holds
                the loop, and the device, for
  part_ms       1000 x the named ``part``'s seconds over the window's
                ``checkpoint`` events / those events
  save_mb_s     ``bytes_raw`` / ``seconds`` / 1e6 over the same events
  recover_s     from a capture: the end of the killed run's last
                ``raft.account`` span (the last call whose statistics
                reached the host before the kill) to the start of the
                recovered run's first: the error exit, ``latest()``, the
                load from disk, ``run_init``, the restore and one call
  redo_share    100 x parents the killed run had expanded past its last
                snapshot (the kind's count, from ``run_end``) / parents
                expanded in the window: work the window did twice
"""

from __future__ import annotations

import bench_lib as lib


def saves(run: dict) -> list:
    return [e for e in run.get("events") or []
            if e.get("event") == "checkpoint"]


def run_spans(cap: dict) -> list:
    """[(start_ns, end_ns)] of the capture's ``raft.run`` spans."""
    return sorted((e[1], e[1] + e[2]) for e in cap["host"] if e[0] == "run")


def print_saves(cap: dict) -> None:
    """Each ``raft.checkpoint`` span of the capture with the spans inside
    it, by their offsets: what of a save lies in no part shows as the
    distance between one part's end and the next one's start."""
    for _name, start, dur, _stats in (e for e in cap["host"]
                                      if e[0] == "checkpoint"):
        inside = [(e[0], (e[1] - start) / 1e9, (e[1] + e[2] - start) / 1e9)
                  for e in cap["host"]
                  if e[0] != "checkpoint" and start <= e[1] < start + dur]
        print(f"save: raft.checkpoint {dur / 1e9:.3f}s; inside it, from "
              f"its start: " + ", ".join(
                  f"{n} {a:.3f}-{b:.3f}" for n, a, b in inside), flush=True)


def recover_s(cap: dict):
    runs = run_spans(cap)
    if len(runs) != 2:
        return None
    (a0, a1), (b0, b1) = runs
    accounts = sorted((e[1], e[1] + e[2]) for e in cap["host"]
                      if e[0] == "account")
    before = [end for start, end in accounts if a0 <= start < a1]
    after = [start for start, _end in accounts if b0 <= start < b1]
    if not before or not after:
        return None
    return (after[0] - before[-1]) / 1e9


def read(run: dict, mode: str, part: str = ""):
    written = saves(run)
    if mode == "save_stall_s":
        seconds = (run.get("phases") or {}).get("checkpoint")
        if not written or seconds is None:
            return None
        return seconds / len(written)
    if mode == "part_ms":
        if not written or any(part not in (e.get("parts") or {})
                              for e in written):
            return None
        return 1000.0 * sum(e["parts"][part] for e in written) / len(written)
    if mode == "save_mb_s":
        if not written or any(not e.get("seconds") or not e.get("bytes_raw")
                              for e in written):
            return None
        return (sum(e["bytes_raw"] for e in written) / 1e6
                / sum(e["seconds"] for e in written))
    if mode == "recover_s":
        cap = lib.load_module("readers", "spans").capture(run)
        if not cap or not cap["host"]:
            return None
        print_saves(cap)
        return recover_s(cap)
    if mode == "redo_share":
        redo = (run.get("recovery") or {}).get("redo_parents")
        if redo is None or not run.get("parents_expanded"):
            return None
        return 100.0 * redo / run["parents_expanded"]
    raise ValueError(f"recovery reader: unknown mode {mode!r}")
