#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON object as the last line of stdout.  The
cell, its configuration, its traffic kind and each per-layer metric are
files found by the names ``BENCHMARK.json`` gives (see README.md here), so
a later PR adds files and edits none.

The run needs a TPU: without one (or with fewer chips than the cell asks
for) it exits 3 and prints no result, whatever ``JAX_PLATFORMS`` says.
``--rehearsal``, which the driver never passes, runs the same command on
whatever device jax finds (``JAX_PLATFORMS=cpu`` here) and its line says
``"platform": "cpu"``; nothing from such a run is a device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

import bench_lib as lib     # noqa: E402


def fail(msg: str, code: int = 3):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    fail(f"no {what} named {name!r} in BENCHMARK.json "
         f"(have {[e['name'] for e in entries]})", 2)


def device_block(jax, chips: int, rehearsal: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not rehearsal:
        fail(f"jax found no TPU (first device {d0}, platform "
             f"{d0.platform!r}); nothing was run")
    if len(devs) < chips:
        fail(f"cell asks for {chips} chip(s), jax.devices() has "
             f"{len(devs)}; nothing was run")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def memory_peak(jax, chips: int) -> int:
    """Peak bytes in use on the fullest chip the cell used (0 where the
    backend reports nothing, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run without a TPU (tests and CPU rehearsals); "
                         "the result line names the platform it ran on")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    entry = find(manifest["workloads"], args.workload, "workload")
    cfg_entry = find(manifest["configs"], entry["config"], "configuration")
    with open(os.path.join(ROOT, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    # A cell is its manifest entry (config, traffic, chips) and the
    # parameters of the traffic mix it names.
    mix = lib.load_json("traffic", entry["traffic"] + ".json")
    kind = lib.load_module("traffic", mix["kind"])
    cell = {**mix, **entry}

    # The compile cache: where the environment says, else one fixed
    # directory inside the checkout.  Set before jax is imported so that
    # jax and the program (utils/platform.py) both take it.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    try:
        import raft_tla_tpu  # noqa: F401
    except ImportError as e:
        fail(f"the program is not in this checkout ({e}); nothing was run")
    import jax
    from raft_tla_tpu.utils.platform import enable_persistent_cache
    device = device_block(jax, cell["chips"], args.rehearsal)
    enable_persistent_cache()       # the call cli.py makes
    print(f"device: {device['platform']} {device['kind']!r} x"
          f"{device['count']}; compile cache "
          f"{os.environ['JAX_COMPILATION_CACHE_DIR']}", flush=True)

    ledger = lib.Ledger()
    ledger.true("device platform is tpu (or --rehearsal was asked for)",
                device["platform"] == "tpu" or args.rehearsal,
                device["platform"])
    tmp = tempfile.mkdtemp(prefix="raftbench_")
    try:
        run = kind.run(lib.Context(
            args=args, cell=cell, config=config, tmp=tmp, ledger=ledger,
            t_start=T_START, compiles=lib.CompileWatch(),
            trace_dir=(os.path.join(tmp, "xplane") if args.trace else None)))
        device["memory_peak_bytes"] = memory_peak(jax, cell["chips"])
        print(f"memory_peak_bytes {device['memory_peak_bytes']}", flush=True)

        if args.trace:
            run["device_kind"] = device["kind"]
            t0 = time.perf_counter()
            metrics, extra = layer_metrics(manifest, run)
            print(f"trace reduced in {time.perf_counter() - t0:.2f}s",
                  flush=True)
            device.update(extra.get("device", {}))
            breakdown = extra.get("breakdown")
        else:
            # What the traffic kind measured, under the manifest's units.
            metrics = {
                m["name"]: {"value": run["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in manifest["end_to_end"]
                if m["name"] in run["end_to_end"]}
            breakdown = None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    line = {"correct": ledger.correct, "attempted": ledger.attempted,
            "failed": ledger.failed, "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


def layer_metrics(manifest: dict, run: dict):
    """The per-layer metrics that move an end-to-end metric this cell
    reports, each by the reader its own file names.  A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in manifest["per_layer"]:
        if m["moves"] not in run["end_to_end"]:
            continue
        spec = lib.load_json("layer_metrics", m["name"] + ".json")
        reader = lib.load_module("readers", spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {}
    if run.get("trace_dir"):
        xplane = lib.load_module("readers", "xplane")
        red = xplane.reduction(run)
        if red is not None:
            # window_s is the span the capture holds, which busy_s is a
            # part of; the profiler stops recording when its buffer is
            # full, so the window's own length stands beside it.
            extra["device"] = {"busy_s": red["busy_s"],
                               "window_s": red["window_s"],
                               "window_wall_s": run["window_wall_s"]}
            print(f"trace: the capture holds {red['window_s']:.2f}s of the "
                  f"window's {run['window_wall_s']:.2f}s", flush=True)
            extra["breakdown"] = {"device_ops": red["device_ops"][:10],
                                  "idle_gaps": red["idle_gaps"][:10]}
    return out, extra


if __name__ == "__main__":
    sys.exit(main())
