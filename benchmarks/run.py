#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and, traced,
`breakdown`). `--trace 0` reports the cell's end-to-end metrics, measured
with the profiler off; `--trace 1` measures the same window, then traces
a few more batches or steps and reports the per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero. Under `JAX_PLATFORMS=cpu` it is a rehearsal:
the cell's traffic file gives a tiny size, the whole path runs, what
would have been reported is listed on a `REHEARSAL` line without a
value, and the exit code is EXIT_REHEARSAL. A CPU number never appears
under a metric's name.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import os.path as osp  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

EXIT_REHEARSAL = 4


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    from benchmarks import harness

    try:
        import dexiraft_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmarks/run.py: the program is not in this checkout "
              f"({e}); nothing to measure", file=sys.stderr)
        return 1

    rehearsal = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    try:
        cell = harness.load_cell(args.workload, rehearsal=rehearsal)
    except harness.BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1

    import jax

    from dexiraft_tpu.profiling import device_banner, enable_persistent_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        print(f"benchmarks/run.py: JAX found platform {platform!r}, not a "
              "TPU: no result", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"benchmarks/run.py: {cell.name} needs {cell.chips} chip(s), "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    cache_dir = enable_persistent_cache()
    entries_before = harness.cache_entries()
    device_banner("bench", workload=cell.name, seed=args.seed,
                  seconds=args.seconds, trace=args.trace, rehearsal=rehearsal,
                  host_cpus=os.cpu_count(),
                  host_cpus_usable=len(os.sched_getaffinity(0)))
    log(f"compile cache {cache_dir}: {entries_before} entries")

    spans = harness.Spans()
    spans.seconds["import"] = time.perf_counter() - PROCESS_START
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), rehearsal=rehearsal,
                          devices=devices, spans=spans, log=log)
    runner = harness.load_runner(cell.traffic["kind"])
    try:
        out = runner.run(ctx)
    except harness.BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1

    out.end_to_end["setup_s"] = out.window_start - PROCESS_START
    log("set-up spans (s): " + json.dumps(
        {k: round(v, 3) for k, v in spans.seconds.items()}))
    log(f"compile cache: {harness.cache_entries()} entries after")
    log("counters: " + json.dumps(out.counters))

    peaks = harness.memory_peaks(devices)
    peak = peaks["reported"]
    log("memory of the fullest chip (bytes): " + json.dumps(
        {**peaks, **{k: out.counters[k] for k in (
            "compiled_temp_bytes", "compiled_argument_bytes")
            if k in out.counters}}))
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": {}, "device": device}
    if not args.trace:
        for m in cell.metrics("end_to_end"):
            if m["name"] not in out.end_to_end:
                print(f"benchmarks/run.py: the {cell.traffic['kind']} runner "
                      f"gave no {m['name']}", file=sys.stderr)
                return 1
            result["metrics"][m["name"]] = {
                "value": float(out.end_to_end[m["name"]]), "unit": m["unit"]}
    else:
        obs = harness.Observation(
            spans=spans.seconds, counters=out.counters,
            end_to_end=out.end_to_end, trace=out.trace,
            peaks=None if rehearsal else harness.load_peaks(
                devices[0].device_kind),
            chips=len(devices), memory_peak_bytes=peak)
        for m in cell.metrics("per_layer"):
            value = harness.load_metric(m["name"]).read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        if out.trace is not None:
            device["busy_s"] = out.trace["busy_s"]
            device["window_s"] = out.trace["window_s"]
            result["breakdown"] = {"device_ops": out.trace["device_ops"],
                                   "idle_gaps": out.trace["idle_gaps"]}

    if rehearsal:
        # what a chip run would report, without a value: a CPU number is
        # never written under the name of a device metric
        print("REHEARSAL " + json.dumps({
            "workload": cell.name, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "would_report": sorted(result["metrics"]), "device": {
                "platform": platform, "count": len(devices)}}), flush=True)
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
