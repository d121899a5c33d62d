#!/usr/bin/env python3
"""Run one cell of the benchmark and print its one JSON line.

    python perf/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json.  Its
configuration (`perf/configs/`), traffic mix (`perf/traffic/`), job
(`perf/jobs/<kind>.py`, named by the traffic file), plain reference
(`perf/reference/`, named by the configuration) and per-layer metrics
(`perf/metrics/<name>.py`) are all found by name: adding one is adding
files and entries, never editing this one.  See perf/README.md.

With `--trace 0` the line carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, the device's busy seconds over a
traced slice of the window, and the breakdown.  The run refuses to
measure (exit 2, no result) unless JAX reports a TPU with exactly the
cell's number of chips.  `--rehearse` runs the same control flow at the
files' tiny sizes on whatever backend JAX finds and prints under the
device name it ran on, with `"rehearsal": true`: for finding faults
before spending chip time, never for a number.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
EXIT_NO_DEVICE = 2
EXIT_NO_REPO = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print(f"run_cell: {ROOT} holds no paddle_tpu package: the "
              "benchmark measures the program and needs its checkout",
              file=sys.stderr)
        return EXIT_NO_REPO
    sys.path.insert(0, ROOT)
    sys.path.insert(0, PERF_DIR)
    import common

    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = common.Cell(bench, args.workload, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       rehearse=args.rehearse,
                       t_process_start=T_PROCESS_START)

    if args.rehearse and cell.chips > 1:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cell.chips}")
    device = common.device_info()
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell.chips):
        print(f"run_cell: cell {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); JAX reports {device['count']} device(s) of "
              f"platform {device['platform']!r}.  Nothing is measured "
              "off the chip (--rehearse only rehearses).",
              file=sys.stderr)
        return EXIT_NO_DEVICE
    peaks = common.peaks_for(device["kind"])

    # ONE compile cache: JAX_COMPILATION_CACHE_DIR where set, else the
    # program's fixed path inside the checkout (<root>/.jax_cache)
    from paddle_tpu.core.compile_cache import compile_cache_dir

    cache_dir = compile_cache_dir()
    cell.mark("imports and device")

    run = cell.job().run(cell)
    run.cell, run.device, run.peaks = cell, device, peaks
    setup_s = run.t_window_open - T_PROCESS_START
    run.end_to_end["setup_s"] = setup_s

    metrics = {}
    if cell.trace:
        for spec in cell.per_layer:
            mod = common.load_module(os.path.join(
                PERF_DIR, "metrics", spec["name"] + ".py"))
            value = mod.compute(run)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
    else:
        for spec in cell.end_to_end:
            value = run.end_to_end.get(spec["name"])
            if value is None:
                print(f"run_cell: the job gave no {spec['name']!r}",
                      file=sys.stderr)
                return 1
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}

    device["memory_peak_bytes"] = common.memory_peak_bytes()
    line = {"correct": bool(run.correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics,
            "device": device}
    if cell.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    # an earlier line for people: why `correct` is what it is, the
    # window as measured, and what the job counted
    print(json.dumps({"workload": cell.name, "seed": cell.seed,
                      "trace": int(cell.trace),
                      "window_s": run.t_window_close - run.t_window_open,
                      "setup_s": setup_s, "setup_marks": cell.setup_marks,
                      "compile_cache_dir": cache_dir,
                      "notes": run.notes, "counters": run.counters,
                      "end_to_end": run.end_to_end}, default=str),
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
