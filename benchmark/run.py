#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: finds the cell's files by the names in BENCHMARK.json, requires a
TPU with the chips the cell asks for (and leaves with no result otherwise),
has the cell's driver build the system from the seed and prove and warm up
only this cell's programs (set-up), measures for `--seconds`, reads the peak
memory, frees the program, has the driver compare what the timed path produced
with the plain reference, and prints one JSON object as the last line of its
standard output. What a window's end-to-end values are and what is compared
is the driver's (`values`, `compare`); no cell's, metric's or updater's name
is in this file: see README.md for how a cell, a configuration, a driver or a
metric is added as files.
"""
from __future__ import annotations

_T_START = __import__("time").perf_counter()

import argparse
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class Run:
    """What a driver and a metric's reader may look at."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.key = None
        self.device = None           # {"platform", "kind", "count"}
        self.devices = []            # the JAX devices used
        self.peaks = None
        self.compiles = None
        self.tracer = None
        self.prepared = None
        self.window = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self.reduced = None          # harness.trace.Reduced, with --trace 1


def execute(cell, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    """The whole run after argument parsing; returns the result object."""
    from harness import compare, device, traffic
    from harness import trace as trace_mod
    from harness.tracer import Tracer

    device.configure_compile_cache()
    import jax

    run = Run(cell, seed, seconds, trace)
    run.device = device.require_chip(cell.chips)
    run.devices = jax.devices()[:cell.chips]
    run.peaks = device.load_peaks(run.device["kind"])
    run.compiles = device.Compiles()
    run.key = traffic.key_from_seed(seed)
    trace_dir = os.path.join(ROOT, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.tracer = Tracer(trace, trace_dir, seconds)

    run.prepared = cell.driver.prepare(run)
    setup_s = time.perf_counter() - t_start

    before = run.compiles.compiles
    run.window = w = cell.driver.window(run, run.prepared, seconds)
    run.compiles_in_window = run.compiles.compiles - before
    run.memory_peak_bytes = device.memory_peak_bytes(run.devices)
    memory_stats = dict(run.devices[0].memory_stats() or {})

    values = {"setup_s": setup_s, **cell.driver.values(run)}
    # the program's state goes before the reference comes
    run.prepared.net = None
    gc.collect()

    device_out = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    breakdown = None
    if trace:
        path = run.tracer.trace_file()
        run.reduced = trace_mod.reduce_file(path, w.marks) if path else None
        if run.reduced is not None:
            device_out["busy_s"] = run.reduced.busy_s
            device_out["window_s"] = run.reduced.stretch_s
            breakdown = {"device_ops": run.reduced.top_ops(),
                         "idle_gaps": run.reduced.top_gaps()}
        metrics = {}
        for m in cell.per_layer():
            value = cell.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        # the trace stays (git-ignored) until the next traced run clears it
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end() if m["name"] in values}

    t_ref = time.perf_counter()
    correct, rows = cell.driver.compare(run)
    reference_s = time.perf_counter() - t_ref

    result = {"correct": correct, "attempted": int(w.attempted),
              "failed": int(w.failed), "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["notes"] = {"steps": w.steps, "window_s": w.t1 - w.t0,
                       "compiles_in_window": run.compiles_in_window,
                       "compiles": run.compiles.compiles,
                       "cache_hits": run.compiles.cache_hits,
                       "reference_s": reference_s,
                       "memory_stats": memory_stats,
                       "step_ms_p50": values.get("step_ms_p50")}
    judged = {k: r for k, r in rows.items() if r["limit"] is not None}
    result["notes"]["not_judged"] = {k: r["value"] for k, r in rows.items()
                                     if r["limit"] is None}
    result["compared"] = {k: {"value": r["value"], "limit": r["limit"]}
                          for k, r in judged.items()}
    compare.report(judged)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness.manifest import Cell
    cell = Cell(args.workload)
    result = execute(cell, args.seed, args.seconds, bool(args.trace), _T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
