"""Says where a cell's traced stretch went by the program's own names: device
time by `dl4j.` scope and phase, idle time by the program's leaf span, device
time by program (`XLA Modules`), the producer thread's spans, and what the
compile of the scope table cost (a cache hit, or not). For PERF.md's section
5; by hand, after a `--trace 1` run of the cell in the same checkout, which
left its trace under `.bench_trace/`:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 20 --trace 1
    python3 benchmark/tools/program_breakdown.py <cell>

Writes chiprun_out/program_breakdown_<cell>.json and prints it.
"""
import collections
import glob
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def top(d, n=15):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def main():
    from harness import device, program_trace, trace
    from harness.manifest import Cell
    cell = Cell(sys.argv[1])
    files = sorted(glob.glob(os.path.join(
        ROOT, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        sys.exit("no trace under .bench_trace/: run the cell with --trace 1")
    device.configure_compile_cache()
    device.require_chip(cell.chips)
    t = cell.traffic
    marks, per_mark = (("bench.fit_call", int(t["steps_per_call"]))
                       if "steps_per_call" in t else ("bench.listener", 1))
    p = program_trace.read(files[-1], marks, per_mark)
    if p is None:
        sys.exit("the trace holds no TPU plane or fewer than two marks")
    from deeplearning4j_tpu.telemetry import profiler
    before, t0 = program_trace.compile_counters(), time.perf_counter()
    table = program_trace.scope_table(cell)
    table_s = time.perf_counter() - t0
    after = program_trace.compile_counters()
    named = p.device_ns_by_scope(table, profiler.scope_phase)
    ms = lambda ns: ns / 1e6 / p.steps
    by_kind = collections.defaultdict(float)
    by_scope = collections.defaultdict(float)
    for (scope, phase), ns in named.items():
        by_kind[f"{str(scope).split('/')[0]} {phase}"] += ms(ns)
        by_scope[f"{scope} {phase}"] += ms(ns)
    modules = collections.defaultdict(float)
    for name, a, b in p.modules:
        modules[name.split("(")[0]] += ms(
            trace.total(program_trace.intersect(p.busy, [(a, b)])))
    spans = collections.defaultdict(float)
    for s in p.spans:
        spans[f"{s.name} thread{s.thread}"] += ms(s.end - s.start)
    named_ns = sum(v for (scope, _), v in named.items() if scope is not None)
    # what the program named nothing for, by what the table does say of it
    unnamed = collections.defaultdict(float)
    for name, a, b in p.train_program_ops():
        op_name = table.get(name, "")
        if profiler.scope_phase(op_name)[0] is None:
            kind = re.sub(r"\d+", "N", op_name) or "no metadata"
            unnamed[f"{re.sub(r'[.]?\d+$', '', name)}: {kind}"] += ms(b - a)
    out = {
        "cell": cell.name, "steps": p.steps, "stretch_s": p.stretch_ns / 1e9,
        "busy_s": p.busy_ns / 1e9,
        "ops_in_train_program_s": sum(named.values()) / 1e9,
        "named_share": named_ns / p.busy_ns,
        "unnamed_rest_ms_per_step": ms(p.busy_ns - named_ns),
        "unnamed_ms_per_step_by_instruction_and_op_name": top(unnamed),
        "device_ms_per_step_by_kind_and_phase": top(by_kind),
        "device_ms_per_step_by_scope_and_phase": top(by_scope),
        "device_ms_per_step_by_program": top(modules),
        "idle_ms_per_step_by_span": top({k: ms(v) for k, v
                                         in p.idle_by_span().items()}),
        "host_ms_per_step_by_span": top(spans, 30),
        "table": {"instructions": len(table), "seconds": table_s,
                  "counters": {k: after[k] - before[k] for k in after}},
    }
    path = os.path.join(ROOT, "chiprun_out",
                        f"program_breakdown_{cell.name}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
