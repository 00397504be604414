"""`calibrate.py` for a cell on `drivers/token_loop.py`: the same readings for
a dozen seeds in one process (the program against the plain reference; in
the program's place the int8 control and the planted fault "only the first
step of the window's call of n steps updating"), each judged by the limits the
cell's file has now, with the reference followed the driver's way (one copy of
the weights on the device: `harness.compare.follow_reference`, which
`calibrate.py` calls, keeps three). A batch of one sequence has no half to
leave out. One net serves every seed (each gets its own weights and ids; the
two programs compile once), `--window` seconds of the cell's closed loop are
timed on the first `--window-seeds` seeds (the rate's spread over seeds), the
control and the fault are read on the first `--control-seeds`. Run by hand
through the chip tool; writes chiprun_out/calibrate_<cell>.jsonl.

    python3 benchmark/tools/calibrate_token_loop.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 3] [--window 20 --window-seeds 3]
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def _raw(readings) -> dict:
    return {k: getattr(readings, k) for k in ("grad", "update", "loop_update",
                                              "loop_move")}


def read_seed(cell, seed: int, net, control: bool, window_s: float):
    """(row, the net for the next seed)."""
    import jax

    import run as bench_run
    from drivers import token_loop
    from harness import compare, traffic
    from harness.tracer import Tracer

    ref_mod, cfg, t = cell.reference, cell.config, cell.traffic
    t0 = time.perf_counter()
    r = bench_run.Run(cell, seed, window_s, False)
    r.key = traffic.key_from_seed(seed)
    r.tracer = Tracer(False, "", 1.0)
    row = {"cell": cell.name, "seed": seed}
    prepared = token_loop.prepare(r, net)
    net, prog, batch = prepared.net, prepared.readings, prepared.batch
    row["setup_split_s"] = prepared.extra["setup_split_s"]
    if window_s:
        w = token_loop.window(r, prepared, window_s)
        row["window"] = {"train_samples_per_s": w.samples / (w.t1 - w.t0),
                         "steps": w.steps, "failed": w.failed,
                         "gauges": _gauges()}
    row["memory_stats"] = dict(jax.devices()[0].memory_stats() or {})
    prepared.net = None
    cell.adapter.free(net)
    gc.collect()
    t1 = time.perf_counter()
    loop = int(t["steps_per_call"])

    def follow(mode="f32", loop_steps=loop):
        return token_loop.follow_reference(
            ref_mod, cfg, ref_mod.init_params(cfg, prepared.weights_key), batch,
            mode=mode, loop_steps=loop_steps)

    ref = follow()
    t2 = time.perf_counter()
    row["ref_loss"] = ref.loss + ref.loop_loss
    row["raw"] = {"reference": _raw(ref)}

    def judged(name, readings):
        found = compare.gaps(readings, ref)
        ok, rows = compare.judge(found, cell.limits)
        row[name] = {"gaps": found, "correct": ok,
                     "failed": [k for k, v in rows.items()
                                if v["limit"] is not None
                                and not (v["value"] is not None
                                         and v["value"] <= v["limit"])]}
        row[name + "_loss"] = readings.loss + readings.loop_loss
        row["raw"][name] = _raw(readings)

    judged("program", prog)
    if control:
        judged("control_int8", follow("int8"))
        first = follow(loop_steps=1)
        first.loop_loss = first.loop_loss * loop
        judged("loop_first_step_only", first)
    row["seconds"] = {"program": t1 - t0, "reference": t2 - t1,
                      "all": time.perf_counter() - t0}
    return row, net


def _gauges() -> dict:
    """The expert layers' gauges after the window's last call."""
    from deeplearning4j_tpu import telemetry
    return {k: v for k, v in telemetry.registry().snapshot().items()
            if k.startswith("moe.")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--window", type=float, default=0.0)
    ap.add_argument("--window-seeds", type=int, default=0)
    args = ap.parse_args()

    from harness import device
    from harness.manifest import Cell

    device.configure_compile_cache()
    cell = Cell(args.workload)
    # the readings set the limits: they come from the chip or not at all
    print(device.require_chip(cell.chips), flush=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"calibrate_{cell.name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    net = None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row, net = read_seed(cell, seed, net, n < args.control_seeds,
                             args.window if n < args.window_seeds else 0.0)
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        short = {k: {"correct": v["correct"], "failed": v["failed"],
                     **{n: round(g["value"], 5) for n, g in v["gaps"].items()}}
                 for k, v in row.items() if isinstance(v, dict) and "gaps" in v}
        mem = row["memory_stats"]
        print(json.dumps({"seed": seed, **short, "s": row["seconds"],
                          "setup_split_s": row["setup_split_s"],
                          "window": row.get("window"),
                          "peak_bytes": [mem.get("peak_bytes_in_use"),
                                         mem.get("peak_bytes_reserved")]}),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
