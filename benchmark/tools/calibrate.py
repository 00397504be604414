"""Reads, for a dozen seeds in one process, what the limits of a cell's
comparison are set from, and judges each reading by the limits the cell's
file has now: the program against the plain reference (the lower reading),
and in the program's place the int8 control and the planted faults (the upper
readings): half of the batch left out; only the first step of the window's
call of n steps updating; on several chips, the exchange left out. The
reference here always follows the window's call too, so every number is read
whether or not the cell's runs compare it. No window is measured. Run by hand
through the chip tool; writes chiprun_out/calibrate_<cell>.jsonl.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--program 1] [--control 1] [--faults half_batch,loop_first_step_only]

`--program 0` reads only the reference's side (the controls and faults of a
four-chip cell, on one chip).
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
    return {k: getattr(readings, k) for k in ("grad", "update", "state",
                                              "loop_update", "loop_move")}


FAULTS = ("half_batch", "loop_first_step_only", "no_exchange")


def read_seed(cell, seed: int, program: bool, control: bool, faults) -> dict:
    """One seed's readings: {"program" | "control_int8" | fault: {"gaps",
    "correct", "failed"}} with the losses and the seconds beside them."""
    import run as bench_run
    from drivers import common
    from harness import compare, traffic
    from harness.tracer import Tracer

    ref_mod, cfg, t = cell.reference, cell.config, cell.traffic
    t0 = time.perf_counter()
    r = bench_run.Run(cell, seed, 0.0, False)
    r.key = traffic.key_from_seed(seed)
    r.tracer = Tracer(False, "", 1.0)
    row = {"cell": cell.name, "seed": seed}
    if program:
        prepared = cell.driver.prepare(r)
        batch, k_w, prog = prepared.batch, prepared.weights_key, prepared.readings
        prepared.net = None
        del prepared
        gc.collect()
    else:
        params0, batch, k_w = common.draw(r)
        del params0
    t1 = time.perf_counter()
    params0 = ref_mod.init_params(cfg, k_w)
    replicas = cell.chips if "gradients_threshold" in t else 1
    loop = int(t.get("steps_per_call", 0))

    def follow(mode="f32", rows=None, loop_steps=loop, **kw):
        if replicas > 1:
            return compare.follow_reference_replicated(
                ref_mod, cfg, params0, batch, replicas,
                float(t["gradients_threshold"]), mode=mode,
                rows=None if rows is None else rows // replicas,
                loop_steps=loop_steps, **kw)
        return compare.follow_reference(ref_mod, cfg, params0, batch, mode=mode,
                                        rows=rows, loop_steps=loop_steps)

    ref = follow()
    t2 = time.perf_counter()
    row["ref_loss"] = ref.loss + ref.loop_loss
    # leaf by leaf, so that another number can be tried on the same readings
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

    if program:
        judged("program", prog)
    if control:
        judged("control_int8", follow("int8"))
    if "half_batch" in faults:
        judged("half_batch", follow(rows=int(t["batch"]) // 2))
    if "loop_first_step_only" in faults and loop > 1:
        first = follow(loop_steps=1)
        first.loop_loss = first.loop_loss * loop
        judged("loop_first_step_only", first)
    if "no_exchange" in faults and replicas > 1:
        judged("no_exchange", follow(exchange=False))
    row["seconds"] = {"program": t1 - t0, "reference": t2 - t1,
                      "all": time.perf_counter() - t0}
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which of them to plant, where the cell can have them")
    args = ap.parse_args()

    from harness import device
    from harness.manifest import Cell

    device.configure_compile_cache()
    cell = Cell(args.workload)
    # the readings set the limits: they come from the chip or not at all
    print(device.require_chip(cell.chips if args.program else 1), flush=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"calibrate_{cell.name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = read_seed(cell, seed, bool(args.program), bool(args.control),
                        [f for f in args.faults.split(",") if f])
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        short = {k: {"correct": v["correct"], "failed": v["failed"],
                     **{n: round(g["value"], 5) for n, g in v["gaps"].items()}}
                 for k, v in row.items() if isinstance(v, dict) and "gaps" in v}
        print(json.dumps({"seed": seed, **short, "s": row["seconds"]}), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
