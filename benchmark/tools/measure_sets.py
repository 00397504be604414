"""Runs one cell as the bound's rule asks: two sets of runs with the same
seeds in both, then traced runs, each run a new process, one after another;
keeps every result line in chiprun_out/sets_<cell>.jsonl. Run by hand through
the chip tool. The parent touches no JAX (one process per chip).

    python3 benchmark/tools/measure_sets.py --workload <cell> --seconds 30 \
        --seeds 11,12,13,14,15,16 --sets 2 --traced 3
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(workload, seed, seconds, trace, tag, out):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    try:
        result = json.loads(last)
    except ValueError:
        result = {"error": (p.stderr or "")[-1500:]}
    row = {"cell": workload, "tag": tag, "seed": seed, "trace": trace,
           "rc": p.returncode, "wall_s": wall, "result": result}
    with open(out, "a") as f:
        f.write(json.dumps(row) + "\n")
    m = {k: round(v["value"], 4) for k, v in result.get("metrics", {}).items()}
    print(tag, seed, "rc", p.returncode, "wall", round(wall, 1), "correct",
          result.get("correct"), m, flush=True)
    if not result.get("correct"):
        print((p.stderr or "")[-1200:], flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", f"sets_{args.workload}.jsonl")
    for s in range(args.sets):
        for seed in seeds:
            one(args.workload, seed, args.seconds, 0, f"{args.tag}set{s + 1}", out)
    for i in range(args.traced):
        one(args.workload, seeds[-1] + 1000 + i, args.seconds, 1,
            f"{args.tag}traced", out)


if __name__ == "__main__":
    main()
