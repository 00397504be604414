"""Reads chiprun_out/sets_<cell>.jsonl and gives, per set and metric, the
median and the spread the bound's rule uses: the distance between the first
and third quartile (`statistics.quantiles(values, n=4)`) over the median.

    python3 benchmark/tools/spreads.py chiprun_out/sets_<cell>.jsonl [...]
"""
import collections
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / (statistics.median(values) or float("nan"))


def main():
    for path in sys.argv[1:]:
        sets = collections.defaultdict(lambda: collections.defaultdict(list))
        for line in open(path):
            row = json.loads(line)
            for name, m in row["result"].get("metrics", {}).items():
                sets[row["tag"]][name].append(m["value"])
            if row["result"].get("correct") is not True:
                print("NOT CORRECT:", row["tag"], row["seed"], row["rc"])
        print(path)
        for tag, metrics in sets.items():
            for name, values in metrics.items():
                if len(values) >= 2:
                    print(f"  {tag:10s} {name:22s} n={len(values)} "
                          f"median={statistics.median(values):.6g} "
                          f"spread={100 * spread(values):.3f}%  "
                          f"min={min(values):.6g} max={max(values):.6g}")
                else:
                    print(f"  {tag:10s} {name:22s} {values}")


if __name__ == "__main__":
    main()
