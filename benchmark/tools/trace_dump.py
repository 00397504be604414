"""Says what is in a profiler trace: planes, lines, event names with their
summed time, and the stats of a few events. For looking at one trace by hand
before writing a reader against it.

    python3 benchmark/tools/trace_dump.py [<file.xplane.pb>] [<tag>]

With no file it takes the newest under `.bench_trace/` (what the last
`--trace 1` run left). Writes chiprun_out/trace_dump_<tag>.json.
"""
import collections
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def summarise(path):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            total = collections.Counter()
            count = collections.Counter()
            sample = {}
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                if e.name not in sample and len(sample) < 400:
                    sample[e.name] = e
            top = total.most_common(40)
            p["lines"].append({
                "line": line.name, "events": len(events),
                "first_start_ns": events[0].start_ns if events else None,
                "last_end_ns": (events[-1].start_ns + events[-1].duration_ns)
                if events else None,
                "top": [[n, t / 1e9, count[n]] for n, t in top],
                "stats": {n: {k: str(v)[:200] for k, v in list(sample[n].stats)[:24]}
                          for n, _ in top[:12] if n in sample}})
        out.append(p)
    return out


def main():
    path = sys.argv[1] if len(sys.argv) > 1 and sys.argv[1] else None
    tag = sys.argv[2] if len(sys.argv) > 2 else "last"
    if path is None:
        files = sorted(glob.glob(os.path.join(
            ROOT, ".bench_trace", "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            sys.exit("no trace under .bench_trace/")
        path = files[-1]
    print("trace", path, os.path.getsize(path), "bytes")
    summary = summarise(path)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"trace_dump_{tag}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for p in summary:
        print(p["plane"])
        for line in p["lines"]:
            print("   ", line["line"], line["events"],
                  [f"{n}:{t:.4f}s x{c}" for n, t, c in line["top"][:8]])


if __name__ == "__main__":
    main()
