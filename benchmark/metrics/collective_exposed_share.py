"""Share of the traced stretch, on device 0, in which a collective ran and no
compute did: communication that nothing hides."""
from harness import trace

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def read(run):
    r = run.reduced
    if r is None or not any(is_collective(n) for n in r.ops_s):
        return None
    lo = min(s for _, s, _ in r.op_events)
    hi = max(s + d for _, s, d in r.op_events)
    alone = trace.exposed(r.op_events, is_collective,
                          lambda n: not is_collective(n), lo, hi)
    return 100.0 * alone / r.stretch_s
