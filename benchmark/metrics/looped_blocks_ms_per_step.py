"""Device time a step of every operation under a looped stack's scope,
`dl4j.LoopedStack/<node>`, at any depth: all passes of its blocks (their
norms, attention and MLP, whose own scopes lie inside it) and of its final
norm, forward, recomputed and backward, with the scan's own work (the shared
weights' gradients summed over the passes). None where no operation of the
program has the scope."""
from harness import program_trace

SCOPE = "dl4j.LoopedStack/"


def read(run):
    p, table = program_trace.of(run), program_trace.scopes(run)
    if p is None or table is None:
        return None
    ns = p.device_ns_by_scope(table, lambda op_name: (SCOPE in op_name, ""))
    if not any(SCOPE in op_name for op_name in table.values()):
        return None
    return ns.get((True, ""), 0) / 1e6 / p.steps
