"""Seconds the process spent tracing the program's functions to jaxprs and
lowering them to modules, by the program's own counters (`dl4j.compile.trace_s`
+ `.lower_s`, JAX's monitoring events) as they stood when the window had
closed and before any reader of this PR's compiled anything: the part of
`setup_s` that neither a compile cache nor a faster compiler takes away."""
from harness import program_trace


def read(run):
    c = program_trace.counters(run)
    return None if c is None else c["trace_s"] + c["lower_s"]
