"""Device time a step under `dl4j.GatedMLP/*`, forward, recomputed and
backward: a dense SwiGLU's three products and the gate between them. (Where a
hyper-connection wraps the MLP the reader takes the sublayer's scope alone;
a residual block's own norm and add are the wrapper's, not in it.)"""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.GatedMLP/"))
