"""Device time a step under `dl4j.GatedAttention/*`, forward, recomputed and
backward: the projections, the norms a head, the rotary part, the attention
kernel on grouped k/v heads, the output gate and product."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.GatedAttention/"))
