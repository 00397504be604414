"""Device time a step under `dl4j.GatedDeltaNet/*`, forward, recomputed and
backward: the projections, the causal convolution, the gates, the recurrence
(`delta_rule` inside it), the output norm and gate, the output product."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.GatedDeltaNet/"))
