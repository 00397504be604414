"""Device time a step under `dl4j.LatentAttention/*`, forward, recomputed and
backward: the low-rank products, the rotary key and the attention kernel."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.LatentAttention/"))
