"""Device time a step under `dl4j.BatchNormalization/*`, forward and
backward."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.BatchNormalization/"))
