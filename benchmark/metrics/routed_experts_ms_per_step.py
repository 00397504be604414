"""Device time a step under `dl4j.RoutedExperts/*`, forward, recomputed and
backward: the router, the sort of the assignments by expert, the held
experts' grouped products and the shared expert."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.RoutedExperts/"))
