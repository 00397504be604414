"""Device time a step of the operations under `dl4j.updater`."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: phase == "update")
