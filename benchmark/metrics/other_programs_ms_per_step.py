"""Busy device time a step inside programs (`XLA Modules`) that are not the
program's train program (`jit_dl4j_*`): casts, copies and slices that the host
code dispatches around the step."""
from harness import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.train_modules():
        return None
    return p.other_programs_ns() / 1e6 / p.steps
