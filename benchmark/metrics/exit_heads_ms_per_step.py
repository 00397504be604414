"""Device time a step under `dl4j.LoopExitHead/*`: the exit head of a looped
stack, every pass's head product and gate, the exit distribution and the
weighted loss, forward, recomputed and backward. None where no operation of
the program has the scope."""
from harness import program_trace

SCOPE = "dl4j.LoopExitHead/"


def read(run):
    table = program_trace.scopes(run)
    if table is None or not any(SCOPE in op_name for op_name in table.values()):
        return None
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith(SCOPE))
