"""Share of the busy device time of the traced stretch whose operation the
scope table gives to a `dl4j.` scope of the program: how much of the step the
names cover. A fusion counts whole for the scope of the instruction whose
metadata XLA kept."""
from harness import program_trace


def read(run):
    named = program_trace.by_scope(run)
    if named is None:
        return None
    ns = sum(v for (scope, _), v in named.items() if scope is not None)
    return 100.0 * ns / program_trace.of(run).busy_ns
