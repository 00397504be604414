"""Share of the traced stretch the producer thread of the program's
`AsyncDataSetIterator` spends making and staging batches (`dl4j.async.produce`
+ `dl4j.async.stage`; the rest it waits on the full queue): at 100% the input
path sets the pace."""
from harness import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.threads_of("dl4j.async."):
        return None
    ns = p.span_ns("dl4j.async.produce") + p.span_ns("dl4j.async.stage")
    return 100.0 * ns / p.stretch_ns
