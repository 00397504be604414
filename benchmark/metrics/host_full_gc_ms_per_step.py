"""Host time in Python's full (generation-2) collections a step: the
program's `dl4j.host.gc` spans that start inside the window, from its own
ring (`harness/program_ring.py`), summed in ms over the window's completed
steps. A full collection stalls the thread that runs it; on the training
thread between two calls the device waits for the next dispatch."""
from harness import program_ring


def read(run):
    spans = program_ring.window_spans(run, "dl4j.host.gc")
    if spans is None or not run.window.steps:
        return None
    return sum(end - start for _, start, end, _, _ in spans) * 1e3 \
        / run.window.steps
