"""Host time the program spends on a step before the chip can start it: the
training thread's time in `dl4j.fit_batch` less its `.listeners` child (where
the benchmark's listener waits for a loss), or in `dl4j.fit_on_device` less
its `.readback` (the wait for the call's losses), over the steps of the
traced stretch."""
from harness import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.threads_of("dl4j.fit"):
        return None
    ns = p.span_ns("dl4j.fit_batch") - p.span_ns("dl4j.fit_batch.listeners") \
        + p.span_ns("dl4j.fit_on_device") \
        - p.span_ns("dl4j.fit_on_device.readback")
    return ns / 1e6 / p.steps
