"""Host time `ParallelWrapper.fit_on_device` spends around a call of the
mesh's program: the time in `dl4j.pw.fit_on_device` less its `.readback` (the
wait for the call's losses), which leaves the batch's placement, the
dispatch and `.write_back` (the copy of the replicas' state back into the
model, dispatched after every call: PERF.md, the 52 ms gap), over the steps
of the traced stretch. The wrapper's twin of `host_dispatch_ms_per_step`,
which reads the nets' own spans and finds none under the wrapper."""
from harness import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.threads_of("dl4j.pw.fit_on_device"):
        return None
    ns = p.span_ns("dl4j.pw.fit_on_device") \
        - p.span_ns("dl4j.pw.fit_on_device.readback")
    return ns / 1e6 / p.steps
