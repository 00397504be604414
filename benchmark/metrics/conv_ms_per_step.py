"""Device time a step under `dl4j.ConvolutionLayer/*`, forward and backward:
the time a convolution roofline would divide into, reported as a time."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.ConvolutionLayer/"))
