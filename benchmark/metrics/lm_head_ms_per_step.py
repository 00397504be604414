"""Device time a step under `dl4j.TokenCrossEntropyHead/*`: the output head's
product and the softmax cross-entropy, of the main model and of the MTP
module, forward, recomputed and backward."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.TokenCrossEntropyHead/"))
