"""Device time a step of the operations whose innermost scope is
`dl4j.HyperConnection/*`: the maps, Sinkhorn, the stream mixing and the norm
before the sublayer, forward, recomputed and backward; the wrapped sublayer's
operations carry its own scope and are not in it."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, lambda scope, phase: scope.startswith("dl4j.HyperConnection/"))
