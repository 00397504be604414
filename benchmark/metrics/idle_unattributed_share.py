"""Share of the traced stretch in which device 0 is idle and no leaf span of
the program's (`dl4j.*`, on the training thread) covers the gap: what the
measurement still cannot see."""
from harness import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.threads_of("dl4j.fit"):
        return None
    return 100.0 * p.idle_by_span()["unattributed"] / p.stretch_ns
