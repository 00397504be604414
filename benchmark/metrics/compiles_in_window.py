"""Backend compiles (JAX monitoring events) between the first and the last
timed step: nothing may compile inside the window."""


def read(run):
    return float(run.compiles_in_window)
