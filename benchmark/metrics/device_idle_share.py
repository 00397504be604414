"""Share of the traced stretch in which no operation ran on the device."""


def read(run):
    r = run.reduced
    return None if r is None else 100.0 * r.idle_share
