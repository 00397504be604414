"""Device time of one step: the busy time of the traced stretch over the
steps that ran in it."""


def read(run):
    r = run.reduced
    if r is None or r.periods <= 0:
        return None
    return 1e3 * r.busy_s / (r.periods * run.window.steps_per_mark)
