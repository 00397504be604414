"""The whole step's share of the chips' peak: the operations that forward and
backward require for one sample (the configuration's analytic count, nothing
recomputed counted) times the samples per second of this run, over chips times
the peak of the device. In a traced run the time the profiler took to start
and stop is left out of the window."""


def read(run):
    w = run.window
    seconds = (w.t1 - w.t0) - run.tracer.overhead_s
    if w.samples <= 0 or seconds <= 0:
        return None
    flops = run.cell.reference.train_flops_per_sample(run.cell.config)
    share = 100.0 * flops * w.samples / seconds / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])
    if share > 100.0:
        raise ValueError(f"step_mfu reads {share:.1f}% of the peak: the "
                         "operations are counted too high or time is missing")
    return share
