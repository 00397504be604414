"""What a DL4J user types: `net.set_listeners(listener)`, `net.fit(iterator)`,
one epoch that lasts the window. The iterator yields one host batch, made once
from the seed and yielded anew for each step (DL4J's BenchmarkDataSetIterator),
until the deadline; the program's AsyncDataSetIterator moves each to the chip.
Traffic parameters: `batch`.

The proof steps are a first `fit` over an iterator of three batches, read by a
listener after the first and the third step; the window is a second `fit` on
the same net.
"""
from __future__ import annotations

import time

import numpy as np

from drivers import common
from harness.compare import PROOF_STEPS
from harness.listener import StepListener

values, compare = common.values, common.compare_run


class _Batches:
    """Yields the same host batch until `stop()` says no (a count, or the
    window's deadline)."""

    def __init__(self, features, labels, stop, annotate):
        self.features, self.labels = features, labels
        self._stop, self._annotate = stop, annotate
        self.yielded = 0

    def reset(self):
        pass

    def __iter__(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        while not self._stop(self.yielded):
            with self._annotate("bench.iterator_yield"):
                ds = DataSet(self.features, self.labels)
            self.yielded += 1
            yield ds


class _ProofListener:
    def __init__(self, probe):
        self.probe = probe

    def iteration_done(self, model, iteration: int):
        if iteration <= PROOF_STEPS:
            self.probe.after_step(model, iteration, model.score())


def prepare(run) -> common.Prepared:
    import jax
    params0, batch, k_weights = common.draw(run)
    host = tuple(np.asarray(a) for a in jax.device_get(batch))
    del batch
    net, probe = common.build(run, params0)
    del params0
    net.set_listeners(_ProofListener(probe))
    net.fit(_Batches(host[0], host[1], lambda n: n >= PROOF_STEPS,
                     run.tracer.annotate))
    return common.Prepared(net, host, k_weights, probe.readings)


def window(run, prepared: common.Prepared, seconds: float) -> common.Window:
    net, host, tracer = prepared.net, prepared.batch, run.tracer
    listener = StepListener(annotate=tracer.annotate, on_step=tracer.tick)
    net.set_listeners(listener)
    t0 = time.perf_counter()
    tracer.begin_window(t0)
    deadline = t0 + seconds
    batches = _Batches(host[0], host[1],
                       lambda n: time.perf_counter() >= deadline,
                       tracer.annotate)
    with tracer.annotate("bench.fit_call"):
        net.fit(batches)
    t1 = listener.finish()          # the last step's loss is on the host
    tracer.end_window()
    steps = len(listener.times)
    failed = listener.failed_steps()
    if net._diverged_at is not None:
        failed = max(failed, 1)
    batch = int(run.cell.traffic["batch"])
    return common.Window(
        t0=t0, t1=t1, steps=steps - failed, samples=(steps - failed) * batch,
        attempted=batches.yielded, failed=failed + (batches.yielded - steps),
        step_times=listener.times, etl_ms=listener.etl_ms,
        marks="bench.listener", steps_per_mark=1)
