"""What the training drivers share: drawing the weights and the batch from
the seed, building the net through the configuration's adapter, the shape of
what a window hands back, the end-to-end values of a window of training
steps, and the comparison with the plain reference. A driver of another kind
(serving) brings its own `values` and `compare`."""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, List

import jax

import numpy as np

from harness import compare, traffic


@dataclasses.dataclass
class Prepared:
    net: Any                  # the program's object: proved, then timed
    batch: Any                # (features, labels) of one step
    weights_key: Any          # redraws the first weights for the reference
    readings: compare.Readings
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    steps: int                # completed
    samples: int
    attempted: int            # steps
    failed: int
    step_times: List[float] = dataclasses.field(default_factory=list)
    etl_ms: List[float] = dataclasses.field(default_factory=list)
    marks: str = ""           # host annotation that marks the periods
    steps_per_mark: int = 1


def draw(run):
    """(first weights, batch, key of the weights) from --seed."""
    k_weights, k_batch = jax.random.split(run.key)
    batch = traffic.make_batch(run.cell.config, run.cell.traffic, k_batch)
    params0 = run.cell.reference.init_params(run.cell.config, k_weights)
    return params0, batch, k_weights


def build(run, params0):
    cfg = run.cell.config
    net = run.cell.adapter.build(cfg, params0, int(run.seed) % (2 ** 31 - 1))
    probe = compare.ProgramProbe(run.cell.adapter, cfg, params0,
                                 run.cell.reference.init_state(cfg))
    return net, probe


def loop_call(probe, net, call: Callable[[int], Any], steps: int) -> None:
    """The proof steps through the window's own entry, one step a call, so
    that the state after the first and the third can be read; then the
    window's own program of `steps` steps, once: that call compiles it (the
    warm-up) and its losses and the state it leaves are read too."""
    for step in range(1, compare.PROOF_STEPS + 1):
        losses = call(1)
        probe.after_step(net, step, losses[0], loop_follows=True)
    probe.after_loop(net, call(steps))


def values(run) -> dict:
    """The end-to-end values of a window of training steps: every sample of
    every completed step over the whole window, and where the driver timed
    single steps, the percentiles of the time between them."""
    w = run.window
    out = {"train_samples_per_s": w.samples / (w.t1 - w.t0)}
    if len(w.step_times) >= 2:
        gaps_ms = np.diff(np.asarray(w.step_times)) * 1e3
        out["step_ms_p95"] = float(np.percentile(gaps_ms, 95))
        out["step_ms_p50"] = float(np.percentile(gaps_ms, 50))
    return out


def compare_run(run):
    """(correct, {name: {"value", "limit", "leaf"}}): the plain reference
    follows the same steps from the same weights and batch, and the two
    sides' readings are judged by the cell's limits. Called once the window
    has closed and the program's state is freed."""
    cell, prepared = run.cell, run.prepared
    params0 = cell.reference.init_params(cell.config, prepared.weights_key)
    loop_steps = len(prepared.readings.loop_loss)   # the window's program
    extra = prepared.extra
    if extra.get("replicas", 1) > 1:
        ref = compare.follow_reference_replicated(
            cell.reference, cell.config, params0, prepared.batch,
            extra["replicas"], extra["threshold"], loop_steps=loop_steps)
    else:
        ref = compare.follow_reference(cell.reference, cell.config, params0,
                                       prepared.batch, loop_steps=loop_steps)
    w = run.window
    ok, rows = compare.judge(compare.gaps(prepared.readings, ref), cell.limits)
    return bool(ok and w.failed == 0 and w.steps > 0), rows


def closed_loop(run, call: Callable[[], Any], steps: int, seconds: float,
                diverged: Callable[[], bool] = lambda: False) -> Window:
    """One client: `call()` runs `steps` steps and returns their losses on the
    host; the next call is made when the last has returned, until the window
    ends. A non-finite loss or a recorded divergence fails the call's steps."""
    tracer = run.tracer
    done = failed = calls = 0
    t0 = time.perf_counter()
    tracer.begin_window(t0)
    deadline = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        tracer.tick(now)
        with tracer.annotate("bench.fit_call"):
            losses = call()
        calls += 1
        if all(math.isfinite(float(v)) for v in losses) and not diverged():
            done += steps
        else:
            failed += steps
    t1 = time.perf_counter()
    tracer.end_window()
    return Window(t0=t0, t1=t1, steps=done,
                  samples=done * int(run.cell.traffic["batch"]),
                  attempted=calls * steps, failed=failed,
                  marks="bench.fit_call", steps_per_mark=steps)
