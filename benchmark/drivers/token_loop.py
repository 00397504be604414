"""Closed loop, one client, on token ids: `net.fit_on_device(x, y, steps=n)`
on a resident batch of `batch` sequences of `sequence_length` ids drawn evenly
from the rows of the vocabulary that the configuration holds, the labels the
next ids (which the configuration's adapter also hands the net as its second
input), called again and again until the window ends. Traffic parameters:
`batch`, `steps_per_call`, `vary_batch`. The loop, the proof steps and the
window's values are `drivers/common.py`'s, as `device_loop.py` has them.

Two things are this driver's own, both because of the size it is for (0.8 B
parameters at 16 bytes each fill three quarters of the chip):

- The copies of the weights that the comparison keeps (the first weights,
  those after the proof steps) are held on the host, on both sides, and the
  reference gives up its weights to each step. `harness/compare.py`'s
  `follow_reference` and `ProgramProbe` keep them on the device, three at a
  time, which does not fit beside Adam's moments.
- With `--trace 1` the scope table of the window's program is read off the
  timed net itself once the window has closed, and left where
  `harness/program_trace.py` caches it: `lower_window_program` there asks
  `harness/traffic.py` for the batch's shapes, which knows no token ids.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

from drivers import common
from harness import compare as compare_mod, program_trace, refmath


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ids(batch, length, rows, first_row, key):
    ids = first_row + jax.random.randint(key, (batch, length + 1), 0, rows)
    return ids[:, :-1], ids[:, 1:]


def make_batch(cfg: dict, traffic: dict, key):
    """(features, labels), integer, one step's."""
    first_row = cfg.get("share", {}).get("index", 0) * cfg["vocab_size"]
    return _ids(int(traffic["batch"]), int(cfg["sequence_length"]),
                int(cfg["vocab_size"]), int(first_row), key)


def _host(tree):
    return {k: float(v) for k, v in jax.device_get(tree).items()}


@jax.jit
def _leaf_diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _diff_norms(on_device, on_host):
    """{leaf: ||a - b||_2}, a leaf at a time: the host's copy of one leaf is
    all that crosses to the device at once."""
    return {k: float(_leaf_diff_norm(v, on_host[k])) for k, v in on_device.items()}


def _first_gradient(readings, grad_sq) -> None:
    """The first gradient's norms, and its squares kept on the host."""
    readings.grad = {k: max(v, 0.0) ** 0.5
                     for k, v in _host(refmath.leaf_sums(grad_sq)).items()}
    readings.grad_sq1 = jax.device_get(grad_sq)


class HostProbe(compare_mod.ProgramProbe):
    """`ProgramProbe` with the first weights and those after the proof steps
    on the host (they cross to the device for one difference at a time)."""

    def __init__(self, adapter, cfg, params0):
        super().__init__(adapter, cfg, jax.device_get(params0), {})

    def after_step(self, net, step, loss, loop_follows=False):
        if step != compare_mod.PROOF_STEPS:
            return super().after_step(net, step, loss, loop_follows)
        self.readings.loss.append(float(loss))
        params = self.adapter.params_of(net)
        self.readings.update = _diff_norms(params, self.params0)
        self.params3 = jax.device_get(params) if loop_follows else None

    def after_loop(self, net, losses):
        self.readings.loop_loss = [float(v) for v in losses]
        params = self.adapter.params_of(net)
        self.readings.loop_update = _diff_norms(params, self.params0)
        self.readings.loop_move = _diff_norms(params, self.params3)
        self.params0 = self.params3 = None


def follow_reference(ref, cfg, params0, batch, mode: str = "f32",
                     loop_steps: int = 0) -> compare_mod.Readings:
    """`harness.compare.follow_reference`'s readings with one copy of the
    weights on the device: `params0` is given up to the first step."""
    x, y = batch
    start = jax.device_get(params0)
    params, opt = params0, ref.init_opt(cfg, params0)
    del params0
    out = compare_mod.Readings()

    def step():
        nonlocal params, opt
        params, opt, _, loss = ref.train_step(cfg, mode, params, opt, {}, x, y)
        return float(loss)

    for n in range(compare_mod.PROOF_STEPS):
        out.loss.append(step())
        if n == 0:
            _first_gradient(out, ref.first_gradient_sq(cfg, opt))
    out.update = _diff_norms(params, start)
    if loop_steps:
        before = jax.device_get(params)
        out.loop_loss = [step() for _ in range(loop_steps)]
        out.loop_update = _diff_norms(params, start)
        out.loop_move = _diff_norms(params, before)
    return out


def _call(run, net, batch, steps):
    x, y = run.cell.adapter.batch_of(*batch)
    return net.fit_on_device(x, y, steps=steps,
                             vary_batch=bool(run.cell.traffic.get("vary_batch", False)))


def prepare(run, net=None) -> common.Prepared:
    """`net`: a net that an earlier seed built in this process
    (`tools/calibrate_token_loop.py`); it gets this seed's weights and keeps
    its compiled programs. Where set-up goes is written to the standard
    error and kept in `extra["setup_split_s"]`."""
    cfg, t = run.cell.config, run.cell.traffic
    adapter, split, t0 = run.cell.adapter, {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        now = time.perf_counter()
        split[name], t0 = now - t0, now

    # a program without this model fails here, before 3 GB of weights are drawn
    adapter.zoo(cfg, 0)
    if net is not None:
        adapter.free(net)
    k_weights, k_batch = jax.random.split(run.key)
    batch = make_batch(cfg, t, k_batch)
    params0 = run.cell.reference.init_params(cfg, k_weights)
    jax.block_until_ready(params0)
    lap("draw_weights")
    probe = HostProbe(adapter, cfg, params0)
    lap("weights_to_host")
    net = adapter.build(cfg, params0, int(run.seed) % (2 ** 31 - 1)) \
        if net is None else adapter.load(net, params0)
    del params0
    lap("build_net")
    calls = []

    def call(n):
        at = time.perf_counter()
        losses = _call(run, net, batch, n)
        calls.append(time.perf_counter() - at)
        return losses

    common.loop_call(probe, net, call, int(t["steps_per_call"]))
    # the first call of each program traces, lowers and compiles it; what is
    # left of the lap is the probe's copies and differences through the host
    split.update(first_proof_call=calls[0], other_proof_calls=sum(calls[1:-1]),
                 first_loop_call=calls[-1])
    lap("proof_and_loop")
    split["probe_host_copies"] = split.pop("proof_and_loop") - sum(calls)
    print("set-up split, s: " + json.dumps({k: round(v, 2) for k, v in split.items()}),
          file=sys.stderr, flush=True)
    return common.Prepared(net, batch, k_weights, probe.readings,
                           extra={"setup_split_s": split})


def window(run, prepared: common.Prepared, seconds: float) -> common.Window:
    steps = int(run.cell.traffic["steps_per_call"])
    net, batch = prepared.net, prepared.batch
    return common.closed_loop(
        run, lambda: _call(run, net, batch, steps), steps, seconds,
        diverged=lambda: net._diverged_at is not None)


def _seed_scope_table(run) -> None:
    """{instruction: op_name} of the window's program, compiled again from
    the timed net at the batch's own shapes (a cache hit), where
    `program_trace.scopes` looks for it; the compile counters are read first,
    as they stood when the window closed."""
    from deeplearning4j_tpu.telemetry import profiler
    program_trace.counters(run)
    net, t = run.prepared.net, run.cell.traffic
    x, y = run.cell.adapter.batch_of(*run.prepared.batch)
    lowered = net.lower_train_step(x, y, steps=int(t["steps_per_call"]),
                                   vary_batch=bool(t.get("vary_batch", False)))
    run._op_scopes = profiler.op_scopes(lowered.compile())


def values(run) -> dict:
    if run.trace:
        _seed_scope_table(run)
    return common.values(run)


def compare(run):
    """(correct, rows): the plain reference follows the same steps from the
    same weights and ids, and the readings are judged by the cell's limits."""
    cell, prepared = run.cell, run.prepared
    params0 = cell.reference.init_params(cell.config, prepared.weights_key)
    ref = follow_reference(cell.reference, cell.config, params0, prepared.batch,
                           loop_steps=len(prepared.readings.loop_loss))
    del params0
    ok, rows = compare_mod.judge(compare_mod.gaps(prepared.readings, ref),
                                 cell.limits)
    w = run.window
    return bool(ok and w.failed == 0 and w.steps > 0), rows
