"""Closed loop, one client: `net.fit_on_device(x, y, steps=n)` on a resident
batch, called again and again until the window ends (default `sync=True`: one
loss readback a call). Traffic parameters: `batch`, `steps_per_call`,
`vary_batch`.

The proof steps go through the same entry on the same batch, one step a call,
so that the state after the first and the third step can be read; then the
window's own program (`steps_per_call`) runs once, which warms it up, and its
losses and the state it leaves are read for the comparison as well.
"""
from __future__ import annotations

from drivers import common

values, compare = common.values, common.compare_run


def _call(net, batch, steps, vary):
    return net.fit_on_device(batch[0], batch[1], steps=steps, vary_batch=vary)


def prepare(run) -> common.Prepared:
    t = run.cell.traffic
    params0, batch, k_weights = common.draw(run)
    net, probe = common.build(run, params0)
    del params0
    vary = bool(t.get("vary_batch", False))
    common.loop_call(probe, net, lambda n: _call(net, batch, n, vary),
                     int(t["steps_per_call"]))
    return common.Prepared(net, batch, k_weights, probe.readings)


def window(run, prepared: common.Prepared, seconds: float) -> common.Window:
    t = run.cell.traffic
    steps, vary = int(t["steps_per_call"]), bool(t.get("vary_batch", False))
    net, batch = prepared.net, prepared.batch
    return common.closed_loop(
        run, lambda: _call(net, batch, steps, vary), steps, seconds,
        diverged=lambda: net._diverged_at is not None)
