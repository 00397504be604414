"""Data-parallel loop over the cell's chips: `ParallelWrapper(net,
SHARED_GRADIENTS, gradients_threshold).fit_on_device(x, y, steps=n)` on a
resident batch sharded over `make_mesh(chips)`, called again and again until
the window ends. Traffic parameters: `batch` (all chips together),
`steps_per_call`, `gradients_threshold`.

`ParallelWrapper.fit_on_device` has no `vary_batch`; nothing in a net this
driver is given is frozen, so nothing but the input cast can be hoisted.
The proof steps go through the same entry, one step a call, and the window's
own program follows them once (warm-up and reading, as in `device_loop`); the
wrapper writes replica 0's state back into the net after each call, which is
what the probe reads.
"""
from __future__ import annotations

from drivers import common

values, compare = common.values, common.compare_run


def prepare(run) -> common.Prepared:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.parallel_wrapper import (
        ParallelWrapper, TrainingMode)
    t = run.cell.traffic
    params0, batch, k_weights = common.draw(run)
    net, probe = common.build(run, params0)
    del params0
    mesh = make_mesh(run.cell.chips)
    batch = jax.device_put(batch, NamedSharding(mesh, PartitionSpec("data")))
    wrapper = ParallelWrapper(
        net, training_mode=TrainingMode.SHARED_GRADIENTS,
        gradients_threshold=float(t["gradients_threshold"]), mesh=mesh)
    common.loop_call(
        probe, net, lambda n: wrapper.fit_on_device(batch[0], batch[1], steps=n),
        int(t["steps_per_call"]))
    return common.Prepared(wrapper, batch, k_weights, probe.readings,
                           extra={"replicas": run.cell.chips,
                                  "threshold": float(t["gradients_threshold"])})


def window(run, prepared: common.Prepared, seconds: float) -> common.Window:
    steps = int(run.cell.traffic["steps_per_call"])
    wrapper, batch = prepared.net, prepared.batch
    return common.closed_loop(
        run, lambda: wrapper.fit_on_device(batch[0], batch[1], steps=steps),
        steps, seconds)
