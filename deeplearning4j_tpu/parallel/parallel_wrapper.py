"""ParallelWrapper: single-host multi-chip data-parallel training.

Parity: ref deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:53 —
modes (:54-69), fit loop (:178-305), parameter averaging (:306-365 via native
Nd4j.averageAndPropagate), SHARED_GRADIENTS via EncodedGradientsAccumulator, and
trainer-per-device replication (DefaultTrainer.java:242-320). TPU-first redesign
(SURVEY §3.3): the trainer-thread zoo, MagicQueue and affinity pinning disappear —
one `shard_map` over a Mesh('data') runs a per-replica step on every chip in a single
XLA computation, and the averaging/gradient-sharing collectives ride ICI:

- AVERAGING (DP-1): replicas step independently; every `averaging_frequency` steps
  params AND updater state are pmean'd across the mesh (exact
  Nd4j.averageAndPropagate + averageUpdatersState semantics).
- SHARED_GRADIENTS (DP-2): each step, every replica applies its own (stateful) updater
  to its raw gradients, threshold-quantizes the resulting *update* (with residual, ref
  EncodingHandler encodes post-updater updates), psums the messages, and subtracts the
  aggregate from params — the synchronous rendering of the reference's async
  accumulator exchange (documented delta: no staleness).
- CUSTOM: caller-provided GradientsAccumulator applied host-side — per-replica
  gradients are computed on-mesh, stored into the accumulator, and the aggregated
  update is stepped through the updater identically on every replica
  (ref DefaultTrainer + StochasticGradientDescent.java:66-74 accumulator hook).

BatchNormalization running statistics (state_tree) are pmean'd across replicas at every
sync point, mirroring how DL4J's parameter averaging covers BN stats (they live in
params there).

Replicas hold identical params after fit(); the wrapped net receives replica-0's
(post-averaging) state, mirroring how ParallelWrapper writes back into the original
model.
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.multilayer import (
    _apply_updates, _compute_updates, _normalize_gradients)
from deeplearning4j_tpu.parallel.accumulation import threshold_encode
from deeplearning4j_tpu.parallel.mesh import compat_shard_map, make_mesh


class TrainingMode:
    AVERAGING = "averaging"
    SHARED_GRADIENTS = "shared_gradients"
    CUSTOM = "custom"


class ParallelWrapper:
    def __init__(self, model, workers: Optional[int] = None,
                 prefetch_buffer: int = 2, averaging_frequency: int = 1,
                 training_mode: str = TrainingMode.SHARED_GRADIENTS,
                 gradients_threshold: float = 1e-3,
                 report_score_after_averaging: bool = True,
                 mesh: Optional[Mesh] = None,
                 accumulator=None):
        if training_mode not in (TrainingMode.AVERAGING,
                                 TrainingMode.SHARED_GRADIENTS,
                                 TrainingMode.CUSTOM):
            raise ValueError(f"Unknown training mode: {training_mode!r}")
        if training_mode == TrainingMode.CUSTOM and accumulator is None:
            raise ValueError(
                "TrainingMode.CUSTOM requires a GradientsAccumulator "
                "(ref ParallelWrapper custom FancyBlockingQueue/accumulator wiring)")
        self.model = model
        self.mesh = mesh or make_mesh(workers)
        self.workers = int(np.prod(list(self.mesh.shape.values())))
        self.prefetch_buffer = prefetch_buffer
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.training_mode = training_mode
        self.gradients_threshold = float(gradients_threshold)
        self.report_score_after_averaging = report_score_after_averaging
        self.accumulator = accumulator
        self._carry = None  # (params_repl, opt_repl, states_repl, residual, step)
        self._step_fn = None
        self._step_fn_raw = None  # unjitted step (scanned by fit_on_device)
        self._scan_fn = None
        self._score = float("nan")
        self._listeners: List[Any] = []

    # ---------------------------------------------------------------- setup
    def _replicate(self, tree):
        """Stack per-replica copies on a leading axis sharded over the mesh."""
        R = self.workers
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), tree)
        sh = NamedSharding(self.mesh, P("data"))
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), stacked)

    def _ensure_setup(self):
        if self._carry is not None:
            return
        net = self.model
        net._check_init()
        params_repl = self._replicate(net.params_tree)
        opt_repl = self._replicate(net._opt_state)
        states_repl = self._replicate(net.state_tree)
        # residuals carry per-leaf (not as one flat vector): the flat view
        # would cost a full concatenate + re-slice of every parameter per step
        residual = self._replicate(jax.tree_util.tree_map(
            jnp.zeros_like, net.params_tree)) \
            if self.training_mode == TrainingMode.SHARED_GRADIENTS else None
        # step lives on device (replicated) so the carry round-trips through the
        # jitted step without host syncs; a host mirror (_host_step) serves listeners
        rep = NamedSharding(self.mesh, P())
        self._carry = (params_repl, opt_repl, states_repl, residual,
                       jax.device_put(jnp.asarray(net._step, jnp.int32), rep))
        self._host_step = net._step
        self._build_step()

    def _build_step(self):
        net = self.model
        updaters = net._updaters
        layers = net.layers
        mode = self.training_mode
        af = self.averaging_frequency
        thr = self.gradients_threshold
        mesh = self.mesh

        if mode == TrainingMode.CUSTOM:
            self._build_custom_step()
            return

        def _pmean_floats(tree):
            """Average float leaves across replicas (BN running stats); leave
            non-float state (counters/flags) as replica-local."""
            return jax.tree_util.tree_map(
                lambda a: lax.pmean(a, "data")
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)

        def per_replica_step(params, opt, states, residual, step, rng, bx, by, bfm, blm):
            # strip the leading per-replica axis added by shard_map
            params, opt, states = jax.tree_util.tree_map(
                lambda a: a[0], (params, opt, states))
            if residual is not None:
                residual = jax.tree_util.tree_map(lambda a: a[0], residual)
            # bx/by arrive already split along axis 0 by the P("data") spec
            rng = jax.random.fold_in(rng, lax.axis_index("data"))

            def loss_fn(p):
                loss, (ns, _) = net._loss_fn(p, states, bx, by, bfm, blm, rng,
                                             True, None)
                return loss, ns

            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)

            if mode == TrainingMode.SHARED_GRADIENTS:
                # EncodingHandler semantics: each replica applies its own stateful
                # updater to its raw gradients, the resulting *update* is threshold-
                # encoded; every replica then subtracts the SUM of all replicas'
                # sparse messages (EncodedGradientsAccumulator sums, not averages).
                # Encoding runs per-leaf: flattening to the reference's single
                # vector would add a full concatenate + re-slice of every
                # parameter per step (~2 extra HBM passes on a 25M-param net).
                upds, new_opt = _compute_updates(layers, updaters, grads, opt,
                                                 params, step)
                # one source of truth for the encoding math (XLA CSE merges
                # the two tree_map passes inside the jitted step)
                msg = jax.tree_util.tree_map(
                    lambda u, r: threshold_encode(u, r, thr)[0], upds, residual)
                residual = jax.tree_util.tree_map(
                    lambda u, r: threshold_encode(u, r, thr)[1], upds, residual)
                agg = lax.psum(msg, "data")
                new_params = jax.tree_util.tree_map(lambda p, d: p - d,
                                                    params, agg)
                new_states = _pmean_floats(new_states)
            else:  # AVERAGING
                new_params, new_opt = _apply_updates(layers, updaters, grads, opt,
                                                     params, step)
                n = lax.psum(1, "data")

                def avg(tree):
                    return jax.tree_util.tree_map(
                        lambda a: lax.psum(a, "data") / n, tree)

                def sync(t):
                    (p, o), s = t
                    return avg((p, o)), _pmean_floats(s)

                if af == 1:
                    (new_params, new_opt), new_states = sync(
                        ((new_params, new_opt), new_states))
                else:
                    (new_params, new_opt), new_states = lax.cond(
                        (step + 1) % af == 0, sync, lambda t: t,
                        ((new_params, new_opt), new_states))

            mean_loss = lax.psum(loss, "data") / lax.psum(1, "data")
            out = (jax.tree_util.tree_map(lambda a: a[None], (new_params, new_opt,
                                                              new_states)),
                   None if residual is None else jax.tree_util.tree_map(
                       lambda a: a[None], residual), mean_loss)
            return out

        repl_spec = P("data")
        shmapped = compat_shard_map(
            per_replica_step, mesh=mesh,
            in_specs=(repl_spec, repl_spec, repl_spec,
                      repl_spec if mode == TrainingMode.SHARED_GRADIENTS else None,
                      P(), P(), P("data"), P("data"), P("data"), P("data")),
            out_specs=((repl_spec, repl_spec, repl_spec),
                       repl_spec if mode == TrainingMode.SHARED_GRADIENTS else None,
                       P()))

        def step_fn(carry, rng, bx, by, bfm, blm):
            params_repl, opt_repl, states_repl, residual, step = carry
            (trees, new_residual, loss) = shmapped(
                params_repl, opt_repl, states_repl, residual, step, rng,
                bx, by, bfm, blm)
            new_params, new_opt, new_states = trees
            return (new_params, new_opt, new_states, new_residual, step + 1), loss

        # Pin output shardings to the input carry's shardings: without this, XLA may
        # normalize e.g. P("data") to P() on small meshes, the next call sees
        # different arg shardings, and the whole step silently recompiles EVERY fit.
        carry_sh = jax.tree_util.tree_map(lambda a: a.sharding, self._carry)
        loss_sh = NamedSharding(mesh, P())
        self._step_fn_raw = step_fn
        self._step_fn = jax.jit(step_fn, donate_argnums=(0,),
                                out_shardings=(carry_sh, loss_sh))

    def _build_custom_step(self):
        """CUSTOM mode: per-replica gradients computed on-mesh, aggregated through the
        caller's GradientsAccumulator host-side, and the aggregated gradient stepped
        through the updater identically on every replica (so replicas stay in sync)."""
        net = self.model
        updaters = net._updaters
        layers = net.layers
        mesh = self.mesh
        from deeplearning4j_tpu.util.flat_params import flatten_params, unflatten_params

        def per_replica_grads(params, opt, states, residual, step, rng, bx, by,
                              bfm, blm):
            params, opt, states = jax.tree_util.tree_map(
                lambda a: a[0], (params, opt, states))
            rng = jax.random.fold_in(rng, lax.axis_index("data"))

            def loss_fn(p):
                loss, (ns, _) = net._loss_fn(p, states, bx, by, bfm, blm, rng,
                                             True, None)
                return loss, ns

            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # sync BN running stats across replicas (float leaves only)
            new_states = jax.tree_util.tree_map(
                lambda a: lax.pmean(a, "data")
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                new_states)
            flat = flatten_params(grads)
            mean_loss = lax.psum(loss, "data") / lax.psum(1, "data")
            return (flat[None], jax.tree_util.tree_map(lambda a: a[None], new_states),
                    mean_loss)

        repl_spec = P("data")
        grads_shmapped = compat_shard_map(
            per_replica_grads, mesh=mesh,
            in_specs=(repl_spec, repl_spec, repl_spec, None, P(), P(),
                      P("data"), P("data"), P("data"), P("data")),
            out_specs=(repl_spec, repl_spec, P()))

        def apply_agg(params_repl, opt_repl, agg_flat, step):
            """Apply one aggregated flat gradient through the updater on replica-0
            params, then rebroadcast to all replicas (they are identical)."""
            params = jax.tree_util.tree_map(lambda a: a[0], params_repl)
            opt = jax.tree_util.tree_map(lambda a: a[0], opt_repl)
            grads = unflatten_params(params, agg_flat)
            new_params, new_opt = _apply_updates(layers, updaters, grads, opt,
                                                 params, step)
            R = self.workers
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (R,) + a.shape),
                (new_params, new_opt))

        # pin carry-shape output shardings (see _build_step comment)
        params_sh = jax.tree_util.tree_map(lambda a: a.sharding, self._carry[0])
        opt_sh = jax.tree_util.tree_map(lambda a: a.sharding, self._carry[1])
        apply_agg = jax.jit(apply_agg, donate_argnums=(0, 1),
                            out_shardings=(params_sh, opt_sh))

        def step_fn(carry, rng, bx, by, bfm, blm):
            params_repl, opt_repl, states_repl, _, step = carry
            flat_grads, new_states, loss = grads_shmapped(
                params_repl, opt_repl, states_repl, None, step, rng, bx, by, bfm, blm)
            for r in range(self.workers):
                self.accumulator.store_update(flat_grads[r], party=r)
            agg = self.accumulator.get_update()
            new_params, new_opt = apply_agg(params_repl, opt_repl, agg, step)
            return (new_params, new_opt, new_states, None, step + 1), loss

        self._step_fn = step_fn

    # ---------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1):
        """fit(x, y) | fit(DataSetIterator[, epochs]) (ref ParallelWrapper.fit :178)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
        self._ensure_setup()
        net = self.model
        if labels is not None:
            self._fit_one(DataSet(data, labels))
        elif isinstance(data, (DataSet, MultiDataSet)):
            self._fit_one(data)
        else:
            from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
            for _ in range(epochs):
                if hasattr(data, "reset"):
                    data.reset()
                it = data
                if getattr(it, "async_supported", True):
                    it = AsyncDataSetIterator(it, queue_size=self.prefetch_buffer)
                for ds in it:
                    self._fit_one(ds)
        self._write_back()
        return self

    def _fit_one(self, ds):
        net = self.model
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        bsh = NamedSharding(self.mesh, P("data"))

        def place(a):
            return jax.device_put(jnp.asarray(a, net.dtype), bsh)

        if isinstance(ds, MultiDataSet):
            # multi-input/-output graphs: every stream shards over the mesh
            # (ref ParallelWrapper.fit(MultiDataSetIterator))
            x = [place(f) for f in ds.features]
            y = [place(l) for l in ds.labels]
            n = x[0].shape[0]
            fm = None if ds.features_masks is None else [
                jnp.asarray(m) for m in ds.features_masks]
            lm = None if ds.labels_masks is None else [
                jnp.asarray(m) for m in ds.labels_masks]
        else:
            x = place(ds.features)
            y = place(ds.labels)
            n = x.shape[0]
            fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
            lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        if n % self.workers != 0:
            raise ValueError(
                f"Batch size {n} not divisible by workers {self.workers}")
        net._rng, sub = jax.random.split(net._rng)
        self._carry, loss = self._step_fn(self._carry, sub, x, y, fm, lm)
        self._score = loss
        # host mirror of the device step counter: listeners must not force a
        # device->host sync per iteration
        self._host_step += 1
        for lst in self._listeners:
            lst.iteration_done(self, self._host_step)

    def fit_on_device(self, x, y, steps: int, sync: bool = True):
        """Run `steps` data-parallel training steps as ONE jitted lax.scan on device
        (same batch each step — benchmark/epoch-runner mode, see
        MultiLayerNetwork.fit_on_device). This is the TPU-idiomatic measurement path:
        per-step host dispatch would measure the dispatch, not the mesh. Not available for CUSTOM mode (its
        accumulator is host-side by contract). Returns per-step mean losses."""
        step = self.model._step     # the wrapper's own mirror comes with set-up
        with telemetry.span("dl4j.pw.fit_on_device", step=step,
                            steps=int(steps)):
            return self._fit_on_device(x, y, steps, sync, step)

    def _fit_on_device(self, x, y, steps, sync, step):
        if self.training_mode == TrainingMode.CUSTOM:
            raise ValueError(
                "fit_on_device is unsupported in CUSTOM mode: the caller-provided "
                "GradientsAccumulator is applied host-side between steps")
        self._ensure_setup()
        net = self.model
        if np.shape(x)[0] % self.workers != 0:
            raise ValueError(
                f"Batch size {np.shape(x)[0]} not divisible by workers "
                f"{self.workers}")
        bsh = NamedSharding(self.mesh, P("data"))
        x = jax.device_put(jnp.asarray(x, net.dtype), bsh)
        y = jax.device_put(jnp.asarray(y, net.dtype), bsh)
        if self._scan_fn is None:
            raw = self._step_fn_raw
            carry_sh = jax.tree_util.tree_map(lambda a: a.sharding, self._carry)
            loss_sh = NamedSharding(self.mesh, P())

            @functools.partial(jax.jit, donate_argnums=(0,),
                               static_argnames=("n",),
                               out_shardings=(carry_sh, loss_sh))
            def dl4j_pw_device_loop(carry, rng, bx, by, n):
                def body(c, _):
                    carry_c, rng_c = c
                    rng_c, sub = jax.random.split(rng_c)
                    new_carry, loss = raw(carry_c, sub, bx, by, None, None)
                    return (new_carry, rng_c), loss

                (carry, _), losses = lax.scan(body, (carry, rng), None, length=n)
                return carry, losses

            self._scan_fn = dl4j_pw_device_loop
        net._rng, sub = jax.random.split(net._rng)
        with telemetry.span("dl4j.pw.fit_on_device.dispatch", step=step):
            self._carry, losses = self._scan_fn(self._carry, sub, x, y,
                                                n=int(steps))
        self._host_step += int(steps)
        if not sync:
            # deferred readback (see MultiLayerNetwork.fit_on_device): the
            # returned device array is the completion handle — timed callers
            # block_until_ready on it rather than paying a host copy per call
            self._score = losses[-1]
        else:
            # host transfer doubles as the synchronization point: callers must
            # observe completed work, not queued dispatches
            with telemetry.span("dl4j.pw.fit_on_device.readback", step=step):
                losses = np.asarray(losses)
            self._score = float(losses[-1])
        with telemetry.span("dl4j.pw.fit_on_device.write_back", step=step):
            self._write_back()
        return losses

    def _average_partial_window(self):
        """AVERAGING mode, fit() epilogue: when averaging_frequency does not
        divide the step count, the replicas hold un-averaged tail steps — DL4J
        averages that final partial window before writing back
        (ParallelWrapper.java:306-365 runs once more after the fit loop);
        without this, replica-0's un-averaged state would silently win."""
        if self.training_mode != TrainingMode.AVERAGING:
            return
        if self.averaging_frequency <= 1 or \
                self._host_step % self.averaging_frequency == 0:
            return
        if getattr(self, "_final_avg_jit", None) is None:
            mesh = self.mesh

            def avg(trees):
                params_repl, opt_repl, states_repl = trees

                def mean_repl(tree):
                    return jax.tree_util.tree_map(
                        lambda a: jnp.broadcast_to(
                            jnp.mean(a, axis=0, keepdims=True), a.shape)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

                return (mean_repl(params_repl), mean_repl(opt_repl),
                        mean_repl(states_repl))

            carry_sh = jax.tree_util.tree_map(lambda a: a.sharding,
                                              self._carry[:3])
            self._final_avg_jit = jax.jit(avg, donate_argnums=(0,),
                                          out_shardings=carry_sh)
        params_repl, opt_repl, states_repl, residual, step = self._carry
        params_repl, opt_repl, states_repl = self._final_avg_jit(
            (params_repl, opt_repl, states_repl))
        self._carry = (params_repl, opt_repl, states_repl, residual, step)

    def _write_back(self):
        """Copy replica-0 state back into the wrapped model (replicas are
        identical after sync: per-window during fit, with the final partial
        window averaged by _average_partial_window).
        ONE jitted extraction for all trees — per-leaf indexing would dispatch one
        program per parameter."""
        net = self.model
        self._average_partial_window()
        params_repl, opt_repl, states_repl, _, step = self._carry
        if getattr(self, "_writeback_jit", None) is None:
            self._writeback_jit = jax.jit(
                lambda trees: jax.tree_util.tree_map(lambda a: a[0], trees))
        net.params_tree, net._opt_state, net.state_tree = self._writeback_jit(
            (params_repl, opt_repl, states_repl))
        net._step = self._host_step

    def score(self):
        return float(self._score)

    def set_listeners(self, *listeners):
        self._listeners = list(listeners)

    def shutdown(self):
        self._carry = None
        self._step_fn = None
        self._step_fn_raw = None
        self._scan_fn = None

    # ---------------------------------------------------------------- builder
    class Builder:
        """(ref ParallelWrapper.Builder)"""

        def __init__(self, model):
            self._model = model
            self._kw = {}

        def workers(self, n: int):
            self._kw["workers"] = int(n)
            return self

        def prefetch_buffer(self, n: int):
            self._kw["prefetch_buffer"] = int(n)
            return self
        prefetchBuffer = prefetch_buffer

        def averaging_frequency(self, n: int):
            self._kw["averaging_frequency"] = int(n)
            return self
        averagingFrequency = averaging_frequency

        def training_mode(self, m: str):
            self._kw["training_mode"] = m
            return self
        trainingMode = training_mode

        def gradients_threshold(self, t: float):
            self._kw["gradients_threshold"] = float(t)
            return self

        def report_score_after_averaging(self, b: bool):
            self._kw["report_score_after_averaging"] = bool(b)
            return self
        reportScoreAfterAveraging = report_score_after_averaging

        def workspace_mode(self, m):  # parity no-op
            return self

        def mesh(self, m: Mesh):
            self._kw["mesh"] = m
            return self

        def gradients_accumulator(self, acc):
            """Caller-provided GradientsAccumulator for TrainingMode.CUSTOM
            (ref ParallelWrapper.Builder.gradientsAccumulator)."""
            self._kw["accumulator"] = acc
            return self
        gradientsAccumulator = gradients_accumulator

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._model, **self._kw)
