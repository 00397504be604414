"""MultiLayerNetwork: the sequential-stack network.

Parity: ref nn/multilayer/MultiLayerNetwork.java (3,104 LoC) — init with param flattening
(:528-640), feedForward (:849-961), fit loop (:1149-1255), backprop (:1258-1450), tBPTT
(:1484+), score, rnnTimeStep (:2521 area). TPU-first redesign: there is no per-layer
imperative interpreter or hand-written backprop — `fit` builds ONE jitted train step
(forward → loss → jax.grad → updater → params') with params/opt-state donated, so the
whole iteration is a single XLA computation on device. The Solver/StochasticGradientDescent/
BaseOptimizer machinery (ref optimize/Solver.java:43) collapses into that step function.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.enums import BackpropType, GradientNormalization
from deeplearning4j_tpu.nn.conf.configuration import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    BaseLayerConf, apply_dropout, layer_scope as _layer_scope)
from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM
from deeplearning4j_tpu.nn.divergence import DivergenceSentinelMixin
from deeplearning4j_tpu import telemetry as _telemetry
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.nn.conf.preprocessors import (
    FeedForwardToRnnPreProcessor, RnnToFeedForwardPreProcessor)
from deeplearning4j_tpu.nn.updater.updaters import BaseUpdater, Sgd
from deeplearning4j_tpu.util.flat_params import flatten_params, num_params, unflatten_params


_telemetry.count_compiles()   # dl4j.compile.* counters, from here on
_telemetry.count_gc()         # dl4j.host.gc.* counters and full-collection spans


def _cast_params(layers, names, params_tree, dtype):
    """The layers' parameters in the compute type, each layer's casts under
    its own name."""
    from deeplearning4j_tpu.util.dtypes import cast_params
    out = []
    for layer, name, p in zip(layers, names, params_tree):
        with _layer_scope(layer, name):
            out.append(cast_params(p, dtype))
    return out


def _normalize_gradients(layer: BaseLayerConf, grads: Dict[str, jnp.ndarray]):
    """Per-layer gradient normalization (ref GradientNormalization enum semantics)."""
    gn = layer.gradient_normalization
    if gn == GradientNormalization.NoNormalization or not grads:
        return grads
    thr = layer.gradient_normalization_threshold
    if gn == GradientNormalization.ClipElementWiseAbsoluteValue:
        return {k: jnp.clip(g, -thr, thr) for k, g in grads.items()}
    if gn in (GradientNormalization.ClipL2PerLayer,
              GradientNormalization.RenormalizeL2PerLayer):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()) + 1e-12)
        if gn == GradientNormalization.RenormalizeL2PerLayer:
            scale = 1.0 / norm
        else:
            scale = jnp.where(norm > thr, thr / norm, 1.0)
        return {k: g * scale for k, g in grads.items()}
    # per-param-type variants
    out = {}
    for k, g in grads.items():
        norm = jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-12)
        if gn == GradientNormalization.RenormalizeL2PerParamType:
            out[k] = g / norm
        else:  # ClipL2PerParamType
            out[k] = g * jnp.where(norm > thr, thr / norm, 1.0)
    return out


def _compute_updates(layers, updaters, grads, opt_state, params_tree, step):
    """Per-layer: normalize gradients, run the stateful updater.
    Returns (updates, new_opt_state) — the single shared implementation of the
    reference's Solver/updater step, used by every training path."""
    upds, new_opt = [], []
    with jax.named_scope("dl4j.updater"):
        for i, (layer, u) in enumerate(zip(layers, updaters)):
            g = _normalize_gradients(layer, grads[i])
            upd, st = u.update(g, opt_state[i], params_tree[i], step)
            upds.append(upd)
            new_opt.append(st)
    return upds, new_opt


def _subtract_updates(params_tree, upds):
    with jax.named_scope("dl4j.updater"):
        return [jax.tree_util.tree_map(lambda p, d: p - d, pt, ut)
                for pt, ut in zip(params_tree, upds)]


def _apply_updates(layers, updaters, grads, opt_state, params_tree, step):
    """params' = params - updater(grads) for every layer."""
    upds, new_opt = _compute_updates(layers, updaters, grads, opt_state,
                                     params_tree, step)
    return _subtract_updates(params_tree, upds), new_opt


def _abstract(a, dtype) -> jax.ShapeDtypeStruct:
    """The shape a batch array has once `fit_batch`/`fit_on_device` has
    converted it (`jnp.asarray(a, dtype)`), without the array."""
    return jax.ShapeDtypeStruct(a.shape if hasattr(a, "shape")
                                else np.shape(a), dtype)


def _device_loop_args(net, rng, x, y, fmask, lmask):
    """What a net's device loop is called with, all but the static `n`
    (`fit_on_device`, `lower_train_step`)."""
    return (net.params_tree, net._opt_state, net.state_tree,
            jnp.asarray(net._step, jnp.int32), rng, x, y, fmask, lmask,
            net._health_nf_in())


def _train_step_args(net, rng, x, y, fmask, lmask, rnn_init_states):
    """What a net's `_train_step_fn` is called with (`fit_batch`,
    `lower_fit_batch`): the loop's, and the carried RNN states."""
    args = _device_loop_args(net, rng, x, y, fmask, lmask)
    return args[:-1] + (rnn_init_states, args[-1])


class MultiLayerNetwork(DivergenceSentinelMixin, _health.HealthMonitorMixin):
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[BaseLayerConf] = conf.layers
        self.params_tree: List[Dict[str, jnp.ndarray]] = []
        self.state_tree: List[Dict[str, Any]] = []
        self._updaters: List[BaseUpdater] = []
        self._opt_state: List[Any] = []
        self._step = 0
        self._score = float("nan")
        self._listeners: List[Any] = []
        self._rng = None
        self._initialized = False
        self._train_step_fn = None
        self._rnn_state: Optional[List[Any]] = None
        self._accumulator = None  # GradientsAccumulator hook (ref MultiLayerNetwork.java:647)
        self._last_etl_ms = 0.0
        self.dtype = jnp.dtype(conf.global_conf.dtype)
        gc = conf.global_conf
        self.compute_dtype = (jnp.dtype(gc.compute_dtype)
                              if getattr(gc, "compute_dtype", None) else self.dtype)

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[Sequence[Dict[str, jnp.ndarray]]] = None):
        gc = self.conf.global_conf
        key = jax.random.PRNGKey(gc.seed)
        self._rng = jax.random.PRNGKey(gc.seed + 1)
        input_types = self.conf.input_types_per_layer()
        self.params_tree, self.state_tree = [], []
        for i, layer in enumerate(self.layers):
            key, sub = jax.random.split(key)
            if params is not None:
                # deep-copy: the train step donates param buffers, so sharing arrays
                # with the caller (e.g. clone()) would invalidate theirs after fit
                p = {k: jnp.array(v, copy=True) for k, v in params[i].items()}
            else:
                p = layer.init_params(sub, input_types[i], self.dtype) \
                    if layer.has_params() else {}
            self.params_tree.append(p)
            self.state_tree.append(layer.init_state(input_types[i], self.dtype))

        global_updater = self.conf.get_updater()
        self._updaters = []
        for layer in self.layers:
            if layer.frozen:
                from deeplearning4j_tpu.nn.updater.updaters import NoOp
                self._updaters.append(NoOp())  # FrozenLayer: params never step
            elif layer.updater is not None:
                self._updaters.append(BaseUpdater.from_dict(layer.updater))
            else:
                self._updaters.append(global_updater)
        self._opt_state = [u.init(p) for u, p in zip(self._updaters, self.params_tree)]
        self._initialized = True
        self._train_step_fn = None
        self._output_jit = None
        self._rnn_step_jit = None
        self._pretrain_step_jit = None
        return self

    # ----------------------------------------------------------- flat views
    def params(self) -> jnp.ndarray:
        """Single flat parameter vector (ref Model.params flat-view contract)."""
        return flatten_params(self.params_tree)

    def set_params(self, flat: jnp.ndarray):
        self.params_tree = unflatten_params(self.params_tree, jnp.asarray(flat))

    def num_params(self) -> int:
        return num_params(self.params_tree)

    def get_updater_state_view(self) -> jnp.ndarray:
        return flatten_params(self._opt_state)

    def set_updater_state_view(self, flat: jnp.ndarray):
        self._opt_state = unflatten_params(self._opt_state, jnp.asarray(flat))

    # ------------------------------------------------------------- forward
    def _forward(self, params_tree, state_tree, x, *, train: bool, rng=None,
                 fmask=None, lmask=None, rnn_init_states=None, collect=False):
        """Forward through all layers. Returns (final_activation, per-layer activations,
        new_states, final_rnn_states, mask_at_output)."""
        from deeplearning4j_tpu.nn.conf.layers.feedforward import EmbeddingLayer
        from deeplearning4j_tpu.util.dtypes import cast_floats
        cd = self.compute_dtype
        mixed = cd != self.dtype
        if mixed:
            params_tree = _cast_params(self.layers, range(len(self.layers)),
                                       params_tree, cd)
            if rnn_init_states is not None:
                rnn_init_states = cast_floats(rnn_init_states, cd)
        orig_batch = x.shape[0]
        acts = [x]
        mask = fmask
        new_states = []
        final_rnn = []
        cur = x
        for i, layer in enumerate(self.layers):
            if mixed and not isinstance(layer, EmbeddingLayer):
                with _layer_scope(layer, i):
                    cur = cur.astype(cd)
            if i in self.conf.preprocessors:
                pp = self.conf.preprocessors[i]
                if isinstance(pp, FeedForwardToRnnPreProcessor):
                    cur = pp.preprocess(cur, minibatch=orig_batch)
                else:
                    cur = pp.preprocess(cur)
                mask = pp.feed_forward_mask(mask, orig_batch)
            if train and layer.dropout > 0 and rng is not None:
                rng, sub = jax.random.split(rng)
                cur = apply_dropout(cur, layer.dropout, sub)
            lrng = None
            if rng is not None:
                rng, lrng = jax.random.split(rng)
            if isinstance(layer, LSTM) and rnn_init_states is not None:
                init = rnn_init_states[len(final_rnn)]
                with _layer_scope(layer, i):
                    out, (h, c) = layer._scan(
                        params_tree[i], cur, mask,
                        h0=None if init is None else init[0],
                        c0=None if init is None else init[1])
                final_rnn.append((h, c))
                cur, ns, mask = out, state_tree[i], mask
            else:
                if isinstance(layer, LSTM):
                    final_rnn.append(None)
                with _layer_scope(layer, i):
                    cur, ns, mask = layer.forward(
                        params_tree[i], state_tree[i], cur, train=train,
                        rng=lrng, mask=mask)
            new_states.append(ns)
            if collect:
                acts.append(cur)
        if mixed:
            cur = cur.astype(self.dtype)
            new_states = cast_floats(new_states, self.dtype)
        return cur, acts, new_states, final_rnn, mask

    def output(self, x, train: bool = False) -> jnp.ndarray:
        """Inference forward pass (ref MultiLayerNetwork.output). Jitted: the whole
        stack is one cached XLA computation per input shape (jax.jit's aval cache is
        the shape-bucketing), so steady-state serving has no per-layer dispatch —
        the TPU answer to the reference's op-stream-per-layer inference path."""
        self._check_init()
        x = jnp.asarray(x, self.dtype)
        if train:
            out, _, _, _, _ = self._forward(self.params_tree, self.state_tree, x,
                                            train=True)
            return out
        if getattr(self, "_output_jit", None) is None:
            def f(params, states, x):
                out, _, _, _, _ = self._forward(params, states, x, train=False)
                return out
            self._output_jit = jax.jit(f)
        return self._output_jit(self.params_tree, self.state_tree, x)

    def feed_forward(self, x, train: bool = False) -> List[jnp.ndarray]:
        """All layer activations, input first (ref feedForward :849-961)."""
        self._check_init()
        x = jnp.asarray(x, self.dtype)
        _, acts, _, _, _ = self._forward(self.params_tree, self.state_tree, x,
                                         train=train, collect=True)
        return acts

    # ------------------------------------------------------------- loss
    def _loss_fn(self, params_tree, state_tree, x, y, fmask, lmask, rng, train=True,
                 rnn_init_states=None, per_example=False):
        out_layer = self.layers[-1]
        if not out_layer.is_output_layer():
            raise ValueError("Last layer must be an output/loss layer for scoring")
        from deeplearning4j_tpu.nn.conf.layers.feedforward import EmbeddingLayer
        from deeplearning4j_tpu.util.dtypes import cast_floats, cast_params
        cd = self.compute_dtype
        mixed = cd != self.dtype
        params_full = params_tree  # storage-dtype originals (score + regularization)
        # with recomputation by layer the cast goes inside the recomputed
        # block: no second copy of every weight lives through the step
        cast_inside = mixed and bool(self.conf.global_conf.remat)
        if mixed:
            if not cast_inside:
                params_tree = _cast_params(self.layers, range(len(self.layers)),
                                           params_tree, cd)
            if rnn_init_states is not None:
                rnn_init_states = cast_floats(rnn_init_states, cd)
        # forward to input of the output layer
        orig_batch = x.shape[0]
        mask = fmask
        cur = x
        new_states = []
        final_rnn = []
        for i, layer in enumerate(self.layers[:-1]):
            if mixed and not isinstance(layer, EmbeddingLayer):
                with _layer_scope(layer, i):
                    cur = cur.astype(cd)
            if i in self.conf.preprocessors:
                pp = self.conf.preprocessors[i]
                if isinstance(pp, FeedForwardToRnnPreProcessor):
                    cur = pp.preprocess(cur, minibatch=orig_batch)
                else:
                    cur = pp.preprocess(cur)
                mask = pp.feed_forward_mask(mask, orig_batch)
            if train and layer.dropout > 0 and rng is not None:
                rng, sub = jax.random.split(rng)
                cur = apply_dropout(cur, layer.dropout, sub)
            lrng = None
            if rng is not None:
                rng, lrng = jax.random.split(rng)
            from deeplearning4j_tpu.nn.conf.layers.recurrent import (
                GravesBidirectionalLSTM as _BiLSTM)
            if isinstance(layer, LSTM) and not isinstance(layer, _BiLSTM) \
                    and rnn_init_states is not None:
                init = rnn_init_states[len(final_rnn)]
                with _layer_scope(layer, i):
                    cur, (h, c) = layer._scan(
                        cast_params(params_tree[i], cd) if cast_inside
                        else params_tree[i], cur, mask,
                        h0=None if init is None else init[0],
                        c0=None if init is None else init[1])
                final_rnn.append((h, c))
                new_states.append(state_tree[i])
            else:
                if isinstance(layer, LSTM):
                    # bidirectional layers have no streamable state: carry a
                    # None slot so tBPTT indexing stays aligned (its raw
                    # param dict is per-direction-suffixed — _scan on it
                    # used to KeyError on every fit_batch)
                    final_rnn.append(None)

                def fwd(p, s, c, r, m, _layer=layer):
                    if cast_inside:
                        p = cast_params(p, cd)
                    return _layer.forward(p, s, c, train=train, rng=r, mask=m)

                if self.conf.global_conf.remat:
                    # gradient checkpointing: drop this layer's activations and
                    # recompute them in the backward pass (HBM for FLOPs)
                    fwd = jax.checkpoint(fwd)
                with _layer_scope(layer, i):
                    cur, ns, mask = fwd(params_tree[i], state_tree[i], cur,
                                        lrng, mask)
                new_states.append(ns)
        li = len(self.layers) - 1
        if li in self.conf.preprocessors:
            pp = self.conf.preprocessors[li]
            if isinstance(pp, FeedForwardToRnnPreProcessor):
                cur = pp.preprocess(cur, minibatch=orig_batch)
            else:
                cur = pp.preprocess(cur)
            mask = pp.feed_forward_mask(mask, orig_batch)
        if train and out_layer.dropout > 0 and rng is not None:
            rng, sub = jax.random.split(rng)
            cur = apply_dropout(cur, out_layer.dropout, sub)
        score_mask = lmask if lmask is not None else (
            mask if getattr(out_layer, "loss_fn", None) is not None and cur.ndim == 3
            else None)
        if mixed:
            # output-layer matmul + loss in storage dtype for numerical stability
            cur = cur.astype(self.dtype)
            new_states = cast_floats(new_states, self.dtype)
        if per_example:
            fn = getattr(out_layer, "compute_score_per_example", None)
            if fn is None:
                raise NotImplementedError(
                    f"{type(out_layer).__name__} has no per-example scoring")
            loss = fn(params_full[-1], cur, y, score_mask)
        else:
            with jax.named_scope("dl4j.loss"):
                loss = out_layer.compute_score(params_full[-1], cur, y,
                                               score_mask)
        new_states.append(state_tree[-1])
        if per_example:
            # bare per-example data losses; callers add reg/aux themselves
            # (ref scoreExamples addRegularization semantics) — returning
            # before the reg/aux sums keeps the eager path free of dead work
            return loss, (new_states, final_rnn)
        with jax.named_scope("dl4j.regularization"):
            reg = sum((layer.regularization_score(p)
                       for layer, p in zip(self.layers, params_full)),
                      jnp.asarray(0.0))
        # auxiliary-loss seam: layers that contribute a data-dependent loss
        # term (MixtureOfExperts load balancing) publish it in their new state
        # under "__aux_loss__"
        aux = sum((jnp.sum(ns["__aux_loss__"]) for ns in new_states
                   if isinstance(ns, dict) and "__aux_loss__" in ns),
                  jnp.asarray(0.0))
        return loss + reg + aux, (new_states, final_rnn)

    # ------------------------------------------------------------- training
    def _build_train_step(self):
        updaters = self._updaters
        layers = self.layers
        hc = self.health_config  # snapshot: config changes retrace via configure_health
        health_on = hc is not None and hc.enabled
        protect = health_on and hc.protects

        # the function's name is the program's in a profile (`XLA Modules`)
        def dl4j_mln_train_step(params_tree, opt_state, state_tree, step, rng,
                                x, y, fmask, lmask, rnn_init_states,
                                health_nf_in):
            (loss, (new_states, final_rnn)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(params_tree, state_tree, x, y, fmask,
                                             lmask, rng, True, rnn_init_states)
            if not health_on:
                new_params, new_opt = _apply_updates(layers, updaters, grads,
                                                     opt_state, params_tree, step)
                return new_params, new_opt, new_states, loss, final_rnn, None
            # health side-output (ISSUE 5): same update math as _apply_updates,
            # split so the pre-subtraction updates feed the summary — pure
            # observation under policy="record" (bit-parity tested)
            upds, new_opt = _compute_updates(layers, updaters, grads, opt_state,
                                             params_tree, step)
            new_params = _subtract_updates(params_tree, upds)
            stats, bad = _health.summarize(params_tree, grads, upds, loss)
            if protect:
                # skip/raise policy: a nonfinite step leaves every training
                # buffer untouched — one select per buffer, no host sync
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(bad, b, a), new, old)
                new_params = keep(new_params, params_tree)
                new_opt = keep(new_opt, opt_state)
                new_states = keep(new_states, state_tree)
            stash = _health.step_stash(stats, bad, step, health_nf_in)
            return new_params, new_opt, new_states, loss, final_rnn, stash

        # donate params/opt-state/bn-state buffers: in-place update on device
        self._train_step_fn = jax.jit(dl4j_mln_train_step,
                                      donate_argnums=(0, 1, 2),
                                      static_argnames=())
        return self._train_step_fn

    def fit_batch(self, x, y, fmask=None, lmask=None, rnn_init_states=None):
        """One optimization step on one minibatch — the 3.1 call-stack equivalent."""
        self._check_init()
        with _telemetry.span("dl4j.fit_batch", step=self._step):
            return self._fit_batch(x, y, fmask, lmask, rnn_init_states)

    def _fit_batch(self, x, y, fmask, lmask, rnn_init_states):
        step = self._step
        with _telemetry.span("dl4j.fit_batch.prepare", step=step):
            x = jnp.asarray(x, self.dtype)
            y = jnp.asarray(y, self.dtype)
            if self._train_step_fn is None:
                self._build_train_step()
            self._rng, sub = jax.random.split(self._rng)
            n_rnn = sum(1 for l in self.layers if isinstance(l, LSTM))
            if rnn_init_states is None:
                rnn_init_states = [None] * n_rnn
            if self._accumulator is None:
                step_args = _train_step_args(
                    self, sub, x, y, fmask, lmask, rnn_init_states)
        if self._accumulator is not None:
            return self._fit_batch_accumulated(x, y, fmask, lmask, rnn_init_states)
        with _telemetry.span("dl4j.fit_batch.dispatch", step=step):
            new_params, new_opt, new_states, loss, final_rnn, health_stash = \
                self._train_step_fn(*step_args)
        self.params_tree = new_params
        self._opt_state = new_opt
        self.state_tree = new_states
        self._step += 1
        self._score = loss  # device scalar; host sync deferred to score()
        if health_stash is not None:
            self._stash_health(health_stash, steps=1)  # raises under policy="raise"
        with _telemetry.span("dl4j.fit_batch.listeners", step=step):
            for lst in self._listeners:
                lst.iteration_done(self, self._step)
        return final_rnn


    def _fit_batch_accumulated(self, x, y, fmask, lmask, rnn_init_states=None):
        """Gradient-sharing path (ref StochasticGradientDescent.java:66-74): compute grads,
        push to accumulator, apply the aggregated update."""
        self._rng, sub = jax.random.split(self._rng)
        (loss, (new_states, final_rnn)), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(self.params_tree, self.state_tree,
                                         x, y, fmask, lmask, sub, True, rnn_init_states)
        self.state_tree = new_states
        flat_grads = flatten_params(grads)
        self._accumulator.store_update(flat_grads)
        agg = self._accumulator.get_update()
        grads = unflatten_params(grads, agg)
        self.params_tree, self._opt_state = _apply_updates(
            self.layers, self._updaters, grads, self._opt_state, self.params_tree,
            self._step)
        self._step += 1
        self._score = loss
        for lst in self._listeners:
            lst.iteration_done(self, self._step)
        return final_rnn

    def fit_on_device(self, x, y, steps: Optional[int] = None, fmask=None, lmask=None,
                      sync: bool = True, vary_batch: bool = False):
        """Run many training steps as ONE jitted lax.scan on device — no per-step host
        dispatch. TPU-idiomatic epoch runner: if x/y carry a leading step axis
        (steps, batch, ...) each scan step consumes its own minibatch; otherwise the
        same batch is reused `steps` times (benchmark mode). Returns the per-step loss
        array (one host transfer at the end).

        `sync=False` defers EVERY device->host readback: losses return as a device
        array (np.asarray it on demand) and the divergence check resolves lazily on
        the next `_diverged_at` access. Host readback of a computed result is pure
        overhead for a training loop — timed callers want the device time, not the
        wait for a copy.

        `vary_batch=True` (benchmark mode only) rotates the resident batch by the
        step index each iteration (jnp.roll along the batch axis — compute-identical
        permutations, zero extra HBM). Without it, any step computation that does
        not depend on the carry is LOOP-INVARIANT and XLA hoists it out of the scan
        — with frozen layers (transfer learning) that silently caches the whole
        frozen forward pass across "steps" and a throughput reading becomes a
        features-cached number (discovered when the VGG16-transfer slope implied
        269 TFLOPS on a 197 TFLOPS chip). Rolling by the traced step index makes
        every step's input distinct, like a real data pipeline."""
        self._check_init()
        per_step_data = steps is None
        steps, step = int(np.shape(x)[0] if per_step_data else steps), self._step
        with _telemetry.span("dl4j.fit_on_device", step=step, steps=steps,
                             model="mln"):
            with _telemetry.span("dl4j.fit_on_device.prepare", step=step):
                x = jnp.asarray(x, self.dtype)
                y = jnp.asarray(y, self.dtype)
                has_fm = fmask is not None
                has_lm = lmask is not None

                # Cache keyed on the static loop mode only; ALL data (x/y/masks)
                # is passed as jit arguments so the traced computation never
                # captures a batch as a constant (a warm cache must not replay
                # the first call's data). jax.jit's own aval cache handles
                # shape/dtype/None changes. In per-step mode masks (when given)
                # carry a leading step axis and are scanned alongside x/y.
                if vary_batch and per_step_data:
                    raise ValueError("vary_batch applies to the same-batch "
                                     "benchmark mode only (steps=int)")
                run = self._get_device_loop(per_step_data, has_fm, has_lm,
                                            vary_batch)
                self._rng, sub = jax.random.split(self._rng)
                args = _device_loop_args(self, sub, x, y, fmask, lmask)
            with _telemetry.span("dl4j.fit_on_device.dispatch", step=step):
                (self.params_tree, self._opt_state, self.state_tree, _, _,
                 div), losses, health_out = run(*args, n=steps)
            self._step += steps
            # sticky device-side stash: a clean later call must not clobber an
            # unobserved divergence from an earlier deferred call
            self._stash_pending_div(div)
            if health_out is not None:
                # ONE device-side aggregate per fit_on_device call; materializes
                # lazily via health_report() (raises now under policy="raise")
                self._stash_health(health_out, steps=steps)
            if not sync:
                self._score = losses[-1]  # device scalar; host sync deferred
                return losses             # divergence resolves on _diverged_at
            with _telemetry.span("dl4j.fit_on_device.readback", step=step):
                losses, div = jax.device_get(
                    (losses, self._pending_div))  # ONE readback
            self._score = float(losses[-1])
            self._resolve_divergence(int(div))
            return losses

    def _get_device_loop(self, per_step_data: bool, has_fm: bool, has_lm: bool,
                         vary_batch: bool = False):
        """Build (or fetch from cache) the jitted scan training loop used by
        fit_on_device / train_step_flops."""
        cache_key = ("mln", per_step_data, has_fm, has_lm, vary_batch,
                     self._health_key())
        if not hasattr(self, "_device_loop_cache"):
            self._device_loop_cache = {}
        run = self._device_loop_cache.get(cache_key)
        if run is None:
            updaters = self._updaters
            layers = self.layers
            hc = self.health_config
            health_on = hc is not None and hc.enabled
            protect = health_on and hc.protects

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                               static_argnames=("n",))
            def dl4j_mln_device_loop(params, opt, states, step, rng, x, y,
                                     fmask, lmask, health_nf_in, n):
                def body(carry, xs):
                    params_c, opt_c, states_c, step_c, rng_c, div_c, acc = carry
                    if per_step_data:
                        bx, by = xs[0], xs[1]
                        bfm = xs[2] if has_fm else None
                        blm = xs[2 + has_fm] if has_lm else None
                    elif vary_batch:
                        # rotate by the traced step index: defeats
                        # loop-invariant hoisting (see fit_on_device doc)
                        roll = lambda a: None if a is None else \
                            jnp.roll(a, step_c, axis=0)
                        bx, by, bfm, blm = roll(x), roll(y), roll(fmask), \
                            roll(lmask)
                    else:
                        bx, by, bfm, blm = x, y, fmask, lmask
                    rng_c, sub = jax.random.split(rng_c)

                    def loss_fn(p):
                        loss, (ns, _) = self._loss_fn(p, states_c, bx, by, bfm,
                                                      blm, sub, True, None)
                        return loss, ns

                    (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                        params_c)
                    if health_on:
                        # health side-output accumulated in the carry (ISSUE 5):
                        # same update math, split to expose the updates
                        upds, newo = _compute_updates(layers, updaters, grads,
                                                      opt_c, params_c, step_c)
                        newp = _subtract_updates(params_c, upds)
                        stats, badg = _health.summarize(params_c, grads, upds,
                                                        loss)
                        acc = _health.accumulate(acc, stats, badg, step_c)
                    else:
                        newp, newo = _apply_updates(layers, updaters, grads,
                                                    opt_c, params_c, step_c)
                    if protect:
                        # skip/raise policy: drop ONLY the nonfinite step and
                        # keep training (replaces the sticky freeze below —
                        # div_c stays clean, health carries the counts)
                        bad = badg
                    else:
                        # divergence sentinel (SURVEY §5 failure detection):
                        # once a non-finite loss appears, freeze
                        # params/opt/state for the rest of the scan and record
                        # the first bad step — a cheap select per buffer, no
                        # host sync inside the loop
                        bad = jnp.logical_or(~jnp.isfinite(loss), div_c >= 0)
                    keep = lambda new, old: jax.tree_util.tree_map(
                        lambda a, b: jnp.where(bad, b, a), new, old)
                    with jax.named_scope("dl4j.updater"):    # XLA fuses these selects
                        newp = keep(newp, params_c)         # into the update itself
                        newo = keep(newo, opt_c)
                    ns = keep(ns, states_c)
                    if not protect:
                        div_c = jnp.where(jnp.logical_and(div_c < 0,
                                                          ~jnp.isfinite(loss)),
                                          step_c, div_c)
                    return (newp, newo, ns, step_c + 1, rng_c, div_c, acc), loss

                if per_step_data:
                    xs = (x, y) + ((fmask,) if has_fm else ()) \
                        + ((lmask,) if has_lm else ())
                else:
                    xs = None
                div0 = jnp.asarray(-1, jnp.int32)
                acc0 = _health.init_accum(len(layers)) if health_on else None
                carry, losses = jax.lax.scan(
                    body, (params, opt, states, step, rng, div0, acc0), xs,
                    length=n)
                newp, newo, ns, stepf, rngf, divf, accf = carry
                health_out = _health.finalize(accf, n, health_nf_in) \
                    if health_on else None
                return (newp, newo, ns, stepf, rngf, divf), losses, health_out
            run = self._device_loop_cache[cache_key] = dl4j_mln_device_loop
        return run

    def lower_train_step(self, x, y, steps: int = 1, vary_batch: bool = False):
        """AOT-lower the `fit_on_device` loop of `steps` training steps
        (forward + backward + updater; one by default) at this batch's
        shapes: nothing executes and no buffer is donated. `x`/`y` may be
        `jax.ShapeDtypeStruct`s — `fit_on_device(x, y, steps=steps,
        vary_batch=vary_batch)` of arrays of those shapes runs exactly this
        program. `.as_text()` shows what the step lowers to — an engaged
        helper kernel appears as a `tpu_custom_call` — and the compiled form
        carries XLA's cost analysis and, for every operation, the `dl4j.`
        scope it came from (telemetry.profiler.op_scopes)."""
        self._check_init()
        run = self._get_device_loop(False, False, False, vary_batch)
        return run.lower(*_device_loop_args(
            self, self._rng, _abstract(x, self.dtype),
            _abstract(y, self.dtype), None, None), n=int(steps))

    def lower_fit_batch(self, x, y):
        """AOT-lower the train step that `fit_batch` — and so
        `fit(iterator)` — dispatches, at this batch's shapes (arrays or
        `jax.ShapeDtypeStruct`s); see `lower_train_step`."""
        self._check_init()
        if self._train_step_fn is None:
            self._build_train_step()
        n_rnn = sum(1 for l in self.layers if isinstance(l, LSTM))
        return self._train_step_fn.lower(*_train_step_args(
            self, self._rng, _abstract(x, self.dtype),
            _abstract(y, self.dtype), None, None, [None] * n_rnn))

    def train_step_flops(self, x, y) -> Optional[float]:
        """XLA cost-analysis FLOPs of ONE fit_on_device training step
        (forward + backward + updater), or None when the backend exposes no cost
        model. Used by bench.py to report MFU and sanity-check throughput against
        hardware peak."""
        return self.train_step_costs(x, y)["flops"] or None

    def train_step_costs(self, x, y) -> dict:
        """{'flops', 'bytes_accessed'} of ONE fit_on_device training step per
        XLA's cost model — the roofline inputs (bench.py)."""
        from deeplearning4j_tpu.util.costs import costs_of
        return costs_of(self.lower_train_step(x, y))

    def activation_bytes(self, x) -> int:
        """Sum of per-layer training activation bytes for input x, via
        abstract eval (nothing allocates) — the unavoidable-traffic side of
        the roofline."""
        self._check_init()
        shapes = jax.eval_shape(
            lambda p, s, xx: self._forward(p, s, xx, train=True,
                                           collect=True)[1],
            self.params_tree, self.state_tree,
            jax.ShapeDtypeStruct(np.asarray(x).shape, self.compute_dtype))
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(shapes))

    def fit(self, data, labels=None, epochs: int = 1):
        """fit(x, y) | fit(DataSet) | fit(DataSetIterator[, epochs])
        (ref MultiLayerNetwork.fit :1149)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        self._check_init()
        if labels is not None:
            for _ in range(epochs):
                self._fit_one(DataSet(data, labels))
            return self
        if isinstance(data, DataSet):
            for _ in range(epochs):
                self._fit_one(data)
            return self
        # iterator path with async prefetch (ref AsyncDataSetIterator wrap :1153-1156)
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, waited_batches)
        for ep in range(epochs):
            for lst in self._listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self)
            it = data
            if hasattr(it, "reset"):
                it.reset()
            if getattr(it, "async_supported", True):
                it = AsyncDataSetIterator(it)
                it.first_step = self._step
            it = waited_batches(it, self)
            if self.conf.backprop_type == BackpropType.TruncatedBPTT:
                # segment loop needs host-side carry; per-batch path
                t0 = time.time()
                for ds in it:
                    self._last_etl_ms = (time.time() - t0) * 1e3
                    self._fit_one(ds)
                    t0 = time.time()
            else:
                self._fit_epoch_scanned(it)
            for lst in self._listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self)
        return self

    def _fit_epoch_scanned(self, it):
        """Stack consecutive same-shape minibatches and run them as ONE on-device
        lax.scan (fit_on_device per-step mode) — the epoch runner that keeps
        fit(iterator) off the one-host-roundtrip-per-minibatch slow path. Listener
        callbacks fire after each device run with the recorded per-step scores."""
        t0 = time.time()
        group: List[Any] = []
        # Cap the stacked super-step so a long epoch never materializes unbounded
        # host/HBM memory: at most ~256 MB of stacked features, at most 512 steps.
        max_group = None

        def flush():
            nonlocal t0
            if not group:
                return
            self._last_etl_ms = (time.time() - t0) * 1e3
            if len(group) == 1:
                ds0 = group[0]
                self.fit_batch(ds0.features, ds0.labels, ds0.features_mask,
                               ds0.labels_mask)
            else:
                xs = np.stack([np.asarray(d.features) for d in group])
                ys = np.stack([np.asarray(d.labels) for d in group])
                fms = np.stack([np.asarray(d.features_mask) for d in group]) \
                    if group[0].features_mask is not None else None
                lms = np.stack([np.asarray(d.labels_mask) for d in group]) \
                    if group[0].labels_mask is not None else None
                losses = self.fit_on_device(xs, ys, fmask=fms, lmask=lms)
                base = self._step - len(losses)
                for i, loss in enumerate(losses):
                    self._score = float(loss)
                    for lst in self._listeners:
                        lst.iteration_done(self, base + i + 1)
            group.clear()
            t0 = time.time()

        def signature(ds):
            return (np.shape(ds.features), np.shape(ds.labels),
                    None if ds.features_mask is None else np.shape(ds.features_mask),
                    None if ds.labels_mask is None else np.shape(ds.labels_mask))

        sig = None
        for ds in it:
            s = signature(ds)
            if sig is not None and s != sig:
                flush()
            sig = s
            if max_group is None:
                batch_bytes = np.asarray(ds.features).nbytes \
                    + np.asarray(ds.labels).nbytes
                max_group = int(max(1, min(512, (256 << 20) // max(1, batch_bytes))))
            group.append(ds)
            if len(group) >= max_group:
                flush()
        flush()

    def _fit_one(self, ds):
        if self.conf.backprop_type == BackpropType.TruncatedBPTT and ds.features.ndim == 3:
            self._fit_tbptt(ds)
        else:
            self.fit_batch(ds.features, ds.labels, ds.features_mask, ds.labels_mask)

    def _fit_tbptt(self, ds):
        """Truncated BPTT (ref doTruncatedBPTT :1484+): split the time axis into
        fwd-length segments, carry LSTM state across segments, backprop within each."""
        T = ds.features.shape[2]
        L = self.conf.tbptt_fwd_length
        n_rnn = sum(1 for l in self.layers if isinstance(l, LSTM))
        carry = [None] * n_rnn
        with _telemetry.span("dl4j.fit_tbptt", step=self._step):
            for start in range(0, T, L):
                end = min(start + L, T)
                x = ds.features[:, :, start:end]
                y = ds.labels[:, :, start:end] if ds.labels.ndim == 3 \
                    else ds.labels
                fm = None if ds.features_mask is None \
                    else ds.features_mask[:, start:end]
                lm = None if ds.labels_mask is None \
                    else ds.labels_mask[:, start:end]
                final = self.fit_batch(x, y, fm, lm, rnn_init_states=carry)
                if final is not None:
                    carry = [None if s is None else
                             (jax.lax.stop_gradient(s[0]),
                              jax.lax.stop_gradient(s[1]))
                             for s in final]

    # ------------------------------------------------------------- scoring
    def score(self, ds=None, training: bool = False) -> float:
        self._check_init()
        if ds is None:
            return float(self._score)
        x = jnp.asarray(ds.features, self.dtype)
        y = jnp.asarray(ds.labels, self.dtype)
        loss, _ = self._loss_fn(self.params_tree, self.state_tree, x, y,
                                ds.features_mask, ds.labels_mask, None, training, None)
        return float(loss)

    def score_examples(self, ds, add_regularization: bool = False):
        """(batch,) per-example scores (ref MultiLayerNetwork.scoreExamples /
        SparkDl4jMultiLayer.scoreExamples): each example's loss summed over
        its outputs (and unmasked timesteps for RNN heads);
        `add_regularization` adds the net's L1/L2 penalty to every entry,
        matching the reference's addRegularizationTerms flag. The scalar
        `score()` equals mean(score_examples) (divided by T for RNN heads)."""
        self._check_init()
        x = jnp.asarray(ds.features, self.dtype)
        y = jnp.asarray(ds.labels, self.dtype)
        per, _ = self._loss_fn(self.params_tree, self.state_tree, x, y,
                               ds.features_mask, ds.labels_mask, None, False,
                               None, per_example=True)
        if add_regularization:
            reg = sum((layer.regularization_score(p) for layer, p in
                       zip(self.layers, self.params_tree)), jnp.asarray(0.0))
            per = per + reg
        return per
    scoreExamples = score_examples

    def gradient_and_score(self, x, y, fmask=None, lmask=None):
        """(flat gradient, score) — used by gradient checks."""
        self._check_init()
        x = jnp.asarray(x, self.dtype)
        y = jnp.asarray(y, self.dtype)
        (loss, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self.params_tree, self.state_tree, x, y, fmask, lmask, None, True, None)
        return flatten_params(grads), float(loss)

    # ------------------------------------------------------- pretraining
    def _features_to(self, params_tree, state_tree, x, layer_idx: int):
        """Input activations for layer `layer_idx`: inference forward through the
        layers below, then that layer's own preprocessor (ref
        MultiLayerNetwork.pretrainLayer feeding activationFromPrevLayer). Applies the
        same compute_dtype mixed-precision policy as _forward/_loss_fn."""
        from deeplearning4j_tpu.nn.conf.layers.feedforward import EmbeddingLayer
        from deeplearning4j_tpu.util.dtypes import cast_floats
        cd = self.compute_dtype
        mixed = cd != self.dtype
        if mixed:
            params_tree = cast_floats(params_tree, cd)
        cur = x
        mask = None
        orig_batch = x.shape[0]
        for i, layer in enumerate(self.layers[:layer_idx]):
            if mixed and not isinstance(layer, EmbeddingLayer):
                cur = cur.astype(cd)
            if i in self.conf.preprocessors:
                pp = self.conf.preprocessors[i]
                cur = (pp.preprocess(cur, minibatch=orig_batch)
                       if isinstance(pp, FeedForwardToRnnPreProcessor)
                       else pp.preprocess(cur))
            cur, _, mask = layer.forward(params_tree[i], state_tree[i], cur,
                                         train=False, rng=None, mask=mask)
        if layer_idx in self.conf.preprocessors:
            pp = self.conf.preprocessors[layer_idx]
            cur = (pp.preprocess(cur, minibatch=orig_batch)
                   if isinstance(pp, FeedForwardToRnnPreProcessor)
                   else pp.preprocess(cur))
        return cur.astype(self.dtype) if mixed else cur

    def pretrain_layer(self, layer_idx: int, data, epochs: int = 1) -> float:
        """Unsupervised pretraining of one layer (ref MultiLayerNetwork.pretrainLayer
        :379-441). AutoEncoder/VariationalAutoencoder optimize their `pretrain_score`
        via autodiff; RBM supplies direct CD-k statistics via `pretrain_grads`. The
        whole step (lower-layer forward + objective + updater) is one jitted XLA
        computation. Returns the last pretrain score."""
        self._check_init()
        layer = self.layers[layer_idx]
        has_score = hasattr(layer, "pretrain_score")
        has_grads = hasattr(layer, "pretrain_grads")
        if not (has_score or has_grads):
            return float("nan")
        updater = self._updaters[layer_idx]
        _normalize = _normalize_gradients

        if getattr(self, "_pretrain_step_jit", None) is None:
            self._pretrain_step_jit = {}
        if layer_idx not in self._pretrain_step_jit:
            def step(layer_params, opt_i, below_params, below_states, x, step_no, rng):
                # below_* cover layers [0, layer_idx) only, so the donated layer
                # buffers (args 0/1) are never aliased by another argument
                feat = self._features_to(below_params, below_states, x, layer_idx)
                feat = jax.lax.stop_gradient(feat)
                if has_grads:  # RBM: CD-k statistics are the gradient estimate
                    grads, score = layer.pretrain_grads(layer_params, feat, rng)
                    reg_g = jax.grad(layer.regularization_score)(layer_params)
                    grads = jax.tree_util.tree_map(lambda g, r: g + r, grads, reg_g)
                else:
                    score, grads = jax.value_and_grad(
                        lambda p: layer.pretrain_score(p, feat, rng)
                        + layer.regularization_score(p))(layer_params)
                g = _normalize(layer, grads)
                upd, new_opt = updater.update(g, opt_i, layer_params, step_no)
                new_params = jax.tree_util.tree_map(lambda p, d: p - d,
                                                    layer_params, upd)
                return new_params, new_opt, score

            self._pretrain_step_jit[layer_idx] = jax.jit(step, donate_argnums=(0, 1))
        step_jit = self._pretrain_step_jit[layer_idx]
        score = jnp.nan  # device scalar; host sync deferred to the single return

        def one_batch(x):
            nonlocal score
            self._rng, sub = jax.random.split(self._rng)
            new_p, new_opt, score = step_jit(
                self.params_tree[layer_idx], self._opt_state[layer_idx],
                self.params_tree[:layer_idx], self.state_tree[:layer_idx],
                jnp.asarray(x, self.dtype), jnp.asarray(self._step, jnp.int32), sub)
            self.params_tree[layer_idx] = new_p
            self._opt_state[layer_idx] = new_opt
            self._step += 1

        for _ in range(epochs):
            if hasattr(data, "reset") and hasattr(data, "__iter__"):
                data.reset()
                for ds in data:
                    one_batch(ds.features)
            else:
                one_batch(data.features if hasattr(data, "features") else data)
        self._train_step_fn = None  # param buffers were donated; retrace safely
        self._output_jit = None
        return float(score)

    def pretrain(self, data, epochs: int = 1) -> None:
        """Layerwise greedy pretraining over every pretrainable layer, bottom-up
        (ref MultiLayerNetwork.pretrain(DataSetIterator) :358-377)."""
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "pretrain_score") or hasattr(layer, "pretrain_grads"):
                self.pretrain_layer(i, data, epochs=epochs)

    # ------------------------------------------------------------- rnn API
    def rnn_time_step(self, x) -> jnp.ndarray:
        """Streaming inference with persistent state (ref rnnTimeStep)."""
        self._check_init()
        x = jnp.asarray(x, self.dtype)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, :, None]
        n_rnn = sum(1 for l in self.layers if isinstance(l, LSTM))
        if self._rnn_state is None:
            self._rnn_state = [None] * n_rnn
        if getattr(self, "_rnn_step_jit", None) is None:
            def f(params, states, x, rnn_states):
                out, _, _, final_rnn, _ = self._forward(params, states, x,
                                                        train=False,
                                                        rnn_init_states=rnn_states)
                return out, final_rnn
            self._rnn_step_jit = jax.jit(f)
        out, final_rnn = self._rnn_step_jit(self.params_tree, self.state_tree, x,
                                            self._rnn_state)
        self._rnn_state = final_rnn
        return out[:, :, 0] if squeeze else out

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    # ------------------------------------------------------------- misc API
    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def set_listeners(self, *listeners):
        self._listeners = list(listeners)
    setListeners = set_listeners

    def get_listeners(self):
        return self._listeners

    def set_gradients_accumulator(self, acc):
        """Gradient-sharing hook (ref MultiLayerNetwork.java:647)."""
        self._accumulator = acc

    def clone(self) -> "MultiLayerNetwork":
        other = MultiLayerNetwork(MultiLayerConfiguration.from_json(self.conf.to_json()))
        other.init(params=self.params_tree)
        other.set_updater_state_view(self.get_updater_state_view())
        return other

    def _check_init(self):
        if not self._initialized:
            raise RuntimeError("Call init() before using the network")

    @property
    def last_etl_ms(self):
        return self._last_etl_ms
