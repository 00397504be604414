"""ComputationGraph: the DAG network.

Parity: ref nn/graph/ComputationGraph.java (3,234 LoC) — topological sort (:393),
init + param views (:418-470), fit (:852, :972-1055), feedForward in topo order
(:1403-1498), calcBackpropGradients (:1604), multi-input/multi-output. TPU-first
redesign: the topological-order interpreter with its per-vertex workspace choreography
disappears — the DAG is traced once into a single XLA computation (topo order fixed at
config time) and jax.grad provides the backward pass; the jitted train step donates
params/opt-state.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.enums import BackpropType
from deeplearning4j_tpu.nn.conf.graph_configuration import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf, apply_dropout
from deeplearning4j_tpu.nn.divergence import DivergenceSentinelMixin
from deeplearning4j_tpu.nn.multilayer import (
    _abstract, _apply_updates, _cast_params, _compute_updates,
    _device_loop_args, _layer_scope, _normalize_gradients,
    _subtract_updates, _train_step_args)
from deeplearning4j_tpu.nn.updater.updaters import BaseUpdater
from deeplearning4j_tpu import telemetry as _telemetry
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.util.flat_params import flatten_params, num_params, unflatten_params


def _as_list(x) -> List:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _integer_ports(conf: ComputationGraphConfiguration):
    """(for each graph input, for each output) whether the batch array stays
    integer: an input all of whose consumers are layers that declare
    `integer_input` (a token table), an output layer that declares
    `integer_labels`. Every other array is cast to the storage type, whatever
    it comes as (uint8 images, integer one-hot labels)."""
    def consumers(name):
        return [n.conf for n in conf.nodes.values() if name in n.inputs]
    ins = tuple(bool(consumers(name)) and all(
        getattr(c, "integer_input", False) for c in consumers(name))
        for name in conf.inputs)
    outs = tuple(bool(getattr(conf.nodes[name].conf, "integer_labels", False))
                 for name in conf.outputs)
    return ins, outs


class ComputationGraph(DivergenceSentinelMixin, _health.HealthMonitorMixin):
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        # layer nodes in topo order define the flat-param-view ordering
        self.layer_names: List[str] = [n for n in conf.topo_order
                                       if conf.nodes[n].kind == "layer"]
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
        self._accumulator = None
        self._last_etl_ms = 0.0
        self._int_inputs, self._int_labels = _integer_ports(conf)
        self.dtype = jnp.dtype(conf.global_conf.dtype)
        gc = conf.global_conf
        self.compute_dtype = (jnp.dtype(gc.compute_dtype)
                              if getattr(gc, "compute_dtype", None) else self.dtype)

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[Sequence[Dict[str, jnp.ndarray]]] = None):
        gc = self.conf.global_conf
        key = jax.random.PRNGKey(gc.seed)
        self._rng = jax.random.PRNGKey(gc.seed + 1)
        in_types = self.conf.node_input_types()
        self.params_tree, self.state_tree = [], []
        for idx, name in enumerate(self.layer_names):
            layer = self.conf.nodes[name].conf
            key, sub = jax.random.split(key)
            it = in_types[name][0]
            if params is not None:
                p = {k: jnp.array(v, copy=True) for k, v in params[idx].items()}
            elif self.conf.nodes[name].tied_to is not None:
                p = {}           # the owner's copy is the only one
            else:
                p = layer.init_params(sub, it, self.dtype) if layer.has_params() else {}
            self.params_tree.append(p)
            self.state_tree.append(layer.init_state(it, self.dtype))

        global_updater = self.conf.get_updater()
        self._updaters = []
        for name in self.layer_names:
            layer = self.conf.nodes[name].conf
            if layer.frozen:
                from deeplearning4j_tpu.nn.updater.updaters import NoOp
                self._updaters.append(NoOp())  # FrozenLayer: params never step
            elif layer.updater is not None:
                self._updaters.append(BaseUpdater.from_dict(layer.updater))
            else:
                self._updaters.append(global_updater)
        self._opt_state = [u.init(p) for u, p in zip(self._updaters, self.params_tree)]
        self._gauge_layers = [i for i, l in enumerate(self.layers)
                              if hasattr(l, "state_gauges")]
        self._initialized = True
        self._train_step_fn = None
        self._output_jit = None
        return self

    @property
    def layers(self) -> List[BaseLayerConf]:
        return [self.conf.nodes[n].conf for n in self.layer_names]

    # ----------------------------------------------------------- flat views
    def params(self) -> jnp.ndarray:
        return flatten_params(self.params_tree)

    def set_params(self, flat):
        self.params_tree = unflatten_params(self.params_tree, jnp.asarray(flat))

    def num_params(self) -> int:
        return num_params(self.params_tree)

    def get_updater_state_view(self):
        return flatten_params(self._opt_state)

    def set_updater_state_view(self, flat):
        self._opt_state = unflatten_params(self._opt_state, jnp.asarray(flat))

    # ------------------------------------------------------------- forward
    def _forward_all(self, params_tree, state_tree, inputs: List[jnp.ndarray], *,
                     train: bool, rng=None, fmasks: Optional[List] = None,
                     stop_at_scores: bool = False, labels=None, lmasks=None,
                     rnn_init_states: Optional[List] = None):
        """Trace the whole DAG in topo order. If stop_at_scores, output-layer nodes
        contribute their loss instead of activations. Returns
        (activations dict, new_states list, total_loss or None); with
        `rnn_init_states` (tBPTT: per-LSTM (h0, c0) in layer-name order, None
        entries allowed) a 4th element — the final RNN states — is appended."""
        from deeplearning4j_tpu.nn.conf.layers.feedforward import EmbeddingLayer
        from deeplearning4j_tpu.util.dtypes import cast_floats, cast_params
        cd = self.compute_dtype
        mixed = cd != self.dtype
        params_full = params_tree  # storage-dtype originals (score + regularization)
        # with recomputation by layer the cast goes inside the recomputed
        # block: no second copy of every weight lives through the step
        remat = bool(self.conf.global_conf.remat) and train
        cast_inside = mixed and remat

        def recomputed(block):
            """A block recomputed in the backward pass, its parameters cast
            to the compute type inside it."""
            return jax.checkpoint(lambda p, *a: block(
                cast_params(p, cd) if cast_inside else p, *a))
        if mixed and not cast_inside:
            params_tree = _cast_params(self.layers, self.layer_names,
                                       params_tree, cd)
        nodes = self.conf.nodes
        fmasks = fmasks or [None] * len(self.conf.inputs)
        values: Dict[str, jnp.ndarray] = dict(zip(self.conf.inputs, inputs))
        masks: Dict[str, Optional[jnp.ndarray]] = dict(zip(self.conf.inputs, fmasks))
        new_states = [None] * len(self.layer_names)
        layer_idx = {n: i for i, n in enumerate(self.layer_names)}
        # where a node's parameters live: its own slot, or its owner's
        param_idx = {n: layer_idx[nodes[n].tied_to or n] for n in self.layer_names}
        label_map = {}
        lmask_map = {}
        if labels is not None:
            label_map = dict(zip(self.conf.outputs, labels))
            lmask_map = dict(zip(self.conf.outputs, lmasks or [None] * len(labels)))
        total_loss = jnp.asarray(0.0, self.dtype) if stop_at_scores else None
        from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM as _LSTM
        final_rnn: List = []
        if rnn_init_states is not None:
            from deeplearning4j_tpu.util.dtypes import cast_floats as _cf
            if mixed:
                rnn_init_states = _cf(rnn_init_states, cd)

        for name in self.conf.topo_order:
            node = nodes[name]
            in_vals = [values[i] for i in node.inputs]
            in_masks = [masks.get(i) for i in node.inputs]
            if node.kind == "vertex":
                with _layer_scope(node.conf, name):
                    out, m = node.conf.forward(in_vals, in_masks)
                values[name], masks[name] = out, m
                continue
            layer = node.conf
            i, pi = layer_idx[name], param_idx[name]
            cur, mask = in_vals[0], in_masks[0]
            if mixed and not isinstance(layer, EmbeddingLayer) \
                    and not getattr(layer, "integer_input", False):
                with _layer_scope(layer, name):
                    cur = cur.astype(cd)
            if node.preprocessor is not None:
                cur = node.preprocessor.preprocess(cur)
                mask = node.preprocessor.feed_forward_mask(mask)
            if train and layer.dropout > 0 and rng is not None:
                rng, sub = jax.random.split(rng)
                cur = apply_dropout(cur, layer.dropout, sub)
            lrng = None
            if rng is not None:
                rng, lrng = jax.random.split(rng)
            if stop_at_scores and name in label_map and layer.is_output_layer():
                lm = lmask_map.get(name)
                if lm is None and mask is not None and cur.ndim == 3:
                    lm = mask
                # output-layer matmul + loss in storage dtype for stability;
                # a head that recomputes its score in parts (`LoopExitHead`)
                # is not recomputed whole besides
                own = getattr(layer, "recomputes_its_score", False)
                score = jax.checkpoint(layer.compute_score) \
                    if remat and not own else layer.compute_score
                with jax.named_scope("dl4j.loss"):
                    total_loss = total_loss \
                        + getattr(layer, "loss_weight", 1.0) * score(
                            params_full[pi], cur.astype(self.dtype),
                            label_map[name], lm)
                new_states[i] = state_tree[i]
                # still produce activation in case downstream nodes consume it
                with _layer_scope(layer, name):
                    out, ns, m = layer.forward(
                        params_tree[pi], state_tree[i], cur, train=train,
                        rng=lrng, mask=mask)
                values[name], masks[name] = out, m
            else:
                from deeplearning4j_tpu.nn.conf.layers.recurrent import (
                    GravesBidirectionalLSTM as _BiLSTM)
                if isinstance(layer, _LSTM) \
                        and not isinstance(layer, _BiLSTM) \
                        and rnn_init_states is not None:
                    # tBPTT segment: scan from the carried state, export final
                    init = rnn_init_states[len(final_rnn)]
                    with _layer_scope(layer, name):
                        out, (h, c) = layer._scan(
                            cast_params(params_tree[pi], cd) if cast_inside
                            else params_tree[pi], cur, mask,
                            h0=None if init is None else init[0],
                            c0=None if init is None else init[1])
                    final_rnn.append((h, c))
                    ns, m = state_tree[i], mask
                else:
                    if isinstance(layer, _LSTM):
                        final_rnn.append(None)

                    def fwd(p, s, c, r, m, _layer=layer):
                        if cast_inside:
                            p = cast_params(p, cd)
                        return _layer.forward(p, s, c, train=train, rng=r, mask=m)

                    if remat and getattr(layer, "recomputes_blocks", False):
                        # a layer of blocks (`LoopedStack`) takes the policy
                        # a block at a time (`recomputed`): the cast inside each
                        def fwd(p, s, c, r, m, _layer=layer):
                            return _layer.forward(p, s, c, train=train, rng=r,
                                                  mask=m, block=recomputed)
                    elif remat:
                        # the layer's activations are dropped and recomputed
                        # in the backward pass (MultiLayerNetwork._loss_fn)
                        fwd = jax.checkpoint(fwd)
                    with _layer_scope(layer, name):
                        out, ns, m = fwd(params_tree[pi], state_tree[i], cur,
                                         lrng, mask)
                new_states[i] = ns
                values[name], masks[name] = out, m
        if mixed:
            new_states = cast_floats(new_states, self.dtype)
        if rnn_init_states is not None:
            return values, new_states, total_loss, final_rnn
        return values, new_states, total_loss

    def output(self, *inputs, train: bool = False) -> Union[jnp.ndarray, List[jnp.ndarray]]:
        """Inference forward; returns one array per configured output
        (single array if one output) (ref ComputationGraph.output). Jitted: the whole
        DAG is one cached XLA computation per input shape."""
        self._check_init()
        if len(inputs) == 1 and isinstance(inputs[0], (list, tuple)):
            inputs = tuple(inputs[0])  # output([a, b]) == output(a, b)
        ins = self._arrays(inputs, self._int_inputs)
        if train:
            values, _, _ = self._forward_all(self.params_tree, self.state_tree,
                                             list(ins), train=True)
            outs = [values[o].astype(self.dtype) for o in self.conf.outputs]
            return outs[0] if len(outs) == 1 else outs
        if getattr(self, "_output_jit", None) is None:
            def f(params, states, ins):
                values, _, _ = self._forward_all(params, states, list(ins),
                                                 train=False)
                return tuple(values[o].astype(self.dtype)
                             for o in self.conf.outputs)
            self._output_jit = jax.jit(f)
        outs = list(self._output_jit(self.params_tree, self.state_tree, ins))
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs, train: bool = False) -> Dict[str, jnp.ndarray]:
        """All node activations by name."""
        self._check_init()
        ins = list(self._arrays(inputs, self._int_inputs))
        values, _, _ = self._forward_all(self.params_tree, self.state_tree, ins,
                                         train=train)
        return values

    # ------------------------------------------------------------- loss
    def _loss_fn(self, params_tree, state_tree, x, y, fmask, lmask, rng, train=True,
                 rnn_init_states=None):
        inputs = _as_list(x)
        labels = _as_list(y)
        fmasks = _as_list(fmask) if fmask is not None else None
        lmasks = _as_list(lmask) if lmask is not None else None
        if rnn_init_states is not None:
            _, new_states, loss, final_rnn = self._forward_all(
                params_tree, state_tree, inputs, train=train, rng=rng,
                fmasks=fmasks, stop_at_scores=True, labels=labels,
                lmasks=lmasks, rnn_init_states=rnn_init_states)
        else:
            _, new_states, loss = self._forward_all(
                params_tree, state_tree, inputs, train=train, rng=rng,
                fmasks=fmasks, stop_at_scores=True, labels=labels,
                lmasks=lmasks)
            final_rnn = None
        with jax.named_scope("dl4j.regularization"):
            reg = sum((self.conf.nodes[n].conf.regularization_score(p)
                       for n, p in zip(self.layer_names, params_tree)),
                      jnp.asarray(0.0))
        # aux-loss seam (see MultiLayerNetwork._loss_fn): e.g. MoE load balancing
        aux = sum((jnp.sum(ns["__aux_loss__"]) for ns in new_states
                   if isinstance(ns, dict) and "__aux_loss__" in ns),
                  jnp.asarray(0.0))
        return loss + reg + aux, (new_states, final_rnn)

    # ------------------------------------------------------------- training
    def _build_train_step(self):
        updaters = self._updaters
        layer_confs = self.layers
        hc = self.health_config  # snapshot; configure_health retraces
        health_on = hc is not None and hc.enabled
        protect = health_on and hc.protects

        # the function's name is the program's in a profile (`XLA Modules`)
        def dl4j_cg_train_step(params_tree, opt_state, state_tree, step, rng,
                               x, y, fmask, lmask, rnn_init_states,
                               health_nf_in):
            (loss, (new_states, final_rnn)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(params_tree, state_tree, x, y, fmask,
                                             lmask, rng, True, rnn_init_states)
            if not health_on:
                new_params, new_opt = _apply_updates(layer_confs, updaters, grads,
                                                     opt_state, params_tree, step)
                return new_params, new_opt, new_states, loss, final_rnn, None
            # health side-output — see MultiLayerNetwork._build_train_step
            upds, new_opt = _compute_updates(layer_confs, updaters, grads,
                                             opt_state, params_tree, step)
            new_params = _subtract_updates(params_tree, upds)
            stats, bad = _health.summarize(params_tree, grads, upds, loss)
            if protect:
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(bad, b, a), new, old)
                new_params = keep(new_params, params_tree)
                new_opt = keep(new_opt, opt_state)
                new_states = keep(new_states, state_tree)
            stash = _health.step_stash(stats, bad, step, health_nf_in)
            return new_params, new_opt, new_states, loss, final_rnn, stash

        self._train_step_fn = jax.jit(dl4j_cg_train_step,
                                      donate_argnums=(0, 1, 2))
        return self._train_step_fn

    def fit_batch(self, x, y, fmask=None, lmask=None, rnn_init_states=None):
        self._check_init()
        with _telemetry.span("dl4j.fit_batch", step=self._step):
            return self._fit_batch(x, y, fmask, lmask, rnn_init_states)

    def _fit_batch(self, x, y, fmask, lmask, rnn_init_states):
        step = self._step
        with _telemetry.span("dl4j.fit_batch.prepare", step=step):
            x = self._arrays(_as_list(x), self._int_inputs)
            y = self._arrays(_as_list(y), self._int_labels)
            fmask = None if fmask is None else tuple(_as_list(fmask))
            lmask = None if lmask is None else tuple(_as_list(lmask))
            if self._train_step_fn is None:
                self._build_train_step()
            self._rng, sub = jax.random.split(self._rng)
            if self._accumulator is None:
                step_args = _train_step_args(self, sub, x, y, fmask, lmask,
                                             rnn_init_states)
        if self._accumulator is not None:
            return self._fit_batch_accumulated(x, y, fmask, lmask, sub)
        with _telemetry.span("dl4j.fit_batch.dispatch", step=step):
            new_params, new_opt, new_states, loss, final_rnn, health_stash = \
                self._train_step_fn(*step_args)
        self.params_tree = new_params
        self._opt_state = new_opt
        self.state_tree = new_states
        self._step += 1
        self._score = loss
        if health_stash is not None:
            self._stash_health(health_stash, steps=1)  # raises under policy="raise"
        with _telemetry.span("dl4j.fit_batch.listeners", step=step):
            for lst in self._listeners:
                lst.iteration_done(self, self._step)
        return final_rnn

    def _fit_batch_accumulated(self, x, y, fmask, lmask, sub):
        (loss, (new_states, _)), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(self.params_tree, self.state_tree,
                                         x, y, fmask, lmask, sub, True, None)
        self.state_tree = new_states
        self._accumulator.store_update(flatten_params(grads))
        grads = unflatten_params(grads, self._accumulator.get_update())
        self.params_tree, self._opt_state = _apply_updates(
            self.layers, self._updaters, grads, self._opt_state, self.params_tree,
            self._step)
        self._step += 1
        self._score = loss
        for lst in self._listeners:
            lst.iteration_done(self, self._step)

    def fit_on_device(self, x, y, steps: Optional[int] = None, fmask=None, lmask=None,
                      sync: bool = True, vary_batch: bool = False):
        """Jitted lax.scan training loop (see MultiLayerNetwork.fit_on_device,
        including `sync=False` deferred-readback and `vary_batch` anti-hoisting
        semantics). Benchmark mode only here: the same batch is reused `steps`
        times (rotated per step when vary_batch)."""
        self._check_init()
        if steps is None:
            raise ValueError("steps is required (single-batch device loop)")
        steps, step = int(steps), self._step
        with _telemetry.span("dl4j.fit_on_device", step=step, steps=steps,
                             model="cg"):
            with _telemetry.span("dl4j.fit_on_device.prepare", step=step):
                x = self._arrays(_as_list(x), self._int_inputs)
                y = self._arrays(_as_list(y), self._int_labels)
                run = self._get_device_loop(vary_batch)
                self._rng, sub = jax.random.split(self._rng)
                args = _device_loop_args(self, sub, x, y, fmask, lmask)
            with _telemetry.span("dl4j.fit_on_device.dispatch", step=step):
                (self.params_tree, self._opt_state, self.state_tree, _, _,
                 div), losses, health_out = run(*args, n=steps)
            self._step += steps
            # sticky device-side stash (see DivergenceSentinelMixin)
            self._stash_pending_div(div)
            if health_out is not None:
                self._stash_health(health_out, steps=steps)
            if not sync:
                self._score = losses[-1]  # device scalar; host sync deferred
                return losses             # divergence resolves on _diverged_at
            with _telemetry.span("dl4j.fit_on_device.readback", step=step):
                losses, div, gauge_states = jax.device_get(
                    (losses, self._pending_div,
                     [self.state_tree[i] for i in self._gauge_layers]))  # ONE readback
            self._publish_state_gauges(gauge_states)
            self._score = float(losses[-1])
            self._resolve_divergence(int(div))
            return losses

    def _publish_state_gauges(self, states) -> None:
        """Gauges of the layers that keep counters in their state (written on
        the device by every step, read with the call's losses): the last
        step's, by the layer's name."""
        for i, state in zip(self._gauge_layers, states):
            for gauge, value in self.layers[i].state_gauges(state).items():
                _telemetry.registry().gauge(
                    f"{gauge}.{_telemetry.sanitize_component(self.layer_names[i])}"
                ).set(value)

    def _get_device_loop(self, vary_batch: bool = False):
        """Build (or fetch from cache) the jitted scan loop used by fit_on_device /
        train_step_flops. Data (x/y/masks) is passed as jit arguments — never
        captured as traced constants — so a warm cache cannot replay the first
        call's batch. vary_batch: see MultiLayerNetwork.fit_on_device (defeats
        loop-invariant hoisting of frozen-vertex forwards)."""
        cache_key = ("cg", vary_batch, self._health_key())
        if not hasattr(self, "_device_loop_cache"):
            self._device_loop_cache = {}
        run = self._device_loop_cache.get(cache_key)
        if run is None:
            updaters = self._updaters
            layer_confs = self.layers
            hc = self.health_config
            health_on = hc is not None and hc.enabled
            protect = health_on and hc.protects

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                               static_argnames=("n",))
            def dl4j_cg_device_loop(params, opt, states, step, rng, x, y,
                                    fmask, lmask, health_nf_in, n):
                def body(carry, _):
                    params_c, opt_c, states_c, step_c, rng_c, div_c, acc = carry
                    rng_c, sub = jax.random.split(rng_c)
                    if vary_batch:
                        roll = lambda t: jax.tree_util.tree_map(
                            lambda a: jnp.roll(a, step_c, axis=0), t)
                        bx, by, bfm, blm = roll(x), roll(y), roll(fmask), \
                            roll(lmask)
                    else:
                        bx, by, bfm, blm = x, y, fmask, lmask

                    def loss_fn(p):
                        loss, (ns, _) = self._loss_fn(p, states_c, bx, by, bfm,
                                                      blm, sub, True, None)
                        return loss, ns

                    (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                        params_c)
                    if health_on:
                        # health side-output — see MultiLayerNetwork._get_device_loop
                        upds, newo = _compute_updates(layer_confs, updaters,
                                                      grads, opt_c, params_c,
                                                      step_c)
                        newp = _subtract_updates(params_c, upds)
                        stats, badg = _health.summarize(params_c, grads, upds,
                                                        loss)
                        acc = _health.accumulate(acc, stats, badg, step_c)
                    else:
                        newp, newo = _apply_updates(layer_confs, updaters, grads,
                                                    opt_c, params_c, step_c)
                    if protect:
                        # skip/raise policy: drop only the nonfinite step
                        bad = badg
                    else:
                        # divergence sentinel — see MultiLayerNetwork.fit_on_device
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

                div0 = jnp.asarray(-1, jnp.int32)
                acc0 = _health.init_accum(len(layer_confs)) if health_on else None
                carry, losses = jax.lax.scan(
                    body, (params, opt, states, step, rng, div0, acc0), None,
                    length=n)
                newp, newo, ns, stepf, rngf, divf, accf = carry
                health_out = _health.finalize(accf, n, health_nf_in) \
                    if health_on else None
                return (newp, newo, ns, stepf, rngf, divf), losses, health_out
            run = self._device_loop_cache[cache_key] = dl4j_cg_device_loop
        return run

    def lower_train_step(self, x, y, steps: int = 1, vary_batch: bool = False):
        """AOT-lower the `fit_on_device` loop of `steps` training steps (one
        by default) from arrays or `jax.ShapeDtypeStruct`s (see
        MultiLayerNetwork.lower_train_step)."""
        self._check_init()
        run = self._get_device_loop(vary_batch)
        return run.lower(*_device_loop_args(
            self, self._rng, *self._abstract_batch(x, y), None, None),
            n=int(steps))

    def lower_fit_batch(self, x, y):
        """AOT-lower the train step that `fit_batch` — and so
        `fit(iterator)` — dispatches (see MultiLayerNetwork.lower_fit_batch)."""
        self._check_init()
        if self._train_step_fn is None:
            self._build_train_step()
        return self._train_step_fn.lower(*_train_step_args(
            self, self._rng, *self._abstract_batch(x, y), None, None, None))

    def _arrays(self, values, integer) -> tuple:
        """Batch arrays as the net takes them: each in the storage type, but
        for the ports whose layer declared integer ids or labels."""
        return tuple(jnp.asarray(v, jnp.int32 if keep else self.dtype)
                     for v, keep in zip(values, integer))

    def _abstract_batch(self, x, y):
        """Shapes as `fit_on_device` would pass them."""
        return tuple(tuple(_abstract(v, jnp.int32 if keep else self.dtype)
                           for v, keep in zip(_as_list(a), integer))
                     for a, integer in ((x, self._int_inputs),
                                        (y, self._int_labels)))

    def train_step_flops(self, x, y) -> Optional[float]:
        """XLA cost-analysis FLOPs of ONE fit_on_device training step (see
        MultiLayerNetwork.train_step_flops)."""
        from deeplearning4j_tpu.util.costs import costs_of
        return costs_of(self.lower_train_step(x, y))["flops"] or None

    def fit(self, data, labels=None, epochs: int = 1):
        """fit(x(s), y(s)) | fit(DataSet/MultiDataSet) | fit(iterator[, epochs])
        (ref ComputationGraph.fit :852/:972)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
        self._check_init()
        if labels is not None:
            for _ in range(epochs):
                self.fit_batch(data, labels)
            return self
        if isinstance(data, (DataSet, MultiDataSet)):
            for _ in range(epochs):
                self._fit_one(data)
            return self
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, waited_batches)
        for _ in range(epochs):
            for lst in self._listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self)
            it = data
            if hasattr(it, "reset"):
                it.reset()
            if getattr(it, "async_supported", True):
                it = AsyncDataSetIterator(it)
                it.first_step = self._step
            t0 = time.time()
            for ds in waited_batches(it, self):
                self._last_etl_ms = (time.time() - t0) * 1e3
                self._fit_one(ds)
                t0 = time.time()
            for lst in self._listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self)
        return self

    def fit_tbptt(self, x, y, fmask=None, lmask=None):
        """Truncated BPTT for graph nets (ref ComputationGraph.doTruncatedBPTT):
        split the time axis into fwd-length segments, carry LSTM states across
        segments (stop-gradient), backprop within each."""
        from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM as _LSTM
        xs = _as_list(x)
        ys = _as_list(y)
        T = xs[0].shape[2]
        L = self.conf.tbptt_fwd_length
        n_rnn = sum(1 for l in self.layers if isinstance(l, _LSTM))
        carry = [None] * n_rnn

        def seg(a, s, e):
            return a[:, :, s:e] if a is not None and np.ndim(a) == 3 else a

        def seg_mask(m, s, e):
            return None if m is None else m[:, s:e]

        with _telemetry.span("dl4j.fit_tbptt", step=self._step):
            for start in range(0, T, L):
                end = min(start + L, T)
                sx = [seg(v, start, end) for v in xs]
                sy = [seg(v, start, end) for v in ys]
                fm = None if fmask is None else [seg_mask(m, start, end)
                                                 for m in _as_list(fmask)]
                lm = None if lmask is None else [seg_mask(m, start, end)
                                                 for m in _as_list(lmask)]
                final = self.fit_batch(sx, sy, fm, lm, rnn_init_states=carry)
                if final is not None:
                    carry = [None if s is None else
                             (jax.lax.stop_gradient(s[0]),
                              jax.lax.stop_gradient(s[1]))
                             for s in final]

    def _fit_one(self, ds):
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            feats, labs = ds.features, ds.labels
            fm, lm = ds.features_masks, ds.labels_masks
        else:
            feats, labs = ds.features, ds.labels
            fm, lm = ds.features_mask, ds.labels_mask
        if self.conf.backprop_type == BackpropType.TruncatedBPTT \
                and np.ndim(_as_list(feats)[0]) == 3:
            self.fit_tbptt(feats, labs, fm, lm)
        else:
            self.fit_batch(feats, labs, fm, lm)

    # ------------------------------------------------------------- rnn API
    def rnn_time_step(self, *inputs):
        """Streaming inference with persistent LSTM state
        (ref ComputationGraph.rnnTimeStep)."""
        from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM as _LSTM
        self._check_init()
        if len(inputs) == 1 and isinstance(inputs[0], (list, tuple)):
            inputs = tuple(inputs[0])
        ins = [jnp.asarray(v, self.dtype) for v in inputs]
        squeeze = ins[0].ndim == 2
        if squeeze:
            ins = [v[:, :, None] for v in ins]
        n_rnn = sum(1 for l in self.layers if isinstance(l, _LSTM))
        if getattr(self, "_rnn_state", None) is None:
            self._rnn_state = [None] * n_rnn
        if getattr(self, "_rnn_step_jit", None) is None:
            def f(params, states, ins, rnn_states):
                values, _, _, final = self._forward_all(
                    params, states, list(ins), train=False,
                    rnn_init_states=rnn_states)
                return tuple(values[o] for o in self.conf.outputs), final
            self._rnn_step_jit = jax.jit(f)
        outs, final = self._rnn_step_jit(self.params_tree, self.state_tree,
                                         tuple(ins), self._rnn_state)
        self._rnn_state = final
        outs = [o[:, :, 0] if squeeze and o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else list(outs)
    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self):
        self._rnn_state = None
    rnnClearPreviousState = rnn_clear_previous_state

    # ------------------------------------------------------------- scoring
    def score(self, ds=None, training: bool = False) -> float:
        self._check_init()
        if ds is None:
            return float(self._score)
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            x, y, fm, lm = ds.features, ds.labels, ds.features_masks, ds.labels_masks
        else:
            x, y, fm, lm = ds.features, ds.labels, ds.features_mask, ds.labels_mask
        x = tuple(jnp.asarray(v, self.dtype) for v in _as_list(x))
        y = tuple(jnp.asarray(v, self.dtype) for v in _as_list(y))
        loss, _ = self._loss_fn(self.params_tree, self.state_tree, x, y,
                                fm, lm, None, training, None)
        return float(loss)

    def gradient_and_score(self, x, y, fmask=None, lmask=None):
        self._check_init()
        x = tuple(jnp.asarray(v, self.dtype) for v in _as_list(x))
        y = tuple(jnp.asarray(v, self.dtype) for v in _as_list(y))
        (loss, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self.params_tree, self.state_tree, x, y, fmask, lmask, None, True, None)
        return flatten_params(grads), float(loss)

    # ------------------------------------------------------------- misc
    def score_examples(self, ds, add_regularization: bool = False):
        """(batch,) per-example scores for SINGLE-output graphs (ref
        SparkComputationGraph.scoreExamples): the output head's loss per
        example (summed over unmasked timesteps for RNN heads);
        `add_regularization` adds the net's L1/L2 penalty to every entry."""
        self._check_init()
        if len(self.conf.outputs) != 1:
            raise NotImplementedError(
                "score_examples supports single-output graphs")
        out_name = self.conf.outputs[0]
        node = self.conf.nodes[out_name]
        out_layer = node.conf
        fn = getattr(out_layer, "compute_score_per_example", None)
        if fn is None:
            raise NotImplementedError(
                f"{type(out_layer).__name__} has no per-example scoring")
        xs = [jnp.asarray(v, self.dtype) for v in _as_list(ds.features)]
        y = _as_list(ds.labels)[0]
        from deeplearning4j_tpu.parallel.sharded import _ds_masks
        fm, lm = _ds_masks(ds)
        fmasks = None if fm is None else list(_as_list(fm))
        lmask = None if lm is None else _as_list(lm)[0]
        values, _, _ = self._forward_all(self.params_tree, self.state_tree,
                                         xs, train=False, fmasks=fmasks)
        cur = values[node.inputs[0]].astype(self.dtype)
        if node.preprocessor is not None:
            cur = node.preprocessor.preprocess(cur)
        li = self.layer_names.index(out_name)
        per = fn(self.params_tree[li], cur, jnp.asarray(y, self.dtype), lmask)
        if add_regularization:
            reg = sum((layer.regularization_score(p) for layer, p in
                       zip(self.layers, self.params_tree)), jnp.asarray(0.0))
            per = per + reg
        return per
    scoreExamples = score_examples

    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(*_as_list(ds.features))
            out0 = out[0] if isinstance(out, list) else out
            labels = _as_list(ds.labels)[0]
            mask = ds.labels_mask if hasattr(ds, "labels_mask") else None
            ev.eval(labels, np.asarray(out0), mask=mask)
        return ev

    def set_listeners(self, *listeners):
        self._listeners = list(listeners)

    def set_gradients_accumulator(self, acc):
        self._accumulator = acc

    def clone(self) -> "ComputationGraph":
        other = ComputationGraph(
            ComputationGraphConfiguration.from_json(self.conf.to_json()))
        other.init(params=self.params_tree)
        other.set_updater_state_view(self.get_updater_state_view())
        return other

    def _check_init(self):
        if not self._initialized:
            raise RuntimeError("Call init() before using the network")

    @property
    def last_etl_ms(self):
        return self._last_etl_ms
