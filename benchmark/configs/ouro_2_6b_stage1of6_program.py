"""How the benchmark asks the program for Ouro-2.6B's stage, and where the
program keeps what the comparison reads. The only file of this configuration
that imports `deeplearning4j_tpu`.

Leaves are `<node>/<key>` on both sides: the looped stack's node holds every
block's leaves as `<block>.<key>` and the final norm's `norm_g`; the exit
head's node the head's `W` and the gate's `w_gate`, `b_gate`. One input (the
ids), one head.
"""
from __future__ import annotations

import jax
import numpy as np

# the counts the file cuts; the zoo class gets them as published, with how
# many layers are held beside them
CUT = ("num_hidden_layers", "layer_types")


def _updater(cfg):
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    u = cfg["updater"]
    return Adam(learning_rate=u["learning_rate"], beta1=u["beta1"],
                beta2=u["beta2"], epsilon=u["epsilon"])


def zoo(cfg, seed: int):
    """The zoo class asked for the published model and the layers held
    here: the first `num_hidden_layers`."""
    from deeplearning4j_tpu.models.ouro import PUBLISHED, Ouro
    pub = cfg.get("published", {})
    config = {k: pub.get(k, cfg[k]) if k in CUT else cfg[k] for k in PUBLISHED}
    return Ouro(config, seed=seed, sequence_length=cfg["sequence_length"],
                share={"layers": cfg["num_hidden_layers"]}, updater=_updater(cfg),
                compute_dtype=cfg["compute_dtype"],
                remat=cfg.get("recompute") == "block", init_std=cfg["init_std"],
                entropy_weight=cfg["entropy_weight"])


def build(cfg, params, seed: int):
    """The zoo's own graph, initialised with the benchmark's weights."""
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    return load(ComputationGraph(zoo(cfg, seed).conf()), params)


def free(net) -> None:
    """Gives up the net's weights and Adam's moments (7.3 GB at the cell's
    size), so that the next seed's weights can be drawn beside nothing."""
    net.params_tree, net._opt_state = [], []


def load(net, params):
    """Fresh weights into a net, built or used: its compiled programs stay
    (a calibration reads a dozen seeds with one compile of each). The drawn
    leaves are the net's from here on: they go to the host and their device
    buffers are given up before the net takes its copy and Adam its moments,
    so that the device never holds the weights twice: at the cell's size that
    is 2.45 GB over the window's own peak. A caller that reads `params` again
    hands in a copy."""
    per_layer = {name: {} for name in net.layer_names}
    for leaf, value in params.items():
        layer, key = leaf.split("/")
        per_layer[layer][key] = np.array(value)
    for value in params.values():
        value.delete()
    free(net)
    net.init(params=[per_layer[name] for name in net.layer_names])
    net._step, net._diverged_at = 0, None
    return net


def _named(net, trees):
    return {f"{layer}/{key}": value
            for layer, tree in zip(net.layer_names, trees)
            for key, value in tree.items()}


def params_of(net):
    return _named(net, net.params_tree)


def _host_device():
    """The host's CPU device where JAX has one, else None (the chip's)."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def first_gradient_sq(net, cfg):
    """g^2, element by element and leaf for leaf with the parameters, of the
    first gradient as the updater got it, read after one step: Adam's second
    moment is then (1 - beta2) g^2. Made on the host's CPU device where
    there is one: on the chip the squares beside the net's state are, at the
    cell's size, 2.45 GB over the window's own peak."""
    scale = 1.0 / (1.0 - cfg["updater"]["beta2"])
    v = _named(net, [s.get("v", {}) if isinstance(s, dict) else {}
                     for s in net._opt_state])
    host = _host_device()
    return {k: (s if host is None else jax.device_put(s, host)) * scale
            for k, s in v.items()}


def state_of(net):
    return {}


def batch_of(features, labels):
    """(x, y) as the net's `fit_on_device` takes them: the ids in, the exit
    head scored against the next ids."""
    return features, labels
