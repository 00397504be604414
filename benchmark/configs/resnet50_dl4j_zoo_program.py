"""How the benchmark asks the program for the zoo ResNet50, and where the
program keeps what the comparison reads. The only file of this configuration
that imports `deeplearning4j_tpu`."""
from __future__ import annotations

import jax

_TO_PROGRAM = {"gamma": "gamma_w"}       # DL4J's name -> the program's key


def _updater(cfg):
    """The configuration's updater. The zoo class's own default leaves out
    `ResNet50.java`'s third argument, the epsilon, so it is passed."""
    from deeplearning4j_tpu.nn.updater.updaters import RmsProp
    u = cfg["updater"]
    return RmsProp(learning_rate=u["learning_rate"], rms_decay=u["rms_decay"],
                   epsilon=u["epsilon"])


def build(cfg, params, seed: int):
    """The zoo's own graph, initialised with the benchmark's weights."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    zoo = ResNet50(num_labels=cfg["num_labels"], seed=seed,
                   updater=_updater(cfg), compute_dtype=cfg["compute_dtype"],
                   input_shape=tuple(cfg["input_shape"]))
    net = ComputationGraph(zoo.conf())
    per_layer = {name: {} for name in net.layer_names}
    for leaf, value in params.items():
        layer, key = leaf.split("/")
        per_layer[layer][_TO_PROGRAM.get(key, key)] = value
    return net.init(params=[per_layer[name] for name in net.layer_names])


def _named(net, trees):
    back = {v: k for k, v in _TO_PROGRAM.items()}
    return {f"{layer}/{back.get(key, key)}": value
            for layer, tree in zip(net.layer_names, trees)
            for key, value in tree.items()}


def params_of(net):
    return _named(net, net.params_tree)


def first_gradient_sq(net, cfg):
    """g^2, element by element and leaf for leaf with the parameters, of the
    first gradient as the updater got it, read after one step: RmsProp's
    cache is then (1-d) g^2."""
    scale = 1.0 / (1.0 - cfg["updater"]["rms_decay"])
    cache = _named(net, [s.get("g2", {}) if isinstance(s, dict) else {}
                         for s in net._opt_state])
    return jax.tree_util.tree_map(lambda s: s * scale, cache)


def state_of(net):
    return _named(net, net.state_tree)
