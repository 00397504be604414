"""How the benchmark asks the program for the zoo TextGenerationLSTM, and
where the program keeps what the comparison reads. The only file of this
configuration that imports `deeplearning4j_tpu`."""
from __future__ import annotations

import jax


def _layer_names(cfg):
    return [f"lstm{i}" for i in range(cfg["layers"])] + ["output"]


def _updater(cfg):
    from deeplearning4j_tpu.nn.updater.updaters import RmsProp
    u = cfg["updater"]
    return RmsProp(learning_rate=u["learning_rate"], rms_decay=u["rms_decay"],
                   epsilon=u["epsilon"])


def build(cfg, params, seed: int):
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    zoo = TextGenerationLSTM(total_unique_characters=cfg["vocab"], seed=seed,
                             updater=_updater(cfg),
                             compute_dtype=cfg["compute_dtype"])
    net = MultiLayerNetwork(zoo.conf())
    per_layer = {name: {} for name in _layer_names(cfg)}
    for leaf, value in params.items():
        layer, key = leaf.split("/")
        per_layer[layer][key] = value
    net.init(params=[per_layer[name] for name in _layer_names(cfg)])
    net._bench_layer_names = _layer_names(cfg)
    return net


def _named(net, trees):
    return {f"{layer}/{key}": value
            for layer, tree in zip(net._bench_layer_names, trees)
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
    return {}
