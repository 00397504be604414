"""How the benchmark asks the program for Olmo-Hybrid-7B's share, and where
the program keeps what the comparison reads. The only file of this
configuration that imports `deeplearning4j_tpu`.

Leaves are `<node>/<key>` on both sides: a block's node holds the wrapped
sublayer's leaves and the block's own norm (`norm_g`). One input (the ids),
one head.
"""
from __future__ import annotations

import jax

# the counts the file cuts; the zoo class gets them as published, with what
# is held beside them
CUT = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
       "linear_num_key_heads", "linear_num_value_heads", "vocab_size")


def _updater(cfg):
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    u = cfg["updater"]
    return Adam(learning_rate=u["learning_rate"], beta1=u["beta1"],
                beta2=u["beta2"], epsilon=u["epsilon"])


def zoo(cfg, seed: int):
    """The zoo class asked for the published model and this chip's share,
    the layers held here the first `num_hidden_layers` of `layer_types`."""
    from deeplearning4j_tpu.models.olmo_hybrid import PUBLISHED, OlmoHybrid
    pub = cfg.get("published", {})
    config = {k: pub.get(k, cfg[k]) if k in CUT else cfg[k] for k in PUBLISHED}
    config["num_hidden_layers"] = cfg["num_hidden_layers"]
    config["layer_types"] = cfg["layer_types"][:cfg["num_hidden_layers"]]
    held = {"heads": cfg["num_attention_heads"], "vocab": cfg["vocab_size"],
            "index": cfg.get("share", {}).get("index", 0)}
    return OlmoHybrid(config, seed=seed, sequence_length=cfg["sequence_length"],
                      share=held, updater=_updater(cfg),
                      compute_dtype=cfg["compute_dtype"],
                      remat=cfg.get("recompute") == "block",
                      init_std=cfg["init_std"])


def build(cfg, params, seed: int):
    """The zoo's own graph, initialised with the benchmark's weights."""
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    return load(ComputationGraph(zoo(cfg, seed).conf()), params)


def free(net) -> None:
    """Gives up the net's weights and Adam's moments (9.2 GB at the cell's
    size), so that the next seed's weights can be drawn beside nothing."""
    net.params_tree, net._opt_state = [], []


def load(net, params):
    """Fresh weights into a net, built or used: its compiled programs stay
    (a calibration reads a dozen seeds with one compile of each)."""
    per_layer = {name: {} for name in net.layer_names}
    for leaf, value in params.items():
        layer, key = leaf.split("/")
        per_layer[layer][key] = value
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


def first_gradient_sq(net, cfg):
    """g^2, element by element and leaf for leaf with the parameters, of the
    first gradient as the updater got it, read after one step: Adam's second
    moment is then (1 - beta2) g^2."""
    scale = 1.0 / (1.0 - cfg["updater"]["beta2"])
    v = _named(net, [s.get("v", {}) if isinstance(s, dict) else {}
                     for s in net._opt_state])
    return jax.tree_util.tree_map(lambda s: s * scale, v)


def state_of(net):
    return {}


def batch_of(features, labels):
    """(x, y) as the net's `fit_on_device` takes them: the ids in, the one
    head scored against the next ids."""
    return features, labels
