"""How the benchmark asks the program for Xing4.0-29B-A4B's share, and where
the program keeps what the comparison reads. The only file of this
configuration that imports `deeplearning4j_tpu`.

Leaves are `<node>/<key>` on both sides. The router's selection bias is a
buffer of the program (the expert layer's state, no gradient leaf) and a leaf
of the reference's parameters that no gradient reaches: it is moved into the
state here, and read back from there with a gradient of nought.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BUFFER = "router_bias"


def _updater(cfg):
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    u = cfg["updater"]
    return Adam(learning_rate=u["learning_rate"], beta1=u["beta1"],
                beta2=u["beta2"], epsilon=u["epsilon"])


def zoo(cfg, seed: int):
    """The zoo class asked for the published model and this chip's share."""
    from deeplearning4j_tpu.models.xing4 import Xing4
    # the file's keys are the config.json's; the three counts it cuts go to
    # the zoo class as published, with what is held beside them
    pub = cfg.get("published", {})
    config = dict(cfg, **{k: pub.get(k, cfg[k]) for k in (
        "num_attention_heads", "n_routed_experts", "vocab_size")})
    held = {"heads": cfg["num_attention_heads"], "experts": cfg["n_routed_experts"],
            "vocab": cfg["vocab_size"], "index": cfg.get("share", {}).get("index", 0)}
    return Xing4(config, seed=seed, sequence_length=cfg["sequence_length"],
                 share=held, updater=_updater(cfg),
                 compute_dtype=cfg["compute_dtype"],
                 remat=cfg.get("recompute") == "block",
                 mtp_loss_weight=cfg["mtp_loss_weight"], init_std=cfg["init_std"])


def build(cfg, params, seed: int):
    """The zoo's own graph, initialised with the benchmark's weights."""
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    return load(ComputationGraph(zoo(cfg, seed).conf()), params)


def free(net) -> None:
    """Gives up the net's weights and Adam's moments (9.5 GB at the cell's
    size), so that the next seed's weights can be drawn beside nothing."""
    net.params_tree, net._opt_state = [], []


def load(net, params):
    """Fresh weights into a net, built or used: its compiled programs stay
    (a calibration reads a dozen seeds with one compile of each)."""
    per_layer = {name: {} for name in net.layer_names}
    buffers = {}
    for leaf, value in params.items():
        layer, key = leaf.split("/")
        (buffers if key == BUFFER else per_layer[layer])[
            layer if key == BUFFER else key] = value
    free(net)
    net.init(params=[per_layer[name] for name in net.layer_names])
    net._step, net._diverged_at = 0, None
    for layer, value in buffers.items():
        i = net.layer_names.index(layer)
        net.state_tree[i] = dict(net.state_tree[i], **{BUFFER: jnp.array(value)})
    return net


def _buffers(net):
    return {f"{layer}/{BUFFER}": state[BUFFER]
            for layer, state in zip(net.layer_names, net.state_tree)
            if isinstance(state, dict) and BUFFER in state}


def _named(net, trees):
    return {f"{layer}/{key}": value
            for layer, tree in zip(net.layer_names, trees)
            for key, value in tree.items()}


def params_of(net):
    return {**_named(net, net.params_tree), **_buffers(net)}


def first_gradient_sq(net, cfg):
    """g^2, element by element and leaf for leaf with the parameters, of the
    first gradient as the updater got it, read after one step: Adam's second
    moment is then (1 - beta2) g^2. The buffers had no gradient."""
    scale = 1.0 / (1.0 - cfg["updater"]["beta2"])
    v = _named(net, [s.get("v", {}) if isinstance(s, dict) else {}
                     for s in net._opt_state])
    out = jax.tree_util.tree_map(lambda s: s * scale, v)
    out.update({k: jnp.zeros_like(b) for k, b in _buffers(net).items()})
    return out


def state_of(net):
    return {}


def batch_of(features, labels):
    """(x, y) as the net's `fit_on_device` takes them: the ids and the labels
    (which the MTP module reads) in, both heads scored against the labels."""
    return (features, labels), (labels, labels)
