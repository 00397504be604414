"""A large parameter leaf's gradient is held apart from its update (ISSUE 36):
`util.dtypes.cast_params` casts a leaf of at least `GRAD_HELD_APART_MIN`
elements through a `custom_vjp` whose backward rule puts the compute-type
cotangent behind `lax.optimization_barrier` before the upcast, so that XLA
cannot fuse the updater into the weight-gradient product. The values do not
change; smaller leaves lower exactly as the plain `cast_floats` does."""
import hashlib

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (
    Activation, Adam, ComputationGraph, ConvolutionMode, DenseLayer, InputType,
    LossFunction, MultiLayerNetwork, NeuralNetConfiguration, OutputLayer,
    RnnOutputLayer, WeightInit, telemetry)
from deeplearning4j_tpu.nn.conf.layers.convolutional import (
    ConvolutionLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.conf.layers.feedforward import ActivationLayer
from deeplearning4j_tpu.nn.conf.layers.normalization import BatchNormalization
from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM
from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
from deeplearning4j_tpu.util import dtypes

BARRIER = "stablehlo.optimization_barrier"
# the dense net's leaves: d1/W 12 x 40 (480, the threshold below), d2/W 40 x 20
# (800), biases 40 and 20; the output layer scores in float32 and is not cast
LARGE, THRESHOLD = 2, 480


def _builder(seed=5):
    return (NeuralNetConfiguration.Builder().seed(seed).dtype("float32")
            .compute_dtype("bfloat16").weight_init(WeightInit.XAVIER)
            .updater(Adam(learning_rate=1e-2)))


def _dense(kind):
    d1 = DenseLayer(n_in=12, n_out=40, activation=Activation.TANH)
    d2 = DenseLayer(n_in=40, n_out=20, activation=Activation.RELU)
    out = OutputLayer(n_in=20, n_out=3, activation=Activation.SOFTMAX,
                      loss_fn=LossFunction.MCXENT)
    b = _builder().remat(True)
    if kind == "multilayer":
        return MultiLayerNetwork(b.list().layer(d1).layer(d2).layer(out)
                                 .set_input_type(InputType.feed_forward(12))
                                 .build()).init()
    conf = (b.graph_builder().add_inputs("in").add_layer("d1", d1, "in")
            .add_layer("d2", d2, "d1").add_layer("out", out, "d2")
            .set_outputs("out").set_input_types(InputType.feed_forward(12))
            .build())
    return ComputationGraph(conf).init()


def _batch():
    rng = np.random.RandomState(1)
    return (rng.randn(16, 12).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)])


def _count():
    return telemetry.registry().counter("train.grad_held_apart").value


def _leaves(net):
    return jax.tree_util.tree_leaves((net.params_tree, net._opt_state))


@pytest.mark.parametrize("kind", ["graph", "multilayer"])
def test_held_apart_changes_no_value_of_four_steps(monkeypatch, kind):
    x, y = _batch()
    plain = _dense(kind)
    plain_losses = plain.fit_on_device(x, y, steps=4)
    monkeypatch.setattr(dtypes, "GRAD_HELD_APART_MIN", THRESHOLD)
    held = _dense(kind)
    before = _count()
    held_losses = held.fit_on_device(x, y, steps=4)
    assert _count() - before == LARGE           # one a leaf, at trace time
    np.testing.assert_array_equal(np.asarray(held_losses),
                                  np.asarray(plain_losses))
    for a, b in zip(_leaves(held), _leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["graph", "multilayer"])
def test_one_barrier_a_large_leaf_and_the_counter_agrees(monkeypatch, kind):
    x, y = _batch()
    plain = _dense(kind).lower_train_step(x, y, steps=1).as_text()
    monkeypatch.setattr(dtypes, "GRAD_HELD_APART_MIN", THRESHOLD)
    before = _count()
    held = _dense(kind).lower_train_step(x, y, steps=1).as_text()
    added = held.count(BARRIER) - plain.count(BARRIER)
    assert added == LARGE == _count() - before
    # each on a large leaf's bf16 gradient, none on a bias's
    lines = [l for l in held.split("\n") if BARRIER in l]
    for shape in ("12x40xbf16", "40x20xbf16"):
        assert sum(f"({shape}) -> " in l or f": tensor<{shape}>" in l
                   for l in lines) >= 1, shape
    assert not any("tensor<40xbf16>" in l or "tensor<20xbf16>" in l
                   for l in lines)


def test_at_the_threshold_and_above_only(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setattr(dtypes, "GRAD_HELD_APART_MIN", 6)
    tree = {"small": jnp.ones((5,), jnp.float32),
            "edge": jnp.ones((2, 3), jnp.float32),
            "ids": jnp.ones((7,), jnp.int32)}

    def f(t):
        c = dtypes.cast_params(t, jnp.bfloat16)
        assert c["ids"].dtype == jnp.int32
        return jnp.sum(c["small"]) + jnp.sum(c["edge"] * 3)

    text = jax.jit(jax.grad(lambda s, e: f({"small": s, "edge": e,
                                            "ids": tree["ids"]}),
                            argnums=(0, 1))).lower(
        tree["small"], tree["edge"]).as_text()
    assert text.count(BARRIER) == 1
    g = jax.grad(lambda e: f({"small": tree["small"], "edge": e,
                              "ids": tree["ids"]}))(tree["edge"])
    assert g.dtype == jnp.float32 and np.allclose(g, 3.0)


# --------------------------------------- the cells that must not change
def _resnet_like():
    g = (_builder(17).activation(Activation.IDENTITY)
         .convolution_mode(ConvolutionMode.Truncate).graph_builder())
    conv = lambda **kw: ConvolutionLayer(n_out=8, kernel_size=(1, 1), **kw)
    (g.add_inputs("in")
      .add_layer("c1", ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                        convolution_mode=ConvolutionMode.Same),
                 "in")
      .add_layer("b1", BatchNormalization(activation=Activation.RELU), "c1")
      .add_layer("c2", conv(stride=(2, 2)), "b1")
      .add_layer("b2", BatchNormalization(), "c2")
      .add_layer("sc", conv(stride=(2, 2)), "b1")
      .add_layer("bs", BatchNormalization(), "sc")
      .add_vertex("add", ElementWiseVertex(op="Add"), "b2", "bs")
      .add_layer("relu", ActivationLayer(activation=Activation.RELU), "add")
      .add_layer("pool", SubsamplingLayer(kernel_size=(4, 4), stride=(4, 4)),
                 "relu")
      .add_layer("out", OutputLayer(n_out=3, loss_fn=LossFunction.MCXENT,
                                    activation=Activation.SOFTMAX), "pool")
      .set_outputs("out")
      .set_input_types(InputType.convolutional(8, 8, 4)))
    rng = np.random.RandomState(0)
    return (ComputationGraph(g.build()).init(),
            rng.rand(6, 4, 8, 8).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, 6)])


def _graves_lstm():
    b = _builder(3).list()
    b.layer(GravesLSTM(n_out=5, activation=Activation.TANH))
    b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
    net = MultiLayerNetwork(b.set_input_type(InputType.recurrent(3)).build()).init()
    rng = np.random.RandomState(2)
    return (net, rng.rand(4, 3, 7).astype(np.float32),
            np.eye(2, dtype=np.float32)[rng.randint(0, 2, (4, 7))]
            .transpose(0, 2, 1))


@pytest.mark.parametrize("make", [_resnet_like, _graves_lstm],
                         ids=["resnet_like", "graves_lstm"])
@pytest.mark.parametrize("program", ["device_loop", "fit_batch"])
def test_small_leaves_lower_as_the_plain_cast(monkeypatch, make, program):
    def text():
        net, x, y = make()
        low = (net.lower_train_step(x, y, steps=2) if program == "device_loop"
               else net.lower_fit_batch(x, y))
        return low.as_text()

    held = text()
    assert BARRIER not in held
    monkeypatch.setattr(dtypes, "cast_params", dtypes.cast_floats)
    plain = text()
    assert hashlib.sha256(held.encode()).hexdigest() \
        == hashlib.sha256(plain.encode()).hexdigest()


@pytest.mark.parametrize("zoo", ["resnet50", "textgen_lstm"])
def test_the_zoo_cells_hold_no_leaf_at_the_threshold(zoo):
    """The benchmark's ResNet50 and LSTM cells: every leaf under the rule's
    constant, so their programs stay as they were (shapes only)."""
    from deeplearning4j_tpu.models import ResNet50, TextGenerationLSTM
    if zoo == "resnet50":
        net = ComputationGraph(ResNet50(num_labels=1000,
                                        compute_dtype="bfloat16").conf())
    else:
        net = MultiLayerNetwork(TextGenerationLSTM(
            total_unique_characters=47, compute_dtype="bfloat16").conf())
    params = jax.eval_shape(lambda: net.init().params_tree)
    largest = max(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert 0 < largest < dtypes.GRAD_HELD_APART_MIN
