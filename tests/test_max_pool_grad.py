"""The max-pool gradient's kernel (`ops/max_pool.py`, interpreted here)
against `jax.grad` of the plain `reduce_window` pool, and the site that asks
the seam for it (`SubsamplingLayer.forward`).

The kernel is the select-and-scatter's gradient computed in one pass: every
window's cotangent lands on the same cell (the first maximum of the window
in row-major order; a padding cell holds -inf), and what overlapping
windows leave on one element is summed in float32 and rounded once. So the
only difference allowed is the float32 rounding of sums of at most four
contributions, and a bfloat16 gradient's one rounding of that sum."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.common.enums import ConvolutionMode, PoolingType
from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
from deeplearning4j_tpu.ops.max_pool import max_pool, max_pool_grad_tiles

F32, BF16 = jnp.float32, jnp.bfloat16
NONE = ((0, 0), (0, 0))


def _reference_grad(x, dy, window, strides, padding):
    pool = lambda a: lax.reduce_window(
        a, -jnp.inf, lax.max, (1, 1) + window, (1, 1) + strides,
        ((0, 0), (0, 0)) + padding)
    return jax.vjp(pool, x)[1](dy)[0]


def _inputs(shape, ties, dtype, window, strides, padding, seed=0):
    r = np.random.RandomState(seed)
    x = r.randn(*shape)
    if ties == "halves":
        x = np.round(2 * x) / 2
    elif ties == "relu zeros":
        x = np.maximum(np.round(4 * x) / 4, 0.0)
    elif ties == "whole windows equal":
        x = np.where(r.rand(*shape) < 0.7, 1.5, np.round(x))
    x = jnp.asarray(x, dtype)
    n, c = shape[:2]
    oh, ow = (jax.eval_shape(lambda a: lax.reduce_window(
        a, -jnp.inf, lax.max, (1, 1) + window, (1, 1) + strides,
        ((0, 0), (0, 0)) + padding), x).shape[2:])
    dy = jnp.asarray(r.randn(n, c, oh, ow), dtype)
    return x, dy


def _check(x, dy, window, strides, padding):
    """The kernel's gradient against the reference's, both from the same
    values (the reference's in float32)."""
    y, vjp = jax.vjp(lambda a: max_pool(a, window, strides, padding), x)
    got = vjp(dy)[0]
    assert got.dtype == x.dtype and got.shape == x.shape
    want = _reference_grad(x.astype(F32), dy.astype(F32), window, strides,
                           padding)
    assert np.array_equal(np.asarray(y), np.asarray(lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1) + window, (1, 1) + strides,
        ((0, 0), (0, 0)) + padding)))
    got, want = np.asarray(got.astype(F32)), np.asarray(want)
    scale = float(np.max(np.abs(np.asarray(dy.astype(F32)))))
    rounding = np.finfo(np.float32).eps if x.dtype == F32 else 2.0 ** -8
    np.testing.assert_allclose(got, want, rtol=rounding,
                               atol=4 * 4 * np.finfo(np.float32).eps * scale)
    # every window's cotangent landed somewhere: nothing lost, nothing made
    np.testing.assert_allclose(got.sum(), want.sum(), rtol=1e-2, atol=1e-3)


# (shape N C H W, window, strides, padding)
WINDOWS = {
    "stem 3x3/2 at 13x12": ((2, 3, 13, 12), (3, 3), (2, 2), NONE),
    "stem 3x3/2 at 112x112": ((2, 8, 112, 112), (3, 3), (2, 2), NONE),
    "2x2/2 even": ((2, 3, 8, 10), (2, 2), (2, 2), NONE),
    "2x2/2 odd": ((3, 2, 9, 7), (2, 2), (2, 2), NONE),
    "Same 3x3/2 odd, -inf padding": ((2, 3, 13, 11), (3, 3), (2, 2),
                                     ((1, 1), (1, 1))),
    "Same 2x2/1, padding after": ((2, 2, 6, 7), (2, 2), (1, 1),
                                  ((0, 1), (0, 1))),
    "3x2/2 uneven padding": ((3, 2, 9, 7), (3, 2), (2, 2), ((0, 1), (1, 0))),
    "batch on two lane tiles": ((256, 2, 7, 7), (3, 3), (2, 2), NONE),
    "channels on lanes": ((3, 128, 7, 6), (3, 3), (2, 2), NONE),
}
TIES = ["none", "halves", "relu zeros", "whole windows equal"]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("ties", TIES)
@pytest.mark.parametrize("case", [c for c in WINDOWS if "112" not in c])
def test_kernel_gradient_matches_select_and_scatter(case, ties, dtype):
    shape, window, strides, padding = WINDOWS[case]
    _check(*_inputs(shape, ties, dtype, window, strides, padding),
           window, strides, padding)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("ties", ["none", "relu zeros"])
def test_kernel_gradient_at_the_stems_size(ties, dtype):
    shape, window, strides, padding = WINDOWS["stem 3x3/2 at 112x112"]
    _check(*_inputs(shape, ties, dtype, window, strides, padding),
           window, strides, padding)


@pytest.mark.parametrize("case", ["stem 3x3/2 at 13x12",
                                  "Same 3x3/2 odd, -inf padding"])
def test_nan_and_minus_inf_windows_choose_as_the_select_and_scatter_does(case):
    """The walk's own rule where `>=` is no order: a NaN is replaced by
    the cell after it; a padding cell holds -inf and, taken, keeps what it
    takes."""
    shape, window, strides, padding = WINDOWS[case]
    x, dy = _inputs(shape, "halves", F32, window, strides, padding, seed=3)
    r = np.random.RandomState(4)
    x = np.array(x)
    x[r.rand(*shape) < 0.15] = np.nan
    x[r.rand(*shape) < 0.15] = -np.inf
    x, dy = jnp.asarray(x), jnp.abs(dy) + 1.0
    got = jax.vjp(lambda a: max_pool(a, window, strides, padding), x)[1](dy)[0]
    want = _reference_grad(x, dy, window, strides, padding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ------------------------------------------------------------------ the site
def _counts():
    reg = telemetry.registry()
    return tuple(reg.counter(f"ops.helper.max_pool_grad.{path}").value
                 for path in ("kernel", "fallback"))


def _layer_grad(layer, x):
    """The layer's gradient of a weighted sum of its output."""
    def loss(a):
        out = layer.forward({}, {}, a, train=True)[0]
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))
    return jax.grad(loss)(x)


def _pool_layer(**kw):
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        SubsamplingLayer)
    return SubsamplingLayer(**kw)


@pytest.mark.parametrize("override", [True, False])
@pytest.mark.parametrize("mode", ["Truncate", "Same"])
def test_site_asks_the_seam_once_and_agrees(mode, override):
    layer = _pool_layer(pooling_type=PoolingType.MAX, kernel_size=(3, 3),
                        stride=(2, 2),
                        convolution_mode=getattr(ConvolutionMode, mode))
    x = jnp.asarray(np.random.RandomState(5).randn(2, 3, 11, 12), F32)
    with helpers_enabled_ctx(False):
        want = _layer_grad(layer, x)
    before = _counts()
    with helpers_enabled_ctx(override):
        got = _layer_grad(layer, x)
    assert _counts() == (before[0] + override, before[1] + (not override))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


MAX, AVG, PNORM = PoolingType.MAX, PoolingType.AVG, PoolingType.PNORM
REFUSED = {
    "AVG": (dict(pooling_type=AVG, kernel_size=(3, 3), stride=(2, 2)),
            (2, 3, 9, 9), F32),
    "PNORM": (dict(pooling_type=PNORM, kernel_size=(2, 2), stride=(2, 2)),
              (2, 3, 8, 8), F32),
    "3x3/1: windows past the next window's rows":
        (dict(pooling_type=MAX, kernel_size=(3, 3), stride=(1, 1)),
         (2, 3, 9, 9), F32),
    "1x3/1: past the next window's columns":
        (dict(pooling_type=MAX, kernel_size=(1, 3), stride=(1, 1)),
         (2, 3, 9, 9), F32),
    "float64": (dict(pooling_type=MAX, kernel_size=(2, 2), stride=(2, 2)),
                (2, 3, 8, 8), jnp.float64),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_refused_shapes_take_the_plain_path_uncounted(case):
    kw, shape, dtype = REFUSED[case]
    layer = _pool_layer(**kw)
    x = jnp.asarray(np.random.RandomState(6).randn(*shape), dtype)
    with helpers_enabled_ctx(False):
        want = _layer_grad(layer, x)
    before = _counts()
    with helpers_enabled_ctx(True):
        got = _layer_grad(layer, x)
    assert _counts() == before
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_subsampling_1d_does_not_reach_the_site():
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        Subsampling1DLayer)
    layer = Subsampling1DLayer(kernel_size=(2, 2), stride=(2, 2))
    x = jnp.asarray(np.random.RandomState(7).randn(2, 3, 10), F32)
    before = _counts()
    with helpers_enabled_ctx(True):
        _layer_grad(layer, x)
    assert _counts() == before


@pytest.mark.parametrize("shape,window,tiles", [
    # (sublanes, lanes): channels and batch, or batch and channels
    ((512, 64, 112, 112), (3, 3), (64, 128)),    # the ResNet50 stem's
    ((512, 2048, 4, 4), (3, 3), (512, 128)),     # its head's
    ((32, 64, 224, 224), (2, 2), (64, 32)),      # VGG16's first, batch whole
    ((128, 512, 224, 224), (2, 2), (64, 128)),   # the batch halved
    ((8, 3, 4096, 4096), (2, 2), None),          # 3 channels cannot be halved
])
def test_tiles_on_the_chip(shape, window, tiles, monkeypatch):
    monkeypatch.setattr(helpers, "interpret_mode", lambda: False)
    assert max_pool_grad_tiles(shape, BF16, window, (2, 2), NONE) == tiles


# -------------------------------------------------- the count a program holds
def _tiny_cnn():
    """convolution -> batch norm -> ReLU -> 3x3/2 max pool -> dense, float32:
    the ResNet stem's pattern."""
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.common.enums import (
        Activation, LossFunction, WeightInit)
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        ConvolutionLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.conf.layers.feedforward import OutputLayer
    from deeplearning4j_tpu.nn.conf.layers.normalization import (
        BatchNormalization)
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    g = (NeuralNetConfiguration.Builder().seed(3).dtype("float32")
         .weight_init(WeightInit.XAVIER)
         .convolution_mode(ConvolutionMode.Truncate)
         .updater(Adam(learning_rate=1e-2)).graph_builder())
    (g.add_inputs("in")
      .add_layer("conv", ConvolutionLayer(n_out=4, kernel_size=(3, 3)), "in")
      .add_layer("bn", BatchNormalization(activation=Activation.RELU), "conv")
      .add_layer("pool", SubsamplingLayer(pooling_type=PoolingType.MAX,
                                          kernel_size=(3, 3), stride=(2, 2)),
                 "bn")
      .add_layer("out", OutputLayer(n_out=3, loss_fn=LossFunction.MCXENT,
                                    activation=Activation.SOFTMAX), "pool")
      .set_outputs("out")
      .set_input_types(InputType.convolutional(11, 11, 2)))
    S = jax.ShapeDtypeStruct
    return (ComputationGraph(g.build()).init(), S((4, 2, 11, 11), F32),
            S((4, 3), F32))


def _tiny_lstm():
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.common.enums import Activation, LossFunction
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.layers.recurrent import (
        GravesLSTM, RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(3).dtype("float32")
            .updater(Adam(learning_rate=1e-2)).list()
            .layer(GravesLSTM(n_out=8, activation=Activation.TANH))
            .layer(RnnOutputLayer(n_out=5, loss_fn=LossFunction.MCXENT,
                                  activation=Activation.SOFTMAX))
            .set_input_type(InputType.recurrent(5)).build())
    S = jax.ShapeDtypeStruct
    return MultiLayerNetwork(conf).init(), S((2, 5, 6), F32), S((2, 5, 6), F32)


def _tiny_decoder():
    """The benchmark's tiny Xing4 share through its adapter."""
    import importlib.util
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                         "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)       # the reference imports `harness`
    configs = os.path.join(bench, "configs")

    def load(name):
        spec = importlib.util.spec_from_file_location(
            "t_pool_" + name[:-3], os.path.join(configs, name))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    ref = load("xing4_29b_a4b_share8_reference.py")
    prog = load("xing4_29b_a4b_share8_program.py")
    with open(os.path.join(configs, os.pardir, "tests", "tiny", "configs",
                           "tiny_xing4.json")) as f:
        cfg = json.load(f)
    net = prog.build(cfg, ref.init_params(cfg, jax.random.PRNGKey(0)), 0)
    ids = jax.random.randint(jax.random.PRNGKey(1),
                             (1, cfg["sequence_length"] + 1), 0,
                             cfg["vocab_size"])
    return (net,) + tuple(prog.batch_of(ids[:, :-1], ids[:, 1:]))


@pytest.mark.parametrize("build,kernels", [
    (_tiny_cnn, 1), (_tiny_lstm, 0), (_tiny_decoder, 0)],
    ids=["CNN with one max pool", "LSTM", "decoder"])
def test_a_train_program_asks_once_a_max_pool(build, kernels):
    net, x, y = build()
    before = _counts()
    with helpers_enabled_ctx(True):
        net.lower_fit_batch(x, y)
    assert _counts() == (before[0] + kernels, before[1])
