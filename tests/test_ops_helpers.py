"""Helper seam tests: one way to choose a kernel, and every site counted.

Parity: ref the cudnn-vs-builtin consistency tests (deeplearning4j-cuda
ValidateCudnnLSTM etc.): the accelerated path must match the XLA fallback
numerically, and training must produce identical results with the seam on."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.ops import enable_helpers, helper_for, registered_helpers
from deeplearning4j_tpu.ops.helpers import (
    helpers_enabled_ctx, helpers_enabled_for)

SHIPPED = {"graves_lstm_scan", "flash_attention", "grouped_matmul",
           "decode_attention_paged", "decode_attention_spec_paged",
           "hyper_connection", "gated_delta_rule", "max_pool_grad"}


@pytest.fixture(autouse=True)
def _default_policy_after():
    yield
    enable_helpers(None)


def _counts(name):
    reg = telemetry.registry()
    return tuple(reg.counter(f"ops.helper.{name}.{path}").value
                 for path in ("kernel", "fallback"))


def test_registered_helpers_are_exactly_the_kernels_that_ship():
    assert set(registered_helpers()) == SHIPPED


def test_registry_and_dispatch():
    fallback = lambda *a: "fallback"
    enable_helpers(False)
    assert helper_for("graves_lstm_scan", fallback) is fallback
    assert helper_for("flash_attention", None) is None
    enable_helpers(True)
    assert helper_for("graves_lstm_scan", fallback) \
        is registered_helpers()["graves_lstm_scan"]
    assert helper_for("flash_attention", None) is not None
    assert helper_for("nonexistent-op", fallback) is fallback


def test_lstm_training_identical_with_seam_on():
    """End-to-end: an LSTM net trains to the same loss with helpers on/off."""
    from deeplearning4j_tpu import (
        Activation, InputType, LSTM, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)

    def run():
        b = (NeuralNetConfiguration.Builder().seed(9).weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.1)).dtype("float64").list())
        b.layer(LSTM(n_out=6, activation=Activation.TANH))
        b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(3)).build()).init()
        rng = np.random.RandomState(1)
        x = rng.rand(4, 3, 7)
        y = np.eye(2)[rng.randint(0, 2, (4, 7))].transpose(0, 2, 1)
        for _ in range(5):
            net.fit_batch(x, y)
        return float(net.score()), np.asarray(net.params())

    enable_helpers(False)
    s_off, p_off = run()
    kernel, _ = _counts("graves_lstm_scan")
    enable_helpers(True)
    s_on, p_on = run()
    assert _counts("graves_lstm_scan")[0] > kernel  # the scan kernel ran
    assert s_on == pytest.approx(s_off, abs=1e-10)
    assert np.allclose(p_on, p_off, atol=1e-10)


def test_graves_lstm_training_identical_with_seam_on():
    """End-to-end: a GravesLSTM (peephole) net trains to the same params with
    helpers on/off — the ValidateCudnnLSTM pattern for the Graves path."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM

    def run():
        b = (NeuralNetConfiguration.Builder().seed(9).weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.1)).dtype("float64").list())
        b.layer(GravesLSTM(n_out=6, activation=Activation.TANH))
        b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(3)).build()).init()
        rng = np.random.RandomState(1)
        x = rng.rand(4, 3, 7)
        y = np.eye(2)[rng.randint(0, 2, (4, 7))].transpose(0, 2, 1)
        for _ in range(5):
            net.fit_batch(x, y)
        return float(net.score()), np.asarray(net.params())

    enable_helpers(False)
    s_off, p_off = run()
    kernel, _ = _counts("graves_lstm_scan")
    enable_helpers(True)
    s_on, p_on = run()
    assert _counts("graves_lstm_scan")[0] > kernel  # the scan kernel ran
    assert s_on == pytest.approx(s_off, abs=1e-10)
    assert np.allclose(p_on, p_off, atol=1e-10)


def test_graves_gradient_check_through_helper():
    """fp64 finite-difference gradient check THROUGH the whole-sequence
    Pallas kernel and its custom VJP (the CuDNNGradientChecks pattern)."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM
    from deeplearning4j_tpu.gradientcheck import check_gradients

    enable_helpers(True)
    b = (NeuralNetConfiguration.Builder().seed(3).weight_init(WeightInit.XAVIER)
         .updater(Sgd(learning_rate=0.1)).dtype("float64").list())
    b.layer(GravesLSTM(n_out=4, activation=Activation.TANH))
    b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
    net = MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(3)).build()).init()
    rng = np.random.RandomState(2)
    x = rng.rand(3, 3, 5)
    y = np.eye(2)[rng.randint(0, 2, (3, 5))].transpose(0, 2, 1)
    assert check_gradients(net, x, y, epsilon=1e-6, max_rel_error=1e-5)


def _override(monkeypatch):
    """The override as a site would feel it: None engages on a TPU only,
    True everywhere, False nowhere."""
    felt = []
    for backend in ("cpu", "tpu"):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda b=backend: b)
            felt.append(helpers_enabled_for("flash_attention"))
    return {(False, True): None, (True, True): True,
            (False, False): False}[tuple(felt)]


def test_helpers_enabled_ctx_restores_prior_override(monkeypatch):
    """The scoped switch restores the PREVIOUS override (not False) on exit
    and on exception — a temporary bench/test flip must never pin the global
    policy for the rest of the process (ADVICE r4)."""
    enable_helpers(None)  # default policy active
    with helpers_enabled_ctx(True):
        assert _override(monkeypatch) is True
        with helpers_enabled_ctx(False):  # nesting restores one level
            assert _override(monkeypatch) is False
        assert _override(monkeypatch) is True
    assert _override(monkeypatch) is None
    enable_helpers(True)
    with pytest.raises(RuntimeError):
        with helpers_enabled_ctx(False):
            raise RuntimeError("boom")
    assert _override(monkeypatch) is True  # restored on exception too


def test_policy_is_one_sentence(monkeypatch):
    """A registered kernel runs on a TPU unless the override says otherwise,
    and off a TPU only when the override forces it — the reference's 'cuDNN
    used when supported'. Nothing else is read: no environment variable."""
    enable_helpers(None)
    monkeypatch.setenv("DL4J_TPU_HELPERS", "1")  # the knob that went
    for name in SHIPPED:
        assert not helpers_enabled_for(name)        # CPU, no override
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DL4J_TPU_HELPERS", "0")
    for name in SHIPPED:
        assert helpers_enabled_for(name)            # a TPU, no override
    assert not helpers_enabled_for("nonexistent-op")
    enable_helpers(False)
    assert not helpers_enabled_for("graves_lstm_scan")
    enable_helpers(True)
    assert helpers_enabled_for("graves_lstm_scan")
    assert not helpers_enabled_for("nonexistent-op")  # nothing to force
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert helpers_enabled_for("graves_lstm_scan")  # forced, interpreted


# ---------------------------------------------------------------- the sites
def _lstm(cls_name="GravesLSTM", **kw):
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.nn.conf.layers import recurrent
    layer = getattr(recurrent, cls_name)(n_in=3, n_out=5, **kw)
    params = layer.init_params(jax.random.PRNGKey(4), InputType.recurrent(3),
                               jnp.float64)
    r = np.random.RandomState(6)
    params = {k: v + 0.1 * jnp.asarray(r.randn(*v.shape))
              for k, v in params.items()}     # peepholes start at zero
    x = jnp.asarray(np.random.RandomState(1).randn(4, 3, 6))
    return layer, params, x


def _site_lstm_scan(monkeypatch):
    layer, params, x = _lstm()
    ys, (h, c) = layer._scan(params, x, None)
    return ys, h, c


def _site_self_attention(monkeypatch):
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    layer = SelfAttentionLayer(n_in=4, n_out=4, n_heads=2, causal=True,
                               block_size=2)
    params = layer.init_params(jax.random.PRNGKey(1), InputType.recurrent(4),
                               jnp.float64)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 4, 6))
    return layer.forward(params, {}, x, train=False)[0]


def _site_latent_attention(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers import decoder
    monkeypatch.setattr(decoder, "_DENSE_ATTENTION_MAX_T", 4)
    layer = decoder.LatentAttention(n_in=8, n_out=8, n_heads=2,
                                    qk_nope_head_dim=8, qk_rope_head_dim=4,
                                    v_head_dim=8)
    r = np.random.RandomState(3)
    q, k = (jnp.asarray(r.randn(1, 2, 8, 12)) for _ in range(2))
    return layer._attend(q, k, jnp.asarray(r.randn(1, 2, 8, 8)))


def _ring(window=0):
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.sequence_parallel import ring_attention
    r = np.random.RandomState(13)
    q, k, v = (jnp.asarray(r.randn(1, 2, 32, 8)) for _ in range(3))
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("seq",))
    return ring_attention(q, k, v, mesh, causal=True, window=window)


def _site_ring_attention(monkeypatch):
    return _ring()


def _site_grouped_matmul(monkeypatch):
    from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul
    r = np.random.RandomState(15)
    x, w = (jnp.asarray(r.randn(*shape), jnp.float32)   # the kernel's width
            for shape in ((12, 6), (3, 6, 5)))
    return grouped_matmul(x, w, jnp.array([4, 0, 5], jnp.int32))


def _site_decode_paged(monkeypatch):
    from deeplearning4j_tpu.serving.decode import decode_attention_paged
    from tests.test_decode_attention import _paged_case
    return decode_attention_paged(*_paged_case(3, 4, 2, 16, 16, 4, 5))


def _site_decode_spec_paged(monkeypatch):
    from deeplearning4j_tpu.serving.decode import decode_attention_spec_paged
    from tests.test_spec_decode import _spec_case
    return decode_attention_spec_paged(*_spec_case(3, 3, 4, 2, 16, 16, 4, 5))


def _site_hyper_connection(monkeypatch, t=128):
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.nn.conf.layers import decoder
    layer = decoder.HyperConnection(layer=decoder.RMSNorm(n_in=128), n_streams=4,
                                    sinkhorn_iters=3)
    layer.name = "hc"
    kind = InputType.recurrent(4 * 128, t)
    layer.set_n_in(kind)
    params = layer.init_params(jax.random.PRNGKey(5), kind, jnp.float32)
    x = jnp.asarray(np.random.RandomState(16).randn(1, 4, t, 128), jnp.float32)
    return layer.forward(params, {}, x, train=False)[0]


# site -> (the name it asks the seam for, the driver, the kernel's tolerance
# against its fallback: float64 but where the fallback (the latent
# attention's scores) or the kernel (the grouped product, the hyper-connection)
# is float32)
def _site_gated_delta_net(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers.decoder import GatedDeltaNet
    layer = GatedDeltaNet(n_in=16, n_out=16, n_k_heads=2, n_v_heads=4, d_k=8,
                          d_v=8)
    params = layer.init_params(jax.random.PRNGKey(0), None, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 70, 16), jnp.float32)
    return layer.forward(params, {}, x, train=True)[0]


def _site_max_pool(monkeypatch, stride=2):
    """The layer's gradient: the kernel replaces the backward only."""
    from deeplearning4j_tpu.common.enums import PoolingType
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        SubsamplingLayer)
    layer = SubsamplingLayer(pooling_type=PoolingType.MAX, kernel_size=(3, 3),
                             stride=(stride, stride))
    x = jnp.asarray(np.random.RandomState(17).randn(2, 3, 9, 8), jnp.float32)
    out, vjp = jax.vjp(lambda a: layer.forward({}, {}, a, train=True)[0], x)
    return out, vjp(jnp.cos(jnp.arange(out.size, dtype=out.dtype).reshape(
        out.shape)))[0]


SITES = {
    "LSTM._scan": ("graves_lstm_scan", _site_lstm_scan, 1e-10),
    "SelfAttentionLayer.forward": ("flash_attention", _site_self_attention,
                                   1e-10),
    "LatentAttention._attend": ("flash_attention", _site_latent_attention,
                                1e-5),
    "ring_attention": ("flash_attention", _site_ring_attention, 1e-10),
    "grouped_matmul": ("grouped_matmul", _site_grouped_matmul, 1e-5),
    "decode_attention_paged": ("decode_attention_paged", _site_decode_paged,
                               1e-12),
    "decode_attention_spec_paged": ("decode_attention_spec_paged",
                                    _site_decode_spec_paged, 1e-12),
    "HyperConnection.forward": ("hyper_connection", _site_hyper_connection,
                                1e-5),
    "GatedDeltaNet.forward": ("gated_delta_rule", _site_gated_delta_net, 1e-5),
    "SubsamplingLayer.forward": ("max_pool_grad", _site_max_pool, 1e-6),
}


@pytest.mark.parametrize("override", [True, False])
@pytest.mark.parametrize("site", list(SITES))
def test_site_resolves_through_the_seam_and_is_counted(site, override,
                                                       monkeypatch):
    """Every call site asks `helper_for` once, so the seam's counters say
    which implementation a model got; the kernel agrees with the fallback."""
    name, drive, tol = SITES[site]
    with helpers_enabled_ctx(False):
        want = drive(monkeypatch)
    kernel, fallback = _counts(name)
    with helpers_enabled_ctx(override):
        got = drive(monkeypatch)
    assert _counts(name) == (kernel + override, fallback + (not override))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=tol,
                                   rtol=tol)


def _refused_by_activation(monkeypatch):
    from deeplearning4j_tpu import Activation
    layer, params, x = _lstm(gate_activation=Activation.HARDSIGMOID)
    return layer._scan(params, x, None)


def _refused_by_vmem(monkeypatch):
    from deeplearning4j_tpu.ops import lstm_scan_fused
    monkeypatch.setattr(lstm_scan_fused, "VMEM_BUDGET", 1)
    layer, params, x = _lstm()
    return layer._scan(params, x, None)


def _refused_by_width(monkeypatch):
    """Compiled for the chip the rule's kernels want whole lane tiles a head;
    interpreted they take any width."""
    from deeplearning4j_tpu.ops import helpers
    monkeypatch.setattr(helpers, "interpret_mode", lambda: False)
    return _site_gated_delta_net(monkeypatch)


# (the masked LSTM is tests/test_lstm_scan_fused.py::
# test_masked_sequences_keep_the_scan_path)
REFUSED = {
    "a gate activation the kernel does not have":
        ("graves_lstm_scan", _refused_by_activation),
    "a batch fits_vmem refuses": ("graves_lstm_scan", _refused_by_vmem),
    "a ring with a window": ("flash_attention", lambda m: _ring(window=5)),
    "a hyper-connected sequence that is no whole tile":
        ("hyper_connection", lambda m: _site_hyper_connection(m, t=96)),
    "a delta net whose heads are no whole lane tiles, compiled":
        ("gated_delta_rule", _refused_by_width),
    "a max pool whose windows reach past the next one's rows":
        ("max_pool_grad", lambda m: _site_max_pool(m, stride=1)),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_what_a_site_observes_wins_over_the_override_uncounted(case,
                                                               monkeypatch):
    """The override cannot force a kernel onto a call it cannot serve, and
    a decision that was never open is not counted as one."""
    name, drive = REFUSED[case]
    with helpers_enabled_ctx(False):
        want = drive(monkeypatch)
    before = _counts(name)
    with helpers_enabled_ctx(True):
        got = drive(monkeypatch)
    assert _counts(name) == before
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ----------------------------------------------------------- the graph walk
def _bottleneck_graph():
    """1x1 convolution -> batch norm (-> ReLU), three pairs, one of them a
    projected shortcut: the pattern the fused branch of the walk took."""
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.common.enums import (
        Activation, ConvolutionMode, LossFunction, WeightInit)
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        ConvolutionLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.conf.layers.feedforward import (
        ActivationLayer, OutputLayer)
    from deeplearning4j_tpu.nn.conf.layers.normalization import (
        BatchNormalization)
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    g = (NeuralNetConfiguration.Builder().seed(17).dtype("float64")
         .activation(Activation.IDENTITY).weight_init(WeightInit.XAVIER)
         .convolution_mode(ConvolutionMode.Truncate)
         .updater(Adam(learning_rate=1e-2)).graph_builder())
    conv = lambda **kw: ConvolutionLayer(n_out=8, kernel_size=(1, 1), **kw)
    (g.add_inputs("in")
      .add_layer("c1", conv(), "in")
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
    conf = g.build()
    S = jax.ShapeDtypeStruct
    return (ComputationGraph(conf).init(), S((6, 4, 8, 8), jnp.float64),
            S((6, 3), jnp.float64))


def _zoo_resnet50_at_64():
    from deeplearning4j_tpu.models.resnet50 import ResNet50
    S = jax.ShapeDtypeStruct
    net = ResNet50(num_labels=10, seed=42, input_shape=(3, 64, 64),
                   dtype="float64").init()
    return net, S((2, 3, 64, 64), jnp.float64), S((2, 10), jnp.float64)


@pytest.mark.parametrize("build", [_bottleneck_graph, _zoo_resnet50_at_64],
                         ids=["bottleneck block", "zoo ResNet50 at 64x64"])
def test_the_graph_walk_has_no_fork(build):
    """Vertex or layer, nothing else: the override changes nothing in a
    graph none of whose layers asks the seam, and no kernel is in it."""
    net, x, y = build()

    def traced(override):
        with helpers_enabled_ctx(override):
            text = net.lower_fit_batch(x, y).as_text()
        net._train_step_fn = None     # the next one traces anew
        return text

    on, off = traced(True), traced(False)
    assert on == off
    jaxpr = str(jax.make_jaxpr(
        lambda p: net._loss_fn(p, net.state_tree, jnp.zeros(x.shape, x.dtype),
                               jnp.zeros(y.shape, y.dtype), None, None,
                               jax.random.PRNGKey(0))[0])(net.params_tree))
    assert "pallas_call" not in jaxpr and "custom_call" not in on


# ------------------------------------------------------ LSTM._step's formula
def _gates_reference(gates, c, peepholes):
    """The cell update the two XLA gate functions held until PR 29 deleted
    them with their kernels (Graves 2013; LSTMHelpers.java:200), gate
    order [i|f|o|g]; without peepholes they are zeros of no effect."""
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    H = c.shape[-1]
    pi, pf, po = peepholes or (0.0, 0.0, 0.0)
    i = sig(gates[:, :H] + c * pi)
    f = sig(gates[:, H:2 * H] + c * pf)
    g = np.tanh(gates[:, 3 * H:])
    c_new = f * c + i * g
    o = sig(gates[:, 2 * H:3 * H] + c_new * po)
    return o * np.tanh(c_new), c_new


@pytest.mark.parametrize("cls_name", ["LSTM", "GravesLSTM"])
def test_lstm_step_is_the_one_formula(cls_name):
    layer, params, _ = _lstm(cls_name)
    r = np.random.RandomState(8)
    xw, h, c = r.randn(4, 20), r.randn(4, 5), r.randn(4, 5)
    peepholes = tuple(np.asarray(params[k]) for k in ("pi", "pf", "po")) \
        if layer.peephole else None
    assert layer.peephole == (cls_name == "GravesLSTM")
    want = _gates_reference(xw + h @ np.asarray(params["RW"]), c, peepholes)
    for fn in (layer._step, jax.jit(layer._step)):
        got = fn(params, jnp.asarray(xw), jnp.asarray(h), jnp.asarray(c))
        for g, w in zip(got, want):
            assert g.dtype == jnp.float64
            np.testing.assert_allclose(np.asarray(g), w, atol=1e-14, rtol=0)
