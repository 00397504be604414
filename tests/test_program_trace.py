"""The program's own names in a profile (ISSUE 25): `telemetry.span` is also
a `jax.profiler.TraceAnnotation`, the training path carries `dl4j.` spans with
the step they belong to, the compiled programs carry `dl4j.` scopes that
`telemetry.profiler.op_scopes` reads back, and JAX's compile events are
counters of the registry. One profile is recorded for the whole module (the
profiler takes seconds to start and stop)."""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (
    Activation, ComputationGraph, ConvolutionMode, DenseLayer, InputType,
    LossFunction, MultiLayerNetwork, NeuralNetConfiguration, OutputLayer,
    RnnOutputLayer, Sgd, WeightInit, telemetry)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers.convolutional import (
    ConvolutionLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.conf.layers.normalization import BatchNormalization
from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM
from deeplearning4j_tpu.telemetry import profiler

STEPS = 3


def conv_graph():
    g = (NeuralNetConfiguration.Builder().seed(17).dtype("float32")
         .activation(Activation.IDENTITY).weight_init(WeightInit.XAVIER)
         .convolution_mode(ConvolutionMode.Truncate)
         .updater(Sgd(learning_rate=0.05)).l2(1e-4).graph_builder())
    (g.add_inputs("in")
      .add_layer("c1", ConvolutionLayer(n_out=8, kernel_size=(3, 3)), "in")
      .add_layer("b1", BatchNormalization(activation=Activation.RELU), "c1")
      .add_layer("pool", SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
                 "b1")
      .add_layer("d1", DenseLayer(n_out=16, activation=Activation.TANH),
                 "pool")
      .add_layer("out", OutputLayer(n_out=3, loss_fn=LossFunction.MCXENT,
                                    activation=Activation.SOFTMAX), "d1")
      .set_outputs("out")
      .set_input_types(InputType.convolutional(8, 8, 4)))
    return ComputationGraph(g.build()).init()


def lstm_net():
    b = (NeuralNetConfiguration.Builder().seed(3)
         .weight_init(WeightInit.XAVIER).updater(Sgd(learning_rate=0.1))
         .dtype("float32").list())
    b.layer(GravesLSTM(n_out=5, activation=Activation.TANH))
    b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
    return MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(3)).build()).init()


def conv_batch(n=6):
    rng = np.random.RandomState(0)
    return (rng.rand(n, 4, 8, 8).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)])


Event = collections.namedtuple("Event", "thread name start end stats")


def host_events(path):
    """Every event of `/host:CPU`, with the index of its thread's line."""
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                out.append(Event(thread, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One profile on the CPU backend over: a bare span, a span with telemetry
    off, `fit(iterator)` of three batches and one `fit_on_device` call."""
    x, y = conv_batch()
    net = conv_graph()
    net.fit_batch(x, y)                       # compile outside the profile
    net.fit_on_device(x, y, steps=2)
    first_step = net._step
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with telemetry.span("unit.on", step=7, what="x"):
            pass
        telemetry.configure(enabled=False)
        try:
            with telemetry.span("unit.off", step=8):
                pass
        finally:
            telemetry.configure(enabled=True)
        net.fit(ListDataSetIterator([DataSet(x, y)] * STEPS))
        net.fit_on_device(x, y, steps=2)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return host_events(path), first_step


def test_span_is_in_the_profile_with_its_arguments(capture):
    events, _ = capture
    on = [e for e in events if e.name == "unit.on"]
    assert len(on) == 1
    assert on[0].stats["step"] == 7 and on[0].stats["what"] == "x"


def test_span_is_absent_with_telemetry_off(capture):
    events, _ = capture
    assert not [e for e in events if e.name == "unit.off"]
    assert telemetry.span("x") is not telemetry.NULL_SPAN   # switched back on


def test_fit_iterator_spans_share_the_step_across_two_threads(capture):
    events, first = capture
    fit = [e for e in events if e.name.startswith(("dl4j.fit.", "dl4j.fit_batch"))]
    produce = [e for e in events if e.name.startswith("dl4j.async.")]
    training, = {e.thread for e in fit}
    producer, = {e.thread for e in produce}
    assert training != producer
    for k in range(first, first + STEPS):
        names = sorted(e.name for e in events
                       if e.name.startswith("dl4j.") and e.stats.get("step") == k
                       and not e.name.startswith("dl4j.fit_on_device"))
        assert names == sorted([
            "dl4j.async.produce", "dl4j.async.stage", "dl4j.fit.next_batch",
            "dl4j.fit_batch", "dl4j.fit_batch.dispatch",
            "dl4j.fit_batch.listeners", "dl4j.fit_batch.prepare"]), (k, names)
    stage = [e for e in produce if e.name == "dl4j.async.stage"]
    x, y = conv_batch()
    assert all(e.stats["bytes"] == x.nbytes + y.nbytes for e in stage)


def test_children_lie_inside_their_parent(capture):
    events, first = capture
    for parent, children in (
            ("dl4j.fit_batch", ("prepare", "dispatch", "listeners")),
            ("dl4j.fit_on_device", ("prepare", "dispatch", "readback"))):
        outer = [e for e in events if e.name == parent]
        assert outer
        for o in outer:
            inner = [e for e in events if e.name.startswith(parent + ".")
                     and e.stats["step"] == o.stats["step"]]
            assert sorted(e.name for e in inner) == sorted(
                f"{parent}.{c}" for c in children)
            assert all(o.start <= e.start and e.end <= o.end
                       and e.thread == o.thread for e in inner)
    loop, = [e for e in events if e.name == "dl4j.fit_on_device"]
    assert loop.stats["step"] == first + STEPS and loop.stats["steps"] == 2


def _count_host_reads(monkeypatch, fn):
    """How often `fn` materialises a device array on the host."""
    from jax._src import array
    reads = []
    value = array.ArrayImpl._value
    with monkeypatch.context() as m:
        m.setattr(array.ArrayImpl, "_value", property(
            lambda self: (reads.append(1), value.fget(self))[1]))
        fn()
    return len(reads)


def test_training_spans_add_no_host_reads(monkeypatch):
    """The zero-added-syncs rule with the `fit` loop under it: the same host
    reads with the spans on as with telemetry off."""
    x, y = conv_batch()

    def train(enabled):
        telemetry.configure(enabled=enabled)
        try:
            net = conv_graph()

            def go():
                net.fit(ListDataSetIterator([DataSet(x, y)] * STEPS))
                net.fit_on_device(x, y, steps=2)
            return _count_host_reads(monkeypatch, go)
        finally:
            telemetry.configure(enabled=True)

    on, off = train(True), train(False)
    assert on == off == 2          # the loop's one readback: losses and flag


# ------------------------------------------------------------- op_scopes
def _lowered(kind, how):
    if kind == "conv_graph":
        net, (x, y) = conv_graph(), conv_batch()
    else:
        net = lstm_net()
        x, y = np.zeros((4, 3, 6), np.float32), np.zeros((4, 2, 6), np.float32)
    x, y = (jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in (x, y))
    if how == "device_loop":
        return net.lower_train_step(x, y, steps=3, vary_batch=True)
    return net.lower_fit_batch(x, y)


HEAVY = ("fusion(", "dot(", "convolution(", "custom-call(")


@pytest.mark.parametrize("how", ["device_loop", "fit_batch"])
@pytest.mark.parametrize("kind", ["conv_graph", "lstm_net"])
def test_op_scopes_name_the_compiled_programs_operations(kind, how):
    compiled = _lowered(kind, how).compile()
    text = compiled.as_text()
    assert f"jit_dl4j_{'cg' if kind == 'conv_graph' else 'mln'}_" \
        f"{'device_loop' if how == 'device_loop' else 'train_step'}" in text
    table = profiler.op_scopes(compiled)
    assert table == profiler.op_scopes(text)
    # every heavy instruction outside the fused computations that kept
    # metadata is in the table, and what a layer, the loss or the updater
    # made goes to its scope
    inside_fusion = False
    for line in text.splitlines():
        if not line.startswith(" "):
            inside_fusion = "fused_computation" in line
            continue
        if inside_fusion or 'op_name="' not in line \
                or not any(h in line for h in HEAVY):
            continue
        name = line.split(" = ")[0].replace("ROOT", "").strip().lstrip("%")
        op_name = line.split('op_name="')[1].split('"')[0]
        assert table[name] == op_name
        if "dl4j." in op_name:
            assert profiler.scope_phase(op_name)[0] is not None
    by = collections.defaultdict(set)
    for op_name in table.values():
        scope, phase = profiler.scope_phase(op_name)
        if scope is not None:
            by[scope.split("/")[0]].add(phase)
    layer = "dl4j.ConvolutionLayer" if kind == "conv_graph" else "dl4j.GravesLSTM"
    assert {"forward", "backward"} <= by[layer]
    assert by["dl4j.updater"] == {"update"}
    assert "forward" in by["dl4j.loss"]
    if kind == "conv_graph":
        assert {"forward", "backward"} <= by["dl4j.BatchNormalization"]
        assert "dl4j.regularization" in by


def test_scope_phase_reads_the_innermost_scope():
    sp = profiler.scope_phase
    assert sp("jit(f)/while/body/transpose(jvp(dl4j.BatchNormalization/bn2a))"
              "/reduce_sum") == ("dl4j.BatchNormalization/bn2a", "backward")
    assert sp("jit(f)/jvp(dl4j.GravesLSTM/0)/dl4j_lstm_scan_fwd/pallas_call") \
        == ("dl4j.GravesLSTM/0", "forward")
    assert sp("jit(f)/dl4j.updater/sub") == ("dl4j.updater", "update")
    assert sp("jit(f)/jvp(dl4j.OutputLayer/out)/jvp(dl4j.loss)/log") \
        == ("dl4j.loss", "forward")
    assert sp("jit(f)/while/body/roll") == (None, "forward")


# ------------------------------------------------------ compile counters
def test_compile_counters_count_a_fresh_program_once():
    reg = telemetry.registry()
    telemetry.count_compiles()              # idempotent: no second listener
    names = ("dl4j.compile.programs", "dl4j.compile.trace_s",
             "dl4j.compile.lower_s", "dl4j.compile.backend_s")
    read = lambda: {n: reg.counter(n).value for n in names}

    @jax.jit
    def fresh(a):
        return jnp.tanh(a) * 3.0 + 1.0

    a = jnp.ones((7, 5))                    # its own programs, before
    before = read()
    fresh(a).block_until_ready()
    first = read()
    assert first["dl4j.compile.programs"] == before["dl4j.compile.programs"] + 1
    for n in names[1:]:
        assert first[n] > before[n]
    fresh(a).block_until_ready()
    assert read() == first                   # nothing per call
    assert "dl4j_compile_programs" in reg.prometheus_text()
