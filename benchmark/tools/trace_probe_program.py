"""Records a small trace of the program itself on the device it runs on: a
tiny `ComputationGraph` (convolution, batch norm, max-pool, dense, softmax)
trained by `fit(iterator)` under the benchmark's `bench.listener` marks and
then by `fit_on_device` under `bench.fit_call` marks, with the program's own
`dl4j.*` spans, its `jit_dl4j_*` programs and its named scopes in it. Run by
hand through the chip tool; what it writes is what `tests/` keeps as
`data/program_tpu_v5e.*`: the trace, and beside it the compiled text of the
two train programs, lowered again from shapes as `harness/program_trace.py`
does for a cell. Writes under chiprun_out/trace_probe_program/.

    python3 benchmark/tools/trace_probe_program.py
"""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

BATCH, FIT_STEPS, LOOP_CALLS, LOOP_STEPS = 64, 6, 4, 3


def tiny_graph():
    from deeplearning4j_tpu import (
        Activation, ComputationGraph, ConvolutionMode, DenseLayer, InputType,
        LossFunction, NeuralNetConfiguration, OutputLayer, RmsProp, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.convolutional import (
        ConvolutionLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.conf.layers.normalization import (
        BatchNormalization)
    g = (NeuralNetConfiguration.Builder().seed(7).dtype("float32")
         .activation(Activation.IDENTITY).weight_init(WeightInit.XAVIER)
         .convolution_mode(ConvolutionMode.Truncate)
         .updater(RmsProp(learning_rate=0.01)).l2(1e-4).graph_builder())
    (g.add_inputs("in")
      .add_layer("conv1", ConvolutionLayer(n_out=16, kernel_size=(3, 3)), "in")
      .add_layer("bn1", BatchNormalization(activation=Activation.RELU), "conv1")
      .add_layer("pool1", SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
                 "bn1")
      .add_layer("fc1", DenseLayer(n_out=32, activation=Activation.TANH),
                 "pool1")
      .add_layer("out", OutputLayer(n_out=10, loss_fn=LossFunction.MCXENT,
                                    activation=Activation.SOFTMAX), "fc1")
      .set_outputs("out")
      .set_input_types(InputType.convolutional(32, 32, 8)))
    return ComputationGraph(g.build()).init()


def batch():
    import numpy as np
    rng = np.random.RandomState(0)
    x = rng.rand(BATCH, 8, 32, 32).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, BATCH)]
    return x, y


class Listener:
    """The benchmark's listener in small: waits for the loss of the step
    before under `bench.listener`."""

    def __init__(self):
        self.prev = None

    def iteration_done(self, model, iteration):
        import jax
        with jax.profiler.TraceAnnotation("bench.listener"):
            prev, self.prev = self.prev, model._score
            if prev is not None:
                float(prev)


def main():
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.telemetry import profiler
    from harness import program_trace

    out = os.path.join(ROOT, "chiprun_out", "trace_probe_program")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    x, y = batch()
    net = tiny_graph()
    net.set_listeners(Listener())
    loop = lambda: net.fit_on_device(x, y, steps=LOOP_STEPS, vary_batch=True)
    net.fit(ListDataSetIterator([DataSet(x, y)] * 2))     # compile both,
    loop()                                                # outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    net.fit(ListDataSetIterator([DataSet(x, y)] * FIT_STEPS))
    time.sleep(0.002)
    for _ in range(LOOP_CALLS):
        with jax.profiler.TraceAnnotation("bench.fit_call"):
            loop()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(out, "program.xplane.pb"))
    print("trace", path, os.path.getsize(path), "bytes")

    shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, y))
    fresh = tiny_graph()
    texts = {"fit_batch": fresh.lower_fit_batch(*shapes).compile().as_text(),
             "device_loop": fresh.lower_train_step(
                 *shapes, steps=LOOP_STEPS, vary_batch=True).compile().as_text()}
    summary = {"device": jax.devices()[0].device_kind}
    for what, text in texts.items():
        with open(os.path.join(out, f"program.{what}.txt"), "w") as f:
            f.write(text)
        marks, per_mark = (("bench.listener", 1) if what == "fit_batch"
                           else ("bench.fit_call", LOOP_STEPS))
        p = program_trace.read(path, marks, per_mark)
        if p is None:
            summary[what] = None            # no TPU plane: a CPU rehearsal
            continue
        named = p.device_ns_by_scope(profiler.op_scopes(text),
                                     profiler.scope_phase)
        summary[what] = {
            "text_bytes": len(text), "steps": p.steps,
            "stretch_ns": p.stretch_ns, "busy_ns": p.busy_ns,
            "modules": sorted({m[0] for m in p.modules}),
            "spans": sorted({s.name for s in p.spans}),
            "idle_by_span": p.idle_by_span(),
            "by_scope": sorted(([str(k[0]), k[1], v] for k, v in named.items()),
                               key=lambda r: -r[2])}
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary)[:6000])


if __name__ == "__main__":
    main()
