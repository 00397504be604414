"""The readers of what the program itself wrote into a profile
(`harness/program_trace.py` and the ten metrics of ISSUE 25): their arithmetic
on hand-made intervals, on the trace `tools/trace_probe_program.py` recorded
on a TPU v5 lite with the compiled text of its two train programs beside it,
and through `run.py` on the CPU, where the trace has no TPU plane."""
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from conftest import HERE, ROOT, TINY
from harness import manifest, program_trace
from harness.program_trace import ProgramTrace, Span

DATA = os.path.join(HERE, "data")
MS = 1_000_000


@pytest.fixture(scope="module")
def tiny_lstm_cell(tmp_path_factory):
    """The tiny LSTM cell under the tests' manifest with the per-layer
    entries the repository's BENCHMARK.json has beyond it, for the tiny cells."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    have = {m["name"] for m in tiny["per_layer"]}
    tiny["per_layer"] += [
        dict(m, workloads=["tiny_" + w for w in m["workloads"]])
        for m in real["per_layer"] if m["name"] not in have]
    path = str(tmp_path_factory.mktemp("manifest") / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(tiny, f)
    return lambda: manifest.Cell("tiny_textgen_lstm.device_loop",
                                 data_dir=TINY, manifest_path=path)


def reader(name):
    return manifest.load_module(
        os.path.join(manifest.HERE, "metrics", name + ".py"), name)


def fake_run(p, table=None, counters=None):
    """A run whose trace, scope table and counters are given, not read."""
    return types.SimpleNamespace(_program_trace=p, _op_scopes=table,
                                 _program_counters=counters, cell=None)


# ------------------------------------------------------------ hand-made
def hand_made():
    """Two steps of 100 ms. Device: a cast program of 4 ms, then the train
    program 10..90 with a convolution (30), a batch-norm backward (20), the
    updater (10) and an unnamed copy (10), 10 ms idle inside it."""
    spans, modules, ops = [], [], []
    for k in range(2):
        t = k * 100 * MS
        spans += [
            Span(0, "dl4j.fit.next_batch", t, t + 2 * MS),
            Span(0, "dl4j.fit_batch", t + 2 * MS, t + 96 * MS),
            Span(0, "dl4j.fit_batch.prepare", t + 2 * MS, t + 5 * MS),
            Span(0, "dl4j.fit_batch.dispatch", t + 5 * MS, t + 9 * MS),
            Span(0, "dl4j.fit_batch.listeners", t + 10 * MS, t + 95 * MS),
            Span(1, "dl4j.async.produce", t, t + 1 * MS),
            Span(1, "dl4j.async.stage", t + 1 * MS, t + 40 * MS),
            Span(1, "dl4j.async.put_wait", t + 40 * MS, t + 100 * MS)]
        modules += [("jit_convert_element_type(7)", t + 4 * MS, t + 8 * MS),
                    ("jit_dl4j_cg_train_step(9)", t + 10 * MS, t + 90 * MS)]
        ops += [("convert.1", t + 4 * MS, t + 8 * MS),
                ("fusion.1", t + 10 * MS, t + 40 * MS),
                ("fusion.2", t + 40 * MS, t + 60 * MS),
                ("fusion.3", t + 70 * MS, t + 80 * MS),
                ("copy.4", t + 80 * MS, t + 90 * MS)]
    program_trace.mark_leaves(spans)
    busy = program_trace.trace.union([(a, b) for _, a, b in ops])
    return ProgramTrace(lo=0, hi=200 * MS, steps=2, spans=spans,
                        modules=modules, op_events=ops, busy=busy)


TABLE = {
    "fusion.1": "jit(dl4j_cg_train_step)/jvp(dl4j.ConvolutionLayer/c1)/conv",
    "fusion.2": "jit(dl4j_cg_train_step)/transpose(jvp("
                "dl4j.BatchNormalization/b1))/reduce_sum",
    "fusion.3": "jit(dl4j_cg_train_step)/dl4j.updater/sub",
    "copy.4": "jit(dl4j_cg_train_step)/copy",
    # a name of the cast program's: never looked up outside the train program
    "convert.1": "jit(dl4j_cg_train_step)/dl4j.updater/convert",
}


def test_intersect_and_leaves():
    assert program_trace.intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) \
        == [(5, 10), (20, 25), (28, 30)]
    p = hand_made()
    leaf = {s.name: s.leaf for s in p.spans}
    assert leaf["dl4j.fit_batch"] is False
    assert all(leaf[n] for n in leaf if n != "dl4j.fit_batch")


def test_host_dispatch_is_the_step_less_the_listeners():
    # (94 - 85) ms a step
    assert reader("host_dispatch_ms_per_step").read(fake_run(hand_made())) \
        == pytest.approx(9.0)


def test_producer_share_counts_produce_and_stage_not_the_wait():
    assert reader("input_path_busy_share").read(fake_run(hand_made())) \
        == pytest.approx(40.0)


def test_other_programs_are_the_modules_that_are_not_the_train_program():
    assert reader("other_programs_ms_per_step").read(fake_run(hand_made())) \
        == pytest.approx(4.0)


def test_scope_times_and_the_unnamed_rest_sum_to_the_busy_time():
    p = hand_made()
    run = fake_run(p, TABLE)
    named = program_trace.by_scope(run)
    assert {k: v // MS for k, v in named.items()} == {
        ("dl4j.ConvolutionLayer/c1", "forward"): 60,
        ("dl4j.BatchNormalization/b1", "backward"): 40,
        ("dl4j.updater", "update"): 20, (None, "forward"): 20}
    assert reader("conv_ms_per_step").read(run) == pytest.approx(30.0)
    assert reader("batchnorm_ms_per_step").read(run) == pytest.approx(20.0)
    assert reader("updater_ms_per_step").read(run) == pytest.approx(10.0)
    share = reader("named_device_time_share").read(run)
    assert share == pytest.approx(100.0 * 120 / 148)
    # named + (the train program's unnamed + the other programs) = busy
    assert sum(named.values()) + p.other_programs_ns() == p.busy_ns


def test_idle_goes_to_the_training_threads_leaf_span_or_is_unattributed():
    p = hand_made()
    idle = {k: v // MS for k, v in p.idle_by_span().items()}
    # each step: 0-4 (2 next_batch, 2 prepare), 8-10 (1 dispatch, 1 between
    # the children), 60-70 listeners, 90-100 (5 listeners, 1 + 4 uncovered)
    assert idle == {"dl4j.fit.next_batch": 4, "dl4j.fit_batch.prepare": 4,
                    "dl4j.fit_batch.dispatch": 2,
                    "dl4j.fit_batch.listeners": 30, "unattributed": 12}
    assert sum(idle.values()) * MS == p.stretch_ns - p.busy_ns
    assert reader("idle_unattributed_share").read(fake_run(p)) \
        == pytest.approx(6.0)


NEW_TRACE_READERS = [
    "host_dispatch_ms_per_step", "input_path_busy_share",
    "other_programs_ms_per_step", "named_device_time_share",
    "updater_ms_per_step", "batchnorm_ms_per_step", "conv_ms_per_step",
    "idle_unattributed_share"]


@pytest.mark.parametrize("name", NEW_TRACE_READERS)
def test_readers_give_nothing_without_a_device_trace(name):
    assert reader(name).read(fake_run(None)) is None


@pytest.mark.parametrize("name", NEW_TRACE_READERS[:3] + NEW_TRACE_READERS[-1:])
def test_readers_give_nothing_for_a_program_without_spans_or_names(name):
    """The parent of the PR that added them: a device trace, none of the
    program's spans in it and programs called `jit_run`."""
    p = hand_made()
    bare = ProgramTrace(lo=p.lo, hi=p.hi, steps=p.steps, spans=[],
                        modules=[("jit_run(3)", a, b) for _, a, b in p.modules],
                        op_events=p.op_events, busy=p.busy)
    assert reader(name).read(fake_run(bare)) is None


# ------------------------------------------------- the recorded trace
# `tools/trace_probe_program.py` on a TPU v5 lite (my chip run, PR 25): six
# steps of `fit(iterator)` under `bench.listener`, then four calls of
# `fit_on_device(steps=3, vary_batch=True)` under `bench.fit_call`, of a tiny
# convolution / batch norm / max-pool / dense graph at batch 64.
RECORDED = os.path.join(DATA, "program_tpu_v5e.xplane.pb")


def recorded(what):
    marks, per_mark = {"fit_batch": ("bench.listener", 1),
                       "device_loop": ("bench.fit_call", 3)}[what]
    from deeplearning4j_tpu.telemetry import profiler
    with open(os.path.join(DATA, f"program_tpu_v5e.{what}.txt")) as f:
        table = profiler.op_scopes(f.read())
    return program_trace.read(RECORDED, marks, per_mark), table


def test_recorded_fit_iterator_stretch_has_both_threads_and_the_program():
    p, _ = recorded("fit_batch")
    assert (p.steps, p.stretch_ns, p.busy_ns) == (5, 13044769, 331804)
    training, = p.threads_of("dl4j.fit")
    producer, = p.threads_of("dl4j.async.")
    assert training != producer
    assert {m[0].split("(")[0] for m in p.modules} == {
        "jit_dl4j_cg_train_step", "jit__threefry_split", "jit__unstack",
        "jit_convert_element_type"}
    assert len(p.train_modules()) == 5
    run = fake_run(p)
    assert reader("host_dispatch_ms_per_step").read(run) \
        == pytest.approx(1.9464656, rel=1e-9)
    assert reader("input_path_busy_share").read(run) \
        == pytest.approx(15.05921645680349, rel=1e-9)
    # an rng split, an unstack and a cast a step: 3.2 us
    assert reader("other_programs_ms_per_step").read(run) \
        == pytest.approx(0.0031706, rel=1e-9)
    # the tiny step leaves the chip idle 97% of the time; a tenth of the
    # stretch lies between the program's spans (the iterator's own code)
    idle = p.idle_by_span()
    # gaps as `harness/trace.py` counts them: a seam under 2 us is no gap
    gaps = program_trace.trace.gaps_of(p.busy, p.lo, p.hi)
    assert sum(idle.values()) == program_trace.trace.total(gaps) == 12699774
    assert p.stretch_ns - p.busy_ns - 12699774 == 13191
    assert max(idle, key=idle.get) == "dl4j.fit_batch.prepare"
    assert reader("idle_unattributed_share").read(run) \
        == pytest.approx(9.88620036123292, rel=1e-9)


@pytest.mark.parametrize("what, share, conv, batchnorm, updater", [
    ("fit_batch", 92.09774445154368, 0.0265254, 0.014139, 0.0005004),
    ("device_loop", 81.11349263989513, 0.02031122222222222,
     0.014016777777777779, 0.00016888888888888889)])
def test_recorded_scopes_and_the_unnamed_rest_sum_to_the_busy_time(
        what, share, conv, batchnorm, updater):
    p, table = recorded(what)
    run = fake_run(p, table)
    named = program_trace.by_scope(run)
    in_program = sum(named.values())
    # every operation is in the train program or in another one (to a
    # nanosecond of rounding at a program's edge) ...
    assert abs(in_program + p.other_programs_ns()
               - sum(b - a for _, a, b in p.op_events)) <= len(p.modules)
    # ... and what the scan's `while` spans beside its body's operations is
    # busy time too: the unnamed rest is the busy time less the named
    named_ns = sum(v for (scope, _), v in named.items() if scope is not None)
    assert 0 <= p.busy_ns - in_program - p.other_programs_ns() < 0.05 * p.busy_ns
    assert reader("named_device_time_share").read(run) \
        == pytest.approx(100.0 * named_ns / p.busy_ns) == pytest.approx(share)
    assert reader("conv_ms_per_step").read(run) == pytest.approx(conv)
    assert reader("batchnorm_ms_per_step").read(run) == pytest.approx(batchnorm)
    assert reader("updater_ms_per_step").read(run) == pytest.approx(updater)
    phases = {scope.split("/")[0]: set() for scope, _ in named if scope}
    for scope, phase in named:
        if scope:
            phases[scope.split("/")[0]].add(phase)
    assert phases["dl4j.ConvolutionLayer"] == {"forward", "backward"}
    assert phases["dl4j.updater"] == {"update"}


def test_recorded_loop_stretch_splits_its_idle_time_by_the_calls_spans():
    p, _ = recorded("device_loop")
    assert (p.steps, p.stretch_ns, p.busy_ns) == (9, 10512459, 620983)
    assert not p.threads_of("dl4j.async.")
    assert reader("input_path_busy_share").read(fake_run(p)) is None
    assert p.idle_by_span() == {
        "dl4j.fit_on_device.prepare": 4566358,
        "dl4j.fit_on_device.dispatch": 1821601,
        "dl4j.fit_on_device.readback": 3049898, "unattributed": 450029}
    assert reader("host_dispatch_ms_per_step").read(fake_run(p)) \
        == pytest.approx(0.8023622222222222, rel=1e-9)


def test_a_cpu_trace_gives_nothing(tmp_path):
    """No TPU plane: `read` returns None and so does every reader."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.fit_call"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    import glob
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert program_trace.read(path, "bench.fit_call", 1) is None


# ------------------------------------------------------- compile counters
def test_setup_counters_are_taken_before_the_helper_compiles(monkeypatch):
    run = types.SimpleNamespace(cell=None, _program_trace=hand_made())

    def compiles(cell):
        jax.jit(lambda a: jnp.cos(a) * 5.0 - 2.0)(jnp.ones((3, 11)))
        return TABLE
    monkeypatch.setattr(program_trace, "scope_table", compiles)
    before = program_trace.compile_counters()
    assert program_trace.scopes(run) is TABLE
    after = program_trace.compile_counters()
    assert after["programs"] > before["programs"]
    assert after["trace_s"] > before["trace_s"]
    assert reader("setup_trace_lower_s").read(run) \
        == before["trace_s"] + before["lower_s"]
    assert reader("setup_compile_s").read(run) == before["backend_s"]


def test_setup_counters_are_nothing_for_a_program_without_them(monkeypatch):
    monkeypatch.setattr(program_trace, "compile_counters", lambda: None)
    run = types.SimpleNamespace()
    assert reader("setup_trace_lower_s").read(run) is None
    assert reader("setup_compile_s").read(run) is None


# ------------------------------------------------ the table, from a cell
@pytest.mark.parametrize("driver", ["device_loop", "fit_iterator"])
def test_scope_table_of_the_windows_program_from_the_cells_shapes(
        tiny_lstm_cell, driver):
    from deeplearning4j_tpu.telemetry import profiler
    cell = tiny_lstm_cell()
    if driver == "fit_iterator":
        cell.traffic = {"driver": "fit_iterator", "batch": 8}
    table = program_trace.scope_table(cell)
    found = {profiler.scope_phase(v) for v in table.values()}
    assert {("dl4j.GravesLSTM/0", "forward"), ("dl4j.GravesLSTM/1", "backward"),
            ("dl4j.updater", "update"), ("dl4j.loss", "forward")} <= found


def test_traced_run_on_the_cpu_reports_the_counters_and_no_trace_metric(
        tiny_lstm_cell, no_chip_check):
    cell = tiny_lstm_cell()
    assert {m["name"] for m in cell.per_layer()} >= {
        "setup_trace_lower_s", "named_device_time_share", "updater_ms_per_step"}
    out = bench_run.execute(cell, 4000000007, 0.5, True, time.perf_counter())
    assert out["correct"] is True
    assert out["metrics"]["setup_trace_lower_s"]["value"] > 0
    assert out["metrics"]["setup_compile_s"]["value"] > 0
    assert not set(out["metrics"]) & set(NEW_TRACE_READERS)
