"""The Xing4.0-29B-A4B share's cell rehearsed on the CPU at toy widths
(`tiny/BENCHMARK_xing4.json`, `tiny/configs/tiny_xing4.json`): `run.py` end
to end through `drivers/token_loop.py`, its faults and the int8 control, the
analytic counts against XLA's, and the new readers on a hand-made trace."""
import os
import time
import types

import jax
import numpy as np
import pytest

import run as bench_run
from conftest import TINY
from drivers import token_loop
from harness import compare, program_trace, traffic
from harness.manifest import Cell, load_module

CELL = "tiny_xing4.device_loop"
HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


@pytest.fixture
def cell():
    return Cell(CELL, data_dir=TINY,
                manifest_path=os.path.join(TINY, "BENCHMARK_xing4.json"))


def _execute(cell, seed=3000000019, trace=False):
    return bench_run.execute(cell, seed, 0.5, trace, time.perf_counter())


def test_traced_run_is_correct_and_reports_what_needs_no_device(cell, no_chip_check):
    out = _execute(cell, trace=True)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["notes"]["compiles_in_window"] == 0
    for row in out["compared"].values():
        assert row["value"] <= row["limit"]
    # no TPU plane in a CPU trace: the trace's readers return nothing; the
    # counters are there, the expert layers' gauge among them
    assert out["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["setup_trace_lower_s"]["value"] > 0
    assert "routed_experts_roofline" not in out["metrics"]
    assert "latent_attention_ms_per_step" not in out["metrics"]


def test_only_the_first_step_of_a_call_updating_is_not_correct(cell, no_chip_check,
                                                               monkeypatch):
    build = cell.adapter.build

    def broken(cfg, params, seed):
        net = build(cfg, params, seed)
        fit = net.fit_on_device
        net.fit_on_device = lambda x, y, steps, **kw: np.repeat(
            np.asarray(fit(x, y, steps=1, **kw)), steps)
        return net
    monkeypatch.setattr(cell.adapter, "build", broken)
    out = _execute(cell)
    assert out["correct"] is False
    assert out["compared"]["loop_loss_repeats"]["value"] >= 1
    assert out["compared"]["loss_gap"]["value"] <= out["compared"]["loss_gap"]["limit"]


def test_the_int8_control_in_the_programs_place_is_not_correct(cell):
    kw, kb = jax.random.split(traffic.key_from_seed(5))
    batch = token_loop.make_batch(cell.config, cell.traffic, kb)
    assert batch[0].dtype.kind == "i" and batch[0].shape == (2, 16)
    assert np.array_equal(np.asarray(batch[0])[:, 1:], np.asarray(batch[1])[:, :-1])

    def follow(mode):
        return token_loop.follow_reference(
            cell.reference, cell.config, cell.reference.init_params(cell.config, kw),
            batch, mode=mode, loop_steps=2)
    ref = follow("f32")
    ok, rows = compare.judge(compare.gaps(follow("int8"), ref), cell.limits)
    assert ok is False, rows
    ok, rows = compare.judge(compare.gaps(follow("f32"), ref), cell.limits)
    assert ok is True, rows


def test_analytic_counts_against_xla_on_the_reference(cell):
    """XLA's count of the reference's loss and gradients at the toy size holds
    every product once forward and twice backward, the recomputed blocks once
    more (the forward again: a third on top), every held expert's products for
    every token (the plain reference masks, it does not route) and the
    elementwise work. So: the analytic count with the routed share taken as
    'every token through every held expert', times 4/3, is never above XLA's
    and within 35% of it."""
    cfg, ref = cell.config, cell.reference
    macs = ref.train_macs_per_token(cfg)
    m = ref.dims(cfg)
    assert ref.routed_assignments_per_token(cfg) == 2 * 4 / 8
    one = 3.0 * m["d"] * m["expert"]
    dense_routing = 2 * (m["experts"] - ref.routed_assignments_per_token(cfg)) * one
    per_token = sum(macs.values()) + dense_routing
    analytic = 6.0 * per_token * cfg["sequence_length"] * 4.0 / 3.0
    assert abs(ref.train_flops_per_sample(cfg)
               - 6.0 * sum(macs.values()) * cfg["sequence_length"]) < 1.0
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((1, cfg["sequence_length"]), np.int32)
    xla = ref._grads.lower(ref._model_cfg(cfg), "f32", params, ids, ids) \
        .compile().cost_analysis()["flops"]
    assert analytic <= xla <= 1.35 * analytic, (analytic, xla)
    # the routed products' own counts: flops follow the assignments held
    assert ref.routed_products_flops_per_sample(cfg) == \
        6.0 * one * 1.0 * cfg["sequence_length"] * 2
    assert ref.routed_products_bytes_per_sample(cfg, 2) > 0


def test_real_configuration_counts_are_the_issues():
    import json
    bench = os.path.dirname(HERE)
    cfg = json.load(open(os.path.join(bench, "configs", "xing4_29b_a4b_share8.json")))
    ref = load_module(os.path.join(bench, "configs", cfg["reference"]), "ref")
    macs = ref.train_macs_per_token(cfg)
    total = sum(macs.values())
    assert 385e6 < total < 400e6, total           # the issue counts 393.4 M
    assert abs(macs["head"] / total - 0.30) < 0.01
    assert abs(ref.train_flops_per_sample(cfg) - 9.67e12) < 0.15e12
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == 789610308 + 5 * 64


def _reader(name):
    return load_module(os.path.join(METRICS, name + ".py"), name)


def _hand_made_run(cell):
    """A stretch of 2 steps: 8 ms under the routed scope, 2 under the shared
    expert, 3 of attention, 1 of the hyper-connection's own, 4 of the head."""
    ms = 1_000_000
    names = {
        "fusion.1": "jit(f)/while/body/dl4j.HyperConnection/b1_mlp/checkpoint/"
                    "dl4j.RoutedExperts/b1_mlp/routed/ragged_dot",
        "fusion.2": "jit(f)/while/body/transpose(jvp(dl4j.HyperConnection/b1_mlp))/"
                    "dl4j.RoutedExperts/b1_mlp/routed/gather",
        "fusion.3": "jit(f)/while/body/dl4j.HyperConnection/b1_mlp/"
                    "dl4j.RoutedExperts/b1_mlp/shared/dot_general",
        "fusion.4": "jit(f)/while/body/dl4j.HyperConnection/b0_attn/"
                    "dl4j.LatentAttention/b0_attn/dot_general",
        "fusion.5": "jit(f)/while/body/dl4j.HyperConnection/b0_attn/exp",
        "fusion.6": "jit(f)/while/body/dl4j.loss/dl4j.TokenCrossEntropyHead/lm_head/dot",
    }
    spans = [("fusion.1", 5), ("fusion.2", 3), ("fusion.3", 2), ("fusion.4", 3),
             ("fusion.5", 1), ("fusion.6", 4)]
    events, at = [], 0
    for name, dur in spans:
        events.append((name, at, at + dur * ms))
        at += dur * ms
    trace = program_trace.ProgramTrace(
        lo=0, hi=at, steps=2, spans=[], modules=[("jit_dl4j_cg_device_loop", 0, at)],
        op_events=events, busy=[(0, at)])
    return types.SimpleNamespace(
        cell=cell, _program_trace=trace, _op_scopes=names, _program_counters=None,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        window=types.SimpleNamespace(steps_per_mark=2))


def test_new_readers_on_a_hand_made_trace(cell):
    run = _hand_made_run(cell)
    assert _reader("routed_experts_ms_per_step").read(run) == pytest.approx(5.0)
    assert _reader("latent_attention_ms_per_step").read(run) == pytest.approx(1.5)
    assert _reader("hyper_connection_ms_per_step").read(run) == pytest.approx(0.5)
    assert _reader("lm_head_ms_per_step").read(run) == pytest.approx(2.0)
    cfg, ref = cell.config, cell.reference
    samples = 2 * cell.traffic["batch"]
    least = max(ref.routed_products_flops_per_sample(cfg) * samples / 197e12,
                ref.routed_products_bytes_per_sample(cfg, 4) * samples / 819e9)
    assert _reader("routed_experts_roofline").read(run) == \
        pytest.approx(100.0 * least / 8e-3)


def test_new_readers_find_nothing_on_a_program_without_the_layers(cell):
    """As on the parent commit: no such scope, no such gauge, no such count."""
    run = _hand_made_run(cell)
    run._op_scopes = {k: "jit(f)/dl4j.DenseLayer/0/dot" for k in run._op_scopes}
    run.cell = types.SimpleNamespace(config=cell.config, traffic=cell.traffic,
                                     reference=types.SimpleNamespace())
    for name in ("routed_experts_ms_per_step", "latent_attention_ms_per_step",
                 "hyper_connection_ms_per_step", "lm_head_ms_per_step"):
        assert _reader(name).read(run) == 0.0
    assert _reader("routed_experts_roofline").read(run) is None
    run.cell = cell
    assert _reader("routed_experts_roofline").read(run) is None
    from deeplearning4j_tpu import telemetry
    telemetry.registry().reset()
    for name in [n for n in telemetry.registry().snapshot()
                 if n.startswith("moe.expert_load")]:
        telemetry.registry()._metrics.pop(name)
    assert _reader("expert_load_max_over_mean").read(run) is None
