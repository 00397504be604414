"""The Ouro-2.6B stage's cell rehearsed on the CPU at toy widths
(`tiny/BENCHMARK_ouro.json`, `tiny/configs/tiny_ouro.json`): `run.py` end to
end through `drivers/token_loop.py`, its fault and the int8 control, the
analytic count against XLA's and against a count by hand with every pass,
the configuration against the published keys, and the two new readers on a
hand-made trace. What the manifest must hold is asked by name, so that a
later PR's appended cell does not fail it."""
import json
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
from test_qwen3_next_cell import _unrolled      # `jax.lax` with its loops written out

CELL = "tiny_ouro.device_loop"
REAL = "ouro_2_6b.device_loop"
CONFIG = "ouro_2_6b_stage1of6"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
METRICS = os.path.join(BENCH, "metrics")
NEW_READERS = ("looped_blocks_ms_per_step", "exit_heads_ms_per_step")


@pytest.fixture
def cell():
    return Cell(CELL, data_dir=TINY,
                manifest_path=os.path.join(TINY, "BENCHMARK_ouro.json"))


def _real():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    return cfg, load_module(os.path.join(BENCH, "configs", cfg["reference"]), "ref")


def _execute(cell, seed=4000000019, trace=False):
    return bench_run.execute(cell, seed, 0.5, trace, time.perf_counter())


def test_traced_run_is_correct_and_reports_what_needs_no_device(cell, no_chip_check):
    out = _execute(cell, trace=True)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["notes"]["compiles_in_window"] == 0
    for row in out["compared"].values():
        assert row["value"] <= row["limit"]
    assert out["metrics"]["setup_trace_lower_s"]["value"] > 0
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in NEW_READERS + ("gated_attention_ms_per_step", "dense_mlp_ms_per_step"):
        assert name not in out["metrics"]


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


def _follow(cell, mode="f32", seed=5):
    kw, kb = jax.random.split(traffic.key_from_seed(seed))
    batch = token_loop.make_batch(cell.config, cell.traffic, kb)
    return batch, token_loop.follow_reference(
        cell.reference, cell.config, cell.reference.init_params(cell.config, kw),
        batch, mode=mode, loop_steps=2)


def test_the_int8_control_in_the_programs_place_is_not_correct(cell):
    batch, ref = _follow(cell)
    assert batch[0].dtype.kind == "i" and batch[0].shape == (1, 24)
    assert int(np.asarray(batch[0]).max()) < cell.config["vocab_size"]
    ok, rows = compare.judge(compare.gaps(_follow(cell, "int8")[1], ref), cell.limits)
    assert ok is False, rows
    ok, rows = compare.judge(compare.gaps(_follow(cell)[1], ref), cell.limits)
    assert ok is True, rows


def test_analytic_count_against_xla_on_the_reference(cell, monkeypatch):
    """XLA's count of the reference's loss and gradients at the toy size (its
    loops written out) holds every product once forward and twice backward,
    the recomputed passes once more and their sublayers once more again (the
    reference recomputes a pass, then a sublayer inside it: five products
    for three), the heads' once more (four for three), and the elementwise
    work (the softmax over all 24 keys where the count takes the causal
    half, the norms, the rotary part). So the analytic count is never above
    XLA's (a share of the peak computed from it is never too high), and
    XLA's is under twice it."""
    cfg, ref = dict(cell.config, note="loops written out"), cell.reference
    monkeypatch.setattr(ref, "lax", _unrolled(ref.lax))
    analytic = ref.train_flops_per_sample(cfg)
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((1, cfg["sequence_length"]), np.int32)
    xla = ref._grads.lower(ref._model_cfg(cfg), "f32", params, ids, ids) \
        .compile().cost_analysis()["flops"]
    assert analytic <= xla <= 2.0 * analytic, (analytic, xla)


def test_real_configuration_counts_against_a_count_by_hand():
    """The cut's parameters and operations by hand: a layer of 51.39 M, 8
    of them, table and head whole; every pass of every layer and every
    pass's head counted, nothing recomputed."""
    cfg, ref = _real()
    shapes = ref.param_shapes(cfg)
    size = lambda prefix: sum(int(np.prod(s)) for k, s in shapes.items()
                              if k.startswith(prefix))
    d, f, v = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert size("loop/l0_") == layer == 51_388_416
    assert size("embed/") == size("exit_head/W") == d * v == 100_663_296
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 8 * layer + 2 * d * v + d + d + 1 == 612_438_017
    assert 9.79e9 < 16 * total < 9.80e9                  # 9.80 GB at 16 bytes a parameter
    macs = ref.train_macs_per_token(cfg)
    passes, t = 4, 4096
    assert macs["mlp"] == passes * 8 * 3 * d * f
    assert macs["attention"] == passes * 8 * (4 * d * d + 16 * 2 * 128 * t / 2)
    assert macs["exit_heads"] == passes * (d * v + d)
    flops = ref.train_flops_per_sample(cfg)
    assert flops == 6.0 * sum(macs.values()) * t
    blocks = 6.0 * (macs["mlp"] + macs["attention"]) * t
    assert abs(6.0 * 8 * (4 * d * d + 3 * d * f) * t * passes / 1e12 - 40.4) < 0.05
    assert abs(6.0 * 8 * 16 * 2 * 128 * t / 2 * t * passes / 1e12 - 6.6) < 0.05
    assert abs(6.0 * macs["exit_heads"] * t / 1e12 - 9.9) < 0.05
    assert 56.5e12 < flops < 57.3e12                     # 57 TFLOP a sequence
    assert abs(blocks / flops - 0.83) < 0.01


def test_real_configuration_states_the_published_widths_and_the_cut():
    from deeplearning4j_tpu.models.ouro import PUBLISHED
    cfg, _ = _real()
    assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert {k: cfg["published"][k] for k in differs} == {k: PUBLISHED[k] for k in differs}
    assert cfg["num_hidden_layers"] == 8 and cfg["layer_types"] == PUBLISHED["layer_types"][:8]
    assert cfg["total_ut_steps"] == 4 and cfg["sequence_length"] == 4096
    assert cfg["entropy_weight"] == 0.05
    for key in ("deployment", "assumed"):
        assert cfg[key]
    assert any("0.05" in a for a in cfg["assumed"])
    with open(os.path.join(BENCH, "configs", cfg["reference"])) as f:
        assert "deeplearning4j_tpu" not in f.read().replace(
            "Imports nothing\nof `deeplearning4j_tpu`", "")


def _reader(name):
    return load_module(os.path.join(METRICS, name + ".py"), name)


def _hand_made_run(cell):
    """A stretch of 2 steps: 6 ms of the looped stack's attention (forward
    and, under the scan's transpose, backward), 5 of its MLP, 1 of a block's
    own norms, 0.5 of the final norm, 4 of the exit head, 3 of the table
    and 1.5 of the updater."""
    ms = 1_000_000
    loop = "jit(f)/while/body/dl4j.LoopedStack/loop/while/body/checkpoint/"
    back = "jit(f)/while/body/transpose(jvp(dl4j.LoopedStack/loop))/while/body/checkpoint/"
    names = {
        "fusion.1": loop + "dl4j.PreNormResidual/l0_attn/dl4j.GatedAttention/l0_attn/dot_general",
        "fusion.2": back + "dl4j.PreNormResidual/l0_attn/dl4j.GatedAttention/l0_attn/dot_general",
        "fusion.3": loop + "dl4j.PreNormResidual/l0_mlp/dl4j.GatedMLP/l0_mlp/dot_general",
        "fusion.4": loop + "dl4j.PreNormResidual/l0_mlp/rsqrt",
        "fusion.5": "jit(f)/while/body/dl4j.LoopedStack/loop/while/body/rsqrt",
        "fusion.6": "jit(f)/while/body/transpose(jvp(dl4j.loss))/dl4j.LoopExitHead/exit_head/while/dot",
        "fusion.7": "jit(f)/while/body/dl4j.TokenEmbedding/embed/gather",
        "fusion.8": "jit(f)/while/body/dl4j.updater/add",
    }
    spans = [("fusion.1", 2), ("fusion.2", 4), ("fusion.3", 5), ("fusion.4", 1),
             ("fusion.5", 0.5), ("fusion.6", 4), ("fusion.7", 3), ("fusion.8", 1.5)]
    events, at = [], 0
    for name, dur in spans:
        events.append((name, at, at + int(dur * ms)))
        at += int(dur * ms)
    trace = program_trace.ProgramTrace(
        lo=0, hi=at, steps=2, spans=[], modules=[("jit_dl4j_cg_device_loop", 0, at)],
        op_events=events, busy=[(0, at)])
    return types.SimpleNamespace(
        cell=cell, _program_trace=trace, _op_scopes=names, _program_counters=None,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        window=types.SimpleNamespace(steps_per_mark=2))


def test_the_new_readers_on_a_hand_made_trace(cell):
    run = _hand_made_run(cell)
    assert _reader("looped_blocks_ms_per_step").read(run) == pytest.approx(6.25)
    assert _reader("exit_heads_ms_per_step").read(run) == pytest.approx(2.0)
    # the nested scopes are the shared readers' as they are
    assert _reader("gated_attention_ms_per_step").read(run) == pytest.approx(3.0)
    assert _reader("dense_mlp_ms_per_step").read(run) == pytest.approx(2.5)
    assert _reader("updater_ms_per_step").read(run) == pytest.approx(0.75)


def test_the_new_readers_find_nothing_on_a_program_without_the_loop(cell):
    """As on the parent commit, or in another cell: no such scope, and no
    trace at all."""
    for name in NEW_READERS:
        run = _hand_made_run(cell)
        run._op_scopes = {k: "jit(f)/dl4j.DenseLayer/0/dot" for k in run._op_scopes}
        assert _reader(name).read(run) is None
        run = _hand_made_run(cell)
        run._program_trace = None
        assert _reader(name).read(run) is None


def test_the_manifest_holds_the_configuration_the_cell_and_their_metrics():
    """What `BENCHMARK.json` must hold of this PR, wherever later entries
    come to stand: the configuration, the cell on one chip with its traffic,
    the two new readers listing it, and its name in the lists of the
    whole-step metrics and of the attention's and the MLP's readers."""
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _real()[0]["source"]
    assert sorted(config["reduced"]) == sorted(_real()[0]["reduced"])
    entry = next(w for w in manifest["workloads"] if w["name"] == REAL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "token_loop_1x4096x8", 1)
    assert all(len(e["why"]) <= 200 for e in (config, entry))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == [REAL]
        assert (m["source"], m["moves"], m["layer"], m["unit"], m["better"]) == \
            ("device_trace", "train_samples_per_s", "model step", "ms", "lower")
    for name in ("step_mfu", "device_step_ms", "device_idle_share",
                 "compiles_in_window", "peak_hbm_gb", "setup_trace_lower_s",
                 "setup_compile_s", "host_dispatch_ms_per_step",
                 "other_programs_ms_per_step", "named_device_time_share",
                 "updater_ms_per_step", "idle_unattributed_share",
                 "loop_call_max_over_median", "host_full_gc_ms_per_step",
                 "gated_attention_ms_per_step", "dense_mlp_ms_per_step"):
        assert REAL in by_name[name]["workloads"], name
    # readers of layers this model has not
    for name in ("routed_experts_ms_per_step", "gated_delta_net_ms_per_step",
                 "lm_head_ms_per_step", "hyper_connection_ms_per_step"):
        assert REAL not in by_name[name]["workloads"], name
    cell = Cell(REAL)
    assert cell.traffic == dict(cell.traffic, driver="token_loop", batch=1,
                                steps_per_call=8, vary_batch=True)
    assert sorted(m["name"] for m in cell.end_to_end()) == \
        ["setup_s", "train_samples_per_s"]
    assert cell.limits and all(v > 0 or k == "loop_loss_repeats"
                               for k, v in cell.limits.items())
    for m in cell.per_layer():
        assert hasattr(cell.reader(m["name"]), "read")
