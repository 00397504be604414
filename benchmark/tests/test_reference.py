"""Each plain reference against the program at a tiny size, with the
program's compute type set to float32 so that the two must agree to rounding:
the first step's loss and gradient norms, the running statistics, and (for the
LSTM, whose three steps are steady) the change of the parameters. The zoo
ResNet50 under RmsProp(0.1) moves every weight by about 0.5 in its first step
and is chaotic from the second on, so its later steps are compared only on the
chip, at limits set from measured readings (PERF.md)."""
import jax
import pytest

from harness import compare, traffic


def _both(cell, batch_size, seed=5, steps=compare.PROOF_STEPS):
    cfg = dict(cell.config, compute_dtype=None)
    kw, kb = jax.random.split(traffic.key_from_seed(seed))
    batch = traffic.make_batch(cfg, {"batch": batch_size}, kb)
    params0 = cell.reference.init_params(cfg, kw)
    net = cell.adapter.build(cfg, params0, seed)
    probe = compare.ProgramProbe(cell.adapter, cfg, params0,
                                 cell.reference.init_state(cfg))
    for step in range(1, steps + 1):
        losses = net.fit_on_device(batch[0], batch[1], steps=1, vary_batch=True)
        probe.after_step(net, step, losses[0])
    ref = compare.follow_reference(cell.reference, cfg, params0, batch)
    return probe.readings, ref, cfg, params0, batch


def test_lstm_reference_agrees_with_the_program_in_float32(tiny_cell):
    cell = tiny_cell("tiny_textgen_lstm.device_loop")
    prog, ref, cfg, params0, batch = _both(cell, 8)
    found = compare.gaps(prog, ref)
    assert found["loss_gap"]["value"] < 1e-5, found
    assert found["grad_norm_gap"]["value"] < 1e-3, found
    assert found["update_norm_gap"]["value"] < 2e-3, found
    assert found["grad_diff_median"]["value"] < 1e-3, found
    # the control, the reference in int8, has to read far above that
    ctl = compare.follow_reference(cell.reference, cfg, params0, batch, mode="int8")
    worse = compare.gaps(ctl, ref)
    assert worse["grad_diff_median"]["value"] > 10 * found["grad_diff_median"]["value"]
    # and so has half of the batch left out
    half = compare.follow_reference(cell.reference, cfg, params0, batch, rows=4)
    assert compare.gaps(half, ref)["grad_norm_gap"]["value"] > 0.05


@pytest.mark.slow
def test_resnet50_reference_agrees_with_the_program_in_float32(tiny_cell):
    cell = tiny_cell("tiny_resnet50.device_loop")
    prog, ref, cfg, *_ = _both(cell, 4, steps=1)
    found = compare.gaps(prog, ref)
    assert found["loss1_gap"]["value"] < 1e-4, found
    assert found["grad_norm_gap_median"]["value"] < 1e-3, found
    assert found["grad_norm_gap"]["value"] < 1e-2, found
    assert found["state_norm_gap"]["value"] < 1e-3, found


def test_resnet50_parameter_count_and_shapes():
    import json
    import os
    from conftest import BENCH
    from harness.manifest import load_module
    cfg = json.load(open(os.path.join(BENCH, "configs", "resnet50_dl4j_zoo.json")))
    ref = load_module(os.path.join(BENCH, "configs", cfg["reference"]), "ref")
    total = 0
    for shape in ref.param_shapes(cfg).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == cfg["parameters"]
