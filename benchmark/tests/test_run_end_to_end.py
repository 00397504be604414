"""`run.py` end to end on the CPU at a tiny size, the chip check lifted here
in the test, and the same run with the timed path broken underneath: each
fault a training cell can have has to turn `correct` false."""
import time

import jax
import numpy as np
import pytest

import run as bench_run
from harness import compare

CELL = "tiny_textgen_lstm.device_loop"


def _execute(cell, seed=11, trace=False):
    return bench_run.execute(cell, seed, 0.5, trace, time.perf_counter())


def test_run_reports_the_contract_keys_and_is_correct(tiny_cell, no_chip_check):
    out = _execute(tiny_cell(CELL))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert out["notes"]["compiles_in_window"] == 0
    for row in out["compared"].values():
        assert row["value"] <= row["limit"]


def test_traced_run_reports_per_layer_metrics_that_need_no_device(tiny_cell,
                                                                  no_chip_check):
    out = _execute(tiny_cell(CELL), trace=True)
    # no TPU plane in a CPU trace: the trace's readers return nothing and are
    # left out of the line; the counters are there
    assert "compiles_in_window" in out["metrics"]
    assert "device_idle_share" not in out["metrics"]
    assert "lstm_scan_roofline" not in out["metrics"]


def test_off_the_chip_there_is_no_result(tiny_cell, capsys):
    with pytest.raises(SystemExit) as e:
        _execute(tiny_cell(CELL))
    assert e.value.code != 0
    assert "train_samples_per_s" not in capsys.readouterr().out


def _break(monkeypatch, cell, wrap):
    build = cell.adapter.build

    def broken(cfg, params, seed):
        net = build(cfg, params, seed)
        net.fit_on_device = wrap(net, net.fit_on_device)
        return net
    monkeypatch.setattr(cell.adapter, "build", broken)


def _passes(out, *names):
    return all(out["compared"][n]["value"] <= out["compared"][n]["limit"]
               for n in names)


def _state_unchanged(net, fit):
    def unchanged(x, y, **kw):
        keep = jax.tree_util.tree_map(lambda a: a.copy(), net.params_tree)
        losses = fit(x, y, **kw)
        net.params_tree = keep
        return losses
    return unchanged


def _only_first_step_of_a_call_updates(net, fit):
    """Breaks steps 2..n of the window's own program and nothing else: a call
    of n steps makes its first and returns that loss n times; the proof
    steps, one step a call, are sound."""
    def first_only(x, y, steps, **kw):
        return np.repeat(np.asarray(fit(x, y, steps=1, **kw)), steps)
    return first_only


def _long_call_leaves_state_unchanged(net, fit):
    """The window's own program returns its state unchanged; the proof
    steps, one step a call, are sound."""
    whole = _state_unchanged(net, fit)
    return lambda x, y, steps, **kw: (whole if steps > 1 else fit)(
        x, y, steps=steps, **kw)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_cell, no_chip_check, monkeypatch):
    cell = tiny_cell(CELL)
    _break(monkeypatch, cell, _state_unchanged)
    out = _execute(cell)
    assert out["correct"] is False
    assert out["compared"]["update_norm_gap"]["value"] > 0.99


@pytest.mark.parametrize("fault", [_only_first_step_of_a_call_updates,
                                   _long_call_leaves_state_unchanged])
def test_a_fault_in_the_windows_own_program_alone_is_not_correct(
        tiny_cell, no_chip_check, monkeypatch, fault):
    """The proof steps run a program of one step; the window drives one of n.
    A fault that lives only in steps 2..n of that program has to be seen in
    the readings of its first call."""
    cell = tiny_cell(CELL)
    _break(monkeypatch, cell, fault)
    out = _execute(cell)
    assert out["correct"] is False
    assert _passes(out, "loss_gap", "grad_norm_gap", "update_norm_gap",
                   "grad_diff_median"), "the proof steps were to stay sound"
    if fault is _only_first_step_of_a_call_updates:
        assert out["compared"]["loop_loss_repeats"]["value"] >= 1
    else:
        assert out["compared"]["loop_move_gap_median"]["value"] > 0.9


def test_half_of_the_batch_left_out_is_not_correct(tiny_cell, no_chip_check,
                                                   monkeypatch):
    cell = tiny_cell(CELL)

    def wrap(net, fit):
        return lambda x, y, **kw: fit(x[:x.shape[0] // 2], y[:y.shape[0] // 2], **kw)
    _break(monkeypatch, cell, wrap)
    out = _execute(cell)
    assert out["correct"] is False


def _control_in_the_programs_place(cell, seed):
    """(verdict and rows of the int8 control, of the reference itself), each
    judged by the cell's limits against the float32 reference."""
    from harness import traffic
    kw, kb = jax.random.split(traffic.key_from_seed(seed))
    batch = traffic.make_batch(cell.config, cell.traffic, kb)
    params0 = cell.reference.init_params(cell.config, kw)
    loop = int(cell.traffic["steps_per_call"])

    def follow(mode):
        return compare.follow_reference(cell.reference, cell.config, params0,
                                        batch, mode=mode, loop_steps=loop)
    ref = follow("f32")
    return (compare.judge(compare.gaps(follow("int8"), ref), cell.limits),
            compare.judge(compare.gaps(follow("f32"), ref), cell.limits))


def test_the_int8_control_in_the_programs_place_is_not_correct(tiny_cell):
    (ok, rows), (ok_ref, rows_ref) = _control_in_the_programs_place(
        tiny_cell(CELL), 3)
    assert ok is False, rows
    assert rows["grad_diff_median"]["value"] > rows["grad_diff_median"]["limit"]
    assert ok_ref is True, rows_ref      # the reference in its own place passes


@pytest.mark.slow
def test_resnet50_cell_is_correct_and_its_faults_and_control_are_not(
        tiny_cell, no_chip_check, monkeypatch):
    """The second configuration through the same harness (minutes on a CPU:
    it compiles the zoo ResNet50 several times)."""
    cell = tiny_cell("tiny_resnet50.device_loop")
    out = _execute(cell)
    assert out["correct"] is True, out["compared"]
    assert set(out["compared"]) == {
        "grad_norm_gap", "grad_norm_gap_median", "dead_grad_noise",
        "update_norm_gap", "state_norm_gap", "loop_move_gap_median",
        "loop_loss_repeats"}

    with monkeypatch.context() as m:
        _break(m, cell, _state_unchanged)
        out = _execute(cell)
    assert out["correct"] is False
    assert out["compared"]["update_norm_gap"]["value"] > 0.99

    with monkeypatch.context() as m:
        _break(m, cell, _long_call_leaves_state_unchanged)
        out = _execute(cell)
    assert out["correct"] is False
    assert _passes(out, "grad_norm_gap", "update_norm_gap", "state_norm_gap")
    assert out["compared"]["loop_move_gap_median"]["value"] > 0.9

    with monkeypatch.context() as m:
        _break(m, cell, _only_first_step_of_a_call_updates)
        out = _execute(cell)
    assert out["correct"] is False
    assert out["compared"]["loop_loss_repeats"]["value"] >= 1

    (ok, rows), _ = _control_in_the_programs_place(cell, 3)
    assert ok is False, rows
    assert rows["dead_grad_noise"]["value"] > rows["dead_grad_noise"]["limit"]
