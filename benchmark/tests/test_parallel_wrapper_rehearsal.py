"""Rehearsal of the four-chip cell's driver on four virtual CPU devices
(`XLA_FLAGS=--xla_force_host_platform_device_count=4`, set by conftest.py) at
a tiny size, so that four-chip time is not spent on a wrong mesh; and the
fault only that cell can have, the exchange between chips left out."""
import time

import jax
import pytest

import run as bench_run
from harness import compare, traffic

CELL = "tiny_textgen_lstm.parallel_wrapper_x4"


@pytest.fixture(autouse=True)
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")


def test_driver_runs_over_a_mesh_of_four_and_agrees_with_the_reference(
        tiny_cell, no_chip_check):
    cell = tiny_cell(CELL)
    out = bench_run.execute(cell, 21, 0.5, False, time.perf_counter())
    assert out["correct"] is True, out["compared"]
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert out["notes"]["compiles_in_window"] == 0


def test_exchange_left_out_is_not_correct(tiny_cell):
    cell = tiny_cell(CELL)
    cfg = cell.config
    kw, kb = jax.random.split(traffic.key_from_seed(22))
    batch = traffic.make_batch(cfg, cell.traffic, kb)
    params0 = cell.reference.init_params(cfg, kw)
    args = (cell.reference, cfg, params0, batch, 4,
            cell.traffic["gradients_threshold"])
    ref = compare.follow_reference_replicated(*args)
    alone = compare.follow_reference_replicated(*args, exchange=False)
    ok, rows = compare.judge(compare.gaps(alone, ref), cell.limits)
    assert ok is False, rows
    assert rows["update_norm_gap"]["value"] > 3 * rows["update_norm_gap"]["limit"]
