"""`metrics/assignments_held_share_gap.py`: the worst expert layer's distance
from the deployment's share of the assignments, on gauges set by hand and
after a real run of the tiny decoder's cell."""
import json
import os
import time
import types

import pytest

import run as bench_run
from conftest import ROOT, TINY
from harness.manifest import Cell, load_module

NAME = "assignments_held_share_gap"
PREFIX = "moe.assignments_held_share."
HERE = os.path.dirname(os.path.abspath(__file__))
READER = load_module(os.path.join(os.path.dirname(HERE), "metrics", NAME + ".py"), NAME)
ENTRY = {"name": NAME, "unit": "share", "better": "lower", "source": "program_counter",
         "layer": "model step", "moves": "train_samples_per_s"}


def _run(held=8, published=64):
    config = {"n_routed_experts": held}
    if published is not None:
        config["published"] = {"n_routed_experts": published}
    return types.SimpleNamespace(cell=types.SimpleNamespace(config=config))


@pytest.fixture
def gauges():
    from deeplearning4j_tpu import telemetry
    registry = telemetry.registry()

    def clear():
        for name in [n for n in registry.snapshot() if n.startswith(PREFIX)]:
            registry._metrics.pop(name)

    def set_(**shares):
        clear()
        for layer, share in shares.items():
            registry.gauge(PREFIX + layer).set(share)
    clear()
    yield set_
    clear()


@pytest.mark.parametrize("shares, gap", [
    # the resident sequence by the window's end: the MTP module's layer is the worst
    ({"b1_mlp": 0.157, "b2_mlp": 0.150, "b3_mlp": 0.189, "mtp_mlp": 0.441}, 0.316),
    # the stream of fresh ids: four layers emptied and one taking every token;
    # their mean, 0.0501, would read as nearer the share than the line above
    ({"b1_mlp": 0.00006, "b2_mlp": 0.00018, "b3_mlp": 0.00006, "mtp_mlp": 0.25}, 0.125),
    ({"b1_mlp": 0.00006, "b2_mlp": 0.00018}, 0.12494),
    ({"b1_mlp": 0.125, "b2_mlp": 0.127, "mtp_mlp": 0.119}, 0.006),
])
def test_reads_the_worst_layer_and_both_directions_as_worse(gauges, shares, gap):
    gauges(**shares)
    assert READER.read(_run()) == pytest.approx(gap, abs=1e-9)


def test_the_share_is_the_configurations_own(gauges):
    gauges(b1_mlp=0.5, b2_mlp=0.75)
    assert READER.read(_run(held=4, published=8)) == pytest.approx(0.25)
    assert READER.read(_run(held=8, published=64)) == pytest.approx(0.625)


@pytest.mark.parametrize("run", [_run(held=64, published=64), _run(published=None),
                                 _run(held=None)])
def test_a_configuration_that_holds_every_expert_has_nothing_to_read(gauges, run):
    gauges(b1_mlp=1.0)
    assert READER.read(run) is None


def test_no_gauge_no_value(gauges):
    gauges()
    assert READER.read(_run()) is None


def test_the_manifest_lists_it_for_the_decoders_cell_only():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == dict(ENTRY, workloads=["xing4_29b_a4b.device_loop"])
    for name in entry["workloads"]:
        cell = Cell(name)
        assert entry["moves"] in [m["name"] for m in cell.end_to_end()]
        assert cell.config["n_routed_experts"] < cell.config["published"]["n_routed_experts"]


def test_a_traced_run_of_the_tiny_cell_reports_it(tmp_path, no_chip_check, gauges):
    manifest = json.load(open(os.path.join(TINY, "BENCHMARK_xing4.json")))
    cells = [w["name"] for w in manifest["workloads"]]
    manifest["per_layer"].append(dict(ENTRY, workloads=cells))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    cell = Cell(cells[0], data_dir=TINY, manifest_path=str(path))
    out = bench_run.execute(cell, 3200000019, 0.5, True, time.perf_counter())
    assert out["correct"] is True, out["compared"]
    share = cell.config["n_routed_experts"] / cell.config["published"]["n_routed_experts"]
    value = out["metrics"][NAME]["value"]
    assert 0.0 < value <= max(share, 1.0 - share)
    assert out["metrics"][NAME]["unit"] == "share"
