"""`loop_call_max_over_median` and `host_full_gc_ms_per_step` read the
program's own ring over the whole window: on a real tiny `fit_on_device`
window, on the same window with one call stalled by a full collection, on
hand-made calls of the wrapper, and nothing from a program whose tracer
cannot be read back."""
import gc
import time
import types

import pytest

import run as bench_run
from deeplearning4j_tpu import telemetry
from drivers.common import Window
from harness import program_ring, traffic
from harness.tracer import Tracer
from test_program_readers import reader

CELL = "tiny_textgen_lstm.device_loop"
SEED, SECONDS = 11, 0.5


def _window(cell, stall_call=None, stall_s=0.3):
    """A real window of a tiny cell; with `stall_call`, that call of
    the window runs a full collection and sleeps inside its
    `dl4j.fit_on_device` span, after the readback."""
    run = bench_run.Run(cell, SEED, SECONDS, False)
    run.key = traffic.key_from_seed(SEED)
    run.tracer = Tracer(False, "", SECONDS)
    run.prepared = cell.driver.prepare(run)
    if stall_call is not None:
        net, calls = run.prepared.net, []
        resolve = net._resolve_divergence

        def stalled(div):
            calls.append(div)
            if len(calls) == stall_call:
                gc.collect()
                time.sleep(stall_s)
            return resolve(div)
        net._resolve_divergence = stalled
    run.window = cell.driver.window(run, run.prepared, SECONDS)
    return run


@pytest.mark.parametrize("cell", [CELL,
                                  "tiny_textgen_lstm.parallel_wrapper_x4"])
def test_a_steady_window_reads_its_calls_and_collections(tiny_cell, cell):
    run = _window(tiny_cell(cell))
    calls = program_ring.loop_calls(run)
    assert len(calls) * 2 == run.window.attempted     # 2 steps a call
    assert all(run.window.t0 <= s < e <= run.window.t1 for s, e in calls)
    assert reader("loop_call_max_over_median").read(run) >= 1.0
    assert reader("host_full_gc_ms_per_step").read(run) >= 0.0


def test_a_stalled_call_reads_as_one_long_call_holding_a_full_collection(
        tiny_cell):
    run = _window(tiny_cell(CELL), stall_call=3)
    calls = program_ring.loop_calls(run)
    longest = max(calls, key=lambda c: c[1] - c[0])
    assert longest == calls[2]
    assert reader("loop_call_max_over_median").read(run) > 2.0
    full = program_ring.window_spans(run, "dl4j.host.gc")
    assert any(longest[0] <= s < e <= longest[1] for _, s, e, _, _ in full)
    assert reader("host_full_gc_ms_per_step").read(run) \
        >= sum(e - s for _, s, e, _, _ in full) * 1e3 / run.window.steps > 0


class _Ring:
    """A tracer whose ring holds the given spans."""

    def __init__(self, spans):
        self._spans = spans

    def spans(self, prefix, t0=None, t1=None):
        return [s for s in self._spans if s[0].startswith(prefix)
                and t0 <= s[1] <= t1]


def _fake_run(steps=80):
    return types.SimpleNamespace(window=Window(
        t0=0.0, t1=100.0, steps=steps, samples=steps, attempted=steps,
        failed=0))


def test_the_wrappers_call_is_the_outer_one_and_other_threads_do_not_count(
        monkeypatch):
    """Four calls of the wrapper, the third 1.5 times the others, a full
    collection of 0.2 s in it; a net's call nested in each, and two calls
    on another thread, are not the window's calls."""
    spans = []
    for k, dur in enumerate((2.0, 2.0, 3.0, 2.0)):
        t = 10.0 + 5 * k
        spans += [("dl4j.pw.fit_on_device", t, t + dur, 1, {}),
                  ("dl4j.fit_on_device", t + 0.1, t + 0.2, 1, {})]
        if k < 2:
            spans.append(("dl4j.fit_on_device", t, t + 4.5, 2, {}))
    spans.append(("dl4j.host.gc", 21.0, 21.2, 1, {"generation": 2}))
    spans.append(("dl4j.host.gc", 101.0, 102.0, 1, {"generation": 2}))
    monkeypatch.setattr(telemetry, "tracer", lambda: _Ring(spans))
    run = _fake_run()
    assert program_ring.loop_calls(run) == [(10.0 + 5 * k, 10.0 + 5 * k + d)
                                            for k, d in enumerate(
                                                (2.0, 2.0, 3.0, 2.0))]
    assert reader("loop_call_max_over_median").read(run) \
        == pytest.approx(1.5)
    assert reader("host_full_gc_ms_per_step").read(run) \
        == pytest.approx(200.0 / 80)


def test_fewer_than_three_calls_read_nothing(monkeypatch):
    spans = [("dl4j.fit_on_device", 1.0, 2.0, 1, {}),
             ("dl4j.fit_on_device", 3.0, 9.0, 1, {})]
    monkeypatch.setattr(telemetry, "tracer", lambda: _Ring(spans))
    assert reader("loop_call_max_over_median").read(_fake_run()) is None
    assert reader("host_full_gc_ms_per_step").read(_fake_run()) == 0.0


def test_nothing_from_a_program_whose_ring_cannot_be_read(monkeypatch):
    monkeypatch.setattr(telemetry, "tracer", lambda: types.SimpleNamespace())
    for name in ("loop_call_max_over_median", "host_full_gc_ms_per_step"):
        assert reader(name).read(_fake_run()) is None
