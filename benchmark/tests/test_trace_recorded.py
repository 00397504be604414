"""The trace reduction on a small trace recorded on a TPU v5 lite by
`tools/trace_probe.py` (PR 24): four calls of a jitted scan of eight 2048^2
bf16 matrix products under `bench.fit_call`, each read back under
`bench.readback`, with a 2 ms `bench.listener` sleep between calls."""
import os

import pytest

from conftest import HERE
from harness import trace

PATH = os.path.join(HERE, "data", "probe_tpu_v5e.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return trace.read_planes(PATH)


def test_planes_and_spans_are_found(planes):
    devices, spans = planes
    assert list(devices) == ["/device:TPU:0"] and len(devices["/device:TPU:0"]) == 176
    names = [n for n, _, _ in spans]
    assert names.count("bench.fit_call") == 4 and names.count("bench.listener") == 4


def test_busy_idle_over_whole_periods(planes):
    r = trace.reduce(*planes, "bench.fit_call")
    assert r.periods == 3
    assert r.stretch_s == pytest.approx(0.150990589, rel=1e-9)
    # three calls of ~0.78 ms of device work each
    assert r.busy_s == pytest.approx(0.002504869, rel=1e-6)
    assert r.idle_share == pytest.approx(0.98341, abs=1e-4)


def test_sums_by_operation_leave_the_scan_itself_out(planes):
    r = trace.reduce(*planes, "bench.fit_call")
    assert not any(k.startswith("while") for k in r.ops_s)
    top = r.top_ops(3)
    assert top[0][0] == "fusion.8"            # the matrix product and tanh
    assert top[0][1] == pytest.approx(0.002334531, rel=1e-6)
    assert sum(r.ops_s.values()) <= r.busy_s * 1.001


def test_gaps_go_to_the_host_span_over_them(planes):
    r = trace.reduce(*planes, "bench.fit_call")
    gaps = dict(r.top_gaps())
    # the first call's readback compiled its slicing programs: 138 ms
    assert gaps["bench.readback"] == pytest.approx(0.1423, abs=1e-3)
    # three sleeps of 2.2 to 2.8 ms; the device's clock runs some 0.5 ms ahead
    # of the host's in this trace, so the device's work of the next call
    # starts "inside" the listener and the listener is given less than it took
    assert 0.004 < gaps["bench.listener"] < 0.008
    assert sum(gaps.values()) == pytest.approx(r.stretch_s - r.busy_s, rel=1e-3)
