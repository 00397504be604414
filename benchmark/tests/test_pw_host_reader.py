"""`pw_host_ms_per_step` on a hand-made trace: the wrapper's call less the
wait for its losses, a step; nothing without the wrapper's spans."""
import pytest

from harness.program_trace import ProgramTrace, Span
from test_program_readers import MS, fake_run, hand_made, reader


def wrapped():
    """Two calls of 20 steps: 1,400 ms each, of which 1,340 wait for the
    losses; dispatch 8 ms and write-back 44 ms are inside the rest."""
    p = hand_made()
    spans = []
    for call in range(2):
        t = p.lo + call * 1400 * MS
        spans += [Span(0, "dl4j.pw.fit_on_device", t, t + 1400 * MS),
                  Span(0, "dl4j.pw.fit_on_device.dispatch", t + 4 * MS, t + 12 * MS),
                  Span(0, "dl4j.pw.fit_on_device.readback", t + 12 * MS, t + 1352 * MS),
                  Span(0, "dl4j.pw.fit_on_device.write_back", t + 1352 * MS, t + 1396 * MS)]
    return ProgramTrace(lo=p.lo, hi=p.lo + 2800 * MS, steps=40, spans=spans,
                        modules=p.modules, op_events=p.op_events, busy=p.busy)


def test_the_wrappers_host_time_is_its_call_less_the_wait_for_the_losses():
    assert reader("pw_host_ms_per_step").read(fake_run(wrapped())) \
        == pytest.approx(2 * 60.0 / 40)


@pytest.mark.parametrize("trace", [None, "nets"])
def test_nothing_without_a_device_trace_or_the_wrappers_spans(trace):
    p = None if trace is None else hand_made()
    assert reader("pw_host_ms_per_step").read(fake_run(p)) is None
