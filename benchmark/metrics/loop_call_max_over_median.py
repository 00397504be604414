"""The window's longest loop call over the median call: the training
thread's outermost `dl4j.fit_on_device` (`dl4j.pw.fit_on_device` in the
wrapper's cell) spans from the program's own ring that start inside the
window (`harness/program_ring.py`). Every call of a loop cell runs the same
steps, so a steady window reads about 1, and a stall anywhere in the window,
traced or not, reads as one long call. None with fewer than three calls."""
import statistics

from harness import program_ring


def read(run):
    calls = program_ring.loop_calls(run)
    if calls is None or len(calls) < 3:
        return None
    durations = [end - start for start, end in calls]
    return max(durations) / statistics.median(durations)
