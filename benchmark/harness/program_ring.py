"""What the program recorded in its own span ring during the window.

The program keeps its `telemetry.span`s in a bounded ring in every run,
traced or not, and `Tracer.spans` reads them back on `time.perf_counter`'s
clock, which is the clock of `Window.t0` and `t1`. So these readers see the
whole window, where the profile sees at most 5 s of it (`harness/tracer.py`).
A program without `Tracer.spans` (the parent of the change that added it)
gives None and nothing raises.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

CALLS = ("dl4j.fit_on_device", "dl4j.pw.fit_on_device")


def window_spans(run, prefix: str) -> Optional[List[Tuple]]:
    """`(name, start_s, end_s, thread, args)` of the program's spans named
    `prefix...` that start inside the window; None where the program cannot
    say."""
    from deeplearning4j_tpu import telemetry
    spans = getattr(telemetry.tracer(), "spans", None)
    if spans is None:
        return None
    return spans(prefix, run.window.t0, run.window.t1)


def loop_calls(run) -> Optional[List[Tuple[float, float]]]:
    """(start_s, end_s) of the training thread's outermost loop calls that
    start inside the window: `dl4j.fit_on_device`, or `dl4j.pw.fit_on_device`
    where the wrapper's call is the outer one. The training thread is the
    one that made the most of them."""
    spans = window_spans(run, "dl4j.")
    if spans is None:
        return None
    calls = [(s, e, t) for name, s, e, t, _ in spans if name in CALLS]
    outer = [(s, e, t) for s, e, t in calls
             if not any(t2 == t and s2 <= s and e <= e2 and (s2, e2) != (s, e)
                        for s2, e2, t2 in calls)]
    if not outer:
        return []
    threads = [t for _, _, t in outer]
    main = max(set(threads), key=threads.count)
    return sorted((s, e) for s, e, t in outer if t == main)

