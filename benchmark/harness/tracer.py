"""Traces a steady stretch inside the window, not its edges.

The driver calls `tick()` between calls (or steps). The profiler starts once
`start_after` seconds of the window have passed and stops `duration` seconds
later, both on a boundary of the driver's own. With tracing off every method
does nothing, and `annotate` costs nothing.
"""
from __future__ import annotations

import contextlib
import glob
import os
import time


class Tracer:
    def __init__(self, enabled: bool, out_dir: str, seconds: float):
        self.enabled = enabled
        self.out_dir = out_dir
        self.start_after = min(0.25 * seconds, 5.0)
        self.duration = min(max(0.4 * seconds, 1.0), 5.0)
        self.t0 = None
        self.started_at = self.stopped_at = None
        self.overhead_s = 0.0        # spent starting and stopping the profiler

    def begin_window(self, t0: float) -> None:
        self.t0 = t0

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def tick(self, now: float = None) -> None:
        if not self.enabled or self.stopped_at is not None or self.t0 is None:
            return
        now = time.perf_counter() if now is None else now
        import jax
        if self.started_at is None:
            if now - self.t0 >= self.start_after:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # the host's spans, not every call
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.out_dir, profiler_options=opts)
                self.started_at = time.perf_counter()
                self.overhead_s += self.started_at - now
        elif now - self.started_at >= self.duration:
            jax.profiler.stop_trace()
            self.stopped_at = time.perf_counter()
            self.overhead_s += self.stopped_at - now

    def end_window(self) -> None:
        if self.enabled and self.started_at is not None \
                and self.stopped_at is None:
            import jax
            jax.profiler.stop_trace()
            self.stopped_at = time.perf_counter()

    def trace_file(self):
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None
