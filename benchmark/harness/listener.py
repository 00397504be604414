"""The benchmark's listener: a copy of the semantics of the program's
`PerformanceListener` (DL4J's own instrument).

`iteration_done` waits for the loss of the step before the one just
dispatched (the program's `telemetry.training.lagged_score`), so the host runs
at most one step ahead of the chip, and notes the time: the gaps between
consecutive notes are the step times a user watching the log sees. It also
reads `net.last_etl_ms`, the wait of the training thread for its batch.
"""
from __future__ import annotations

import math
import time


class StepListener:
    def __init__(self, annotate=None, on_step=None):
        self.times = []          # perf_counter at each callback
        self.etl_ms = []
        self.losses = []         # of the step before each callback
        self._prev = None
        self._annotate = annotate
        self._on_step = on_step

    def iteration_done(self, model, iteration: int):
        if self._annotate is None:
            self._note(model, iteration)
        else:
            with self._annotate("bench.listener"):
                self._note(model, iteration)

    def _note(self, model, iteration):
        prev, self._prev = self._prev, getattr(model, "_score", None)
        if prev is not None:
            self.losses.append(float(prev))       # waits for the step before
        self.times.append(time.perf_counter())
        self.etl_ms.append(float(getattr(model, "last_etl_ms", 0.0)))
        if self._on_step is not None:
            self._on_step(self.times[-1])

    def finish(self):
        """Wait for the last step's loss, once `fit` has returned."""
        if self._prev is not None:
            self.losses.append(float(self._prev))
            self._prev = None
        return time.perf_counter()

    def failed_steps(self) -> int:
        return sum(1 for v in self.losses if not math.isfinite(v))
