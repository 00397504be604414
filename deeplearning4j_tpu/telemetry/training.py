"""Canonical per-iteration training bookkeeping on top of the registry.

Both `optimize.listeners.TelemetryListener` and `ui.stats.StatsListener`
report per-iteration wall time; before ISSUE 4 each kept its own
`_last_report_time` stopwatch. This module is the single source: every
listener calls `mark_iteration(iteration)` and the FIRST call for a given
iteration number observes the timing into the registry (histogram
`training.iteration_ms`, counter `training.iterations`); later calls for
the same iteration get the cached record back — attach as many listeners
as you like, the iteration is timed once.

`lagged_score` is the sync-free score read (satellite: PerformanceListener
must not force a device sync per iteration): it returns the PREVIOUS
iteration's score — whose device buffer has materialized while the current
step ran — and stashes the current handle for next time. One step stale by
construction, never a forced pipeline flush.

Iteration bookkeeping is keyed PER STORE (ISSUE 5 satellite): pass the
model as `store` so two networks training concurrently in one process each
get their own stopwatch — with a single process-global one their
interleaved iteration numbers corrupted `iteration_ms` (every boundary
measured listener-to-listener across models). `store=None` keeps the old
process-global behavior for single-model callers.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Optional

from deeplearning4j_tpu.telemetry.registry import (DEFAULT_MS_BUCKETS,
                                                   MetricsRegistry)


class _IterState:
    """Per-store iteration stopwatch (idempotent-per-iteration record)."""

    __slots__ = ("lock", "last_time", "last_iter", "last_record")

    def __init__(self):
        self.lock = threading.Lock()
        self.last_time: Optional[float] = None
        self.last_iter: Optional[int] = None
        self.last_record: dict = {"iteration": None, "iteration_ms": None}


_GLOBAL_STATE = _IterState()
# weak keys: a model's stopwatch dies with the model, no registry leak
_STATES: "weakref.WeakKeyDictionary[Any, _IterState]" = \
    weakref.WeakKeyDictionary()
_STATES_LOCK = threading.Lock()


def _state_for(store: Any) -> _IterState:
    if store is None:
        return _GLOBAL_STATE
    try:
        with _STATES_LOCK:
            st = _STATES.get(store)
            if st is None:
                st = _STATES[store] = _IterState()
        return st
    except TypeError:  # unhashable / not weakref-able store
        return _GLOBAL_STATE


def mark_iteration(iteration: int, registry: Optional[MetricsRegistry] = None,
                   store: Any = None) -> dict:
    """Record one training iteration boundary (idempotent per iteration
    number per `store`). Returns {"iteration", "iteration_ms"} where
    iteration_ms is the host wall time since the previous distinct
    iteration of the SAME store (None on the first). Listeners pass the
    model as `store`: co-attached listeners on one model still time each
    iteration exactly once, while concurrent models no longer interleave
    into one shared stopwatch."""
    from deeplearning4j_tpu import telemetry
    reg = registry or telemetry.registry()
    st = _state_for(store)
    now = time.perf_counter()
    with st.lock:
        if iteration == st.last_iter:
            return dict(st.last_record)
        ms = None if st.last_time is None else (now - st.last_time) * 1e3
        st.last_time, st.last_iter = now, iteration
        st.last_record = {"iteration": iteration, "iteration_ms": ms}
        record = dict(st.last_record)
    reg.counter("training.iterations",
                "training iterations completed").inc()
    if ms is not None:
        reg.histogram("training.iteration_ms",
                      "wall time per training iteration (host clock)",
                      buckets=DEFAULT_MS_BUCKETS).observe(ms)
    return record


def reset() -> None:
    """Forget iteration-boundary state, global and per-store (tests)."""
    global _GLOBAL_STATE
    with _STATES_LOCK:
        _GLOBAL_STATE = _IterState()
        _STATES.clear()


def lagged_score(store, model) -> Optional[float]:
    """One-step-stale, sync-free score read. `store` holds the stash (any
    object with settable attributes — typically the listener); `model` is
    the network whose `_score` is a deferred device scalar. Returns the
    score the model had BEFORE its latest step (that buffer has had a full
    step's wall time to materialize, so reading it is a copy of a completed
    result, not a forced `block_until_ready` on in-flight compute), or None
    until two iterations have run."""
    prev = getattr(store, "_telemetry_prev_score", None)
    store._telemetry_prev_score = getattr(model, "_score", None)
    if prev is None:
        return None
    try:
        return float(prev)  # sync-ok: buffer materialized one step ago (lagged)
    except Exception:
        return None
