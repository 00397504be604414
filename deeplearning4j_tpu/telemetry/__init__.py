"""Framework-wide observability (ISSUE 4): sync-free metrics + span tracing.

Three pieces:
- `MetricsRegistry` (registry.py): lock-free counters/gauges/fixed-bucket
  histograms fed ONLY from values the caller already holds on the host —
  recording a metric never adds a device sync. `registry()` returns the
  process-wide default; subsystems that want isolation (one per
  ServingEngine) build `MetricsRegistry(parent=registry())` so the global
  Prometheus exposition still sees them.
- `Tracer` (tracing.py): context-manager spans -> Chrome-trace/Perfetto
  JSON. `span("name", **args)` on the module records into the global
  tracer; `maybe_export_trace()` writes it to `$DL4J_TPU_TRACE_PATH`.
- `count_compiles()`: JAX's monitoring events as `dl4j.compile.*` counters
  of the default registry (called where the nets' modules are imported).
- `count_gc()`: Python's garbage collections as `dl4j.host.gc.*` counters
  of the default registry, and each full collection as a `dl4j.host.gc`
  span (called beside `count_compiles`).
- Prometheus text exposition: `registry().prometheus_text()`, served by
  ui/server.py at GET /metrics, or mount `metrics_route()` on any
  util/http.JsonHttpServer.

Env toggles:
- DL4J_TPU_TELEMETRY=0 disables span RECORDING (metrics counting stays on —
  it is what `engine.stats()` is built from, and it is sync-free either
  way; the on-vs-off regression test asserts identical sync counts).
- DL4J_TPU_TRACE_PATH=/path/trace.json makes instrumented drains/epochs
  export the trace there (last writer wins).
- DL4J_TPU_HEALTH=record|skip|raise (or 1/0) sets the default in-step
  training-health policy for models that did not call `configure_health`
  (health.py, ISSUE 5). Unset means health is off unless a listener or the
  model opts in.
- DL4J_TPU_PROFILE=1 (any value but 0/false/off) enables the
  compiled-function cost registry + per-function MFU/roofline gauges of
  the serving engine's programs (profiler.py; the training programs'
  costs are counted analytically by the benchmark). Unset/0 keeps the
  profiling call sites inert (default). A device trace needs no switch: a span is also a
  `jax.profiler.TraceAnnotation`, so any recorded profile holds them.
- DL4J_TPU_FLIGHT_RECORDER=1 attaches a default flight recorder
  (flight_recorder.py, ISSUE 8) to every new ServingEngine: it retains
  lifecycle timelines for the worst-TTFT / SLO-violating requests and
  dumps them as Perfetto JSON on demand. Off by default.
- DL4J_TPU_LOADGEN_SEED seeds serving/loadgen.py arrival schedules when
  no explicit seed is passed (default 0 — schedules are deterministic
  either way).
- DL4J_TPU_KV_OBS=1 attaches a KV-pressure observatory (kv_observatory.py,
  ISSUE 12) to every new ServingEngine: serving.kv.* heat/attribution
  gauges, admission-rejection forensics, and the eviction dry-run scorer.
  Off by default.
- DL4J_TPU_TS=1 attaches a windowed time-series layer (timeseries.py,
  ISSUE 19) to every new ServingEngine: one bounded ring-buffer sample
  per scheduler iteration, serving.ts.* windowed-rate/quantile gauges.
  DL4J_TPU_TS_WINDOW sets the short window in iterations (default 30;
  long window = 10x). Off by default.
- DL4J_TPU_ALERTS=1 attaches a multi-window SLO burn-rate monitor
  (alerts.py, ISSUE 19) — implies the time-series layer; typed
  overload/goodput-regression/KV-pressure-spiral/starvation alerts into
  a bounded log, serving.alerts.* metrics, and flight-recorder Perfetto
  instants. Off by default.
"""
from __future__ import annotations

import os
from typing import Optional

from deeplearning4j_tpu.telemetry.registry import (Counter,
                                                   DEFAULT_MS_BUCKETS,
                                                   DEFAULT_S_BUCKETS, Gauge,
                                                   Histogram,
                                                   MetricsRegistry)
from deeplearning4j_tpu.telemetry.tracing import NULL_SPAN, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Tracer",
    "DEFAULT_MS_BUCKETS", "DEFAULT_S_BUCKETS", "registry", "tracer", "span",
    "instant", "enabled", "configure", "maybe_export_trace", "metrics_route",
    "count_compiles", "count_gc",
    "PROMETHEUS_CONTENT_TYPE", "sanitize_component", "set_track", "health",
    "profiler", "memory", "slo", "flight_recorder", "kv_observatory",
    "blame", "timeseries", "alerts",
]

from deeplearning4j_tpu.telemetry.registry import sanitize_component  # noqa: E402,F401


def __getattr__(name):
    # health (ISSUE 5) / profiler / memory (ISSUE 6) import jax (lazily in
    # the ISSUE 6 pair's case, but profiler also pulls util.costs) — loaded
    # on first attribute access so registry/tracing users stay jax-free.
    # slo / flight_recorder (ISSUE 8) / blame (ISSUE 14) / timeseries /
    # alerts (ISSUE 19) are jax-free but rarely needed, so they load
    # lazily too
    if name in ("health", "profiler", "memory", "slo", "flight_recorder",
                "kv_observatory", "blame", "timeseries", "alerts"):
        import importlib
        return importlib.import_module(
            f"deeplearning4j_tpu.telemetry.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

_ENABLED = os.environ.get("DL4J_TPU_TELEMETRY", "1").lower() \
    not in ("0", "false", "off")
_REGISTRY = MetricsRegistry()
_TRACER = Tracer(enabled=_ENABLED,
                 drop_counter=_REGISTRY.counter(
                     "telemetry.trace.dropped_events",
                     "span events dropped by the tracer's bounded buffer"))


def registry() -> MetricsRegistry:
    """The process-wide default metrics registry."""
    return _REGISTRY


def tracer() -> Tracer:
    """The process-wide default tracer."""
    return _TRACER


def enabled() -> bool:
    """Whether span recording is on (DL4J_TPU_TELEMETRY, default on)."""
    return _ENABLED


def configure(enabled: Optional[bool] = None) -> None:
    """Override the env default at runtime (tests, embedding apps)."""
    global _ENABLED
    if enabled is not None:
        _ENABLED = bool(enabled)
        _TRACER.enabled = _ENABLED


def span(name: str, **args):
    """Record a span into the global tracer (no-op when disabled)."""
    if not _ENABLED:
        return NULL_SPAN
    return _TRACER.span(name, **args)


def instant(name: str, **args) -> None:
    """Record an instant event into the global tracer (no-op when
    disabled)."""
    if _ENABLED:
        _TRACER.instant(name, **args)


def set_track(name: Optional[str], **meta) -> None:
    """Route the calling thread's spans onto a named track in the global
    tracer (replica engines label their scheduler threads, ISSUE 14
    satellite). `meta` (e.g. replica_id) lands on the track's
    thread_name metadata event in the Perfetto export."""
    _TRACER.set_track(name, **meta)


def maybe_export_trace(path: Optional[str] = None) -> Optional[str]:
    """Export the global tracer's Chrome trace to `path` or
    `$DL4J_TPU_TRACE_PATH`; returns the written path or None when no
    destination is configured / tracing is disabled / nothing recorded."""
    path = path or os.environ.get("DL4J_TPU_TRACE_PATH")
    if not path or not _ENABLED or _TRACER.n_events == 0:
        return None
    return _TRACER.export(path)


# JAX's monitoring events -> counters of the registry: seconds, and events
_COMPILE_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "dl4j.compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "dl4j.compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "dl4j.compile.backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "dl4j.compile.cache_load_s",
}
_COMPILE_COUNTS = {
    "/jax/core/compile/backend_compile_duration": "dl4j.compile.programs",
    "/jax/compilation_cache/cache_hits": "dl4j.compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "dl4j.compile.cache_misses",
}
_COMPILES_COUNTED = False


def count_compiles() -> None:
    """Count what this process compiles from now on, as counters of the
    default registry: seconds tracing (`dl4j.compile.trace_s`), lowering
    (`.lower_s`) and in the backend (`.backend_s`; on a cache hit that is
    the load) with the programs (`.programs`), and the persistent cache's
    hits, misses and load seconds. JAX fires these on compile paths only,
    never per step: a counter that moves during training is a step that
    recompiled. Idempotent; imports `jax`, so the nets' modules call it and
    this package does not."""
    global _COMPILES_COUNTED
    if _COMPILES_COUNTED:
        return
    _COMPILES_COUNTED = True
    from jax import monitoring
    seconds = {event: _REGISTRY.counter(name, f"seconds of JAX's {event}")
               for event, name in _COMPILE_SECONDS.items()}
    counts = {event: _REGISTRY.counter(name, f"events of JAX's {event}")
              for event, name in _COMPILE_COUNTS.items()}

    def on_duration(event, secs, **_):
        if event in seconds:
            seconds[event].inc(secs)
        if event in counts:
            counts[event].inc()

    def on_event(event, **_):
        if event in counts:
            counts[event].inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


GC_SPAN = "dl4j.host.gc"
_GC_COUNTED = False


def count_gc() -> None:
    """Count Python's garbage collections from now on: every collection
    adds to `dl4j.host.gc.collections.gen<g>` and `dl4j.host.gc.seconds.gen<g>`
    of the default registry, and every full (generation-2) collection is
    also a `dl4j.host.gc` span with `generation`, `collected` and
    `uncollectable` — in the ring on the collecting thread and, while a
    profile records, a `jax.profiler.TraceAnnotation` inside the span it
    interrupted. The young generations get counters only, so that their
    hundreds in a set-up leave the ring to the calls. With span recording
    off the counters stay.
    A collection runs on whichever thread allocates, between two
    bytecodes, and stalls that thread: on the training thread between two
    calls, the device waits. Idempotent: one `gc.callbacks` hook, two
    Python calls a collection."""
    global _GC_COUNTED
    if _GC_COUNTED:
        return
    _GC_COUNTED = True
    import gc
    import threading
    import time

    from deeplearning4j_tpu.telemetry.tracing import _annotation
    counts = [_REGISTRY.counter(f"dl4j.host.gc.collections.gen{g}",
                                f"Python's generation-{g} collections")
              for g in range(3)]
    seconds = [_REGISTRY.counter(f"dl4j.host.gc.seconds.gen{g}",
                                 f"seconds in Python's generation-{g} "
                                 "collections") for g in range(3)]
    running = {}    # the collection in progress: CPython runs one at a time

    def on_gc(phase, info):
        full = info["generation"] == 2
        if phase == "start":
            ann = _annotation(GC_SPAN, {"generation": 2}) \
                if full and _ENABLED else None
            if ann is not None:
                ann.__enter__()
            running["ann"] = ann
            running["t0"] = time.perf_counter()
            return
        t1 = time.perf_counter()
        t0, ann = running["t0"], running["ann"]
        g = info["generation"]
        counts[g].inc()
        seconds[g].inc(t1 - t0)
        if ann is not None:
            ann.set_metadata(collected=info["collected"],
                             uncollectable=info["uncollectable"])
            ann.__exit__(None, None, None)
        if full and _ENABLED:
            _TRACER._record("X", GC_SPAN, t0, t1 - t0, threading.get_ident(),
                            {"generation": 2, "collected": info["collected"],
                             "uncollectable": info["uncollectable"]})

    gc.callbacks.append(on_gc)


def metrics_route(reg: Optional[MetricsRegistry] = None):
    """A GET route fn for util/http.JsonHttpServer serving the Prometheus
    text exposition: JsonHttpServer({"GET /metrics": metrics_route()})."""
    from deeplearning4j_tpu.util.http import PlainTextResponse

    def handler(_query):
        return PlainTextResponse((reg or _REGISTRY).prometheus_text(),
                                 content_type=PROMETHEUS_CONTENT_TYPE)
    return handler
