"""Span tracing with Chrome-trace/Perfetto JSON export.

Spans are recorded from HOST timestamps only (`time.perf_counter`) — entering
or exiting a span never materializes device data, so tracing the decode hot
loop adds zero host syncs (the ISSUE 4 invariant, asserted in
tests/test_telemetry.py). Events land in a bounded in-memory ring (the
newest `max_events` stay, what falls out is counted) and export as standard
Chrome trace JSON (`{"traceEvents": [...]}` — load in chrome://tracing or
https://ui.perfetto.dev).

Where `jax` is already imported a span is also a
`jax.profiler.TraceAnnotation(name, **args)`: whenever anyone records a
profile (`jax.profiler.start_trace`, TensorBoard, the benchmark's traced
run) the span is in it, on `/host:CPU`, on the device trace's clock, on the
thread that ran it, with its arguments as stats. With no profile running
that costs a flag check.

Span vocabulary used across the framework (see serving/engine.py,
optimize/solvers.py, optimize/listeners.py):
- "prefill"       — one admission's prompt prefill dispatch
- "decode_chunk"  — one chunked-decode dispatch (args: k, active)
- "host_sync"     — an existing device->host materialization (args: what)
- "jit_compile"   — first-use of a compiled shape (cache-miss attribution);
                    wraps the dispatch that triggered the compile
- "admit"/"retire" — instant events for scheduling decisions
- "epoch"/"solver.optimize" — training-side phases

The training path's spans all start with `dl4j.` and carry `step=<the net's
iteration when the span began>`, so the spans of one step share it:
- "dl4j.fit.next_batch"   — `fit`'s wait for the iterator (what
                            `last_etl_ms` times)
- "dl4j.fit_batch" with children ".prepare" (input conversion, rng split,
  argument tuple), ".dispatch" (the call of the jitted train step) and
  ".listeners" (the `iteration_done` loop); "dl4j.fit_tbptt" around the
  segments of one truncated-BPTT batch
- "dl4j.fit_on_device" with children ".prepare", ".dispatch" and
  ".readback" (`jax.device_get` of the losses and the divergence flag)
- on the producer thread of `AsyncDataSetIterator`: "dl4j.async.produce"
  (the underlying iterator's `next`), "dl4j.async.stage" (`np.asarray` +
  `jax.device_put`, args: bytes) and "dl4j.async.put_wait" (blocked on the
  full queue)
- "dl4j.pw.fit_on_device" with ".prepare", ".dispatch", ".readback",
  ".write_back" — `ParallelWrapper.fit_on_device`
- "dl4j.host.gc" (args: generation, collected, uncollectable) — one of
  Python's full (generation-2) collections, on the thread that ran it
  (`telemetry.count_gc`, which counts every generation's collections and
  seconds as `dl4j.host.gc.*` counters)

`Tracer.spans(prefix, t0, t1)` reads the ring back on `time.perf_counter`'s
clock, so a caller that timed a stretch with that clock can put the
program's own spans on it.
"""
from __future__ import annotations

import collections
import json
import sys
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

_US = 1e6

#: Base for synthetic track tids handed out by `Tracer.set_track` —
#: far below CPython thread idents (pointer-sized on Linux), so named
#: tracks and raw-ident tracks never collide in one dump.
_TRACK_TID0 = 10_001

# thread-local current track: spans recorded by a thread that called
# set_track() land on its named track instead of the raw thread ident
_TRACK = threading.local()


class _NullSpan:
    """No-op context manager returned when tracing is disabled — the hot
    path pays one attribute check and nothing else."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def _annotation(name: str, args: Optional[dict]):
    """The span as a `jax.profiler.TraceAnnotation` while a profile is being
    recorded; None otherwise, and while `jax` is not imported (this module
    stays importable without it)."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return None
    return jax.profiler.TraceAnnotation(name, **(args or {}))


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_tid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        self._tid = threading.get_ident()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record("X", self.name, self._t0, t1 - self._t0,
                             self._tid, self.args)
        return False


class Tracer:
    """Bounded in-memory span recorder: a ring of the newest `max_events`.
    All methods are cheap host work; `export()` is the only I/O."""

    def __init__(self, max_events: int = 65536, enabled: bool = True,
                 drop_counter=None):
        self.max_events = int(max_events)
        self.enabled = bool(enabled)
        self._events: Deque[dict] = collections.deque(maxlen=self.max_events)
        self._dropped = 0
        # optional registry Counter mirroring the drop count on /metrics
        # (ISSUE 6 satellite) — before, drops were only visible in the
        # exported JSON, i.e. precisely when the buffer was already full
        self._drop_counter = drop_counter
        self._epoch = time.perf_counter()
        # named tracks (ISSUE 14 satellite): replica engines label their
        # scheduler threads so multi-replica dumps are distinguishable
        self._tracks: Dict[str, int] = {}
        self._track_meta: Dict[str, dict] = {}
        self._lock = threading.Lock()   # append-side: deque.append is atomic
        #                                 under the GIL; the lock guards only
        #                                 clear()/export() vs. appends

    # ------------------------------------------------------------ tracks
    def set_track(self, name: Optional[str], **meta) -> None:
        """Route the CALLING thread's subsequent spans onto a named
        track (stable synthetic tid + a thread_name metadata event in
        the export, carrying `meta` — e.g. replica_id). `None` restores
        the raw thread-ident track. Idempotent and cheap enough for a
        scheduler loop to call every iteration."""
        if name is None:
            _TRACK.tid = None
            return
        tid = self._tracks.get(name)
        if tid is None:
            with self._lock:
                tid = self._tracks.get(name)
                if tid is None:
                    tid = _TRACK_TID0 + len(self._tracks)
                    self._tracks[name] = tid
                    self._track_meta[name] = {k: v for k, v in meta.items()
                                              if v is not None}
        _TRACK.tid = tid

    # ------------------------------------------------------------ record
    def span(self, name: str, **args):
        """Context manager timing a region as one Chrome 'X' complete
        event. Returns a no-op when the tracer is disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        """Zero-duration instant event (scheduling decisions)."""
        if not self.enabled:
            return
        self._record("i", name, time.perf_counter(), None,
                     threading.get_ident(), args or None)

    def _record(self, ph: str, name: str, t0: float, dur: Optional[float],
                tid: int, args: Optional[dict]) -> None:
        if len(self._events) >= self.max_events:
            # the ring drops its oldest event for this one
            self._dropped += 1
            if self._drop_counter is not None:
                self._drop_counter.inc()
        track = getattr(_TRACK, "tid", None)
        if track is not None:
            tid = track
        ev: Dict[str, object] = {
            "name": name, "ph": ph, "pid": 1, "tid": tid,
            "ts": round((t0 - self._epoch) * _US, 3),
            "cat": name.split(".")[0].split("_")[0],
        }
        if ph == "X":
            ev["dur"] = round((dur or 0.0) * _US, 3)
        elif ph == "i":
            ev["s"] = "t"
        if args:
            ev["args"] = args
        self._events.append(ev)

    # ------------------------------------------------------------ export
    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    @property
    def n_events(self) -> int:
        return len(self._events)

    def spans(self, prefix: str, t0: Optional[float] = None,
              t1: Optional[float] = None) -> List[Tuple]:
        """The ring's complete spans whose name starts with `prefix` and
        whose start lies in [t0, t1] (either bound may be None), in the
        order they ended, as `(name, start_s, end_s, thread, args)` on
        `time.perf_counter`'s clock; `thread` is the recording thread's
        ident or its named track's tid."""
        with self._lock:
            events = list(self._events)
        out = []
        for ev in events:
            if ev["ph"] != "X" or not ev["name"].startswith(prefix):
                continue
            start = self._epoch + ev["ts"] / _US
            if (t0 is not None and start < t0) or (t1 is not None
                                                   and start > t1):
                continue
            out.append((ev["name"], start, start + ev["dur"] / _US,
                        ev["tid"], ev.get("args") or {}))
        return out

    def chrome_trace(self) -> dict:
        """The exported document: Chrome trace 'JSON Object Format'."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            tracks = dict(self._tracks)
            tmeta = {k: dict(v) for k, v in self._track_meta.items()}
        metas = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                  "args": {"name": name, **tmeta.get(name, {})}}
                 for name, tid in sorted(tracks.items(),
                                         key=lambda kv: kv[1])]
        doc = {"traceEvents": metas + events, "displayTimeUnit": "ms",
               "otherData": {"producer": "deeplearning4j_tpu.telemetry"}}
        if dropped:
            doc["otherData"]["dropped_events"] = dropped
        return doc

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to `path`; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path
