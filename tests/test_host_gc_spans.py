"""Python's garbage collections on the program's own record
(`telemetry.count_gc`): counters for every generation, a `dl4j.host.gc` span
for every full collection, on `time.perf_counter`'s clock in the ring
(`Tracer.spans`) and on the device trace's clock in a profile."""
import contextlib
import gc
import glob
import os
import threading
import time

import jax
import pytest

from deeplearning4j_tpu import telemetry

import deeplearning4j_tpu.nn.multilayer  # noqa: F401  (calls count_gc)


@pytest.fixture(autouse=True)
def _quiet_collector():
    """Only the collections a test makes: the automatic ones are off, and
    the ring is empty."""
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()


def _counters():
    snap = telemetry.registry().snapshot()
    return {k: v for k, v in snap.items() if k.startswith("dl4j.host.gc.")}


def _gc_spans():
    return telemetry.tracer().spans(telemetry.GC_SPAN)


def test_a_full_collection_in_a_call_is_one_span_inside_it():
    t_before = time.perf_counter()
    with telemetry.span("dl4j.fit_on_device", step=0):
        gc.collect()
    t_after = time.perf_counter()
    (gc_span,) = _gc_spans()
    ((_, c0, c1, call_thread, _),) = telemetry.tracer().spans(
        "dl4j.fit_on_device")
    name, s0, s1, thread, args = gc_span
    assert name == "dl4j.host.gc"
    assert args["generation"] == 2 and args["collected"] >= 0 \
        and args["uncollectable"] >= 0
    assert t_before <= c0 <= s0 < s1 <= c1 <= t_after
    assert thread == call_thread == threading.get_ident()


def test_the_counters_move_for_every_generation():
    for g in range(3):
        before = _counters()
        gc.collect(g)
        after = _counters()
        assert after[f"dl4j.host.gc.collections.gen{g}"] \
            == before.get(f"dl4j.host.gc.collections.gen{g}", 0) + 1
        assert after[f"dl4j.host.gc.seconds.gen{g}"] \
            > before.get(f"dl4j.host.gc.seconds.gen{g}", 0)
        for other in set(range(3)) - {g}:
            key = f"dl4j.host.gc.collections.gen{other}"
            assert after.get(key) == before.get(key)


def test_young_collections_leave_no_span():
    gc.collect(0)
    gc.collect(1)
    assert _gc_spans() == []
    gc.collect(2)
    assert len(_gc_spans()) == 1


def test_span_recording_off_keeps_the_counters_and_drops_the_span():
    telemetry.configure(enabled=False)
    before = _counters()["dl4j.host.gc.collections.gen2"]
    gc.collect()
    assert _counters()["dl4j.host.gc.collections.gen2"] == before + 1
    assert _gc_spans() == []


def test_count_gc_twice_installs_one_hook():
    def ours():
        return [cb for cb in gc.callbacks
                if getattr(cb, "__qualname__", "").startswith("count_gc.")]
    assert len(ours()) == 1
    telemetry.count_gc()
    telemetry.count_gc()
    assert len(ours()) == 1


def test_in_a_profile_the_span_is_an_annotation_on_the_training_thread(
        tmp_path):
    jax.numpy.ones(3).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with contextlib.ExitStack() as stop:
        stop.callback(jax.profiler.stop_trace)
        with telemetry.span("dl4j.fit_on_device", step=0):
            gc.collect()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in ("dl4j.fit_on_device", "dl4j.host.gc"):
                    lines.setdefault(e.name, []).append(
                        (i, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    ((call_line, c0, c1, _),) = lines["dl4j.fit_on_device"]
    ((gc_line, g0, g1, stats),) = lines["dl4j.host.gc"]
    assert gc_line == call_line
    assert c0 <= g0 < g1 <= c1
    assert stats["generation"] == 2 and "collected" in stats \
        and "uncollectable" in stats
