"""Telemetry subsystem tests (ISSUE 4): sync-free metrics registry, span
tracing with Chrome-trace export, Prometheus exposition, and the hard
invariant — instrumentation adds ZERO host syncs to the decode path
(host_syncs_per_token is bit-identical with telemetry on vs off).
"""
import json
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (Activation, InputType, MultiLayerNetwork,
                                NeuralNetConfiguration, RnnOutputLayer, Sgd,
                                WeightInit)
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.telemetry import (Counter, Gauge, Histogram,
                                          MetricsRegistry, Tracer)
from deeplearning4j_tpu.telemetry import training as tel_training
from deeplearning4j_tpu.telemetry.tracing import NULL_SPAN

V = 13


def _build_net(n_kv=0, n_layers=2, seed=5):
    b = (NeuralNetConfiguration.Builder().seed(seed)
         .weight_init(WeightInit.XAVIER)
         .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
    for _ in range(n_layers):
        b.layer(SelfAttentionLayer(n_out=8, n_heads=4, n_kv_heads=n_kv,
                                   causal=True, block_size=0))
    b.layer(RnnOutputLayer(n_out=V, activation=Activation.SOFTMAX))
    return MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(V)).build()).init()


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts with tracing enabled and an empty global trace."""
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()
    tel_training.reset()
    yield
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()
    tel_training.reset()


# ------------------------------------------------------------- registry
def test_counter_gauge_basic():
    reg = MetricsRegistry()
    c = reg.counter("t.count", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("t.count") is c          # get-or-create
    c.reset()
    assert c.value == 0
    g = reg.gauge("t.gauge")
    g.set(2.5)
    assert g.value == 2.5
    with pytest.raises(TypeError):
        reg.gauge("t.count")                    # name/type conflict


def test_histogram_buckets_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("t.lat", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 500):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(560.5)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["p50"] == 5                     # exact window quantile
    assert snap["p99"] == 500
    # bucket assignment: le=1 gets 0.5; le=10 gets the two 5s; +Inf gets 500
    assert snap["buckets"]["1.0"] == 1
    assert snap["buckets"]["10.0"] == 2
    assert snap["buckets"]["+Inf"] == 1
    h.reset()
    assert h.count == 0 and h.quantile(0.5) is None


def test_histogram_ring_window_is_recent():
    h = Histogram("w", buckets=(10,))
    for _ in range(2000):
        h.observe(1.0)
    for _ in range(1024):                       # overwrite the whole ring
        h.observe(9.0)
    assert h.quantile(0.5) == 9.0
    assert h.count == 3024                      # bucket counts stay lifetime


def test_registry_snapshot_and_reset():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(7)
    reg.histogram("c").observe(1.5)
    snap = reg.snapshot()
    assert snap["a"] == 3 and snap["b"] == 7 and snap["c"]["count"] == 1
    reg.reset()
    snap = reg.snapshot()
    assert snap["a"] == 0 and snap["b"] == 0 and snap["c"]["count"] == 0


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serving.tokens_out", "tokens emitted").inc(42)
    reg.gauge("queue.depth").set(3)
    h = reg.histogram("lat.ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5)
    h.observe(50)
    text = reg.prometheus_text()
    lines = text.strip().split("\n")
    assert "# TYPE serving_tokens_out counter" in lines
    assert "serving_tokens_out 42" in lines
    assert "# HELP serving_tokens_out tokens emitted" in lines
    assert "queue_depth 3" in lines
    # histogram: cumulative buckets + sum + count
    assert 'lat_ms_bucket{le="1"} 1' in lines
    assert 'lat_ms_bucket{le="10"} 2' in lines
    assert 'lat_ms_bucket{le="+Inf"} 3' in lines
    assert "lat_ms_sum 55.5" in lines
    assert "lat_ms_count 3" in lines


def test_child_registry_aggregates_into_parent_exposition():
    parent = MetricsRegistry()
    parent.counter("x.n").inc(1)
    child = MetricsRegistry(parent=parent)
    child.counter("x.n").inc(2)
    child.gauge("x.g").set(9)
    text = parent.prometheus_text()
    assert "x_n 3" in text                      # counters sum across children
    assert "x_g 9" in text                      # child-only metric shows up
    # child keeps isolated storage
    assert child.snapshot()["x.n"] == 2
    assert parent.snapshot()["x.n"] == 1


def _parse_prometheus(text):
    """Reference parse of the v0.0.4 text format: returns
    (samples {name_or_name{labels}: float}, types {name: type},
    helps {name: raw help text})."""
    samples, types, helps = {}, {}, {}
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            types[name] = typ
        elif line.startswith("# HELP "):
            _, _, name, h = line.split(" ", 3)
            helps[name] = h.replace("\\n", "\n").replace("\\\\", "\\")
        elif line.startswith("#"):
            continue
        else:
            key, val = line.rsplit(" ", 1)
            assert key not in samples, f"duplicate sample {key}"
            samples[key] = float(val)
    return samples, types, helps


def test_prometheus_round_trip_against_reference_parse():
    """ISSUE 8 satellite: audit the exposition against an independent parse
    — cumulative monotone buckets, `+Inf` == `_count`, `_sum` == raw sum,
    HELP escaping survives the round trip."""
    reg = MetricsRegistry()
    reg.counter("rt.count", help="lines with \\ and\nnewline").inc(7)
    h = reg.histogram("rt.lat", help="latency", buckets=(1, 5, 25))
    obs = (0.2, 0.7, 3, 3, 17, 90, 120)
    for v in obs:
        h.observe(v)
    samples, types, helps = _parse_prometheus(reg.prometheus_text())
    assert types == {"rt_count": "counter", "rt_lat": "histogram"}
    # HELP escaping round-trips to the original text
    assert helps["rt_count"] == "lines with \\ and\nnewline"
    assert samples["rt_count"] == 7
    # buckets are CUMULATIVE and monotone non-decreasing
    buckets = [samples['rt_lat_bucket{le="1"}'],
               samples['rt_lat_bucket{le="5"}'],
               samples['rt_lat_bucket{le="25"}'],
               samples['rt_lat_bucket{le="+Inf"}']]
    assert buckets == [2, 4, 5, 7]
    assert buckets == sorted(buckets)
    # +Inf bucket equals _count; _sum is the raw observation sum
    assert samples['rt_lat_bucket{le="+Inf"}'] == samples["rt_lat_count"]
    assert samples["rt_lat_sum"] == pytest.approx(sum(obs))


def test_prometheus_mixed_type_name_collision_is_single_typed():
    """A name registered as different TYPES across child registries must
    expose only the first-seen type — a mixed family is unparseable (and
    used to crash the exposition)."""
    parent = MetricsRegistry()
    parent.counter("clash.m").inc(3)
    child = MetricsRegistry(parent=parent)
    child.histogram("clash.m", buckets=(1,)).observe(0.5)
    samples, types, _ = _parse_prometheus(parent.prometheus_text())
    assert types["clash_m"] == "counter"
    assert samples["clash_m"] == 3          # histogram instance not summed in
    assert not any(k.startswith("clash_m_bucket") for k in samples)


def test_prometheus_mismatched_histogram_bounds_excluded_whole():
    """Same-name histograms with DIFFERENT bucket bounds: only the
    first-seen bounds aggregate, and the excluded instance is left out of
    buckets, _sum AND _count (else +Inf desyncs from _count)."""
    parent = MetricsRegistry()
    parent.histogram("mm.h", buckets=(1, 10)).observe(0.5)
    child = MetricsRegistry(parent=parent)
    child.histogram("mm.h", buckets=(2, 20)).observe(0.5)
    samples, _, _ = _parse_prometheus(parent.prometheus_text())
    assert samples['mm_h_bucket{le="+Inf"}'] == samples["mm_h_count"] == 1
    assert samples["mm_h_sum"] == pytest.approx(0.5)


# -------------------------------------------------------------- tracing
def test_chrome_trace_schema_and_nesting():
    tr = Tracer()
    with tr.span("outer", kind="test"):
        time.sleep(0.002)
        with tr.span("inner"):
            time.sleep(0.001)
    tr.instant("mark", detail=1)
    doc = tr.chrome_trace()
    # schema: valid JSON object format
    json.loads(json.dumps(doc))
    assert isinstance(doc["traceEvents"], list)
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"outer", "inner", "mark"}
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], float) and e["pid"] == 1 and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # nesting: inner's [ts, ts+dur] lies within outer's
    o, i = evs["outer"], evs["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert o["args"] == {"kind": "test"}
    assert evs["mark"]["s"] == "t"


def test_tracer_bounded_buffer_counts_drops():
    tr = Tracer(max_events=3)
    for k in range(5):
        tr.instant(f"e{k}")
    assert tr.n_events == 3
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 2
    # a ring: the newest stay (a training run keeps its last steps)
    assert [e["name"] for e in doc["traceEvents"]] == ["e2", "e3", "e4"]
    tr.clear()
    assert tr.n_events == 0


def test_disabled_tracer_returns_null_span():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN
    tr.instant("y")
    assert tr.n_events == 0
    telemetry.configure(enabled=False)
    assert telemetry.span("z") is NULL_SPAN
    telemetry.configure(enabled=True)


def test_trace_export_writes_valid_json(tmp_path):
    tr = Tracer()
    with tr.span("s"):
        pass
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["traceEvents"][0]["name"] == "s"


# --------------------------------------------------- engine instrumentation
def test_engine_trace_export_has_decode_spans(tmp_path):
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=0, decode_chunk=4,
                        overlap=False)
    eng.generate([Request([1, 2, 3, 4, 5], max_new_tokens=8)])
    path = eng.export_trace(str(tmp_path / "serve.json"))
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"prefill", "decode_chunk", "host_sync",
            "jit_compile", "admit", "retire"} <= names
    # spans must be well-formed complete events
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_trace_path_env_export_on_drain(tmp_path, monkeypatch):
    out = tmp_path / "drain_trace.json"
    monkeypatch.setenv("DL4J_TPU_TRACE_PATH", str(out))
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=0, decode_chunk=4,
                        overlap=False)
    eng.submit(Request([1, 2, 3], max_new_tokens=6))
    eng.drain()
    assert out.exists()
    doc = json.loads(out.read_text())
    assert any(e["name"] == "decode_chunk" for e in doc["traceEvents"])


def test_engine_metrics_and_stats_snapshot():
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=0, decode_chunk=4,
                        overlap=False)
    res = eng.generate([Request([1, 2, 3, 4], max_new_tokens=8),
                        Request([5, 6], max_new_tokens=8)])
    st = eng.stats()
    # one consistent snapshot includes live scheduler state (satellite)
    assert st["queue_depth"] == 0
    assert st["free_slots"] == 2 and st["active_slots"] == 0
    assert st["tokens_out"] == sum(len(r.tokens) for r in res) == 16
    assert st["host_syncs"] == eng.host_syncs > 0
    snap = eng.metrics.snapshot()
    assert snap["serving.admissions"] == 2
    assert snap["serving.retirements"] == 2
    assert snap["serving.ttft_s"]["count"] == 2
    assert snap["serving.jit_compiles"] >= 1
    assert snap["serving.chunk_k"]["count"] >= 1
    # per-engine registry reaches the global Prometheus exposition
    assert "serving_tokens_out" in telemetry.registry().prometheus_text()
    # counters are resettable through the legacy attribute API (bench.py)
    eng.host_syncs = 0
    assert eng.stats()["host_syncs"] == 0


def test_chunked_prefill_metrics_and_exposition():
    """ISSUE 9 satellite: serving.prefill_chunks / prefill_chunk_tokens /
    decode_stall_ms are wired into stats(), the registry snapshot, and the
    global /metrics exposition — fed from host values the scheduler
    already holds (zero added syncs, same discipline as every other
    serving metric)."""
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=0, decode_chunk=1,
                        overlap=False, kv_block=4, prefill_chunk=4)
    # a resident decoder first, so the long admission's chunks stall it
    f1 = eng.submit(Request([1, 2, 3], max_new_tokens=10))
    for _ in range(3):
        eng.step()
    f2 = eng.submit(Request([1, 5, 2, 9, 3, 7, 4, 8, 6, 1, 2, 3, 11],
                            max_new_tokens=4))
    eng.drain()
    assert len(f1.get(timeout=0).tokens) == 10
    assert len(f2.get(timeout=0).tokens) == 4
    st = eng.stats()
    assert st["prefill_chunk"] == 4 and st["prefill_chunks"] == 4
    snap = eng.metrics.snapshot()
    assert snap["serving.prefill_chunks"] == 4
    assert snap["serving.prefill_chunk_tokens"]["count"] == 4
    assert snap["serving.prefill_chunk_tokens"]["sum"] == 13
    # every chunk ran while f1's slot was decode-active -> each one is a
    # bounded decode stall observation
    assert snap["serving.decode_stall_ms"]["count"] == 4
    text = telemetry.registry().prometheus_text()
    assert "serving_prefill_chunks" in text
    assert "serving_prefill_chunk_tokens_bucket" in text
    assert "serving_decode_stall_ms_bucket" in text


def test_monolithic_prefill_records_decode_stall():
    """With chunking off, a mid-stream admission's WHOLE prompt pass is
    one decode_stall_ms observation — the unbounded stall the A/B bench
    measures against."""
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=0, decode_chunk=1,
                        overlap=False, kv_block=4, prefill_chunk=0)
    f1 = eng.submit(Request([1, 2, 3], max_new_tokens=8))
    for _ in range(3):
        eng.step()
    eng.submit(Request([1, 5, 2, 9, 3, 7, 4, 8, 6], max_new_tokens=2))
    eng.drain()
    snap = eng.metrics.snapshot()
    assert snap["serving.prefill_chunks"] == 0
    assert snap["serving.decode_stall_ms"]["count"] == 1
    assert len(f1.get(timeout=0).tokens) == 8


def test_tokens_per_sec_not_none_for_single_token():
    net = _build_net()
    eng = ServingEngine(net, max_seqs=1, max_len=32, seed=0)
    res = eng.generate([Request([1, 2, 3], max_new_tokens=1)])[0]
    assert len(res.tokens) == 1
    assert res.tokens_per_sec is not None and res.tokens_per_sec > 0
    assert res.ttft_s is not None


def test_host_syncs_identical_telemetry_on_vs_off():
    """The ISSUE 4 hard constraint: enabling telemetry adds ZERO host syncs
    (and changes no tokens) on the decode path."""
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8]]

    def serve(enabled):
        telemetry.configure(enabled=enabled)
        telemetry.tracer().clear()
        net = _build_net(seed=11)
        eng = ServingEngine(net, max_seqs=2, max_len=64, seed=4,
                            decode_chunk=4, overlap=False)
        res = eng.generate([Request(list(p), max_new_tokens=10)
                            for p in prompts])
        return [r.tokens for r in res], eng.stats()

    toks_on, st_on = serve(True)
    toks_off, st_off = serve(False)
    assert toks_on == toks_off
    assert st_on["host_syncs"] == st_off["host_syncs"]
    assert st_on["host_syncs_per_token"] == st_off["host_syncs_per_token"]


def test_chunked_parity_with_telemetry_enabled():
    """Acceptance: chunked decode (K=4) matches K=1 token-for-token while
    fully instrumented."""
    telemetry.configure(enabled=True)
    net = _build_net(seed=9)
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    out = {}
    for k in (1, 4):
        eng = ServingEngine(net, max_seqs=2, max_len=64, seed=2,
                            decode_chunk=k, overlap=False)
        out[k] = [r.tokens for r in
                  eng.generate([Request(list(p), max_new_tokens=12)
                                for p in prompts])]
    assert out[1] == out[4]


# --------------------------------------------------------- training bridge
def test_mark_iteration_is_idempotent_per_iteration():
    reg = MetricsRegistry()
    r1 = tel_training.mark_iteration(0, reg)
    assert r1["iteration_ms"] is None           # first iteration: no delta
    r_dup = tel_training.mark_iteration(0, reg)  # co-attached listener
    assert r_dup == r1
    time.sleep(0.002)
    r2 = tel_training.mark_iteration(1, reg)
    assert r2["iteration_ms"] is not None and r2["iteration_ms"] > 0
    assert reg.counter("training.iterations").value == 2
    assert reg.histogram("training.iteration_ms").count == 1


def test_telemetry_listener_records_training_metrics():
    from deeplearning4j_tpu.optimize.listeners import TelemetryListener
    net = _build_net(n_layers=1, seed=3)
    reg = MetricsRegistry()
    lst = TelemetryListener(registry=reg)
    net.set_listeners(lst)
    rng = np.random.RandomState(0)
    x = jax.nn.one_hot(jnp.asarray(rng.randint(0, V, (2, 6))), V,
                       dtype=jnp.float64).transpose(0, 2, 1)
    y = jax.nn.one_hot(jnp.asarray(rng.randint(0, V, (2, 6))), V,
                       dtype=jnp.float64).transpose(0, 2, 1)
    for _ in range(3):
        net.fit_batch(x, y)
    snap = reg.snapshot()
    assert snap["training.iterations"] == 3
    assert snap["training.iteration_ms"]["count"] == 2
    # one-step-stale materialized score lands on the gauge eventually
    assert snap["training.score"] > 0


def test_performance_listener_score_is_lagged_not_synced():
    from deeplearning4j_tpu.optimize.listeners import PerformanceListener
    net = _build_net(n_layers=1, seed=3)
    lst = PerformanceListener(frequency=1, report=False)
    net.set_listeners(lst)
    rng = np.random.RandomState(0)
    x = jax.nn.one_hot(jnp.asarray(rng.randint(0, V, (2, 6))), V,
                       dtype=jnp.float64).transpose(0, 2, 1)
    y = jax.nn.one_hot(jnp.asarray(rng.randint(0, V, (2, 6))), V,
                       dtype=jnp.float64).transpose(0, 2, 1)
    for _ in range(4):
        net.fit_batch(x, y)
    recs = lst.history
    assert len(recs) == 3                       # first iteration has no dt
    # every recorded score is the PREVIOUS step's already-materialized
    # loss — present and finite without any forced per-iteration sync
    assert all(r["score"] is not None and np.isfinite(r["score"])
               for r in recs)


# ------------------------------------------------------------ exposition
def test_ui_server_metrics_endpoint():
    from deeplearning4j_tpu.ui.server import UIServer
    reg = MetricsRegistry()
    reg.counter("demo.requests", "demo").inc(7)
    reg.histogram("demo.ms", buckets=(1, 10)).observe(3)
    srv = UIServer(port=0)
    try:
        srv.attach_metrics(reg)
        with urllib.request.urlopen(
                f"http://localhost:{srv.port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "# TYPE demo_requests counter" in body
        assert "demo_requests 7" in body
        assert 'demo_ms_bucket{le="10"} 1' in body
    finally:
        srv.stop()


def test_json_http_metrics_route():
    from deeplearning4j_tpu.util.http import JsonHttpServer
    reg = MetricsRegistry()
    reg.gauge("alive").set(1)
    srv = JsonHttpServer({"GET /metrics": telemetry.metrics_route(reg)},
                         port=0)
    try:
        with urllib.request.urlopen(
                f"http://localhost:{srv.port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers["Content-Type"]
            assert "alive 1" in resp.read().decode()
    finally:
        srv.stop()
