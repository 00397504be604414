"""Device-time profiler + HBM memory accounting tests (ISSUE 6).

The tentpole invariants:
- compiled-function costs (XLA cost_analysis) land in the util/costs named
  registry and surface as `profiler.fn.<name>.*` roofline gauges on the
  metrics registry / /metrics exposition;
- feeding observations is pure host arithmetic — the decode path's
  `host_syncs_per_token` is BIT-IDENTICAL with profiling on vs off (the
  PR 4 zero-added-syncs constraint, regression-tested here);
- memory accounting polls `memory_stats()` at phase boundaries only and
  degrades gracefully on CPU (live-buffer fallback, platform label);
- the merged Perfetto trace folds host tracer spans into a device capture.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (Activation, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, RnnOutputLayer, Sgd, WeightInit)
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.telemetry import MetricsRegistry, Tracer
from deeplearning4j_tpu.telemetry import memory as tmemory
from deeplearning4j_tpu.telemetry import profiler
from deeplearning4j_tpu.telemetry.registry import sanitize_component
from deeplearning4j_tpu.util import costs as ucosts

V = 13


def _build_net(seed=5):
    b = (NeuralNetConfiguration.Builder().seed(seed)
         .weight_init(WeightInit.XAVIER)
         .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
    for _ in range(2):
        b.layer(SelfAttentionLayer(n_out=8, n_heads=4, n_kv_heads=0,
                                   causal=True, block_size=0))
    b.layer(RnnOutputLayer(n_out=V, activation=Activation.SOFTMAX))
    return MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(V)).build()).init()


def _mlp(seed=3):
    b = (NeuralNetConfiguration.Builder().seed(seed)
         .weight_init(WeightInit.XAVIER)
         .updater(Sgd(learning_rate=0.05)).dtype("float64").list()
         .layer(DenseLayer(n_out=16, activation=Activation.RELU))
         .layer(OutputLayer(n_out=4, activation=Activation.SOFTMAX)))
    return MultiLayerNetwork(
        b.set_input_type(InputType.feed_forward(8)).build()).init()


@pytest.fixture(autouse=True)
def _clean_profiler():
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()
    profiler.reset()
    ucosts.clear_costs()
    yield
    profiler.reset()
    ucosts.clear_costs()
    telemetry.configure(enabled=True)
    telemetry.tracer().clear()


# ----------------------------------------------------- costs registry
def test_costs_record_and_lookup():
    ucosts.record_costs("f", flops=10.0, bytes_accessed=20.0,
                        meta={"k": 1})
    rec = ucosts.get_costs("f")
    assert rec == {"flops": 10.0, "bytes_accessed": 20.0, "meta": {"k": 1}}
    assert "f" in ucosts.all_costs()
    ucosts.clear_costs()
    assert ucosts.get_costs("f") is None


def test_analyze_and_record_matches_lowered_costs():
    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((8, 8), jnp.float32)
    rec = ucosts.analyze_and_record("matmul8", f, x, x)
    direct = ucosts.lowered_costs(f, x, x)
    assert rec["flops"] == direct["flops"] > 0
    assert ucosts.get_costs("matmul8")["flops"] == rec["flops"]


# ------------------------------------------------- sanitize_component
def test_sanitize_component_round_trip_and_idempotence():
    import re
    prom = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
    for raw in ("decode_chunk_k8", "conv1x1-bn-relu", "a.b/c d",
                "8gpu", "", "prefill_b128", "Ω-op"):
        s = sanitize_component(raw)
        assert prom.match(s), f"{raw!r} -> {s!r} not a valid metric part"
        assert sanitize_component(s) == s, "sanitize must be idempotent"
    assert sanitize_component("conv1x1-bn-relu") == "conv1x1_bn_relu"
    assert sanitize_component("8gpu").startswith("_")


def test_helper_seam_resolution_counters():
    from deeplearning4j_tpu.ops.helpers import helper_for
    reg = telemetry.registry()
    before = reg.counter("ops.helper.no_such_op.fallback", "d").value
    helper_for("no_such_op", lambda: None)
    assert reg.counter("ops.helper.no_such_op.fallback",
                       "d").value == before + 1


# ------------------------------------------------- register / observe
V5E = "TPU v5 lite"


def test_register_publishes_roofline_gauges():
    reg = MetricsRegistry()
    profiler.configure(enabled=True, platform="tpu", device_kind=V5E)
    rec = profiler.register("my_fn", flops=197e9, bytes_accessed=1e6,
                            registry=reg)
    assert rec["flops"] == 197e9
    text = reg.prometheus_text()
    assert "profiler_fn_my_fn_flops 197" in text
    assert "profiler_fn_my_fn_mxu_floor_ms 1" in text
    assert math.isclose(profiler.mxu_floor_ms(197e9, "tpu"), 1.0)


def test_off_chip_rows_carry_no_floor_and_no_mfu():
    """A CPU run reports counts and its own wall time, never a share of a
    chip's peak (the `decode_chunk_k4 | cpu (ref)` row this replaces
    published a 2,288,064.9x floor)."""
    reg = MetricsRegistry()
    profiler.configure(enabled=True, platform="cpu")
    profiler.register("c", flops=197e9, bytes_accessed=5.0, registry=reg)
    profiler.observe("c", 2.0, registry=reg)
    text = reg.prometheus_text()
    assert "profiler_fn_c_flops 197" in text
    assert "profiler_fn_c_measured_ms 2" in text
    for gauge in ("mxu_floor_ms", "mfu", "x_floor", "roofline_frac"):
        assert f"profiler_fn_c_{gauge}" not in text
    assert profiler.mxu_floor_ms(197e9, "cpu") is None
    row = {r["function"]: r for r in profiler.roofline_table()}["c"]
    assert row["platform"] == "cpu" and row["measured_ms"] == 2.0
    assert row["mxu_floor_ms"] is None and row["mfu"] is None \
        and row["x_floor"] is None


def test_peak_table_is_keyed_by_device_kind_and_unknown_kind_raises():
    peaks = profiler.device_peaks(V5E)
    assert peaks["bf16_flops"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["source"]
    with pytest.raises(KeyError, match="TPU v9000"):
        profiler.device_peaks("TPU v9000")
    # a TPU the table does not know gets no floor either: it raises
    profiler.configure(platform="tpu", device_kind="TPU v9000")
    with pytest.raises(KeyError):
        profiler.mxu_floor_ms(1e9)


def test_observe_publishes_mfu_and_x_floor():
    reg = MetricsRegistry()
    profiler.configure(enabled=True, platform="tpu", device_kind=V5E)
    profiler.register("g", flops=197e9, registry=reg)   # floor = 1.0 ms
    profiler.observe("g", 4.0, registry=reg)
    text = reg.prometheus_text()
    assert "profiler_fn_g_measured_ms 4" in text
    assert "profiler_fn_g_x_floor 4" in text
    assert "profiler_fn_g_roofline_frac 0.25" in text
    assert "profiler_fn_g_mfu 0.25" in text
    agg = profiler.observed("g")
    assert agg["count"] == 1 and agg["last_ms"] == 4.0
    profiler.observe("g", 2.0, registry=reg)
    assert profiler.observed("g")["total_ms"] == 6.0


def test_roofline_table_rows():
    profiler.configure(enabled=True, platform="tpu", device_kind=V5E)
    profiler.register("t", flops=197e9, bytes_accessed=5.0,
                      registry=MetricsRegistry())
    profiler.observe("t", 2.0, registry=MetricsRegistry())
    rows = {r["function"]: r for r in profiler.roofline_table()}
    row = rows["t"]
    assert row["platform"] == "tpu" and row["mxu_floor_ms"] == 1.0
    assert row["calls"] == 1 and row["measured_ms"] == 2.0
    assert row["x_floor"] == 2.0 and row["mfu"] == 0.5
    assert 0 < row["mfu"] < 1


def test_observe_is_inert_noop_without_costs():
    reg = MetricsRegistry()
    profiler.observe("never_registered", 1.5, registry=reg)
    text = reg.prometheus_text()
    assert "profiler_fn_never_registered_measured_ms" in text
    assert "mfu" not in text    # no costs on file -> no attribution gauges


# ------------------------------------------------- train loop costs
def test_register_train_loop_warm_semantics():
    profiler.configure(enabled=True, platform="cpu")

    class Owner:
        pass

    owner = Owner()
    f = jax.jit(lambda x, n: x * n, static_argnames=("n",))
    x = jnp.ones((4,), jnp.float32)
    warm = profiler.register_train_loop(owner, ("k",), f, (x,), steps=4,
                                        name="loop_fn")
    assert warm is False
    rec = ucosts.get_costs("loop_fn")
    assert rec is not None and rec["meta"]["normalized_per_step"]
    assert rec["meta"]["steps_analyzed"] == 4
    assert profiler.register_train_loop(owner, ("k",), f, (x,), 4,
                                        name="loop_fn") is True
    # off -> always cold, nothing registered
    profiler.configure(enabled=False)
    assert profiler.register_train_loop(owner, ("k2",), f, (x,), 4,
                                        name="loop2") is False
    assert ucosts.get_costs("loop2") is None


def test_fit_on_device_registers_train_step_costs():
    profiler.configure(enabled=True)
    net = _mlp()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 16)]
    net.fit_on_device(x, y, steps=3)
    rec = ucosts.get_costs("train_step")
    assert rec is not None and rec["flops"] > 0
    net.fit_on_device(x, y, steps=3)        # warm call feeds observe
    assert profiler.observed("train_step")["count"] >= 1
    text = telemetry.registry().prometheus_text()
    assert "profiler_fn_train_step_flops" in text
    assert "profiler_fn_train_step_measured_ms" in text


# ------------------------------------------------------ serving path
def test_serving_publishes_prefill_and_decode_chunk_gauges():
    profiler.configure(enabled=True)
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=4,
                        decode_chunk=4, overlap=False)
    eng.generate([Request([1, 2, 3], max_new_tokens=8)])
    text = eng.metrics.prometheus_text()
    # the ISSUE 6 acceptance gauges: prefill bucket + decode chunk rooflines
    # (prefill buckets are pow2 rounded UP to KV-block granularity — ISSUE 7)
    b = eng.decoder.prefill_bucket(3)
    assert f"profiler_fn_prefill_b{b}_flops" in text
    assert f"profiler_fn_prefill_b{b}_measured_ms" in text
    assert "profiler_fn_decode_chunk_k4_flops" in text
    assert "profiler_fn_decode_chunk_k4_measured_ms" in text
    names = {r["function"] for r in profiler.roofline_table()}
    assert any(n.startswith("prefill_b") for n in names)
    assert any(n.startswith("decode_chunk_k") for n in names)
    # KV/param memory gauges on the engine's child registry
    assert "serving_kv_cache_bytes" in text
    assert "serving_param_bytes" in text
    assert "memory_polls" in text


def test_host_syncs_identical_profiler_on_vs_off():
    """THE regression test for the ISSUE 6 acceptance criterion: profiling
    adds zero host syncs on the decode path — host_syncs_per_token is
    bit-identical (and tokens unchanged) with the profiler on vs off."""
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8]]

    def serve(profile_on):
        profiler.reset()
        profiler.configure(enabled=profile_on)
        ucosts.clear_costs()
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


def test_kv_bytes_resident_tracks_scheduler_state():
    net = _build_net()
    eng = ServingEngine(net, max_seqs=2, max_len=64, seed=4,
                        decode_chunk=4, overlap=False)
    g = eng.metrics.gauge("serving.kv_bytes_resident", "d")
    assert g.value == 0.0
    fut = eng.submit(Request([1, 2, 3], max_new_tokens=6))
    eng.step()
    per_pos = eng.decoder.cache.bytes_per_position
    assert g.value > 0 and g.value % per_pos == 0
    eng.drain()
    fut.get(timeout=0)
    assert g.value == 0.0    # everything retired
    assert eng.metrics.gauge("serving.kv_cache_bytes", "d").value == \
        eng.decoder.cache.bytes()


# ----------------------------------------------------------- memory
def test_memory_stats_graceful_on_cpu():
    s = tmemory.stats()
    assert s["platform"] == jax.default_backend()
    assert isinstance(s["stats_available"], bool)
    assert s["live_buffer_bytes"] >= 0
    if not s["stats_available"]:
        # CPU degradation: bytes_in_use falls back to the live-buffer sum
        assert s["bytes_in_use"] == s["live_buffer_bytes"]


def test_memory_poll_publishes_gauges_and_watermark():
    reg = MetricsRegistry()
    tmemory.reset_watermark()
    keep = jnp.ones((1024,), jnp.float32)   # ensure a live buffer exists
    out = tmemory.poll("test.phase", registry=reg)
    text = reg.prometheus_text()
    assert "memory_polls 1" in text
    assert "memory_live_buffer_bytes" in text
    assert "memory_device_watermark_bytes" in text
    assert out["phase"] == "test.phase"
    assert out["watermark_bytes"] >= 0
    first = tmemory.watermark_bytes()
    tmemory.poll("test.phase2", registry=reg)
    assert tmemory.watermark_bytes() >= first    # monotonic
    del keep


def test_param_bytes_is_metadata_only():
    params = {"w": jnp.ones((10, 4), jnp.float32),
              "b": jnp.ones((4,), jnp.float64)}
    assert tmemory.param_bytes(params) == 10 * 4 * 4 + 4 * 8
    reg = MetricsRegistry()
    tmemory.publish_param_bytes(params, name="m", registry=reg)
    assert "memory_params_m_bytes 192" in reg.prometheus_text()


# ------------------------------------------------------------ drops
def test_trace_drop_counter_reaches_metrics():
    reg = MetricsRegistry()
    c = reg.counter("telemetry.trace.dropped_events", "d")
    tr = Tracer(max_events=2, drop_counter=c)
    for k in range(5):
        tr.instant(f"e{k}")
    assert c.value == 3
    assert "telemetry_trace_dropped_events 3" in reg.prometheus_text()
    # the GLOBAL tracer is wired to the global registry's counter at import
    assert "telemetry.trace.dropped_events" in \
        telemetry.registry().snapshot()


# ------------------------------------------------------- env parsing
def test_profile_env_parsing(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PROFILE", "0")
    profiler.reset()
    assert not profiler.enabled()
    monkeypatch.setenv("DL4J_TPU_PROFILE", "1")
    profiler.reset()
    assert profiler.enabled()
    monkeypatch.delenv("DL4J_TPU_PROFILE")
    profiler.reset()
    assert not profiler.enabled()
