#!/usr/bin/env python3
"""Chip smoke: does the program still start, compiled, on the chip?

Drives the paths a default user hits — the trainer on the zoo ResNet50, the
training kernels behind the helper seam (flash attention, fused Graves-LSTM
scan, in the zoo's sparse decoder the hyper-connection's calls and the
grouped product, and the hybrid decoder's Gated DeltaNet layer on the gated
delta rule's kernels) and the serving engine with its paged flash-decode kernel —
once each, through the normal entry points, at the widths of the
benchmark's configurations (`benchmark/configs/`: ResNet50 and the LSTM; the
attention and serving nets have no cell yet and keep the widths their phase
names; the decoder's cell fills the chip, so its phase is the zoo class cut
small). Weights are random from a seed; steps and request counts are cut to
fit the time limit, widths never.

    python chip_smoke.py            # every phase, one child process each
    python chip_smoke.py serve      # only the named phases

The parent imports neither jax nor deeplearning4j_tpu: one process holds the
chip at a time, so every phase runs as its own child, one after another,
all sharing one compile cache (util/compile_cache.py). A kernel fault in one
phase fails that phase and cannot poison the next. Each child first
requires `jax.devices()[0].platform == "tpu"` and prints what it found; with
no accelerator it exits non-zero at once and the parent stops. No phase's
failure is caught: an assertion or a device error ends the child non-zero,
the parent runs the remaining phases, then exits non-zero.

Kernel phases also check, on the chip, what the CPU tests cannot: that the
compiled step holds a Mosaic `tpu_custom_call` (neither interpret mode nor a
quiet give-way to the reference ran), and that the result agrees with the
same run under `helpers_enabled_ctx(False)`.

On success the last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
Rates printed along the way are for the builder's eyes — one cold run on
the named device, not a measurement.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

PHASES = ("train_resnet50", "train_attention", "train_graves_lstm",
          "train_decoder", "delta_net", "serve", "multichip")
EXIT_NO_ACCELERATOR = 4
# the driver allows 1200 s in all, compilation included
DEADLINE_S = 1140.0
_RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "


# --------------------------------------------------------------- child side
def _require_chip(phase: str) -> dict:
    """Name the device this process got; exit unless it is a TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[{phase}] platform={device['platform']} "
          f"device_kind={device['kind']!r} devices={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator — JAX found platform "
              f"{device['platform']!r} ({device['kind']}, {device['count']} "
              "device(s)); this program checks the TPU path and does not "
              "fall back", file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_ACCELERATOR)
    return device


class _Compiles:
    """Counts what this process compiled, from JAX's own monitoring events:
    every backend compile request, and how the persistent cache answered."""

    def __init__(self):
        from jax import monitoring
        self.compiles = self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


_counter: "_Compiles | None" = None


def _compile_counter() -> _Compiles:
    """The process's one counter, listening from its first use on."""
    global _counter
    if _counter is None:
        _counter = _Compiles()
    return _counter


def _kernel_policy():
    """The override the kernel side of a phase runs under (ops/helpers.py):
    None on the chip — the default a user gets, under which every registered
    kernel runs on a TPU; True elsewhere, so that the CPU tests drive the
    same control flow with the kernels in interpret mode."""
    import jax
    return None if jax.default_backend() == "tpu" else True


def _assert_mosaic(text: str, expected: bool, what: str) -> None:
    """On the chip, an engaged kernel is a Mosaic `tpu_custom_call` in the
    lowered step. Off the chip kernels run interpreted and lower to plain
    HLO, so there is nothing to look for."""
    import jax
    if jax.default_backend() != "tpu":
        return
    found = "tpu_custom_call" in text
    assert found == expected, (
        f"{what}: lowered step {'holds' if found else 'holds no'} "
        f"tpu_custom_call, expected {'one' if expected else 'none'}")


def _finite(losses, what: str):
    import numpy as np
    losses = np.asarray(losses, np.float64)
    assert losses.size and np.all(np.isfinite(losses)), \
        f"{what}: non-finite loss in {losses}"
    return [round(float(v), 5) for v in losses]


def _fit_on_device_twice(net, x, y, steps: int, what: str) -> dict:
    """fit_on_device twice at the same step count: the first call compiles,
    the second must not; losses finite, no divergence recorded."""
    counter = _compile_counter()
    first = _finite(net.fit_on_device(x, y, steps=steps), what)
    before = counter.compiles
    t0 = time.perf_counter()
    second = _finite(net.fit_on_device(x, y, steps=steps), what)
    warm_s = time.perf_counter() - t0
    assert counter.compiles == before, \
        f"{what}: the second fit_on_device call compiled " \
        f"{counter.compiles - before} program(s)"
    # (ParallelWrapper keeps no divergence sentinel; its losses are checked)
    assert getattr(net, "_diverged_at", None) is None, \
        f"{what}: diverged at step {net._diverged_at}"
    return {"losses": first + second, "warm_call_s": round(warm_s, 4)}


def _kernel_vs_reference(build_net, x, y, steps: int, what: str,
                         rtol: float = 2e-2) -> dict:
    """Train a fresh net with the default-on kernel and, from the same seed,
    one step with helpers off; the first-step losses (same params, same
    batch) must agree within bf16 tolerance."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

    with helpers_enabled_ctx(_kernel_policy()):
        net = build_net()
        _assert_mosaic(net.lower_train_step(x, y).as_text(), True, what)
        out = _fit_on_device_twice(net, x, y, steps, what)
    # a module-level jit that asks the seam while it is traced (the decoder's
    # `_held_experts_part`) would hand the kernel side's answer on
    jax.clear_caches()
    with helpers_enabled_ctx(False):
        ref = build_net()
        _assert_mosaic(ref.lower_train_step(x, y).as_text(), False,
                       what + " (helpers off)")
        ref_loss = _finite(ref.fit_on_device(x, y, steps=1),
                           what + " (helpers off)")[0]
    np.testing.assert_allclose(
        out["losses"][0], ref_loss, rtol=rtol,
        err_msg=f"{what}: first-step loss, kernel vs helpers off")
    out["first_loss_helpers_off"] = ref_loss
    return out


def _rate(units: int, seconds: float, what: str, chips: int = 1) -> None:
    """For the builder's eyes, with its device named; never off the chip,
    where a rate says nothing about the system."""
    import jax
    if jax.default_backend() != "tpu":
        return
    kind = jax.devices()[0].device_kind
    print(f"    {units / seconds:,.0f} {what} on "
          f"{'' if chips == 1 else f'{chips} x '}{kind} — one cold run, "
          "not a measurement", flush=True)


def _images(batch: int, classes: int, image: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, image, image).astype(np.float32))
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rng.randint(0, classes, batch)])
    return x, y


def _resnet50(classes: int, image: int):
    from deeplearning4j_tpu.models import ResNet50
    return ResNet50(num_labels=classes, seed=42, compute_dtype="bfloat16",
                    input_shape=(3, image, image)).init()


def phase_train_resnet50(batch=256, image=224, classes=1000, steps=5):
    """The main path (BASELINE's primary metric): zoo ResNet50, bf16
    compute, one host-dispatched step through net.fit(x, y), then the
    on-device loop twice."""
    net = _resnet50(classes, image)
    x, y = _images(batch, classes, image)
    net.fit(x, y)
    host_loss = _finite([net.score()], "resnet50 fit(x, y)")[0]
    out = _fit_on_device_twice(net, x, y, steps, "resnet50")
    out["host_step_loss"] = host_loss
    _rate(batch * steps, out["warm_call_s"], "images/s in the warm call")
    return out


def _attention_net(d_model, heads, seq_len, block_size, window):
    """bench_attention_longcontext's net: two causal SelfAttentionLayers and
    a softmax head."""
    from deeplearning4j_tpu import (
        Activation, InputType, NeuralNetConfiguration, RnnOutputLayer, Sgd,
        WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    b = (NeuralNetConfiguration.Builder().seed(42)
         .weight_init(WeightInit.XAVIER).updater(Sgd(learning_rate=1e-3))
         .compute_dtype("bfloat16").list())
    for _ in range(2):
        b.layer(SelfAttentionLayer(n_out=d_model, n_heads=heads, causal=True,
                                   block_size=block_size,
                                   attention_window=window))
    b.layer(RnnOutputLayer(n_out=64, activation=Activation.SOFTMAX))
    return MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(d_model, seq_len)).build()).init()


def phase_train_attention(batch=4, seq_len=8192, d_model=256, heads=4,
                          block_size=512, window=1024, steps=3):
    """Long-context training through the flash-attention kernel, plain
    causal and sliding-window, each against the blockwise lax.scan path."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, d_model, seq_len).astype(np.float32))
    y = jnp.asarray(np.eye(64, dtype=np.float32)[
        rng.randint(0, 64, (batch, seq_len))].transpose(0, 2, 1))
    out = {}
    for w in (0, window):
        name = f"attention window={w}"
        out[name] = _kernel_vs_reference(
            lambda: _attention_net(d_model, heads, seq_len, block_size, w),
            x, y, steps, name)
        _rate(batch * seq_len * steps, out[name]["warm_call_s"],
              "tokens/s in the warm call")
    return out


def phase_train_graves_lstm(batch=8192, seq_len=100, steps=3):
    """Zoo TextGenerationLSTM (2x GravesLSTM 256) through the fused
    whole-sequence scan kernel, against the lax.scan recurrence."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models import TextGenerationLSTM
    vocab = 47
    rng = np.random.RandomState(0)
    idx = rng.randint(0, vocab, (batch, seq_len))
    # one-hot char sequences, DL4J RNN layout (batch, features, time)
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[idx].transpose(0, 2, 1))
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        np.roll(idx, -1, axis=1)].transpose(0, 2, 1))
    out = _kernel_vs_reference(
        lambda: TextGenerationLSTM(total_unique_characters=vocab, seed=42,
                                   compute_dtype="bfloat16").init(),
        x, y, steps, "graves_lstm")
    _rate(batch * seq_len * steps, out["warm_call_s"],
          "tokens/s in the warm call")
    return out


def _helper_counts(op: str) -> dict:
    from deeplearning4j_tpu import telemetry
    reg = telemetry.registry()
    return {path: reg.counter(f"ops.helper.{op}.{path}", "").value
            for path in ("kernel", "fallback")}


def phase_train_decoder(seq_len=1024, hidden=512, heads=4, experts=8,
                        vocab=2048, steps=2, sinkhorn_rounds=20,
                        compute_dtype="bfloat16"):
    """The zoo's sparse decoder (models/xing4.py) cut small, bf16 compute,
    recomputation by block: one dense and one expert block and the
    multi-token-prediction module, six hyper-connections in all. Its three
    default-on kernels (the hyper-connection's four calls, the grouped
    product, flash attention) against the same net with helpers off; the
    seam has to have answered "kernel" for every hyper-connection."""
    import numpy as np

    from deeplearning4j_tpu.models.xing4 import PUBLISHED, Xing4
    config = dict(
        PUBLISHED, hidden_size=hidden, intermediate_size=2 * hidden,
        moe_intermediate_size=hidden // 2, q_lora_rank=hidden // 4,
        kv_lora_rank=hidden // 4, qk_nope_head_dim=32, qk_rope_head_dim=32,
        v_head_dim=32, num_attention_heads=heads, n_routed_experts=experts,
        num_experts_per_tok=2, num_hidden_layers=2, first_k_dense_replace=1,
        hc_sinkhorn_iters=sinkhorn_rounds, vocab_size=vocab)
    ids = np.random.RandomState(0).randint(0, vocab, (1, seq_len + 1))
    x, y = (ids[:, :-1], ids[:, 1:]), (ids[:, 1:], ids[:, 1:])
    before = _helper_counts("hyper_connection")
    out = _kernel_vs_reference(
        lambda: Xing4(config, seed=42, sequence_length=seq_len,
                      compute_dtype=compute_dtype).init(),
        x, y, steps, "decoder")
    after = _helper_counts("hyper_connection")
    out["seam"] = {k: after[k] - before[k] for k in after}
    # the kernel side traces its six layers once or more; the helpers-off
    # side never asks on a chip's behalf: its answers are the fallbacks
    assert out["seam"]["kernel"] >= 6 and out["seam"]["kernel"] % 6 == 0, \
        f"decoder: the seam answered {out['seam']} for six hyper-connections"
    _rate(seq_len * steps, out["warm_call_s"], "tokens/s in the warm call")
    return out


def phase_delta_net(batch=1, seq_len=1280, hidden=2048, k_heads=16, v_heads=32,
                    width=128, compute_dtype="bfloat16",
                    second=(3840, 15, 96, 192)):
    """One `GatedDeltaNet` layer at the Qwen3-Next share's widths, value and
    gradients, the gated delta rule as its two Mosaic kernels (ten chunks a
    sequence, two tiles of the grid) against the same layer on the token
    scan; the seam has to have answered "kernel" for the one and to have been
    asked for the other. Then the same at `second` = (hidden, heads, key
    width, value width), the Olmo-Hybrid share's: one value head a key head,
    widths that are no whole lane tiles (96 and 192), a write strength up to
    2, and a grid step's last block of heads reaching past the fifteenth."""
    out = _delta_net_layer(batch, seq_len, hidden, k_heads, v_heads, width, width,
                           1.0, compute_dtype, "delta_net")
    if second:
        d, heads, d_k, d_v = second
        out["second"] = _delta_net_layer(batch, seq_len, d, heads, heads, d_k, d_v,
                                         2.0, compute_dtype, f"delta_net {d_k}/{d_v}")
    return out


def _delta_net_layer(batch, seq_len, hidden, k_heads, v_heads, d_k, d_v,
                     beta_scale, compute_dtype, what):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.conf.layers.decoder import GatedDeltaNet
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
    layer = GatedDeltaNet(n_in=hidden, n_out=hidden, n_k_heads=k_heads,
                          n_v_heads=v_heads, d_k=d_k, d_v=d_v,
                          beta_scale=beta_scale)
    dtype = jnp.dtype(compute_dtype)
    keys = jax.random.split(jax.random.PRNGKey(42), 3)
    params = layer.init_params(keys[0], None, dtype)
    x = jax.random.normal(keys[1], (batch, seq_len, hidden), dtype)
    weigh = jax.random.normal(keys[2], x.shape, jnp.float32)

    def loss(p, x_):
        out = layer.forward(p, {}, x_, train=True)[0]
        return jnp.mean(jnp.square(out.astype(jnp.float32) - weigh))

    def side(policy, mosaic, which):
        before = _helper_counts("gated_delta_rule")
        with helpers_enabled_ctx(policy):
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            _assert_mosaic(step.lower(params, x).as_text(), mosaic, which)
            got = jax.block_until_ready(step(params, x))
        after = _helper_counts("gated_delta_rule")
        return got, {k: after[k] - before[k] for k in after}
    kernel, seam = side(_kernel_policy(), True, what)
    scan, seam_off = side(False, False, what + " (helpers off)")
    assert seam["kernel"] >= 1 and seam["fallback"] == 0, \
        f"{what}: the seam answered {seam} for one layer"
    assert seam_off["kernel"] == 0 and seam_off["fallback"] >= 1, seam_off
    _finite([kernel[0]], what)
    # bf16: both sides round every product's operands, in another order
    far = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kernel),
                            jax.tree.leaves(scan)):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        far[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    worst = max(far, key=far.get)
    assert far[worst] <= (3e-2 if dtype.itemsize < 4 else 1e-4), \
        f"{what}: {worst} is {far[worst]:.3e} off the token scan's"
    return {"loss": float(kernel[0]), "loss_scan": float(scan[0]),
            "farthest": [worst, far[worst]], "seam": seam}


def _serve(net, helpers, prompts, new_tokens, max_seqs, max_len):
    """One ServingEngine run at the engine's defaults (block size, decode
    chunk, overlap on): half the requests up front, the rest submitted
    mid-stream. Returns (tokens per request, seam counts, decode step
    text, wall seconds)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
    from deeplearning4j_tpu.serving import Request, ServingEngine
    op = "decode_attention_paged"
    with helpers_enabled_ctx(helpers):
        before = _helper_counts(op)
        eng = ServingEngine(net, max_seqs=max_seqs, max_len=max_len,
                            dtype=jnp.bfloat16, max_new_tokens_cap=new_tokens)
        half = len(prompts) // 2
        t0 = time.perf_counter()
        futs = [eng.submit(Request(p, max_new_tokens=new_tokens))
                for p in prompts[:half]]
        while eng.tokens_out < half * (new_tokens // 2) and eng.step():
            pass                          # first wave halfway through...
        futs += [eng.submit(Request(p, max_new_tokens=new_tokens))
                 for p in prompts[half:]]  # ...second wave arrives
        eng.drain()
        wall = time.perf_counter() - t0
        tokens = [f.get(timeout=0).tokens for f in futs]
        after = _helper_counts(op)
        dec = eng.decoder
        S = dec.cache.max_seqs
        text = dec._decode_jit.lower(
            dec.params, dec.cache.state, jnp.zeros((S, dec.n_in), dec.dtype),
            jnp.ones((S,), bool)).as_text()
    counts = {k: after[k] - before[k] for k in after}
    return tokens, counts, text, wall


def phase_serve(d_model=256, heads=4, kv_heads=2, vocab=64, max_seqs=8,
                max_len=1024, prompt_len=512, new_tokens=64, n_requests=8):
    """ServingEngine over the bench_decode_serving net through the paged
    flash-decode kernel; every request returns its tokens, and greedy
    decoding agrees token for token with the dense paged path."""
    import numpy as np

    from deeplearning4j_tpu import (
        Activation, InputType, NeuralNetConfiguration, RnnOutputLayer, Sgd,
        WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    b = (NeuralNetConfiguration.Builder().seed(42)
         .weight_init(WeightInit.XAVIER).updater(Sgd(learning_rate=1e-3))
         .list())
    for _ in range(2):
        b.layer(SelfAttentionLayer(n_out=d_model, n_heads=heads,
                                   n_kv_heads=kv_heads, causal=True,
                                   block_size=0))
    b.layer(RnnOutputLayer(n_out=vocab, activation=Activation.SOFTMAX))
    net = MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(vocab)).build()).init()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, prompt_len).tolist()
               for _ in range(n_requests)]

    tokens, counts, text, wall = _serve(net, _kernel_policy(), prompts,
                                        new_tokens, max_seqs, max_len)
    assert [len(t) for t in tokens] == [new_tokens] * n_requests, \
        f"serve: token counts {[len(t) for t in tokens]}"
    _assert_mosaic(text, True, "serve decode step")
    assert counts["kernel"] > 0 and counts["fallback"] == 0, \
        f"serve: decode_attention_paged seam resolved {counts}"
    ref, ref_counts, ref_text, _ = _serve(net, False, prompts, new_tokens,
                                          max_seqs, max_len)
    _assert_mosaic(ref_text, False, "serve decode step (helpers off)")
    assert ref_counts["kernel"] == 0, \
        f"serve (helpers off): seam resolved {ref_counts}"
    assert tokens == ref, "serve: greedy tokens differ, kernel vs " \
        f"helpers off, first at request " \
        f"{next(i for i, (a, b) in enumerate(zip(tokens, ref)) if a != b)}"
    _rate(n_requests * new_tokens, wall,
          "tokens/s over the whole serve, compilation included,")
    return {"requests": n_requests, "tokens_each": new_tokens,
            "seam": counts, "wall_s_with_compile": round(wall, 3)}


def phase_multichip(per_chip_batch=256, image=224, classes=1000, steps=3,
                    chips=4):
    """Data-parallel ResNet50 through ParallelWrapper (SHARED_GRADIENTS)
    over the first `chips` real devices. With fewer devices it says so and
    does not run: it never builds a mesh of virtual devices."""
    import jax

    from deeplearning4j_tpu.parallel import (ParallelWrapper, TrainingMode,
                                             make_mesh)
    from deeplearning4j_tpu.parallel.mesh import batch_sharded
    n = len(jax.devices())
    if n < chips:
        print(f"    saw {n} device(s); the data-parallel phase needs "
              f"{chips} and was not run", flush=True)
        return {"skipped": f"{n} device(s), needs {chips}"}
    mesh = make_mesh(chips)
    net = _resnet50(classes, image)
    pw = (ParallelWrapper.Builder(net).mesh(mesh)
          .training_mode(TrainingMode.SHARED_GRADIENTS)
          .gradients_threshold(1e-3).build())
    x, y = _images(per_chip_batch * chips, classes, image)
    x = jax.device_put(x, batch_sharded(mesh))
    y = jax.device_put(y, batch_sharded(mesh))
    holders = {s.device for s in x.addressable_shards}
    assert len(holders) == chips, \
        f"multichip: batch shards sit on {len(holders)} device(s): {holders}"
    out = _fit_on_device_twice(pw, x, y, steps, "multichip")
    out["shard_devices"] = sorted(str(d) for d in holders)
    _rate(per_chip_batch * chips * steps, out["warm_call_s"],
          "images/s in the warm call", chips)
    return out


def run_phase(phase: str) -> int:
    """Child entry point: one phase in this process, on the chip."""
    from deeplearning4j_tpu.util.compile_cache import configure_compile_cache
    placed = configure_compile_cache()
    device = _require_chip(phase)
    counter = _compile_counter()        # start counting before any compile
    t0 = time.perf_counter()
    result = globals()[f"phase_{phase}"]()
    result = {"phase": phase, "device": device, "result": result,
              "wall_s": round(time.perf_counter() - t0, 2),
              "compile_cache": {
                  "dir": placed or os.environ["JAX_COMPILATION_CACHE_DIR"],
                  "placed_by": "code" if placed else "environment",
                  "compiles": counter.compiles,
                  "hits": counter.cache_hits,
                  "misses": counter.cache_misses}}
    print(_RESULT_TAG + json.dumps(result), flush=True)
    return 0


# -------------------------------------------------------------- parent side
def _run_child(phase: str, budget_s: float):
    """Run one phase as a child in its own process group; echo its output
    and pick out its result line. Returns (exit code, result or None)."""
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    result = None
    timer = threading.Timer(budget_s, _kill_group, args=(child,))
    try:
        timer.start()
        for line in child.stdout:
            if line.startswith(_RESULT_TAG):
                result = json.loads(line[len(_RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        code = child.wait()
    finally:
        timer.cancel()
        _kill_group(child)              # no process outlives its phase
    return code, result


def _kill_group(child) -> None:
    try:
        os.killpg(child.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def main(argv) -> int:
    if len(argv) == 3 and argv[1] == "--phase":
        return run_phase(argv[2])
    phases = argv[1:] or list(PHASES)
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        print(f"chip_smoke: unknown phase(s) {unknown}; known: "
              f"{list(PHASES)}", file=sys.stderr)
        return 2
    start = time.monotonic()
    device, failed, misses, hits = None, [], 0, 0
    for phase in phases:
        left = DEADLINE_S - (time.monotonic() - start)
        if left <= 0:
            print(f"[{phase}] not run: out of time", file=sys.stderr)
            failed.append(phase)
            continue
        print(f"=== {phase} ===", flush=True)
        t0 = time.monotonic()
        code, result = _run_child(phase, left)
        took = time.monotonic() - t0
        if code == EXIT_NO_ACCELERATOR:
            return code                 # nothing to check here; say no more
        if code != 0 or result is None:
            print(f"[{phase}] FAILED: exit code {code} after {took:.1f} s",
                  file=sys.stderr, flush=True)
            failed.append(phase)
            continue
        device = result["device"]
        cache = result["compile_cache"]
        misses += cache["misses"]
        hits += cache["hits"]
        print(f"[{phase}] ok in {took:.1f} s — {cache['compiles']} compiles, "
              f"{cache['hits']} from the cache, {cache['misses']} built; "
              f"cache at {cache['dir']} (placed by {cache['placed_by']})\n"
              f"    {json.dumps(result['result'])}", flush=True)
    total = time.monotonic() - start
    print(f"chip_smoke: {len(phases) - len(failed)}/{len(phases)} phases ok "
          f"in {total:.1f} s; compile cache {hits} hits, {misses} misses",
          flush=True)
    if failed or device is None:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
