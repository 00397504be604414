"""Fused flash-attention Pallas kernels (ops/flash_attention.py).

Beyond-reference long-context hot path (SURVEY §5): value AND gradient
parity against the dense softmax oracle in fp64 through interpret mode
(finite differences through the custom VJP included), across causal x
key-mask x block-size combinations including non-divisible T, plus the
SelfAttentionLayer integration (helpers-on must match the lax.scan
blockwise path the layer otherwise uses)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.flash_attention import (
    flash_attention, flash_attention_reference)

RNG = np.random.RandomState(11)


def _data(B=2, H=3, T=23, D=8):
    q, k, v = (jnp.asarray(RNG.randn(B, H, T, D) * 0.5) for _ in range(3))
    mask = jnp.asarray((RNG.rand(B, T) > 0.25).astype(np.int32))
    return q, k, v, mask


@pytest.fixture(params=["fused", "two_pass"])
def bwd_mode(request):
    """Run the parametrized tests under BOTH backward schedules (the
    default fused single-pass and the flash-2 two-pass)."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    prev, _ = fa.configure(bwd=request.param)
    yield request.param
    fa.configure(bwd=prev)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("blk", [8, 16])
def test_value_and_grad_match_dense_oracle(causal, use_mask, blk, bwd_mode):
    q, k, v, mask = _data()
    m = mask if use_mask else None

    def lf(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, m, causal, None,
                                               blk, blk)))

    def lr(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_reference(q, k, v, m, causal)))

    vf, gf = jax.value_and_grad(lf, argnums=(0, 1, 2))(q, k, v)
    vr, gr = jax.value_and_grad(lr, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(vf - vr)) < 1e-10
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-10)


def test_rectangular_blocks_and_auto_resolution():
    """bq != bk (the auto-resolver can pick asymmetric tiles) and the
    bq=bk=0 'auto' default must both match the oracle — values and grads,
    both backward schedules."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    q, k, v, mask = _data(T=40)

    def lr(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_reference(q, k, v, mask, True)))

    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for mode in ("fused", "two_pass"):
        prev, _ = fa.configure(bwd=mode)
        try:
            for bq, bk in ((8, 16), (16, 8), (0, 0)):
                def lf(q, k, v):
                    return jnp.sum(jnp.sin(flash_attention(
                        q, k, v, mask, True, None, bq, bk)))
                gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
                for a, b in zip(gf, gr):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=1e-10,
                        err_msg=f"{mode} bq={bq} bk={bk}")
        finally:
            fa.configure(bwd=prev)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("window", [1, 5, 12, 100])
def test_sliding_window_matches_dense_oracle(causal, use_mask, window,
                                             bwd_mode):
    """Sliding-window (local) attention: kernel AND lax.scan blockwise path
    must match the dense oracle with the band mask — values and grads, fp64,
    windows below/at/above the block size and covering the whole sequence."""
    from deeplearning4j_tpu.parallel.sequence_parallel import (
        blockwise_attention)
    q, k, v, mask = _data(T=23)
    m = mask if use_mask else None

    def lf(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, m, causal, None,
                                               8, 8, window)))

    def lb(q, k, v):
        return jnp.sum(jnp.sin(blockwise_attention(
            q, k, v, 8, causal=causal, mask=m, window=window)))

    def lr(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_reference(
            q, k, v, m, causal, None, window)))

    vr, gr = jax.value_and_grad(lr, argnums=(0, 1, 2))(q, k, v)
    for name, fn in (("flash", lf), ("blockwise", lb)):
        vf, gf = jax.value_and_grad(fn, argnums=(0, 1, 2))(q, k, v)
        assert abs(float(vf - vr)) < 1e-10, name
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-10, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_with_window_matches_oracle(causal):
    """Windowed ring CP (classic masked body + out-of-window round
    skipping) must match the dense banded oracle — values and grads on the
    multi-device mesh."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.sequence_parallel import ring_attention

    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("seq",))
    B, H, T, D, W = 2, 2, 4 * n, 8, 5   # window crosses block boundaries
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D) * 0.5) for _ in range(3))
    mask = jnp.asarray((rng.rand(B, T) > 0.3).astype(np.int64))

    for m in (None, mask):
        ring_f = lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, mask=m, window=W)
        ref_f = lambda q, k, v: flash_attention_reference(
            q, k, v, m, causal, None, W)
        loss = lambda fn: (lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))))
        vf, gf = jax.value_and_grad(loss(ring_f), argnums=(0, 1, 2))(q, k, v)
        vr, gr = jax.value_and_grad(loss(ref_f), argnums=(0, 1, 2))(q, k, v)
        assert abs(float(vf - vr)) < 1e-9
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-9)


def test_layer_sliding_window_helpers_on_off_and_serde():
    """SelfAttentionLayer(attention_window=...): flash (helpers on) ==
    blockwise (helpers off) end to end through fit_batch, and the window
    survives the config JSON round-trip."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.configuration import (
        MultiLayerConfiguration)
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

    def build():
        b = (NeuralNetConfiguration.Builder().seed(5)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
        b.layer(SelfAttentionLayer(n_out=8, n_heads=2, causal=True,
                                   block_size=4, attention_window=6))
        b.layer(RnnOutputLayer(n_out=3, activation=Activation.SOFTMAX))
        return b.set_input_type(InputType.recurrent(6)).build()

    conf = build()
    rt = MultiLayerConfiguration.from_json(conf.to_json())
    assert rt.layers[0].attention_window == 6

    def run(helpers):
        net = MultiLayerNetwork(build()).init()
        rng = np.random.RandomState(3)
        x = rng.rand(4, 6, 12)
        y = np.eye(3)[rng.randint(0, 3, (4, 12))].transpose(0, 2, 1)
        with helpers_enabled_ctx(helpers):
            for _ in range(3):
                net.fit_batch(x, y)
            return float(net.score()), np.asarray(net.params())

    s_off, p_off = run(False)
    s_on, p_on = run(True)
    assert s_on == pytest.approx(s_off, abs=1e-9)
    np.testing.assert_allclose(p_on, p_off, atol=1e-9)


def test_fully_masked_rows_zero_output_and_grads():
    """A batch row whose mask drops EVERY key must produce zero output and
    zero gradients, not NaNs (the L = NEG_INF guard)."""
    q, k, v, _ = _data(B=2, T=12)
    mask = jnp.asarray(np.stack([np.zeros(12), np.ones(12)]).astype(np.int32))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask, False, None, 8, 8) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    out = flash_attention(q, k, v, mask, False, None, 8, 8)
    assert np.allclose(np.asarray(out[0]), 0.0)
    assert np.isfinite(float(val))
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert np.allclose(np.asarray(g[0]), 0.0)  # masked batch row


def test_finite_differences_through_custom_vjp():
    q, k, v, mask = _data(B=1, H=2, T=10, D=4)

    def loss(flat):
        qq = flat[:80].reshape(1, 2, 10, 4)
        kk = flat[80:160].reshape(1, 2, 10, 4)
        vv = flat[160:].reshape(1, 2, 10, 4)
        return jnp.sum(jnp.tanh(
            flash_attention(qq, kk, vv, mask, True, None, 8, 8)))

    flat = jnp.concatenate([a.reshape(-1) for a in (q, k, v)])
    ana = np.asarray(jax.grad(loss)(flat))
    eps = 1e-6
    for i in RNG.choice(flat.size, 25, replace=False):
        e = jnp.zeros_like(flat).at[i].set(eps)
        num = (float(loss(flat + e)) - float(loss(flat - e))) / (2 * eps)
        denom = max(abs(num), abs(ana[i]), 1e-8)
        assert abs(num - ana[i]) / denom < 1e-5, (i, num, ana[i])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
def test_ring_attention_with_flash_matches_classic_and_oracle(causal,
                                                              use_mask):
    """Context parallelism x fused kernel: each ring round through
    flash_attention_lse with the logaddexp merge must match BOTH the
    classic ring (einsum online-softmax) and the dense oracle — values AND
    gradients, fp64, on the 8-device mesh."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.parallel.sequence_parallel import (
        attention_reference, ring_attention)

    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("seq",))
    B, H, T, D = 2, 2, 4 * n, 8
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D) * 0.5) for _ in range(3))
    mask = jnp.asarray((rng.rand(B, T) > 0.3).astype(np.int64)) \
        if use_mask else None

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(jnp.sin(fn(q, k, v)))
        return f

    ring_f = lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal, mask=mask, use_flash=True,
        flash_bq=8, flash_bk=8)
    ring_c = lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal, mask=mask, use_flash=False)
    vf, gf = jax.value_and_grad(loss(ring_f), argnums=(0, 1, 2))(q, k, v)
    vc, gc = jax.value_and_grad(loss(ring_c), argnums=(0, 1, 2))(q, k, v)
    assert abs(float(vf - vc)) < 1e-9
    for a, b in zip(gf, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9)
    # and against the dense oracle (values)
    if mask is None:
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring_f(q, k, v)),
                                   np.asarray(ref), atol=1e-10)


def test_layer_dispatch_flash_matches_blockwise():
    """SelfAttentionLayer long-context path: helpers-on (flash kernel) must
    match helpers-off (lax.scan blockwise) — the ValidateCudnn pattern for
    the attention seam, end to end through fit_batch."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

    def run(helpers):
        b = (NeuralNetConfiguration.Builder().seed(5)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
        b.layer(SelfAttentionLayer(n_out=8, n_heads=2, causal=True,
                                   block_size=4))  # T=12 > 4: long-ctx path
        b.layer(RnnOutputLayer(n_out=3, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(6)).build()).init()
        rng = np.random.RandomState(3)
        x = rng.rand(4, 6, 12)
        y = np.eye(3)[rng.randint(0, 3, (4, 12))].transpose(0, 2, 1)
        with helpers_enabled_ctx(helpers):
            for _ in range(3):
                net.fit_batch(x, y)
            return float(net.score()), np.asarray(net.params())

    s_off, p_off = run(False)
    s_on, p_on = run(True)
    assert s_on == pytest.approx(s_off, abs=1e-9)
    np.testing.assert_allclose(p_on, p_off, atol=1e-9)


def test_layer_dispatch_flash_with_padding_mask():
    """Same equivalence with a feature mask (padded timesteps) flowing to
    the kernel's key-padding mask."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

    rng = np.random.RandomState(8)
    x = rng.rand(3, 5, 10)
    y = np.eye(2)[rng.randint(0, 2, (3, 10))].transpose(0, 2, 1)
    fm = (np.arange(10)[None, :] < np.array([10, 7, 4])[:, None]).astype(
        np.float64)
    ds = DataSet(x, y, features_mask=fm, labels_mask=fm)

    def run(helpers):
        b = (NeuralNetConfiguration.Builder().seed(9)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
        b.layer(SelfAttentionLayer(n_out=6, n_heads=2, block_size=4))
        b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(5)).build()).init()
        with helpers_enabled_ctx(helpers):
            net.fit(ds)
            return float(net.score()), np.asarray(net.params())

    s_off, p_off = run(False)
    s_on, p_on = run(True)
    assert s_on == pytest.approx(s_off, abs=1e-9)
    np.testing.assert_allclose(p_on, p_off, atol=1e-9)

# ------------------------------------------------------------------- GQA
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hk", [1, 2])
def test_gqa_forward_matches_dense_oracle(causal, hk):
    """Grouped-query FORWARD (k/v with Hk | H heads, never materializing
    the repeat — the kernels' BlockSpecs map q-head rows to kv rows) must
    match the dense oracle with the same grouping."""
    B, H, T, D = 2, 4, 23, 8
    q = jnp.asarray(RNG.randn(B, H, T, D) * 0.5)
    k, v = (jnp.asarray(RNG.randn(B, hk, T, D) * 0.5) for _ in range(2))
    mask = jnp.asarray((RNG.rand(B, T) > 0.25).astype(np.int32))
    out = flash_attention(q, k, v, mask, causal, None, 8, 8)
    ref = flash_attention_reference(q, k, v, mask, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-10)
    # explicit repeat equivalence (the grouping is _kv_row's: query head h
    # reads kv head h // (H // Hk))
    kr = jnp.repeat(k, H // hk, axis=1)
    vr = jnp.repeat(v, H // hk, axis=1)
    full = flash_attention(q, kr, vr, mask, causal, None, 8, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=1e-10)


@pytest.mark.parametrize("bwd", ["fused", "two_pass"])
@pytest.mark.parametrize("hk,causal", [(2, True), (1, True), (2, False)])
def test_gqa_backward_sums_each_groups_query_heads(bwd, hk, causal):
    """Grouped k/v heads train through the kernels: k/v are read at the
    group's row (no repeat is written), dk and dv come back a query head and
    are summed over each group. Against the dense path on repeated k/v, whose
    transpose makes the same sum (interpreted; float64, so the two differ by
    the order of summation only)."""
    B, H, T, D = 2, 4, 20, 8
    q = jnp.asarray(RNG.randn(B, H, T, D))
    k, v = (jnp.asarray(RNG.randn(B, hk, T, D)) for _ in range(2))
    w = jnp.asarray(RNG.randn(B, H, T, D))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * w)
    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, None, causal, None, 8, 8, bwd=bwd)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: flash_attention_reference(
        q, k, v, None, causal)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-10)


def test_gqa_layer_trains_and_roundtrips():
    """SelfAttentionLayer(n_kv_heads=...) trains (k/v broadcast to full
    heads keeps every backward path valid), matches an equal-weight MHA
    layer when the GQA weights are tiled, and survives config serde."""
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.configuration import (
        MultiLayerConfiguration)
    from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer

    def build(n_kv):
        b = (NeuralNetConfiguration.Builder().seed(5)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.05)).dtype("float64").list())
        b.layer(SelfAttentionLayer(n_out=8, n_heads=4, n_kv_heads=n_kv,
                                   causal=True, block_size=0))
        b.layer(RnnOutputLayer(n_out=3, activation=Activation.SOFTMAX))
        return b.set_input_type(InputType.recurrent(6)).build()

    conf = build(2)
    rt = MultiLayerConfiguration.from_json(conf.to_json())
    assert rt.layers[0].n_kv_heads == 2

    gqa = MultiLayerNetwork(build(2)).init()
    assert gqa.params_tree[0]["w_k"].shape == (6, 4)   # Hk * Dh = 2 * 2
    mha = MultiLayerNetwork(build(0)).init()
    # tile the GQA k/v weights into the MHA net: outputs must agree exactly
    pt = [dict(p) for p in gqa.params_tree]
    wk = pt[0]["w_k"].reshape(6, 2, 2)                 # (n_in, Hk, Dh)
    pt0 = dict(pt[0])
    pt0["w_k"] = jnp.repeat(wk, 2, axis=1).reshape(6, 8)
    pt0["w_v"] = jnp.repeat(pt[0]["w_v"].reshape(6, 2, 2), 2,
                            axis=1).reshape(6, 8)
    pt0["w_q"], pt0["w_o"], pt0["b"] = (pt[0]["w_q"], pt[0]["w_o"],
                                        pt[0]["b"])
    mha.params_tree = [pt0] + pt[1:]
    rng = np.random.RandomState(3)
    x = rng.rand(2, 6, 10)
    np.testing.assert_allclose(np.asarray(gqa.output(x)),
                               np.asarray(mha.output(x)), atol=1e-12)
    # and it trains without error
    y = np.eye(3)[rng.randint(0, 3, (2, 10))].transpose(0, 2, 1)
    gqa.fit_batch(x, y)
    assert np.isfinite(gqa.score())


# -------------------------------------------------- schedule config plumbing
def test_configure_takes_effect_after_first_trace():
    """The r5 hole: _CONFIG used to be read at trace time, so configure()
    after the first backward was silently ignored. The schedule is now
    threaded through the custom VJP as a non-diff argument resolved at
    call time — both schedules must produce oracle-matching grads when
    selected AFTER a first trace of the other."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    q, k, v, _ = _data(T=16)

    def g(bwd=None):
        return jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, None, True, None, 8, 8, 0,
                            bwd)))(q)

    ref = jax.grad(lambda q: jnp.sum(
        flash_attention_reference(q, k, v, None, True)))(q)
    prev = fa.configure(bwd="fused")
    try:
        np.testing.assert_allclose(np.asarray(g()), np.asarray(ref),
                                   atol=1e-10)
        fa.configure(bwd="two_pass")          # AFTER the fused trace
        np.testing.assert_allclose(np.asarray(g()), np.asarray(ref),
                                   atol=1e-10)
        # per-call override beats the global default
        np.testing.assert_allclose(np.asarray(g(bwd="fused")),
                                   np.asarray(ref), atol=1e-10)
    finally:
        fa.configure(bwd=prev[0], dq_partials=prev[1])


def test_fused_dq_partials_byte_cap_falls_back_to_two_pass(monkeypatch):
    """Above DQ_PARTIALS_MAX_BYTES the fused schedule's O(T^2*D/bk)
    partials buffer must not be allocated — the backward silently takes
    the two_pass schedule and still matches the oracle."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "DQ_PARTIALS_MAX_BYTES", 1)   # force fallback
    q, k, v, _ = _data(T=16)
    gf = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, None, True, None, 8, 8, 0, "fused")))(q)
    ref = jax.grad(lambda q: jnp.sum(
        flash_attention_reference(q, k, v, None, True)))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(ref), atol=1e-10)
