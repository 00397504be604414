"""Whole-sequence fused Graves-LSTM scan kernel (the cuDNN-LSTM analog,
ref CudnnLSTMHelper.java:175): forward + custom-VJP backward must match the
lax.scan composition exactly (fp64) — the ValidateCudnnLSTM pattern."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.lstm_scan_fused import (
    graves_lstm_scan_pallas, graves_lstm_scan_xla)

RNG = np.random.RandomState(7)


def _data(T=9, B=16, H=8, dtype=np.float64):
    xw = jnp.asarray(RNG.randn(T, B, 4 * H).astype(dtype) * 0.5)
    b = jnp.asarray(RNG.randn(4 * H).astype(dtype) * 0.3)
    rw = jnp.asarray(RNG.randn(H, 4 * H).astype(dtype) * 0.3)
    pi, pf, po = (jnp.asarray(RNG.randn(H).astype(dtype) * 0.1)
                  for _ in range(3))
    h0 = jnp.asarray(RNG.randn(B, H).astype(dtype) * 0.2)
    c0 = jnp.asarray(RNG.randn(B, H).astype(dtype) * 0.2)
    return xw, b, rw, pi, pf, po, h0, c0


def test_forward_matches_scan_fp64():
    args = _data()
    ys_p, cs_p = graves_lstm_scan_pallas(*args)
    ys_x, cs_x = graves_lstm_scan_xla(*args)
    np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_x), atol=1e-12)
    np.testing.assert_allclose(np.asarray(cs_p), np.asarray(cs_x), atol=1e-12)


def test_non_divisible_batch_pads_exactly():
    """B not divisible by any tile candidate (e.g. 20) must be padded, not
    truncated — a truncating grid silently corrupted the trailing rows
    (caught in review; the kernel is default-on, so this was a production
    data-corruption bug)."""
    for B in (20, 12, 9):
        args = _data(T=5, B=B, H=8)
        ys_p, cs_p = graves_lstm_scan_pallas(*args)
        ys_x, cs_x = graves_lstm_scan_xla(*args)
        np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_x),
                                   atol=1e-12, err_msg=f"B={B}")
        np.testing.assert_allclose(np.asarray(cs_p), np.asarray(cs_x),
                                   atol=1e-12, err_msg=f"B={B}")

    # gradients through the padded path contribute nothing from pad rows
    args = _data(T=4, B=10, H=8)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0])) + jnp.sum(fn(*a)[1] ** 2)

    gp = jax.grad(loss(graves_lstm_scan_pallas), argnums=tuple(range(8)))(*args)
    gx = jax.grad(loss(graves_lstm_scan_xla), argnums=tuple(range(8)))(*args)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9)


@pytest.mark.parametrize("grid,K", [("bm", 1), ("bm", 2), ("bm", 5),
                                    ("tm", 1), ("tm", 2), ("tm", 5)])
def test_layout_matrix_value_and_grad_fp64(grid, K):
    """Every grid layout x K-step combination the dispatcher can pick must
    match the lax.scan oracle exactly — value AND all eight gradients
    (non-divisible B exercises the padding path in both layouts)."""
    import deeplearning4j_tpu.ops.lstm_scan_fused as m
    args = _data(T=10, B=12, H=8)

    def loss(fn):
        def f(*a):
            ys, cs = fn(*a)
            return jnp.sum(jnp.sin(ys)) + jnp.sum(cs ** 2)
        return f

    ref_v, ref_g = jax.value_and_grad(
        loss(graves_lstm_scan_xla), argnums=tuple(range(8)))(*args)
    prev = m.configure(grid=grid, k_steps=K)
    try:
        v, g = jax.value_and_grad(
            loss(graves_lstm_scan_pallas), argnums=tuple(range(8)))(*args)
    finally:
        m.configure(**prev)
    assert abs(float(v - ref_v)) < 1e-10
    for a, b in zip(g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9)


@pytest.mark.parametrize("use_dcs", [False, True])
def test_backward_matches_scan_autodiff_fp64(use_dcs):
    args = _data(T=7, B=8, H=8)

    def loss(fn):
        def f(*a):
            ys, cs = fn(*a)
            val = jnp.sum(jnp.sin(ys)) + jnp.sum(ys[-1] ** 2)
            if use_dcs:
                val = val + jnp.sum(jnp.cos(cs)) + jnp.sum(cs[-1] * 0.5)
            return val
        return f

    gp = jax.grad(loss(graves_lstm_scan_pallas),
                  argnums=tuple(range(8)))(*args)
    gx = jax.grad(loss(graves_lstm_scan_xla), argnums=tuple(range(8)))(*args)
    names = ("dxw", "db", "drw", "dpi", "dpf", "dpo", "dh0", "dc0")
    for n, a, b in zip(names, gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9,
                                   err_msg=n)


def test_fp64_finite_differences_through_kernel():
    args = _data(T=4, B=4, H=8)
    shapes = [a.shape for a in args]
    sizes = [int(np.prod(s)) for s in shapes]

    def loss(flat):
        parts, i = [], 0
        for s, n in zip(shapes, sizes):
            parts.append(flat[i:i + n].reshape(s))
            i += n
        ys, cs = graves_lstm_scan_pallas(*parts)
        return jnp.sum(jnp.tanh(ys)) + jnp.sum(cs ** 2) * 0.1

    flat = jnp.concatenate([a.reshape(-1) for a in args])
    ana = np.asarray(jax.grad(loss)(flat))
    eps = 1e-6
    for i in RNG.choice(flat.size, 30, replace=False):
        e = jnp.zeros_like(flat).at[i].set(eps)
        num = (float(loss(flat + e)) - float(loss(flat - e))) / (2 * eps)
        denom = max(abs(num), abs(ana[i]), 1e-8)
        assert abs(num - ana[i]) / denom < 1e-5, (i, num, ana[i])


def _bwd_calls(jaxpr):
    """The backward kernel's pallas_call equations (eight outputs: dxw, db,
    dRW, three peephole grads, dh0, dc0) anywhere in a closed jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and len(eqn.outvars) == 8:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _bwd_calls(sub)
    return found


def _counter(which):
    from deeplearning4j_tpu import telemetry
    return telemetry.registry().counter(
        f"ops.lstm_scan.bwd.dcs_{which}").value


@pytest.mark.parametrize("use_cs", [False, True])
def test_zero_cotangent_of_cs_is_not_streamed(use_cs):
    """The variant is chosen by the cotangent: a caller that drops cs (the
    layers do) gets a backward call without the dcs operand — no (T, B, H)
    block of zeros is built for it — and one that consumes cs streams it as
    before. Gradients match the lax.scan twin in both; the counters say
    which variant was traced."""
    T, B, H = 7, 8, 8
    args = _data(T=T, B=B, H=H)

    def loss(fn):
        def f(*a):
            ys, cs = fn(*a)
            val = jnp.sum(jnp.sin(ys))
            return val + jnp.sum(cs[-1] * 0.5) if use_cs else val
        return f

    grad = jax.grad(loss(graves_lstm_scan_pallas), argnums=tuple(range(8)))
    before = _counter("streamed"), _counter("elided")
    closed = jax.make_jaxpr(grad)(*args)
    after = _counter("streamed"), _counter("elided")
    assert (after[0] - before[0], after[1] - before[1]) == \
        ((1, 0) if use_cs else (0, 1))
    (call,) = _bwd_calls(closed.jaxpr)
    # xw with the bias, rw, pi, pf, po, h_prev, c_prev, h0, c0, dys (+ dcs)
    assert len(call.invars) == (11 if use_cs else 10)
    if not use_cs:
        made = {v: e.primitive.name for e in closed.jaxpr.eqns
                for v in e.outvars}
        stream = [v for v in call.invars
                  if getattr(v.aval, "shape", None) == (T, B, H)]
        assert len(stream) == 3                     # ys, cs, dys
        assert not any(made.get(v) == "broadcast_in_dim" for v in stream)
    gx = jax.grad(loss(graves_lstm_scan_xla), argnums=tuple(range(8)))(*args)
    for a, b in zip(grad(*args), gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-9)


@pytest.mark.parametrize("helpers_on", [True, False])
def test_train_step_sums_the_bias_gradient_in_the_kernel(helpers_on):
    """With the fused scan engaged, the lowered train step of the zoo's
    two-layer GravesLSTM net holds no reduce over a (T, B, 4H) operand —
    the bias gradient XLA used to take by reading the whole gate gradient
    again — and both layers' backwards were built without dcs. With the
    helper off the lax.scan path adds the bias outside and that reduce is
    there (the control: the pattern finds it)."""
    import re

    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

    vocab, B, T = 12, 4, 6
    with helpers_enabled_ctx(helpers_on):
        net = TextGenerationLSTM(total_unique_characters=vocab, seed=5,
                                 dtype="float64").init()
        H = next(l.n_out for l in net.layers if isinstance(l, GravesLSTM))
        xy = jax.ShapeDtypeStruct((B, vocab, T), jnp.float64)
        before = _counter("elided"), _counter("streamed")
        text = net.lower_train_step(xy, xy).as_text()
        after = _counter("elided"), _counter("streamed")
    reduces = re.findall(
        rf"stablehlo\.reduce\(.*: \(tensor<{T}x{B}x{4 * H}xf64>", text)
    if helpers_on:
        assert not reduces
        assert (after[0] - before[0], after[1] - before[1]) == (2, 0)
    else:
        assert len(reduces) == 2
        assert after == before


@pytest.mark.parametrize("grid", ["bm", "tm"])
def test_multi_batch_tile_parity(monkeypatch, grid):
    """nb > 1 in BOTH grid layouts: the VMEM state carries must be per-tile
    rows, not a shared buffer (regression: a (bt, H) scratch was clobbered
    between tiles)."""
    import deeplearning4j_tpu.ops.lstm_scan_fused as m
    monkeypatch.setattr(
        m, "_pick_bt",
        lambda B, H, db, bwd, time_major, K=1, stream_dcs=True: B // 4)
    prev = m.configure(grid=grid)
    try:
        args = _data(T=6, B=16, H=8)
        ys_p, cs_p = m.graves_lstm_scan_pallas(*args)
        ys_x, cs_x = graves_lstm_scan_xla(*args)
        np.testing.assert_allclose(np.asarray(ys_p), np.asarray(ys_x),
                                   atol=1e-12)

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)[0]))

        gp = jax.grad(loss(m.graves_lstm_scan_pallas),
                      argnums=tuple(range(8)))(*args)
        gx = jax.grad(loss(graves_lstm_scan_xla),
                      argnums=tuple(range(8)))(*args)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-9)
    finally:
        m.configure(**prev)


def test_net_level_training_identical_with_fused_scan(monkeypatch):
    """GravesLSTM + plain LSTM nets train to identical fp64 params with the
    fused-scan helper on/off (ValidateCudnnLSTM pattern, sequence form),
    including a bidirectional net (reverse path)."""
    from deeplearning4j_tpu import (
        Activation, InputType, LSTM, MultiLayerNetwork,
        NeuralNetConfiguration, RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.recurrent import (
        GravesBidirectionalLSTM, GravesLSTM)
    from deeplearning4j_tpu.ops.helpers import enable_helpers

    def run(layer_cls, on):
        enable_helpers(on)
        b = (NeuralNetConfiguration.Builder().seed(9)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.1)).dtype("float64").list())
        b.layer(layer_cls(n_out=6, activation=Activation.TANH))
        b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(3)).build()).init()
        rng = np.random.RandomState(1)
        x = rng.rand(4, 3, 7)
        y = np.eye(2)[rng.randint(0, 2, (4, 7))].transpose(0, 2, 1)
        for _ in range(5):
            net.fit_batch(x, y)
        enable_helpers(False)
        return float(net.score()), np.asarray(net.params())

    try:
        for cls in (GravesLSTM, LSTM, GravesBidirectionalLSTM):
            s_off, p_off = run(cls, False)
            s_on, p_on = run(cls, True)
            assert s_on == pytest.approx(s_off, abs=1e-10), cls.__name__
            np.testing.assert_allclose(p_on, p_off, atol=1e-10,
                                       err_msg=cls.__name__)
    finally:
        enable_helpers(False)


def test_masked_sequences_keep_the_scan_path():
    """Masks must fall back to lax.scan (the kernel has no state-hold):
    masked training with helpers on == helpers off exactly, and the seam is
    not asked — a decision that was never open is not counted as one."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu import (
        Activation, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        RnnOutputLayer, Sgd, WeightInit)
    from deeplearning4j_tpu.nn.conf.layers.recurrent import GravesLSTM
    from deeplearning4j_tpu.ops.helpers import enable_helpers

    def run(on):
        enable_helpers(on)
        b = (NeuralNetConfiguration.Builder().seed(3)
             .weight_init(WeightInit.XAVIER)
             .updater(Sgd(learning_rate=0.1)).dtype("float64").list())
        b.layer(GravesLSTM(n_out=5, activation=Activation.TANH))
        b.layer(RnnOutputLayer(n_out=2, activation=Activation.SOFTMAX))
        net = MultiLayerNetwork(
            b.set_input_type(InputType.recurrent(3)).build()).init()
        rng = np.random.RandomState(2)
        x = rng.rand(4, 3, 6)
        y = np.eye(2)[rng.randint(0, 2, (4, 6))].transpose(0, 2, 1)
        mask = (rng.rand(4, 6) > 0.3).astype(np.float64)
        mask[:, 0] = 1.0
        for _ in range(3):
            net.fit_batch(x, y, fmask=mask, lmask=mask)
        enable_helpers(False)
        return np.asarray(net.params())

    asked = lambda: [telemetry.registry().counter(
        f"ops.helper.graves_lstm_scan.{path}").value
        for path in ("kernel", "fallback")]
    before = asked()
    try:
        p_off = run(False)
        p_on = run(True)
    finally:
        enable_helpers(False)
    np.testing.assert_allclose(p_on, p_off, atol=1e-12)
    assert asked() == before


def test_fused_scan_composes_with_sharded_trainer_gspmd():
    """The fused scan kernel (default-on for TPU) must stay CORRECT inside
    ShardedTrainer's GSPMD-partitioned step: XLA reshards around the opaque
    custom call (on multi-chip tp this costs RW gathers — a perf matter to
    measure on real hardware, where a sharding-aware guard may be added —
    but never correctness)."""
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh
    from deeplearning4j_tpu.ops.helpers import enable_helpers

    vocab = 12
    rng = np.random.RandomState(0)
    idx = rng.randint(0, vocab, (8, 10))
    x = np.eye(vocab)[idx].transpose(0, 2, 1).astype(np.float64)
    y = np.eye(vocab)[np.roll(idx, -1, 1)].transpose(0, 2, 1).astype(
        np.float64)

    def build():
        return TextGenerationLSTM(total_unique_characters=vocab, seed=5,
                                  dtype="float64").init()

    net0 = build()
    ref = [float(net0.fit_on_device(x, y, steps=1)[0]) for _ in range(2)]
    enable_helpers(True)
    try:
        net1 = build()
        st = ShardedTrainer.Builder(net1).mesh(
            make_mesh(8, axes=("data", "model"), shape=(2, 4))).build()
        got = [float(st.fit_on_device(x, y, steps=1)[0]) for _ in range(2)]
    finally:
        enable_helpers(False)
    np.testing.assert_allclose(got, ref, rtol=1e-9)
