"""The hyper-connection's four Pallas calls (ops/hyper_connection.py),
interpreted on the CPU, against the layer's plain body.

The plain body is the reference: `HyperConnection.forward` with the seam
answering no. With a float32 state both sides work in float32 and differ by
the order of summation (2e-5 of the largest entry). A bfloat16 state cannot
run the plain body on the CPU (its runtime has no bf16 x bf16 -> f32 product),
so the reference there is the plain body in float32 on the same
bfloat16-rounded state and the tolerance is bfloat16's: 2e-2 of the largest
entry (`u`, `y`, `out` and every cotangent of the state are rounded once a
hop). The parameters stay float32 on both sides, so that their gradients are
sums in float32 as the kernels make them, not those sums rounded to 8 bits.
Most cases run 6 of Sinkhorn's rounds (the rounds are unrolled: they are the
interpreter's time); one case a type runs the published 20.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import decoder
from deeplearning4j_tpu.ops import hyper_connection as hc
from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx

N, D = 4, 128
F32, BF16 = jnp.float32, jnp.bfloat16


def _layer(kind, rounds=6):
    inner = decoder.GatedMLP(n_in=D, n_out=D, width=64) if kind == "mlp" else \
        decoder.LatentAttention(n_in=D, n_out=D, n_heads=2, q_lora_rank=32,
                                kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=16)
    layer = decoder.HyperConnection(layer=inner, n_streams=N,
                                    sinkhorn_iters=rounds)
    layer.name = "hc"
    return layer


def _params(layer, t):
    """Maps far from their start (scales, biases and weights that matter), so
    that every gradient is well above rounding."""
    kind = InputType.recurrent(N * D, t)
    layer.set_n_in(kind)
    p = layer.init_params(jax.random.PRNGKey(0), kind, F32)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    p["hc_a"] = 0.3 + 0.1 * jax.random.normal(k[0], (3,))
    p["hc_b_pre"] = 0.3 * jax.random.normal(k[1], (N,))
    p["hc_b_post"] = 0.3 * jax.random.normal(k[2], (N,))
    p["hc_b_res"] = 0.3 * p["hc_b_res"] + 0.5 * jax.random.normal(k[3], (N, N))
    p["norm_g"] = 1.0 + 0.1 * jax.random.normal(k[4], (D,))
    for leaf in ("hc_phi_pre", "hc_phi_post", "hc_phi_res"):
        p[leaf] = 10.0 * p[leaf]
    return p


def _value_and_grads(layer, params, x, weights, dtype, kernels):
    use = dtype if kernels else F32           # module docstring

    def loss(params, x):
        with helpers_enabled_ctx(kernels):
            out, _, _ = layer.forward(params, {}, x.astype(dtype).astype(use),
                                      train=True)
        return jnp.sum(out.astype(F32) * weights), out
    (_, out), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
        params, x)
    return out, grads


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), \
        (np.abs(got - want).max(), np.abs(want).max())


@pytest.mark.parametrize("dtype,batch,t,kind,rounds", [
    (F32, 1, 128, "mlp", 20), (F32, 2, 384, "mlp", 6),
    (F32, 2, 128, "attention", 6), (BF16, 1, 384, "mlp", 6),
    (BF16, 2, 128, "attention", 20), (BF16, 1, 256, "mlp", 6),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_kernels_match_the_plain_body_values_and_every_gradient(
        dtype, batch, t, kind, rounds):
    assert hc.token_tile(N, t, D, 2) == (256 if t == 256 else 128)
    layer = _layer(kind, rounds)
    params = _params(layer, t)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, N, t, D), F32)
    weights = jax.random.normal(jax.random.PRNGKey(3), (batch, N, t, D), F32)
    before = telemetry.registry().counter("ops.helper.hyper_connection.kernel").value
    out, (d_params, d_x) = _value_and_grads(layer, params, x, weights, dtype, True)
    assert telemetry.registry().counter(
        "ops.helper.hyper_connection.kernel").value > before
    want, (want_params, want_x) = _value_and_grads(layer, params, x, weights,
                                                   dtype, False)
    tol = 2e-5 if dtype == F32 else 2e-2
    assert out.dtype == dtype
    _close(out, want, tol)
    _close(d_x, want_x, tol)
    assert set(d_params) > set(decoder._HC_KEYS)      # the sublayer's are there
    for leaf in want_params:
        _close(d_params[leaf], want_params[leaf], tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-12), (F32, 2e-5)],
                         ids=["float64", "float32"])
def test_sinkhorn_backward_in_the_kernel_is_jax_grad_of_the_layers_sinkhorn(dtype, tol):
    logits = jax.random.normal(jax.random.PRNGKey(4), (N, N, 128), dtype)
    weights = jax.random.normal(jax.random.PRNGKey(5), (N, N, 128), dtype)
    want_value = decoder.sinkhorn(logits, 20, 1e-6)
    want = jax.grad(lambda z: jnp.sum(decoder.sinkhorn(z, 20, 1e-6) * weights))(logits)
    first = [jnp.exp(logits[i]) for i in range(N)]
    rows, kept = hc._sinkhorn_rounds(first, 20, 1e-6)
    _close(jnp.stack(rows), want_value, tol)
    d_first = hc._sinkhorn_rounds_bwd(kept, [weights[i] for i in range(N)], 1e-6)
    _close(jnp.stack([g * e for g, e in zip(d_first, first)]), want, tol)


def _counts():
    read = lambda path: telemetry.registry().counter(
        f"ops.helper.hyper_connection.{path}").value
    return read("kernel"), read("fallback")


@pytest.mark.parametrize("t,masked", [(192, False), (128, True)],
                         ids=["t=192", "mask"])
def test_a_shape_the_site_refuses_runs_the_plain_body_and_is_not_counted(t, masked):
    """What the site can see itself it checks before it asks the seam
    (ops/helpers.py): a refused call moves neither counter. (Where the seam
    is asked and answers no, `.fallback` counts:
    tests/test_ops_helpers.py::test_site_resolves_through_the_seam_and_is_counted.)"""
    layer = _layer("mlp")
    params = _params(layer, t)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, N, t, D), F32)
    mask = jnp.ones((1, t), F32) if masked else None
    before = _counts()
    with helpers_enabled_ctx(True):
        forced, _, _ = layer.forward(params, {}, x, train=True, mask=mask)
    assert _counts() == before
    with helpers_enabled_ctx(False):
        plain, _, _ = layer.forward(params, {}, x, train=True, mask=mask)
    assert np.array_equal(np.asarray(forced), np.asarray(plain))


@pytest.mark.parametrize("shape,tile", [
    ((4, 4096, 3584, 2), 256),      # the decoder cell's
    ((4, 384, 3584, 2), 128), ((4, 4096, 3584, 4), 128),
    ((4, 4096, 3500, 2), None), ((4, 4000, 3584, 2), None),
    ((5, 4096, 3584, 2), None),     # 35 maps a token pass the block's 24 rows
    ((4, 4096, 3584, 8), None),     # float64: the kernels reckon in float32
    ((4, 4096, 16384, 2), None),    # no tile of such a row fits in VMEM
])
def test_token_tile_takes_whole_tiles_that_fit_in_vmem(shape, tile):
    assert hc.token_tile(*shape) == tile
