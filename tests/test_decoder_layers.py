"""The sparse decoder's layers (nn/conf/layers/decoder.py), the graph features
they forced (tied parameters, weighted losses, integer inputs, recomputation
with the cast inside the block) and the zoo class (models/xing4.py), against
the benchmark's plain reference on seeded weights, at tiny widths.

Tolerances: both sides work in float32 here (compute type float32, no
kernel), so they differ by the order of summation only: 2e-5 relative to the
largest entry for activations and gradients; 1e-5 on the parameters after
three Adam steps (a step is 3e-4 whatever the gradient's size, so a gradient
of rounding noise moves a leaf by up to a step: leaves whose gradient is
under 1e-6 of the largest are left out). Float64 where a gradient is checked
by finite differences.
"""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from deeplearning4j_tpu.nn.conf.graph_configuration import (  # noqa: E402
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.conf.layers import decoder  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf  # noqa: E402
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops import grouped_matmul as gmm  # noqa: E402
from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx  # noqa: E402


def _load(name):
    path = os.path.join(BENCH, "configs", name)
    spec = importlib.util.spec_from_file_location("t_" + name[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("xing4_29b_a4b_share8_reference.py")
prog = _load("xing4_29b_a4b_share8_program.py")
TOL = 2e-5
ID = lambda x: x

with open(os.path.join(BENCH, "tests", "tiny", "configs", "tiny_xing4.json")) as f:
    CFG = json.load(f)
# the same model uncut: every head, expert and row held by one share
FULL = dict(CFG, num_attention_heads=4, n_routed_experts=8, vocab_size=128,
            share={"index": 0, "of": 1})


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        (np.abs(a - b).max(), np.abs(b).max())


@pytest.fixture(scope="module")
def setup():
    params = ref.init_params(CFG, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, CFG["sequence_length"] + 1),
                             0, CFG["vocab_size"])
    net = prog.build(CFG, params, 0)
    return params, ids[:, :-1], ids[:, 1:], net


@pytest.fixture(scope="module")
def trained(setup):
    """One compile a side: three steps of the program's device loop, one step
    a call, with the first gradient read off Adam's second moment after the
    first (as the benchmark reads it); the reference's loss, gradients and
    three steps of its own Adam."""
    params, x, y, net = setup
    bx, by = prog.batch_of(x, y)
    head0 = np.asarray(net.params_tree[net.layer_names.index("lm_head")]["W"])
    losses = [float(net.fit_on_device(bx, by, steps=1)[0])]
    grad_sq = jax.device_get(prog.first_gradient_sq(net, CFG))
    losses += [float(net.fit_on_device(bx, by, steps=1)[0]) for _ in range(2)]

    def ref_loss_fn(p_):
        main, second = ref.losses(CFG, "f32", p_, x, y)
        return main + CFG["mtp_loss_weight"] * second, (main, second)
    with jax.default_matmul_precision("highest"):
        value_and_grad = jax.jit(jax.value_and_grad(ref_loss_fn, has_aux=True))
    p, opt, ref_losses = params, ref.init_opt(CFG, params), []
    for n in range(3):
        (loss, parts), grads = value_and_grad(p)
        if n == 0:
            ref_loss, ref_parts, ref_grads = loss, parts, grads
        opt, update = ref.apply_updater(CFG, opt, grads)
        p = jax.tree_util.tree_map(jnp.subtract, p, update)
        ref_losses.append(float(loss))
    return {"grad_sq": grad_sq, "losses": losses, "head0": head0,
            "ref_loss": ref_loss, "ref_parts": ref_parts, "ref_grads": ref_grads,
            "ref_losses": ref_losses, "ref_params": p}


def _of(params, node, layer=None):
    """The node's leaves as the program's layer takes them."""
    own = {k.split("/")[1]: v for k, v in params.items()
           if k.startswith(node + "/") and not k.endswith("router_bias")}
    if layer is not None:       # the wrapped sublayer's alone
        own = {k: v for k, v in own.items() if k not in decoder._HC_KEYS}
    return own


def _state(layer, params, node):
    """The state of the expert layer (or of the hyper-connection around it)
    with the reference's selection bias in it."""
    state = getattr(layer, "layer", layer).init_state(None)
    return dict(state, router_bias=params[f"{node}/router_bias"])


def _tokens(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ------------------------------------------------------------ layer by layer
def test_latent_attention_matches_the_reference(setup):
    params, _, _, net = setup
    layer = net.conf.nodes["b0_attn"].conf.layer
    u = _tokens(2, 2, 16, CFG["hidden_size"])
    out, _, _ = layer.forward(_of(params, "b0_attn", layer), {}, u, train=True)
    _close(out, ref.latent_attention(CFG, params, "b0_attn", u, ID))


def test_latent_attention_through_the_flash_kernel_with_padded_values(
        setup, monkeypatch):
    """The kernel path (V padded to QK's width), interpreted on the CPU."""
    params, _, _, net = setup
    layer = net.conf.nodes["b0_attn"].conf.layer
    # 16 tokens are under the width from which the layer asks for the kernel
    monkeypatch.setattr(decoder, "_DENSE_ATTENTION_MAX_T", 8)
    u = _tokens(3, 1, 16, CFG["hidden_size"])
    with helpers_enabled_ctx(True):
        out, _, _ = layer.forward(_of(params, "b0_attn", layer), {}, u, train=True)
    _close(out, ref.latent_attention(CFG, params, "b0_attn", u, ID), 1e-4)


def test_yarn_frequencies_ramp_between_interpolated_and_extrapolated():
    rs = CFG["rope_scaling"]
    got = decoder.yarn_inv_freq(64, 10000.0, rs)
    _close(got, ref.yarn_inv_freq(dict(CFG, qk_rope_head_dim=64)), 1e-6)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:8], plain[:8])                  # fast: as trained
    assert np.allclose(got[-8:], plain[-8:] / rs["factor"])  # slow: interpolated
    assert np.all(np.diff(np.asarray(got / plain)) <= 1e-6)     # rounding apart


def test_gated_mlp_matches_the_reference(setup):
    params, _, _, net = setup
    layer = net.conf.nodes["b0_mlp"].conf.layer
    u = _tokens(4, 2, 16, CFG["hidden_size"])
    out, _, _ = layer.forward(_of(params, "b0_mlp", layer), {}, u, train=True)
    _close(out, ref.gated_mlp(u, params["b0_mlp/w_g"], params["b0_mlp/w_u"],
                              params["b0_mlp/w_d"], ID))


@pytest.mark.parametrize("helpers", [False, True])
def test_routed_experts_match_the_reference(setup, helpers):
    """Both grouped products: the masked fallback and `jax.lax.ragged_dot`."""
    params, _, _, net = setup
    layer = net.conf.nodes["b1_mlp"].conf.layer
    u = _tokens(5, 2, 16, CFG["hidden_size"])
    with helpers_enabled_ctx(helpers):
        out, state, _ = layer.forward(_of(params, "b1_mlp", layer),
                                      _state(layer, params, "b1_mlp"), u, train=True)
    _close(out, ref.routed_experts(CFG, params, "b1_mlp", u, ID))
    sel, _ = ref.route(CFG, params, "b1_mlp", u)
    held = np.asarray(sel) < CFG["n_routed_experts"]
    assert int(state["assignments_absent"]) == (~held).sum()
    assert [int(v) for v in state["expert_load"]] == \
        [int((np.asarray(sel) == e).sum()) for e in range(CFG["n_routed_experts"])]


def test_routing_is_dropless_under_a_skewed_router(setup):
    """A router that sends every token to the same held experts: each
    assignment is computed (no capacity), and the gauges say how uneven."""
    params, _, _, net = setup
    layer = net.conf.nodes["b1_mlp"].conf.layer
    skew = dict(params)
    skew["b1_mlp/router_bias"] = jnp.zeros(8).at[jnp.array([1, 2])].set(10.0)
    u = _tokens(6, 1, 32, CFG["hidden_size"])
    out, state, _ = layer.forward(_of(skew, "b1_mlp", layer),
                                  _state(layer, skew, "b1_mlp"), u, train=True)
    assert [int(v) for v in state["expert_load"]] == [0, 32, 32, 0]
    assert int(state["assignments_absent"]) == 0
    _close(out, ref.routed_experts(CFG, skew, "b1_mlp", u, ID))
    gauges = layer.state_gauges(jax.device_get(state))
    # half the experts held: twice the even share is every assignment, the
    # layer has no bounded part and `moe.routed_rows.*` stay away
    assert layer.row_bound(64) >= 64
    assert gauges == {"moe.expert_load.max_over_mean": 2.0,
                      "moe.assignments_held_share": 1.0}


# 2 of 16 experts held, top 4: 200 tokens make 800 assignments of which 100
# fall to the share when the router is even; twice that in whole tiles, 256,
# is the bound and the block, and 800 is no whole number of blocks
SPARSE = dict(CFG, n_routed_experts=2, num_experts_per_tok=4,
              published=dict(CFG["published"], n_routed_experts=16))
EVERY = dict(SPARSE, n_routed_experts=16)
TOKENS = 200


def _steered(cfg, both, one):
    """Weights and tokens of which the first `both` are routed to both held
    experts, the next `one` to the first alone and the others to none (two
    rows of the router's matrix read two features that only these tokens
    carry); nothing steered where both are None."""
    params = dict(ref.init_params(cfg, jax.random.PRNGKey(3)))
    u = _tokens(17, 1, TOKENS, cfg["hidden_size"])
    if both is not None:
        w_r = params["b1_mlp/w_r"].at[:2].set(0.0)
        params["b1_mlp/w_r"] = w_r.at[0, :2].set(1.0).at[1, 0].set(1.0)
        kind = jnp.arange(TOKENS)
        u = u.at[0, :, 0].set(jnp.where(kind < both, 20.0, -20.0))
        u = u.at[0, :, 1].set(jnp.where((kind >= both) & (kind < both + one),
                                        40.0, 0.0))
    return params, u


ROUTED_ROWS = {
    # name: (configuration, steering, held assignments, within one block,
    #        the grouped product's kernel (interpreted) or its fallback)
    "well_under_the_bound": (SPARSE, (None, None), None, 1.0, False),
    "at_the_bound": (SPARSE, (128, 0), 256, 1.0, False),
    "at_the_bound_through_the_kernel": (SPARSE, (128, 0), 256, 1.0, True),
    "one_over_the_bound": (SPARSE, (128, 1), 257, 0.0, False),
    "every_token_to_both_held_experts": (SPARSE, (TOKENS, 0), 400, 0.0, False),
    "every_expert_held": (EVERY, (None, None), 800, None, False),
}


@pytest.mark.parametrize("case", ROUTED_ROWS)
def test_routed_rows_are_moved_in_blocks_of_twice_the_even_share(case, monkeypatch):
    """One block of `row_bound` rows while the held assignments fit it, a
    second where they pass it by one or fill it half, and the single pass of
    a layer that holds every expert: the same answer, counters and gradients
    as the reference and as one pass over every row."""
    cfg, steering, held, bounded, kernel = ROUTED_ROWS[case]
    params, u = _steered(cfg, *steering)
    layer = prog.zoo(cfg, 0).conf().nodes["b1_mlp"].conf.layer
    rows = TOKENS * 4
    assert layer.row_bound(rows) == (256 if cfg is SPARSE else 1664)
    leaves = ("w_r", "e_w_g", "e_w_u", "e_w_d")
    ct = _tokens(18, 1, TOKENS, cfg["hidden_size"])

    def through_layer(u_, own):
        out, state, _ = layer.forward(own, _state(layer, params, "b1_mlp"), u_,
                                      train=True)
        return jnp.sum(out * ct), (out, state)

    def through_reference(u_, own):
        p = dict(params, **{f"b1_mlp/{k}": v for k, v in own.items()})
        return jnp.sum(ref.routed_experts(cfg, p, "b1_mlp", u_, ID) * ct)

    own = _of(params, "b1_mlp", layer)
    run = jax.jit(jax.value_and_grad(through_layer, (0, 1), has_aux=True))
    with helpers_enabled_ctx(kernel):
        (_, (out, state)), (du, dp) = run(u, own)
        lowered = run.lower(u, own).as_text(debug_info=True)
    assert ("blocks/while" in lowered) == (bounded is not None)
    _close(out, ref.routed_experts(cfg, params, "b1_mlp", u, ID))
    ref_du, ref_dp = jax.grad(through_reference, (0, 1))(u, own)
    _close(du, ref_du)
    for leaf in leaves:
        _close(dp[leaf], ref_dp[leaf])
    sel, _ = ref.route(cfg, params, "b1_mlp", u)
    load = [int((np.asarray(sel) == e).sum()) for e in range(layer.held)]
    assert held in (None, sum(load))
    assert [int(v) for v in state["expert_load"]] == load
    assert int(state["assignments_absent"]) == rows - sum(load)
    gauges = layer.state_gauges(jax.device_get(state))
    assert gauges.get("moe.routed_rows.bounded") == bounded
    assert gauges.get("moe.routed_rows.held_over_bound") == \
        (None if bounded is None else sum(load) / 256)
    # one pass over every row: a bound of all of them, as where all are held
    monkeypatch.setattr(decoder.RoutedExperts, "row_bound", lambda self, rows: rows)
    whole = jax.jit(jax.value_and_grad(through_layer, (0, 1), has_aux=True))
    (_, (out_w, state_w)), (du_w, dp_w) = whole(u, own)
    assert "blocks/while" not in whole.lower(u, own).as_text(debug_info=True)
    _close(out, out_w)
    _close(du, du_w)
    for leaf in leaves:
        _close(dp[leaf], dp_w[leaf])
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), state, state_w))


def test_routed_rows_gauges_reach_the_registry_after_fit_on_device():
    """A tiny decoder whose expert layers hold 2 of 16 experts, 128 tokens a
    step: the bound is 128 of 512 assignment rows and fresh weights route
    about 64 to the share, so the step runs bounded and says so."""
    from deeplearning4j_tpu import telemetry
    cfg = dict(SPARSE, sequence_length=64)
    net = prog.build(cfg, ref.init_params(cfg, jax.random.PRNGKey(4)), 0)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 65), 0, cfg["vocab_size"])
    net.fit_on_device(*prog.batch_of(ids[:, :-1], ids[:, 1:]), steps=1)
    for node in ("b1_mlp", "mtp_mlp"):
        assert telemetry.registry().get(f"moe.routed_rows.bounded.{node}").value == 1.0
        assert 0.0 < telemetry.registry().get(
            f"moe.routed_rows.held_over_bound.{node}").value < 1.0


@pytest.mark.parametrize("node", ["b0_attn", "b1_mlp"])
def test_hyper_connection_matches_the_reference(setup, node):
    params, _, _, net = setup
    layer = net.conf.nodes[node].conf
    x = _tokens(7, 2, 16, CFG["hc_mult"], CFG["hidden_size"])   # the reference's
    major = jnp.swapaxes(x, 1, 2)              # the program's: streams lead time
    state = _state(layer, params, node) if node == "b1_mlp" else {}
    out, _, _ = layer.forward(_of(params, node), state, major, train=True)
    out = jnp.swapaxes(out, 1, 2)
    inner = (lambda u: ref.latent_attention(CFG, params, node, u, ID)) \
        if node == "b0_attn" else (lambda u: ref.routed_experts(CFG, params, node, u, ID))
    _close(out, ref.hyper_connection(CFG, params, node, x, inner))
    for got, want in zip(layer.maps(_of(params, node), major),
                         ref.hyper_maps(CFG, params, node, x)):
        lead = got.ndim - 2                   # n or n x n lead, tokens trail
        _close(jnp.moveaxis(got, tuple(range(lead)), tuple(range(2, 2 + lead))), want)


def test_sinkhorn_is_doubly_stochastic_and_differentiable():
    logits = jax.random.normal(jax.random.PRNGKey(8), (4, 4, 5), jnp.float64)
    mat = decoder.sinkhorn(logits, 20, 1e-6)
    assert np.allclose(mat.sum(1), 1.0, atol=1e-5)      # rows were last
    assert np.allclose(mat.sum(0), 1.0, atol=2e-3)
    assert np.all(np.asarray(mat) > 0)
    _close(jnp.moveaxis(mat, 2, 0), ref.sinkhorn(jnp.moveaxis(logits, 2, 0), 20, 1e-6),
           1e-12)
    weights = jax.random.normal(jax.random.PRNGKey(9), (4, 4, 5), jnp.float64)
    f = lambda z: jnp.sum(decoder.sinkhorn(z, 20, 1e-6) * weights)
    grad = jax.grad(f)(logits)
    step = 1e-6
    for idx in [(0, 0, 0), (2, 1, 3), (3, 3, 4)]:
        bump = jnp.zeros_like(logits).at[idx].set(step)
        numeric = (f(logits + bump) - f(logits - bump)) / (2 * step)
        assert abs(numeric - grad[idx]) <= 1e-6 * max(1.0, abs(numeric))


def test_token_table_and_head_keep_their_slice_of_the_vocabulary():
    table = decoder.TokenEmbedding(n_in=128, n_out=8, rows_held=64, first_row=64)
    head = decoder.TokenCrossEntropyHead(n_in=8, n_out=128, rows_held=64,
                                         first_row=64, shift=1)
    w = _tokens(10, 64, 8)
    ids = jnp.array([[64, 100, 127]])
    out, _, _ = table.forward({"W": w}, {}, ids, train=True)
    _close(out[0], w[jnp.array([0, 36, 63])])
    h = _tokens(11, 1, 3, 8)
    logits = h @ w.T
    want = -jax.nn.log_softmax(logits[0, :2])[jnp.arange(2), jnp.array([36, 63])]
    _close(head.compute_score({"W": w.T}, h, ids), want.mean())


# ------------------------------------------------------------- the whole step
def test_losses_main_and_mtp_match_the_reference(setup, trained):
    main, second = trained["ref_parts"]
    _close(trained["losses"][0], trained["ref_loss"])
    _close(trained["ref_loss"], main + CFG["mtp_loss_weight"] * second)
    assert float(second) > 0 and float(main) > 0


def test_gradients_match_the_reference(trained):
    """Magnitudes, element by element, as Adam got them (the signs are in the
    parameters after the steps, below)."""
    got, grads = trained["grad_sq"], trained["ref_grads"]
    top = max(float(jnp.abs(g).max()) for g in grads.values())
    assert set(got) == set(grads)
    for leaf, sq in got.items():
        want = np.abs(np.asarray(grads[leaf]))
        assert np.abs(np.sqrt(np.maximum(sq, 0)) - want).max() <= TOL * max(
            want.max(), 1e-4 * top), leaf
    assert all(float(jnp.abs(grads[k]).max()) == 0 for k in grads if ref.is_buffer(k))


def test_three_adam_steps_on_the_device_loop_match_the_reference(setup, trained):
    params, _, _, net = setup
    _close(trained["losses"], trained["ref_losses"])
    got, want, grads = prog.params_of(net), trained["ref_params"], trained["ref_grads"]
    top = max(float(jnp.abs(g).max()) for g in grads.values())
    for leaf in want:
        if float(jnp.abs(grads[leaf]).max()) > 1e-6 * top or ref.is_buffer(leaf):
            assert float(jnp.abs(got[leaf] - want[leaf]).max()) <= 1e-5, leaf
    assert set(prog.first_gradient_sq(net, CFG)) == set(params)
    from deeplearning4j_tpu import telemetry
    assert telemetry.registry().get("moe.assignments_held_share.b1_mlp") is not None
    assert telemetry.registry().get("moe.expert_load.max_over_mean.mtp_mlp") is not None


def test_ids_and_labels_stay_integer_where_the_layer_declares_them(setup):
    """The token table and the head declare integer ids and labels, so
    `fit_on_device` and the lowering from shapes leave those ports integer
    (they were cast to the storage type); the walk sniffs no dtype."""
    _, x, y, net = setup
    assert net._int_inputs == (True, True) and net._int_labels == (True, True)
    ids = net._arrays([np.asarray(x, np.int64)], net._int_inputs)[0]
    assert ids.dtype == jnp.int32
    shapes = net._abstract_batch(*prog.batch_of(x, y))
    assert all(a.dtype == jnp.int32 for part in shapes for a in part)


def test_a_conv_graph_fed_uint8_images_and_integer_one_hot_labels_still_trains():
    """Every other graph gets its batch in the storage type whatever it comes
    as: uint8 images and integer one-hot labels train a conv graph, by
    `fit`, `fit_on_device` and `output`, as they did before ids could stay
    integer."""
    from deeplearning4j_tpu import (
        Activation, Adam, ConvolutionLayer, InputType, LossFunction,
        NeuralNetConfiguration, OutputLayer)
    g = (NeuralNetConfiguration.Builder().seed(3).dtype("float32")
         .updater(Adam(learning_rate=1e-2)).graph_builder().add_inputs("img"))
    g.add_layer("conv", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                         activation=Activation.RELU), "img")
    g.add_layer("out", OutputLayer(n_out=3, loss_fn=LossFunction.MCXENT,
                                   activation=Activation.SOFTMAX), "conv")
    net = ComputationGraph(g.set_outputs("out").set_input_types(
        InputType.convolutional(8, 8, 1)).build()).init()
    assert net._int_inputs == (False,) and net._int_labels == (False,)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (6, 1, 8, 8)).astype(np.uint8)
    y = np.eye(3, dtype=np.int64)[rng.integers(0, 3, 6)]
    net.fit(x, y)
    first = float(net.score())
    losses = net.fit_on_device(x, y, steps=20)
    assert np.all(np.isfinite(losses)) and losses[-1] < first
    assert net.output(x).dtype == net.dtype
    assert net._abstract_batch(x, y)[0][0].dtype == net.dtype


@pytest.mark.parametrize("net_kind", ["graph", "multilayer"])
def test_recomputation_with_bf16_casts_inside_the_block(net_kind):
    """With `remat` the bfloat16 copy of a layer's weights is made inside the
    recomputed block (no whole-tree cast): the same losses as without."""
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.layers.feedforward import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.common.enums import Activation, LossFunction
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    def make(remat):
        b = (NeuralNetConfiguration.Builder().seed(7).compute_dtype("bfloat16")
             .remat(remat).updater(Sgd(learning_rate=0.1)))
        dense = DenseLayer(n_in=6, n_out=8, activation=Activation.TANH)
        out = OutputLayer(n_in=8, n_out=3, activation=Activation.SOFTMAX,
                          loss_fn=LossFunction.MCXENT)
        if net_kind == "multilayer":
            return MultiLayerNetwork(b.list().layer(dense).layer(out).set_input_type(
                InputType.feed_forward(6)).build()).init()
        conf = (b.graph_builder().add_inputs("in").add_layer("d", dense, "in")
                .add_layer("out", out, "d").set_outputs("out")
                .set_input_types(InputType.feed_forward(6)).build())
        return ComputationGraph(conf).init()

    x = np.asarray(_tokens(20, 4, 6))
    y = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
    plain, recomputed = (make(r).fit_on_device(x, y, steps=3) for r in (False, True))
    assert np.allclose(plain, recomputed, rtol=1e-6)
    text = make(True).lower_train_step(x, y, steps=1).as_text()
    assert "bf16" in text


# -------------------------------------------------------------------- shares
def _share(full, index, of=2):
    """Share `index` of `of` of the uncut weights: its heads' columns and
    rows, its experts, its rows of the vocabulary; the rest is on every chip."""
    m = ref.dims(FULL)
    h, e, v = m["heads"] // of, m["experts"] // of, m["vocab"] // of
    heads, out = slice(index * h, (index + 1) * h), {}
    for leaf, w in full.items():
        tail = leaf.split("/")[1]
        if tail == "w_qb":
            w = w.reshape(w.shape[0], m["heads"], -1)[:, heads].reshape(w.shape[0], -1)
        elif tail == "w_kvb":
            w = w.reshape(w.shape[0], m["heads"], -1)[:, heads].reshape(w.shape[0], -1)
        elif tail == "w_o":
            w = w.reshape(m["heads"], m["v"], -1)[heads].reshape(h * m["v"], -1)
        elif tail in ("e_w_g", "e_w_u", "e_w_d"):
            w = w[index * e:(index + 1) * e]
        elif leaf == "embed/W":
            w = w[index * v:(index + 1) * v]
        elif leaf == "lm_head/W":
            w = w[:, index * v:(index + 1) * v]
        out[leaf] = w
    return out


@pytest.fixture(scope="module")
def shares():
    full = ref.init_params(FULL, jax.random.PRNGKey(3))
    nets = [prog.build(dict(CFG, share={"index": i, "of": 2}), _share(full, i), 0)
            for i in range(2)]
    return full, nets


def test_the_shares_attention_adds_up_to_the_uncut_layer(shares):
    full, nets = shares
    u = _tokens(12, 2, 16, CFG["hidden_size"])
    parts = []
    for i, net in enumerate(nets):
        layer = net.conf.nodes["b0_attn"].conf.layer
        parts.append(layer.forward(_of(_share(full, i), "b0_attn", layer), {}, u,
                                   train=True)[0])
    _close(sum(parts), ref.latent_attention(FULL, full, "b0_attn", u, ID))


def test_the_shares_experts_add_up_with_the_shared_expert_counted_once(shares):
    full, nets = shares
    u = _tokens(13, 2, 16, CFG["hidden_size"])
    total, absent = 0, 0
    for i, net in enumerate(nets):
        layer = net.conf.nodes["b1_mlp"].conf.layer
        assert (layer.first_expert, layer.held, layer.n_experts) == (4 * i, 4, 8)
        mine = _share(full, i)
        out, state, _ = layer.forward(_of(mine, "b1_mlp", layer),
                                      _state(layer, mine, "b1_mlp"), u, train=True)
        total = total + out
        absent += int(state["assignments_absent"])
    shared = ref.gated_mlp(u, full["b1_mlp/s_w_g"], full["b1_mlp/s_w_u"],
                           full["b1_mlp/s_w_d"], ID)
    _close(total - shared, ref.routed_experts(FULL, full, "b1_mlp", u, ID))
    # every assignment was held by exactly one share
    assert absent == 2 * 16 * CFG["num_experts_per_tok"]
    # the dense MLP is on every chip alike: counted once
    dense = nets[0].conf.nodes["b0_mlp"].conf.layer
    _close(dense.forward(_of(full, "b0_mlp", dense), {}, u, train=True)[0],
           ref.gated_mlp(u, full["b0_mlp/w_g"], full["b0_mlp/w_u"],
                         full["b0_mlp/w_d"], ID))


def test_the_shares_logits_are_the_uncut_heads_columns(shares):
    full, nets = shares
    h = _tokens(14, 2, 16, CFG["hidden_size"])
    parts = []
    for i, net in enumerate(nets):
        head = net.conf.nodes["lm_head"].conf
        assert (head.first_row, head.rows) == (64 * i, 64)
        parts.append(head.forward({"W": _share(full, i)["lm_head/W"]}, {}, h,
                                  train=True)[0])
    _close(jnp.concatenate(parts, axis=-1), h @ full["lm_head/W"])
    table = nets[1].conf.nodes["embed"].conf
    ids = jnp.array([[64, 127]])
    _close(table.forward({"W": _share(full, 1)["embed/W"]}, {}, ids, train=True)[0],
           full["embed/W"][ids])


# ----------------------------------------------------------- graph and serde
LAYERS = [
    decoder.RMSNorm(n_in=8, eps=1e-5),
    decoder.TokenEmbedding(n_in=128, n_out=8, rows_held=64, first_row=64),
    decoder.LatentAttention(n_in=8, n_out=8, n_heads=4, heads_held=2,
                            rope_scaling=CFG["rope_scaling"]),
    decoder.GatedMLP(n_in=8, n_out=8, width=16),
    decoder.RoutedExperts(n_in=8, n_out=8, n_experts=8, experts_held=4,
                          first_expert=4, top_k=2, width=16),
    decoder.HyperConnection(layer=decoder.GatedMLP(n_in=8, n_out=8, width=16),
                            n_streams=2, sinkhorn_iters=5),
    decoder.MTPInput(n_in=16, n_out=8),
    decoder.TokenCrossEntropyHead(n_in=8, n_out=128, rows_held=64, shift=1,
                                  loss_weight=0.3),
]


@pytest.mark.parametrize("layer", LAYERS, ids=lambda l: type(l).__name__)
def test_layer_config_round_trips_through_json(layer):
    back = BaseLayerConf.from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(back) is type(layer) and back.to_dict() == layer.to_dict()


def test_graph_with_tied_parameters_round_trips_saves_loads_and_updates_one_copy(
        setup, trained, tmp_path):
    _, _, _, net = setup
    conf = ComputationGraphConfiguration.from_json(net.conf.to_json())
    assert conf.nodes["mtp_head"].tied_to == "lm_head"
    assert conf.nodes["mtp_embed"].tied_to == "embed"
    tied = [net.layer_names.index(n) for n in ("mtp_head", "mtp_embed")]
    assert all(net.params_tree[i] == {} for i in tied)
    assert net.num_params() == sum(int(np.prod(s)) for k, s in
                                   ref.param_shapes(CFG).items()
                                   if not ref.is_buffer(k))
    # three steps later the one copy has moved and the tied nodes hold none
    assert np.abs(np.asarray(prog.params_of(net)["lm_head/W"])
                  - trained["head0"]).max() > 0
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    path = str(tmp_path / "xing4.zip")
    ModelSerializer.write_model(net, path)
    back = ModelSerializer.restore(path)
    assert isinstance(back, ComputationGraph)
    assert all(back.params_tree[i] == {} for i in tied)
    _close(back.params(), net.params(), 0)
    with pytest.raises(ValueError, match="tied"):
        from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.input_type import InputType
        (NeuralNetConfiguration.Builder().graph_builder().add_inputs("in")
         .add_layer("a", decoder.RMSNorm(n_in=4), "in", tied_to="nowhere")
         .set_outputs("a").set_input_types(InputType.recurrent(4, 3)).build())


def test_zoo_builds_the_published_model_without_allocating_it():
    """The published 40 layers from the config.json keys: 30.28 B parameters
    by shape alone, and the share configuration through the same code."""
    from deeplearning4j_tpu.models import Xing4
    conf = Xing4(sequence_length=4096).conf()
    types = conf.node_input_types()
    total = 0
    for name, node in conf.nodes.items():
        if node.kind != "layer" or node.tied_to is not None:
            continue
        shapes = jax.eval_shape(lambda n=node, t=types[name][0]: n.conf.init_params(
            jax.random.PRNGKey(0), t, jnp.float32))
        total += sum(int(np.prod(s.shape)) for s in shapes.values())
    assert sum(1 for n in conf.nodes if n.endswith("_attn")) == 41     # 40 + MTP
    assert isinstance(conf.nodes["b1_mlp"].conf.layer, decoder.GatedMLP)
    assert isinstance(conf.nodes["b2_mlp"].conf.layer, decoder.RoutedExperts)
    assert 30.2e9 < total < 30.4e9, total
    with open(os.path.join(BENCH, "configs", "xing4_29b_a4b_share8.json")) as f:
        cut = json.load(f)
    net = ComputationGraph(prog.zoo(cut, 0).conf())
    types = net.conf.node_input_types()
    shapes = {}
    for name in net.layer_names:
        node = net.conf.nodes[name]
        if node.tied_to is None:
            got = jax.eval_shape(lambda n=node, t=types[name][0]: n.conf.init_params(
                jax.random.PRNGKey(0), t, jnp.float32))
            shapes.update({f"{name}/{k}": v.shape for k, v in got.items()})
    want = {k: v for k, v in ref.param_shapes(cut).items() if not ref.is_buffer(k)}
    assert shapes == want
    assert 789e6 < sum(int(np.prod(s)) for s in want.values()) < 791e6


def test_grouped_matmul_paths_agree_and_zero_the_rows_past_the_groups():
    x, w = _tokens(15, 12, 6), _tokens(16, 3, 6, 5)
    sizes = jnp.array([4, 0, 5], jnp.int32)
    want = np.zeros((12, 5))
    want[:4] = x[:4] @ w[0]
    want[4:9] = x[4:9] @ w[2]
    _close(gmm.grouped_matmul_xla(x, w, sizes), want)
    _close(gmm.grouped_matmul_kernel(x, w, sizes), want)
    grads = [jax.grad(lambda x_, w_: jnp.sum(jnp.sin(f(x_, w_, sizes))), (0, 1))(x, w)
             for f in (gmm.grouped_matmul_xla, gmm.grouped_matmul_kernel)]
    _close(grads[0][0], grads[1][0])
    _close(grads[0][1], grads[1][1])
