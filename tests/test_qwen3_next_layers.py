"""Qwen3-Next's layers (nn/conf/layers/decoder.py: the pre-norm residual
block, the Gated DeltaNet and gated attention mixers, the expert layer's
softmax scores, gated shared expert and constant gate), the recurrence behind
the helper seam (ops/gated_delta_rule.py) and the zoo class
(models/qwen3_next.py), against the benchmark's plain reference on seeded
weights, at tiny widths.

Tolerances: both sides work in float32 here (compute type float32, no
kernel), so they differ by the order of summation only: 2e-5 relative to the
largest entry for activations and gradients, 1e-5 between the chunked
recurrence's kernels (interpreted here) and the token-by-token scan (the
chunked form inverts a triangular system and takes a decay as the `exp` of the
sum of the g it spans where the scan multiplies them one by one: rounding of a
few float32 ulps a chunk), and between a chunk's T, W, U_0 and a float64
solve, whatever type the keys and values come in; 1e-5 on
the parameters after three Adam steps (leaves whose gradient is rounding
noise are left out, as in test_decoder_layers.py).
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

from deeplearning4j_tpu.nn.conf.layers import decoder  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf  # noqa: E402
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.ops import gated_delta_rule as gdr  # noqa: E402
from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx  # noqa: E402


def _load(name):
    path = os.path.join(BENCH, "configs", name)
    spec = importlib.util.spec_from_file_location("t_" + name[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("qwen3_next_80b_a3b_share16_reference.py")
prog = _load("qwen3_next_80b_a3b_share16_program.py")
TOL = 2e-5
ID = lambda x: x

with open(os.path.join(BENCH, "tests", "tiny", "configs", "tiny_qwen3_next.json")) as f:
    CFG = json.load(f)
# the same model uncut: every expert and row held by one share, gate trained
FULL = dict(CFG, num_experts=16, vocab_size=64, share={"index": 0, "of": 1})
SHARES = CFG["share"]["of"]


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        (np.abs(a - b).max(), np.abs(b).max())


def _tokens(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _of(params, node, wrapped=True):
    """The node's leaves as the program's layer takes them (the wrapped
    sublayer's alone, or with the block's own norm)."""
    return {k.split("/")[1]: v for k, v in params.items()
            if k.startswith(node + "/") and not (wrapped and k.endswith("/norm_g"))}


@pytest.fixture(scope="module")
def setup():
    params = ref.init_params(CFG, jax.random.PRNGKey(0))
    # gains off their starting point, so that a norm that forgot its 1 + g
    # or a gate that forgot its sigmoid shows
    params = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape, v.dtype)
              if k.split("/")[1] in ("norm_g", "q_norm_g", "k_norm_g", "g",
                                     "o_norm_w", "dt_bias") else v
              for i, (k, v) in enumerate(params.items())}
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, CFG["sequence_length"] + 1),
                             0, CFG["vocab_size"])
    net = prog.build(CFG, params, 0)
    return params, ids[:, :-1], ids[:, 1:], net


# ------------------------------------------------------------ the recurrence
def _rule_inputs(t=37, b=2, h=3, d_k=8, d_v=6, dtype=jnp.float32, h_k=None):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q = unit(draw(ks[0], b, t, h_k or h, d_k)) * d_k ** -0.5
    k = unit(draw(ks[1], b, t, h_k or h, d_k))
    v = draw(ks[2], b, t, h, d_v)
    g = -2.0 * jax.nn.softplus(draw(ks[3], b, t, h))
    beta = jax.nn.sigmoid(draw(ks[4], b, t, h))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)


def _value_and_grads(rule, args):
    w = _tokens(9, *args[2].shape)
    return jax.value_and_grad(lambda *a: jnp.sum(rule(*a) * w),
                              argnums=(0, 1, 2, 3, 4))(*args)


def _kernel_matches(args, chunk, reference=gdr.gated_delta_rule_scan):
    """The kernels (interpreted) against the scan: values and all five
    gradients, float32 at 1e-5."""
    _close(gdr.gated_delta_rule(*args, chunk), reference(*args), 1e-5)
    got = _value_and_grads(lambda *a: gdr.gated_delta_rule(*a, chunk), args)[1]
    for a, b in zip(got, _value_and_grads(reference, args)[1]):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_recurrence_matches_the_token_by_token_scan(chunk):
    """Values and every gradient, at a length (37) that is no whole number
    of chunks of either size; at 4 a sequence is two tiles of the grid, so the
    state and its cotangent cross a tile's border in VMEM, and the second
    sequence starts from zero."""
    _kernel_matches(_rule_inputs(), chunk)


def test_grouped_key_heads_against_the_scan_on_repeated_q_and_k():
    """4 value heads on 2 key heads: the kernel maps a value head to its key
    head, and dq, dk come summed over the group."""
    args = _rule_inputs(h=4, h_k=2)

    def repeated(q, k, *rest):
        return gdr.gated_delta_rule_scan(jnp.repeat(q, 2, axis=2),
                                         jnp.repeat(k, 2, axis=2), *rest)
    _kernel_matches(args, 4, repeated)
    _close(gdr.gated_delta_rule_scan(*args), repeated(*args), 0.0)


def test_the_cells_widths_over_chunk_and_tile_borders():
    """d_k = d_v = 128 in the chunks the layer gets, two sequences of ten
    chunks (nine and a few tokens): two tiles of the grid, two value heads on
    their key head."""
    _kernel_matches(_rule_inputs(t=9 * gdr.CHUNK + 5, h=2, h_k=1, d_k=128,
                                 d_v=128), gdr.CHUNK)


def test_the_scan_is_the_references_recurrence():
    args = _rule_inputs(t=70)       # over one of the reference's segments
    _close(gdr.gated_delta_rule_scan(*args), ref.delta_rule(*args))


def test_strong_decays_stay_finite_in_the_chunked_form():
    """g of -40 a token: the cumulative sum passes float32's exp range inside
    one chunk; only differences are ever exponentiated."""
    q, k, v, g, beta = _rule_inputs(t=64)
    g = jnp.full_like(g, -40.0)
    out = gdr.gated_delta_rule(q, k, v, g, beta, 64)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, gdr.gated_delta_rule_scan(q, k, v, g, beta), 1e-5)
    grads = jax.grad(lambda *a: jnp.sum(gdr.gated_delta_rule(*a, 64)),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in grads)


def test_bfloat16_operands_keep_the_state_in_float32():
    args = _rule_inputs(t=48, dtype=jnp.bfloat16)
    out = gdr.gated_delta_rule(*args, 16)
    assert out.dtype == jnp.bfloat16
    want = gdr.gated_delta_rule_scan(*(a.astype(jnp.float32) for a in args))
    _close(out.astype(jnp.float32), want, 0.03)
    grads = _value_and_grads(lambda *a: gdr.gated_delta_rule(*a, 16), args)[1]
    assert [a.dtype for a in grads] == [a.dtype for a in args]


def _one_chunk(dtype, alike, rates, chunk=128, d=16):
    """A chunk of one head whose keys share `alike` of a common direction and
    whose log-decays are about -`rates[0]` a token in its first half and
    -`rates[1]` in its second; k and v rounded to `dtype`."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    k = alike * draw(ks[0], 1, d) + (1 - alike) * draw(ks[1], chunk, d)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = draw(ks[2], chunk, d).astype(dtype)
    g = -jnp.repeat(jnp.asarray(rates, jnp.float32), chunk // 2) \
        * jax.random.uniform(ks[3], (1, chunk), jnp.float32, 0.5, 1.5)
    beta = jax.random.uniform(ks[4], (1, chunk), jnp.float32, 0.4, 0.8)
    return k, v, g, beta


@pytest.mark.parametrize("alike,rates", [(0.8, (0.01, 0.01)), (0.5, (30.0, 0.1))],
                         ids=["keys alike, weak decays", "strong decays first"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda t: jnp.dtype(t).name)
def test_a_chunks_solve_is_a_float32_solve_whatever_the_operands_type(
        dtype, alike, rates):
    """T, W and U_0 as the kernels make them (the same functions, on arrays)
    against a float64 triangular solve of the same system: the decays, the
    system and its inverse are float32 where k and v come in bfloat16 too.
    Keys that are alike under weak decays make a system whose powers grow
    without bound while its inverse stays small; after a half chunk of strong
    decays the cumulative sums are in the thousands, and the decays between
    the neighbours that follow are their differences."""
    from jax.scipy.linalg import solve_triangular
    k, v, g, beta = _one_chunk(dtype, alike, rates)
    masks = gdr._masks(k.shape[0])
    decays = gdr._decays(g, masks)
    _, inverse, w, u0 = gdr._solved(k, v, gdr._mm(k, k, gdr._NT), beta, decays,
                                    masks)
    k64, v64, g64, beta64 = (np.asarray(a, np.float64) for a in (k, v, g, beta))
    total = np.cumsum(g64[0])
    decay = np.exp(np.minimum(total[:, None] - total[None, :], 0.0))
    system = np.tril(beta64[0][:, None] * decay * (k64 @ k64.T), -1)
    _close(decays[0], np.tril(decay), 1e-6)
    eye = np.eye(len(total))
    want = np.asarray(solve_triangular(eye + system, eye, lower=True))
    _close(inverse, want, 1e-5)
    _close(w, want @ ((beta64[0] * np.exp(total))[:, None] * k64), 1e-5)
    _close(u0, want @ (beta64[0][:, None] * v64), 1e-5)


# ------------------------------------------------------------ layer by layer
def test_gated_delta_net_matches_the_reference(setup):
    params, _, _, net = setup
    layer = net.conf.nodes["b0_mix"].conf.layer
    assert isinstance(layer, decoder.GatedDeltaNet)
    u = _tokens(2, 2, 24, CFG["hidden_size"])
    out, _, _ = layer.forward(_of(params, "b0_mix"), {}, u, train=True)
    _close(out, ref.gated_delta_net(CFG, params, "b0_mix", u, ID))


def test_gated_delta_net_through_the_chunked_form(setup):
    """The seam forced: the layer on the kernels (interpreted) against the
    same layer on the scan, value and gradient."""
    params, _, _, net = setup
    layer = net.conf.nodes["b1_mix"].conf.layer
    p, u = _of(params, "b1_mix"), _tokens(4, 2, 24, CFG["hidden_size"])

    def loss(p_, u_):
        return jnp.sum(jnp.sin(layer.forward(p_, {}, u_, train=True)[0]))
    with helpers_enabled_ctx(False):
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    with helpers_enabled_ctx(True):
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    # 1e-4: the decay's four rates a layer get their gradient as a sum over
    # every token and head, in another order on each side
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)


def test_gated_attention_matches_the_reference(setup):
    """Grouped k/v heads (4 on 2), rotary on a quarter of each head, the
    norm a head, the output gate."""
    params, _, _, net = setup
    layer = net.conf.nodes["b3_mix"].conf.layer
    assert isinstance(layer, decoder.GatedAttention)
    assert (layer.n_heads, layer.n_kv_heads, layer.head_dim, layer.rotary_dim) \
        == (4, 2, 16, 4)
    u = _tokens(5, 2, 24, CFG["hidden_size"])
    out, _, _ = layer.forward(_of(params, "b3_mix"), {}, u, train=True)
    _close(out, ref.gated_attention(CFG, params, "b3_mix", u, ID))


def test_gated_attention_through_the_flash_kernel_on_grouped_heads(setup, monkeypatch):
    """The kernel path, interpreted on the CPU: k/v stay on their 2 heads,
    value and gradient against the dense path."""
    params, _, _, net = setup
    layer = net.conf.nodes["b3_mix"].conf.layer
    p, u = _of(params, "b3_mix"), _tokens(6, 2, 24, CFG["hidden_size"])

    def loss(p_, u_):
        return jnp.sum(jnp.sin(layer.forward(p_, {}, u_, train=True)[0]))
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    # 24 tokens are under the width from which the layer asks for the kernel
    monkeypatch.setattr(decoder, "_DENSE_ATTENTION_MAX_T", 8)
    with helpers_enabled_ctx(True):
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)


def _experts_layer(net, node="b1_mlp"):
    layer = net.conf.nodes[node].conf.layer
    return layer, layer.init_state(None)


def test_routed_experts_with_softmax_scores_and_a_gated_shared_expert(setup):
    params, _, _, net = setup
    layer, state = _experts_layer(net)
    assert (layer.scoring_func, layer.shared_gate, layer.train_gate) \
        == ("softmax", True, False)
    u = _tokens(7, 2, 24, CFG["hidden_size"])
    out, new_state, _ = layer.forward(_of(params, "b1_mlp"), state, u, train=True)
    _close(out, ref.experts(CFG, params, "b1_mlp", u, ID))
    sel, _ = ref.route(CFG, params, "b1_mlp", u)
    held = (np.asarray(sel) < CFG["num_experts"]).sum()
    assert int(new_state["expert_load"].sum()) == held
    assert int(new_state["assignments_absent"]) == sel.size - held


def test_a_constant_gate_sends_nothing_to_the_router_and_leaves_the_experts_alone(setup):
    """`train_gate=False`: the gradient of `w_r` is exactly zero and the
    experts' gradients are those of `train_gate=True`; the hidden state's
    gradient loses the gate's part. The reference agrees on both settings."""
    import dataclasses
    params, _, _, net = setup
    constant, state = _experts_layer(net)
    trained = dataclasses.replace(constant, train_gate=True)
    p, u = _of(params, "b1_mlp"), _tokens(8, 2, 24, CFG["hidden_size"])
    w = _tokens(18, 2, 24, CFG["hidden_size"])

    def grads(layer):
        return jax.grad(lambda p_, u_: jnp.sum(
            layer.forward(p_, state, u_, train=True)[0] * w), argnums=(0, 1))(p, u)
    (gp_c, gu_c), (gp_t, gu_t) = grads(constant), grads(trained)
    assert float(jnp.abs(gp_c["w_r"]).max()) == 0.0
    assert float(jnp.abs(gp_t["w_r"]).max()) > 0.0
    for key in ("e_w_g", "e_w_u", "e_w_d", "s_w_g", "s_w_u", "s_w_d", "s_gate"):
        _close(gp_c[key], gp_t[key], 1e-6)
    assert float(jnp.abs(gu_c - gu_t).max()) > 1e-6 * float(jnp.abs(gu_t).max())
    for cfg, (gp, gu) in ((CFG, (gp_c, gu_c)),
                          (dict(CFG, train_gate=True), (gp_t, gu_t))):
        want_p, want_u = jax.grad(lambda p_, u_: jnp.sum(
            ref.experts(cfg, p_, "b1_mlp", u_, ID) * w), argnums=(0, 1))(params, u)
        _close(gu, want_u)
        for key, value in gp.items():
            if key == "w_r" and not cfg["train_gate"]:
                assert float(jnp.abs(want_p["b1_mlp/w_r"]).max()) == 0.0
            else:
                _close(value, want_p[f"b1_mlp/{key}"])


def test_sigmoid_scores_without_a_gate_are_todays_layer():
    """The defaults are the decoder's: no new leaf, the same route."""
    layer = decoder.RoutedExperts(n_in=8, n_out=8, n_experts=8, experts_held=4,
                                  top_k=2, width=16)
    assert (layer.scoring_func, layer.shared_gate, layer.train_gate) \
        == ("sigmoid", False, True)
    p = layer.init_params(jax.random.PRNGKey(0), None)
    assert "s_gate" not in p
    u = _tokens(3, 10, 8)
    sel, w = layer.route(p, layer.init_state(None), u)
    s = jax.nn.sigmoid(u @ p["w_r"])
    picked = jnp.take_along_axis(s, sel, axis=-1)
    _close(w, picked / picked.sum(-1, keepdims=True))
    with pytest.raises(ValueError, match="scoring_func"):
        decoder.RoutedExperts(n_in=8, n_out=8, scoring_func="tanh")


def test_pre_norm_residual_block_matches_the_reference(setup):
    params, _, _, net = setup
    block = net.conf.nodes["b0_mix"].conf
    assert isinstance(block, decoder.PreNormResidual) and block.zero_centred
    x = _tokens(10, 2, 24, CFG["hidden_size"])
    out, _, _ = block.forward(_of(params, "b0_mix", wrapped=False), {}, x, train=True)
    want = x + ref.gated_delta_net(
        CFG, params, "b0_mix",
        ref.norm(x, params["b0_mix/norm_g"], CFG["rms_norm_eps"]), ID)
    _close(out, want)
    plain = decoder.RMSNorm(n_in=CFG["hidden_size"], zero_centred=True)
    g = params["final_norm/g"]
    _close(plain.forward({"g": g}, {}, x, train=True)[0],
           ref.norm(x, g, CFG["rms_norm_eps"]))
    assert float(jnp.abs(plain.init_params(None, None)["g"]).max()) == 0.0


# ------------------------------------------------------------------ the share
def test_the_shares_experts_add_up_with_the_shared_expert_counted_once():
    """Section 4 of the model-configs guide: the routed parts of all the
    shares, with the shared expert (which every chip computes alike) counted
    once, add up to what the uncut reference gives for the whole layer."""
    full = ref.init_params(FULL, jax.random.PRNGKey(11))
    u = _tokens(13, 2, 24, CFG["hidden_size"])
    held = CFG["num_experts"]
    total, absent = 0, 0
    for i in range(SHARES):
        cfg = dict(CFG, share={"index": i, "of": SHARES})
        layer = ComputationGraph(prog.zoo(cfg, 0).conf()).conf.nodes["b1_mlp"].conf.layer
        assert (layer.first_expert, layer.held, layer.n_experts) == (held * i, held, 16)
        mine = {k: v[held * i:held * (i + 1)] if k.split("/")[1].startswith("e_w_")
                else v for k, v in full.items()}
        out, state, _ = layer.forward(_of(mine, "b1_mlp"), layer.init_state(None), u,
                                      train=True)
        total = total + out
        absent += int(state["assignments_absent"])
        # the reference's share is the same part
        _close(out, ref.experts(cfg, mine, "b1_mlp", u, ID))
    shared = jax.nn.sigmoid(u @ full["b1_mlp/s_gate"]) * ref.gated_mlp(
        u, full["b1_mlp/s_w_g"], full["b1_mlp/s_w_u"], full["b1_mlp/s_w_d"], ID)
    _close(total - (SHARES - 1) * shared, ref.experts(FULL, full, "b1_mlp", u, ID))
    # every assignment was held by exactly one share
    assert absent == (SHARES - 1) * 2 * 24 * CFG["num_experts_per_tok"]


def test_the_shares_logits_are_the_uncut_heads_columns():
    full = ref.init_params(FULL, jax.random.PRNGKey(12))
    h = _tokens(14, 2, 24, CFG["hidden_size"])
    rows = CFG["vocab_size"]
    parts = []
    for i in range(2):
        cfg = dict(CFG, share={"index": i, "of": SHARES})
        conf = ComputationGraph(prog.zoo(cfg, 0).conf()).conf
        head, table = conf.nodes["lm_head"].conf, conf.nodes["embed"].conf
        assert (head.first_row, head.rows, table.first_row) == (rows * i, rows, rows * i)
        parts.append(head.forward(
            {"W": full["lm_head/W"][:, rows * i:rows * (i + 1)]}, {}, h, train=True)[0])
    _close(jnp.concatenate(parts, axis=-1), h @ full["lm_head/W"])


# ------------------------------------------------------- the model, trained
def test_zoo_model_through_fit_on_device_follows_the_references_steps(setup):
    """Loss and first gradient (read off Adam's second moment, as the
    benchmark reads it) of step 1, the losses of three steps, one a call,
    then a call of two; the parameters after all five."""
    params, x, y, _ = setup
    net = prog.build(CFG, params, 0)
    losses = [float(net.fit_on_device(x, y, steps=1)[0])]
    grad_sq = jax.device_get(prog.first_gradient_sq(net, CFG))
    losses += [float(net.fit_on_device(x, y, steps=1)[0]) for _ in range(2)]
    losses += [float(v) for v in net.fit_on_device(x, y, steps=2)]
    loss, grads, _ = ref.loss_and_grads(CFG, "f32", params, {}, x, y)
    assert abs(losses[0] - float(loss)) <= TOL * abs(float(loss))
    biggest = max(float(jnp.abs(g).max()) for g in grads.values())
    for leaf, g in grads.items():
        got = np.sqrt(np.maximum(np.asarray(grad_sq[leaf], np.float64), 0.0))
        assert np.abs(got - np.abs(np.asarray(g))).max() <= TOL * biggest, leaf
    assert all(float(jnp.abs(grads[k]).max()) == 0.0
               for k in grads if k.endswith("/w_r"))
    p, opt, ref_losses = dict(params), ref.init_opt(CFG, params), []
    for _ in range(5):
        p, opt, _, step_loss = ref.train_step(CFG, "f32", p, opt, {}, x, y)
        ref_losses.append(float(step_loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    after = prog.params_of(net)
    assert set(after) == set(p)
    for leaf, value in p.items():
        if float(jnp.abs(grads[leaf]).max()) > 1e-6 * biggest:
            assert float(jnp.abs(after[leaf] - value).max()) <= 1e-5 * max(
                float(jnp.abs(value).max()), 1.0), leaf


def test_zoo_builds_the_published_model_without_allocating_it():
    """The published 48 layers from the config.json keys: 79.7 B parameters
    by shape alone, three DeltaNet layers to each attention layer, and the
    share's configuration through the same code: leaf for leaf the
    reference's shapes, 625.7 M parameters."""
    from deeplearning4j_tpu.models import Qwen3Next
    conf = Qwen3Next().conf()
    types = conf.node_input_types()
    kinds = [type(conf.nodes[f"b{i}_mix"].conf.layer).__name__ for i in range(48)]
    assert kinds == (["GatedDeltaNet"] * 3 + ["GatedAttention"]) * 12

    def shapes_of(conf, types):
        out = {}
        for name, node in conf.nodes.items():
            if node.kind == "layer":
                got = jax.eval_shape(lambda n=node, t=types[name][0]: n.conf.init_params(
                    jax.random.PRNGKey(0), t, jnp.float32))
                out.update({f"{name}/{k}": v.shape for k, v in got.items()})
        return out
    total = sum(int(np.prod(s)) for s in shapes_of(conf, types).values())
    assert 79.0e9 < total < 80.5e9, total
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b_share16.json")) as f:
        cut = json.load(f)
    net = ComputationGraph(prog.zoo(cut, 0).conf())
    shapes = shapes_of(net.conf, net.conf.node_input_types())
    assert shapes == ref.param_shapes(cut)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 625_667_136
    experts = net.conf.nodes["b0_mlp"].conf.layer
    assert (experts.n_experts, experts.held, experts.top_k, experts.train_gate) \
        == (512, 32, 10, False)
    assert experts.row_bound(2 * 8192 * 10) == 20480


@pytest.mark.parametrize("model", ["Qwen3Next", "Xing4"])
def test_the_zoo_hands_the_shares_train_gate_to_its_expert_layers(model):
    """Default: the gate is trained, as before; `"train_gate": False` in the
    share reaches every expert layer."""
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.models.xing4 import PUBLISHED
    cls = getattr(models, model)
    small = dict(prog.zoo(CFG, 0).config, num_hidden_layers=1) if model == "Qwen3Next" \
        else dict(PUBLISHED, num_hidden_layers=3, num_nextn_predict_layers=0)

    def gates(share):
        conf = cls(small, sequence_length=16, share=share).conf()
        return [n.conf.layer.train_gate for n in conf.nodes.values()
                if n.kind == "layer"
                and isinstance(getattr(n.conf, "layer", None), decoder.RoutedExperts)]
    assert gates(None) and all(gates(None))
    assert gates({"experts": 4, "index": 0}) and all(gates({"experts": 4, "index": 0}))
    assert not any(gates({"experts": 4, "index": 0, "train_gate": False}))


# ------------------------------------------------------------------- serde
LAYERS = [
    decoder.RMSNorm(n_in=8, zero_centred=True),
    decoder.GatedDeltaNet(n_in=8, n_out=8, n_k_heads=2, n_v_heads=4, d_k=4, d_v=4),
    decoder.GatedAttention(n_in=8, n_out=8, n_heads=4, n_kv_heads=2, head_dim=8,
                           rotary_dim=4),
    decoder.RoutedExperts(n_in=8, n_out=8, n_experts=8, experts_held=4, top_k=2,
                          width=16, scoring_func="softmax", shared_gate=True,
                          train_gate=False),
    decoder.PreNormResidual(layer=decoder.GatedDeltaNet(
        n_in=8, n_out=8, n_k_heads=2, n_v_heads=4, d_k=4, d_v=4)),
]


@pytest.mark.parametrize("layer", LAYERS, ids=lambda l: type(l).__name__)
def test_layer_config_round_trips_through_json(layer):
    back = BaseLayerConf.from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(back) is type(layer) and back.to_dict() == layer.to_dict()


def test_scopes_name_the_block_the_mixer_and_the_recurrence(setup):
    """What the per-layer metrics read: `dl4j.PreNormResidual/<node>` round
    `dl4j.GatedDeltaNet/<node>` with `delta_rule` inside it, and
    `dl4j.GatedAttention/<node>`."""
    _, x, y, net = setup
    from deeplearning4j_tpu.telemetry import profiler
    text = "\n".join(profiler.op_scopes(net.lower_train_step(x, y).compile()).values())
    for scope in ("dl4j.PreNormResidual/b0_mix", "dl4j.GatedDeltaNet/b0_mix/delta_rule",
                  "dl4j.PreNormResidual/b3_mix", "dl4j.GatedAttention/b3_mix",
                  "dl4j.RoutedExperts/b0_mlp/routed", "dl4j.RoutedExperts/b0_mlp/shared"):
        assert scope in text, scope
