"""Olmo-Hybrid's layers (nn/conf/layers/decoder.py: the residual block with
the norm on the sublayer's output, the Gated DeltaNet with a write strength
up to 2 and one value head a key head, the attention without gate or rotary
and a norm over q's and k's whole width, the dense MLP), the recurrence's
kernels at key and value widths that are no whole lane tiles
(ops/gated_delta_rule.py) and the zoo class (models/olmo_hybrid.py), against
the benchmark's plain reference on seeded weights, at tiny widths that keep
the published 96 : 192 (12 : 24 here).

Tolerances: both sides work in float32 here (compute type float32; the
kernels interpreted where a test forces the seam), so they differ by the
order of summation only: 2e-5 relative to the largest entry for activations
and gradients; 1e-5 between the recurrence's kernels and the token-by-token
scan (the chunked form inverts a triangular system, whose entries double with
beta in (0, 2), and takes a decay as the `exp` of a sum where the scan
multiplies one by one: a few float32 ulps a chunk); 3e-4 on a layer's
gradients through the kernels (the decay's rates get theirs as a sum over
every token and head in another order on each side); 1e-5 on the parameters
after five Adam steps. bfloat16 where float32 is stated is off by 4e-3 or
more and fails every one of them.
"""
import dataclasses
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

from deeplearning4j_tpu.nn.conf.input_type import InputType  # noqa: E402
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


ref = _load("olmo_hybrid_7b_share2_reference.py")
prog = _load("olmo_hybrid_7b_share2_program.py")
TOL = 2e-5
ID = lambda x: x

with open(os.path.join(BENCH, "tests", "tiny", "configs", "tiny_olmo_hybrid.json")) as f:
    CFG = json.load(f)
# the same model uncut: every head and row held by one share
FULL = dict(CFG, share={"index": 0, "of": 1}, **CFG["published"])
SHARES = CFG["share"]["of"]
D = CFG["hidden_size"]


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


def _off_their_start(params, seed=0):
    """Gains and biases off 1, so that a norm that forgot its gain shows."""
    return {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + i), v.shape, v.dtype)
            if k.split("/")[1] in ("norm_g", "q_norm_g", "k_norm_g", "g",
                                   "o_norm_w", "dt_bias") else v
            for i, (k, v) in enumerate(params.items())}


@pytest.fixture(scope="module")
def setup():
    params = _off_their_start(ref.init_params(CFG, jax.random.PRNGKey(0)))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, CFG["sequence_length"] + 1),
                             0, CFG["vocab_size"])
    net = prog.build(CFG, params, 0)
    return params, ids[:, :-1], ids[:, 1:], net


# ------------------------------------------------------------ the recurrence
def _rule_inputs(t, b, h, d_k, d_v, dtype=jnp.float32, seed=3):
    """One value head a key head, beta drawn in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q = unit(draw(ks[0], b, t, h, d_k)) * d_k ** -0.5
    k = unit(draw(ks[1], b, t, h, d_k))
    v = draw(ks[2], b, t, h, d_v)
    g = -0.5 * jax.nn.softplus(draw(ks[3], b, t, h))
    beta = 2.0 * jax.nn.sigmoid(2.0 * draw(ks[4], b, t, h))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)


def _value_and_grads(rule, args):
    w = _tokens(9, *args[2].shape)
    return jax.value_and_grad(lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * w),
                              argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("t,heads,d_k,d_v,chunk", [
    (300, 5, 96, 192, 128),    # the cell's widths; 4 heads a step, the last
                               # block reaching past the fifth; 3 chunks, the
                               # last one part padding
    (40, 3, 12, 24, 16),       # the tests' widths: any width interpreted
])
def test_kernels_at_widths_that_are_no_whole_lane_tiles(t, heads, d_k, d_v, chunk):
    """Forward and backward, interpreted, against the token-by-token scan:
    values and all five gradients, float32 at 1e-5; beta in (0, 2)."""
    args = _rule_inputs(t, 1, heads, d_k, d_v)
    assert float(args[4].max()) > 1.5 and float(args[4].min()) < 0.5
    want = _value_and_grads(gdr.gated_delta_rule_scan, args)
    got = _value_and_grads(lambda *a: gdr.gated_delta_rule(*a, chunk), args)
    _close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):
        _close(a, b, 1e-5)


def test_interpreted_the_kernels_take_the_chips_heads_a_step_or_any_width():
    """Off the chip the kernels take the heads a step that the chip would
    where a count of them is whole lane tiles wide (so these tests walk the
    chip's grid, the last block reaching past the heads), and any width
    besides. What the chip takes and refuses: test_kernels_lower_for_tpu.py."""
    assert gdr.heads_a_step(5, 5, 96, 192, 4) == 4
    assert gdr.heads_a_step(3, 3, 12, 24, 4) == 3
    assert gdr.heads_a_step(2, 4, 8, 8, 4) == 4


def test_bfloat16_operands_at_the_cells_widths_keep_the_state_in_float32():
    """q, k, v in bfloat16 through the kernels against the float32 scan on
    the same rounded operands: off by the products' rounding (4e-3 of the
    largest entry), not by a state kept in bfloat16 (which drifts by 5e-2
    over 300 tokens)."""
    args = _rule_inputs(300, 1, 5, 96, 192, jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    got = gdr.gated_delta_rule(*args)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), gdr.gated_delta_rule_scan(*wide), 1.2e-2)


# ------------------------------------------------------------------ the layers
def test_gated_delta_net_matches_the_reference(setup):
    """One value head a key head, beta = 2 sigmoid, 12-wide keys against
    24-wide values."""
    params, _, _, net = setup
    layer = net.conf.nodes["b0_mix"].conf.layer
    assert isinstance(layer, decoder.GatedDeltaNet)
    assert (layer.n_k_heads, layer.n_v_heads, layer.d_k, layer.d_v, layer.beta_scale) \
        == (2, 2, 12, 24, 2.0)
    u = _tokens(2, 2, 24, D)
    out, _, _ = layer.forward(_of(params, "b0_mix"), {}, u, train=True)
    _close(out, ref.gated_delta_net(CFG, params, "b0_mix", u, ID))
    # without the doubling the layer is not this model's
    halved = dataclasses.replace(layer, beta_scale=1.0)
    assert float(jnp.abs(halved.forward(_of(params, "b0_mix"), {}, u, train=True)[0]
                         - out).max()) > 1e-3 * float(jnp.abs(out).max())


def test_gated_delta_net_through_the_kernels(setup):
    """The seam forced: the layer on the kernels (interpreted) against the
    same layer on the scan, value and gradient."""
    params, _, _, net = setup
    layer = net.conf.nodes["b1_mix"].conf.layer
    p, u = _of(params, "b1_mix"), _tokens(4, 2, 24, D)

    def loss(p_, u_):
        return jnp.sum(jnp.sin(layer.forward(p_, {}, u_, train=True)[0]))
    with helpers_enabled_ctx(False):
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    with helpers_enabled_ctx(True):
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    # 3e-4: the decay's two rates a layer get their gradient as a sum over
    # every token of their head, in another order on each side
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 3e-4)


def test_attention_without_gate_or_rotary_matches_the_reference(setup):
    """The norm over q's and k's whole held width with a gain of that width,
    no rotary, no output gate; one class with Qwen3-Next's gated layer."""
    params, _, _, net = setup
    layer = net.conf.nodes["b3_mix"].conf.layer
    assert isinstance(layer, decoder.GatedAttention)
    assert (layer.n_heads, layer.n_kv_heads, layer.head_dim, layer.rotary_dim,
            layer.output_gate, layer.qk_norm_whole, layer.zero_centred) \
        == (2, 2, 8, 0, False, True, False)
    p = _of(params, "b3_mix")
    assert p["w_q"].shape == (D, 16) and p["q_norm_g"].shape == (16,)
    u = _tokens(5, 2, 24, D)
    out, _, _ = layer.forward(p, {}, u, train=True)
    _close(out, ref.attention(CFG, params, "b3_mix", u, ID))
    # positions enter through the causal mask alone: the last token's output
    # does not change when the tokens before it change places
    swapped = u.at[:, jnp.array([3, 7])].set(u[:, jnp.array([7, 3])])
    _close(layer.forward(p, {}, swapped, train=True)[0][:, -1], out[:, -1])


def test_attention_through_the_flash_kernel(setup, monkeypatch):
    """Past a tile's length the layer attends through the flash kernel
    (interpreted): 2 heads of 8 on 2 k/v heads."""
    params, _, _, net = setup
    layer = net.conf.nodes["b3_mix"].conf.layer
    monkeypatch.setattr(decoder, "_DENSE_ATTENTION_MAX_T", 8)
    p, u = _of(params, "b3_mix"), _tokens(6, 1, 24, D)

    def loss(p_, u_):
        return jnp.sum(jnp.sin(layer.forward(p_, {}, u_, train=True)[0]))
    with helpers_enabled_ctx(False):
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    with helpers_enabled_ctx(True):
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, u)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)


def test_residual_block_with_the_norm_on_the_output_matches_the_reference(setup):
    params, _, _, net = setup
    block = net.conf.nodes["b0_mlp"].conf
    assert isinstance(block, decoder.PreNormResidual)
    assert block.norm_output and not block.zero_centred
    assert isinstance(block.layer, decoder.GatedMLP) and block.layer.width == 48
    x = _tokens(10, 2, 24, D)
    out, _, _ = block.forward(_of(params, "b0_mlp", wrapped=False), {}, x, train=True)
    want = x + ref.norm(ref.gated_mlp(CFG, params, "b0_mlp", x, ID),
                        params["b0_mlp/norm_g"], CFG["rms_norm_eps"])
    _close(out, want)
    # the norm before the sublayer is another model's block
    before = dataclasses.replace(block, norm_output=False)
    assert float(jnp.abs(before.forward(_of(params, "b0_mlp", wrapped=False), {}, x,
                                        train=True)[0] - out).max()) > 1e-2
    fresh = block.init_params(jax.random.PRNGKey(0), InputType.recurrent(D, 24))
    assert float(fresh["norm_g"].min()) == float(fresh["norm_g"].max()) == 1.0


# ------------------------------------------------------------------ the share
def _mixer_of(cfg, node):
    return ComputationGraph(prog.zoo(cfg, 0).conf()).conf.nodes[node].conf.layer


def _head_columns(full, node, i, held, heads, runs, rows=("w_out",)):
    """Share i's part of a mixer's leaves: `runs` maps a leaf to the head
    widths of the projections its columns (its rows, for the leaves named in
    `rows`) are made of, side by side, each `heads` heads wide uncut."""
    out = {}
    for leaf, value in full.items():
        key = leaf.split("/")[1]
        if not leaf.startswith(node + "/"):
            continue
        if key not in runs:
            out[leaf] = value
            continue
        take, at = [], 0
        for width in runs[key]:
            take.append(jnp.arange(at + i * held * width, at + (i + 1) * held * width))
            at += heads * width
        take = jnp.concatenate(take)
        out[leaf] = value[take] if key in rows else value[..., take]
    return out


def test_the_head_shares_of_a_delta_net_mixer_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: a head's recurrence, gate and
    output norm know no other head, so the parts that the two shares give,
    each through its own rows of W_out, add up to the uncut reference's
    output. (The MLP is whole on every chip and is counted once: it has no
    share to add up.)"""
    full = _off_their_start(ref.init_params(FULL, jax.random.PRNGKey(11)), 50)
    u = _tokens(13, 2, 24, D)
    heads, held = FULL["linear_num_value_heads"], CFG["linear_num_value_heads"]
    dk, dv = CFG["linear_key_head_dim"], CFG["linear_value_head_dim"]
    runs = {"w_qkvz": [dk, dk, dv, dv], "w_ba": [1, 1], "conv_w": [dk, dk, dv],
            "a_log": [1], "dt_bias": [1], "w_out": [dv]}
    total = 0
    for i in range(SHARES):
        cfg = dict(CFG, share={"index": i, "of": SHARES})
        layer = _mixer_of(cfg, "b0_mix")
        assert (layer.n_k_heads, layer.n_v_heads) == (held, held) and held * SHARES == heads
        mine = _head_columns(full, "b0_mix", i, held, heads, runs)
        out, _, _ = layer.forward(_of(mine, "b0_mix"), {}, u, train=True)
        _close(out, ref.gated_delta_net(cfg, mine, "b0_mix", u, ID))
        total = total + out
    _close(total, ref.gated_delta_net(FULL, full, "b0_mix", u, ID))


def test_the_head_shares_of_the_attention_add_up_given_the_normed_q_and_k():
    """The norm over q's and k's whole width sums its mean square over the
    group's columns, which one share cannot see (the configuration's
    `left_out`). Given the uncut model's normed q and k, a head's softmax
    knows no other head, and the shares' parts add up: each share's
    attention over its heads' columns of the normed q and k, through its rows
    of W_o, summed, is the uncut reference's output."""
    full = _off_their_start(ref.init_params(FULL, jax.random.PRNGKey(12)), 70)
    u = _tokens(14, 2, 24, D)
    m = ref.dims(FULL)
    heads, hd, eps = m["heads"], m["head"], FULL["rms_norm_eps"]
    held = CFG["num_attention_heads"]
    q = ref.norm(u @ full["b3_mix/w_q"], full["b3_mix/q_norm_g"], eps)
    k = ref.norm(u @ full["b3_mix/w_k"], full["b3_mix/k_norm_g"], eps)
    v = u @ full["b3_mix/w_v"]
    total = 0
    for i in range(SHARES):
        layer = _mixer_of(dict(CFG, share={"index": i, "of": SHARES}), "b3_mix")
        assert (layer.n_heads, layer.n_kv_heads) == (held, held) and held * SHARES == heads
        cols = slice(i * held * hd, (i + 1) * held * hd)
        split = lambda a: jnp.swapaxes(a[..., cols].reshape(2, 24, held, hd), 1, 2)
        out = jnp.swapaxes(layer._attend(split(q), split(k), split(v)), 1, 2)
        total = total + out.reshape(2, 24, held * hd) @ full["b3_mix/w_o"][cols]
    _close(total, ref.attention(FULL, full, "b3_mix", u, ID))
    # and the share's own norm runs over its held columns, as the reference's
    cfg = dict(CFG, share={"index": 0, "of": SHARES})
    mine = {kk: (vv[..., :held * hd] if kk.split("/")[1] in
                 ("w_q", "w_k", "w_v", "q_norm_g", "k_norm_g") else
                 vv[:held * hd] if kk.endswith("/w_o") else vv)
            for kk, vv in full.items() if kk.startswith("b3_mix/")}
    out, _, _ = _mixer_of(cfg, "b3_mix").forward(_of(mine, "b3_mix"), {}, u, train=True)
    _close(out, ref.attention(cfg, mine, "b3_mix", u, ID))


def test_the_shares_logits_are_the_uncut_heads_columns():
    full = ref.init_params(FULL, jax.random.PRNGKey(12))
    h = _tokens(14, 2, 24, D)
    rows = CFG["vocab_size"]
    parts = []
    for i in range(2):
        conf = ComputationGraph(
            prog.zoo(dict(CFG, share={"index": i, "of": SHARES}), 0).conf()).conf
        head, table = conf.nodes["lm_head"].conf, conf.nodes["embed"].conf
        assert (head.first_row, head.rows, table.first_row) == (rows * i, rows, rows * i)
        parts.append(head.forward(
            {"W": full["lm_head/W"][:, rows * i:rows * (i + 1)]}, {}, h, train=True)[0])
    _close(jnp.concatenate(parts, axis=-1), h @ full["lm_head/W"])


# ------------------------------------------------------- the model, trained
def test_zoo_model_through_fit_on_device_follows_the_references_steps(setup):
    """Loss and first gradient (read off Adam's second moment, as the
    benchmark reads it) of step 1, the losses of three steps, one a call,
    then a call of two; the parameters after all five, the reference's Adam
    a leaf at a time."""
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
    p = {k: jnp.array(v) for k, v in params.items()}
    opt, ref_losses = ref.init_opt(CFG, p), []
    for n in range(5):
        p, opt, _, step_loss = ref.train_step(CFG, "f32", p, opt, {}, x, y)
        ref_losses.append(float(step_loss))
        if n == 0:
            after_one = jax.device_get(p)     # the next step takes p's buffers
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    after = prog.params_of(net)
    assert set(after) == set(p)
    for leaf, value in p.items():
        if float(jnp.abs(grads[leaf]).max()) > 1e-6 * biggest:
            assert float(jnp.abs(after[leaf] - value).max()) <= 1e-5 * max(
                float(jnp.abs(value).max()), 1.0), leaf
    # the step's Adam, a leaf at a time, is the whole-tree `apply_updater`:
    # the difference of two float32 weights is the update to an ulp of the
    # weight
    opt1, update = ref.apply_updater(CFG, ref.init_opt(CFG, params), grads)
    assert int(opt1["t"]) == 1
    for leaf in grads:
        moved = np.asarray(params[leaf], np.float64) - after_one[leaf]
        assert np.abs(moved - update[leaf]).max() <= 2.4e-7 * max(
            float(jnp.abs(params[leaf]).max()), 1e-3), leaf


def test_zoo_builds_the_published_model_without_allocating_it():
    from deeplearning4j_tpu.models import OlmoHybrid
    from deeplearning4j_tpu.models.olmo_hybrid import PUBLISHED
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert PUBLISHED == row["config"]
    conf = OlmoHybrid().conf()
    kinds = [type(conf.nodes[f"b{i}_mix"].conf.layer).__name__ for i in range(32)]
    assert kinds == (["GatedDeltaNet"] * 3 + ["GatedAttention"]) * 8
    assert all(isinstance(conf.nodes[f"b{i}_mlp"].conf.layer, decoder.GatedMLP)
               for i in range(32))

    def shapes_of(conf):
        types, out = conf.node_input_types(), {}
        for name, node in conf.nodes.items():
            if node.kind == "layer":
                got = jax.eval_shape(lambda n=node, t=types[name][0]: n.conf.init_params(
                    jax.random.PRNGKey(0), t, jnp.float32))
                out.update({f"{name}/{k}": v.shape for k, v in got.items()})
        return out
    total = sum(int(np.prod(s)) for s in shapes_of(conf).values())
    # 24 DeltaNet layers of 88.7 M, 8 attention layers of 59.0 M, 32 MLPs of
    # 126.8 M, table and head 770.7 M: the 7 B the model's name says
    assert 7.4e9 < total < 7.5e9, total
    with open(os.path.join(BENCH, "configs", "olmo_hybrid_7b_share2.json")) as f:
        cut = json.load(f)
    shapes = shapes_of(ComputationGraph(prog.zoo(cut, 0).conf()).conf)
    assert shapes == ref.param_shapes(cut)
    assert 765e6 < sum(int(np.prod(s)) for s in shapes.values()) < 768e6
    share = OlmoHybrid(share={"heads": 15, "vocab": 12544, "index": 0}).conf()
    mixer, attn = share.nodes["b0_mix"].conf.layer, share.nodes["b3_mix"].conf.layer
    assert (mixer.n_k_heads, mixer.n_v_heads, attn.n_heads, attn.n_kv_heads) == (15,) * 4
    assert (mixer.d_k, mixer.d_v, attn.head_dim) == (96, 192, 128)
    with pytest.raises(ValueError, match="no rotary"):
        OlmoHybrid(dict(PUBLISHED, rope_parameters={"rope_theta": 500000.0}))


LAYERS = [
    decoder.GatedDeltaNet(n_in=8, n_out=8, n_k_heads=2, n_v_heads=2, d_k=6, d_v=12,
                          beta_scale=2.0),
    decoder.GatedAttention(n_in=8, n_out=8, n_heads=2, n_kv_heads=2, head_dim=2, rotary_dim=0, output_gate=False,
                           qk_norm_whole=True, zero_centred=False),
    decoder.PreNormResidual(layer=decoder.GatedMLP(n_in=8, n_out=8, width=12),
                            zero_centred=False, norm_output=True),
]


@pytest.mark.parametrize("layer", LAYERS, ids=lambda l: type(l).__name__)
def test_layer_config_round_trips_through_json(layer):
    back = BaseLayerConf.from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(back) is type(layer) and back.to_dict() == layer.to_dict()


def test_scopes_name_the_block_the_mixers_the_recurrence_and_the_mlp(setup):
    """What the per-layer metrics read: `dl4j.PreNormResidual/<node>` round
    `dl4j.GatedDeltaNet/<node>` with `delta_rule` inside it,
    `dl4j.GatedAttention/<node>` and `dl4j.GatedMLP/<node>`."""
    _, x, y, net = setup
    from deeplearning4j_tpu.telemetry import profiler
    text = "\n".join(profiler.op_scopes(net.lower_train_step(x, y).compile()).values())
    for scope in ("dl4j.PreNormResidual/b0_mix", "dl4j.GatedDeltaNet/b0_mix/delta_rule",
                  "dl4j.PreNormResidual/b3_mix", "dl4j.GatedAttention/b3_mix",
                  "dl4j.PreNormResidual/b0_mlp", "dl4j.GatedMLP/b0_mlp",
                  "dl4j.TokenCrossEntropyHead/lm_head"):
        assert scope in text, scope
