"""Ouro's looped decoder (nn/conf/layers/decoder.py: `LoopedStack`, the stack
of blocks run several times with one set of weights; `LoopExitHead`, the head
that scores every pass through the exit gate; `PreNormResidual`'s sandwich
order; `GatedAttention` without a q/k norm) and the zoo class
(models/ouro.py), against the benchmark's plain reference on seeded weights,
at tiny widths (hidden 32, 4 heads of 8, MLP 48, 2 layers, 4 passes).

Tolerances: both sides work in float32 here (compute type float32), so they
differ by the order of summation only. TOL = 5e-6, relative to the largest
entry, for activations, gradients and losses: the whole model reads 1.2e-7
on the loss and 7.7e-7 on the first gradient, so 5e-6 leaves six times
that; 1e-5 on the parameters after five Adam steps (Adam divides by the
gradient's own scale, so a weight moves by the step size whatever the
gradient's rounding). The loop against the same blocks written out, each
compiled, is held to TOL as well (the compiler sums in its own order).
bfloat16 where float32 is stated reads
3e-5 on the loss and 1.7e-2 on the gradient, and fails both (asserted
below).
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

from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.nn.conf.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import decoder  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf  # noqa: E402
from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx  # noqa: E402


def _load(name):
    path = os.path.join(BENCH, "configs", name)
    spec = importlib.util.spec_from_file_location("t_" + name[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("ouro_2_6b_stage1of6_reference.py")
prog = _load("ouro_2_6b_stage1of6_program.py")
TOL = 5e-6
ID = lambda x: x

with open(os.path.join(BENCH, "tests", "tiny", "configs", "tiny_ouro.json")) as f:
    CFG = json.load(f)
D, T, EPS = CFG["hidden_size"], CFG["sequence_length"], CFG["rms_norm_eps"]


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        (np.abs(a - b).max(), np.abs(b).max())


def _tokens(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _off_their_start(params, seed=0):
    """Gains and the gate's bias off their start, so that a norm that forgot
    its gain, or a gate that forgot its bias, shows."""
    return {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + i), v.shape, v.dtype)
            if k.split(".")[-1] in ("norm_g", "norm_out_g") or k.endswith("b_gate")
            else v for i, (k, v) in enumerate(params.items())}


def _copy(params):
    """A copy to hand the program's `load`, which gives up the leaves it
    takes."""
    return {k: jnp.array(v) for k, v in params.items()}


def _node(params, node):
    return {k.split("/")[1]: v for k, v in params.items() if k.startswith(node + "/")}


@pytest.fixture(scope="module")
def setup():
    params = _off_their_start(ref.init_params(CFG, jax.random.PRNGKey(0)))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, CFG["vocab_size"])
    net = prog.build(CFG, _copy(params), 0)
    return params, ids[:, :-1], ids[:, 1:], net


def _stack(net):
    return net.conf.nodes["loop"].conf


def _unrolled(stack, copies, x):
    """The stack's blocks and final norm written out pass by pass, pass t
    with its own copy `copies[t]` of the leaves."""
    states = []
    for p in copies:
        for b in stack.blocks:
            x = b.forward(stack.leaves_of(p, b), {}, x, train=True)[0]
        x = decoder.rms_norm(x, p["norm_g"], stack.eps)
        states.append(x)
    return jnp.stack(states)


# ------------------------------------------------------------ the loop
def test_one_pass_is_the_blocks_run_once(setup):
    params, _, _, net = setup
    stack = dataclasses.replace(_stack(net), passes=1)
    p, x = _node(params, "loop"), _tokens(2, 2, T, D)
    out, _, _ = stack.forward(p, {}, x, train=True)
    assert out.shape == (1, 2, T, D)
    _close(out, _unrolled(stack, [p], x), 1e-6)


def test_three_passes_are_tied_copies_written_out_and_the_gradients_add_up(setup):
    """Output and gradients of the scan over 3 passes against the blocks
    written out 3 times, each pass with its own copy of the leaves: the
    shared leaves' gradient is the sum of the copies' (autodiff's sum over
    the passes, nothing accumulated by hand), and the input's is the same."""
    params, _, _, net = setup
    stack = dataclasses.replace(_stack(net), passes=3)
    p, x = _node(params, "loop"), _tokens(3, 2, T, D)
    w = _tokens(4, 3, 2, T, D)

    def looped(p_, x_):
        return jnp.sum(jnp.sin(stack.forward(p_, {}, x_, train=True)[0]) * w)

    def written_out(copies, x_):
        return jnp.sum(jnp.sin(_unrolled(stack, copies, x_)) * w)
    got, (g_p, g_x) = jax.jit(jax.value_and_grad(looped, argnums=(0, 1)))(p, x)
    want, (g_copies, g_xw) = jax.jit(jax.value_and_grad(written_out, argnums=(0, 1)))(
        [p] * 3, x)
    _close(got, want)
    _close(g_x, g_xw)
    for leaf, g in g_p.items():
        _close(g, sum(c[leaf] for c in g_copies))
        # every pass gives a leaf part of its gradient
        assert all(float(jnp.abs(c[leaf]).max()) > 0 for c in g_copies), leaf


def test_the_loop_traces_one_pass_body_whatever_the_passes(setup):
    """The counters say one body was traced for the P passes of a program,
    for the layer's forward and for the whole train program alike: the
    program holds one pass."""
    _, x, y, net = setup
    reg = telemetry.registry()
    bodies, passes = (reg.counter(f"loop.{w}_traced.loop") for w in ("bodies", "passes"))
    stack = _stack(net)
    for n in (2, 5):
        b0, p0 = bodies.value, passes.value
        jax.make_jaxpr(lambda v: dataclasses.replace(stack, passes=n).forward(
            net.params_tree[1], {}, v, train=True)[0])(_tokens(5, 1, T, D))
        assert (bodies.value - b0, passes.value - p0) == (1, n)
    b0, p0 = bodies.value, passes.value
    net.lower_train_step(x, y, steps=3)
    assert (bodies.value - b0, passes.value - p0) == (1, CFG["total_ut_steps"])


# ------------------------------------------------------- the layers inside
def test_sandwich_block_is_norm_sublayer_norm(setup):
    params, _, _, net = setup
    block = _stack(net).blocks[1]
    assert isinstance(block, decoder.PreNormResidual) and block.sandwich
    assert not block.norm_output and not block.zero_centred
    p = _stack(net).leaves_of(_node(params, "loop"), block)
    assert set(p) == {"w_g", "w_u", "w_d", "norm_g", "norm_out_g"}
    x = _tokens(6, 2, T, D)
    out, _, _ = block.forward(p, {}, x, train=True)
    u = decoder.rms_norm(x, p["norm_g"], EPS)
    y = block.layer.forward(p, {}, u, train=True)[0]
    _close(out, x + decoder.rms_norm(y, p["norm_out_g"], EPS))
    # and against the reference's sublayer
    _close(out, ref._sublayer(CFG, ID, ref.gated_mlp, "l0_mlp", params, x))
    # each order is another model's block
    for other in (dataclasses.replace(block, sandwich=False),
                  dataclasses.replace(block, sandwich=False, norm_output=True)):
        assert float(jnp.abs(other.forward(p, {}, x, train=True)[0] - out).max()) > 1e-2
    fresh = block.init_params(jax.random.PRNGKey(0), InputType.recurrent(D, T))
    assert float(fresh["norm_out_g"].min()) == float(fresh["norm_out_g"].max()) == 1.0


def test_attention_without_a_qk_norm_matches_the_reference(setup):
    """No q/k norm (and no leaves for one), rotary over the whole head, no
    gate."""
    params, _, _, net = setup
    layer = _stack(net).blocks[0].layer
    assert isinstance(layer, decoder.GatedAttention)
    assert (layer.n_heads, layer.n_kv_heads, layer.head_dim, layer.rotary_dim,
            layer.output_gate, layer.qk_norm, layer.rope_theta) \
        == (4, 4, 8, 8, False, False, 1e6)
    p = {k: v for k, v in _stack(net).leaves_of(_node(params, "loop"),
                                                 _stack(net).blocks[0]).items()
         if not k.startswith("norm")}
    assert set(p) == {"w_q", "w_k", "w_v", "w_o"}
    assert set(layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(D, T))) == set(p)
    u = _tokens(7, 2, T, D)
    out, _, _ = layer.forward(p, {}, u, train=True)
    _close(out, ref.attention(CFG, params, "l0_attn", u, ID))
    # without the rotary part, or with the norm, the layer is another model's
    unrotated = dataclasses.replace(layer, rotary_dim=0)
    assert float(jnp.abs(unrotated.forward(p, {}, u, train=True)[0]
                         - out).max()) > 1e-3 * float(jnp.abs(out).max())
    normed = dataclasses.replace(layer, qk_norm=True, zero_centred=False)
    gains = {"q_norm_g": jnp.ones((8,)), "k_norm_g": jnp.ones((8,))}
    assert float(jnp.abs(normed.forward({**p, **gains}, {}, u, train=True)[0]
                         - out).max()) > 1e-3 * float(jnp.abs(out).max())


def test_attention_through_the_flash_kernel(setup, monkeypatch):
    """Past a tile's length the layer attends through the flash kernel
    (interpreted): 4 ungrouped heads of 8, rotary over all of each."""
    params, _, _, net = setup
    block = _stack(net).blocks[0]
    monkeypatch.setattr(decoder, "_DENSE_ATTENTION_MAX_T", 8)
    p = {k: v for k, v in _stack(net).leaves_of(_node(params, "loop"), block).items()
         if not k.startswith("norm")}
    u = _tokens(8, 1, T, D)

    def loss(p_, u_):
        return jnp.sum(jnp.sin(block.layer.forward(p_, {}, u_, train=True)[0]))
    with helpers_enabled_ctx(False):
        want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    with helpers_enabled_ctx(True):
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)


# ------------------------------------------------------------ the exit head
def test_the_exit_distribution_sums_to_one_and_weights_the_passes(setup):
    params, _, y, net = setup
    head = net.conf.nodes["exit_head"].conf
    assert isinstance(head, decoder.LoopExitHead) and head.entropy_weight == 0.05
    z = 3.0 * _tokens(9, 4, 2, T)
    p, log_p = decoder.exit_distribution(z)
    _close(jnp.sum(p, axis=0), jnp.ones((2, T)), 1e-6)
    _close(log_p, jnp.log(p), 1e-5)
    lam = jax.nn.sigmoid(z)
    _close(p[1], lam[1] * (1 - lam[0]), 1e-6)
    _close(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), 1e-6)
    # a gate of 0 on a pass's tokens: nobody leaves there
    z0 = z.at[0].set(-1e4)
    assert float(jnp.abs(decoder.exit_distribution(z0)[0][0]).max()) == 0.0
    # beta = 0: the loss is the p-weighted cross-entropy, pass by pass
    hp = _node(params, "exit_head")
    h = _tokens(10, 4, 2, T, D)
    plain = dataclasses.replace(head, entropy_weight=0.0)
    ce = jnp.stack([plain._nll(hp, h[t], y, None)[0] for t in range(4)])
    gate = jnp.einsum("pbtd,d->pbt", h, hp["w_gate"][:, 0]) + hp["b_gate"][0]
    weights, _ = decoder.exit_distribution(gate)
    _close(plain.compute_score(hp, h, y), jnp.mean(jnp.sum(weights * ce, axis=0)))
    # and against the reference's exit loss, beta as configured
    _close(head.compute_score(hp, h, y), ref.exit_loss(ce, gate, CFG["entropy_weight"]))
    # the gate and its bias receive a gradient
    g = jax.jit(jax.grad(lambda p_: head.compute_score(p_, h, y)))(hp)
    assert float(jnp.abs(g["w_gate"]).max()) > 0 and float(jnp.abs(g["b_gate"]).max()) > 0


# ------------------------------------------------------- the model, trained
def test_zoo_model_through_fit_on_device_follows_the_references_steps(setup):
    """Loss and first gradient (read off Adam's second moment, as the
    benchmark reads it) of step 1, the losses of three steps, one a call,
    then a call of two; the parameters after all five, the reference's Adam
    a leaf at a time."""
    params, x, y, _ = setup
    net = prog.build(CFG, _copy(params), 0)
    losses = [float(net.fit_on_device(x, y, steps=1)[0])]
    grad_sq = jax.device_get(prog.first_gradient_sq(net, CFG))
    losses += [float(net.fit_on_device(x, y, steps=1)[0]) for _ in range(2)]
    losses += [float(v) for v in net.fit_on_device(x, y, steps=2)]
    loss, grads, _ = ref.loss_and_grads(CFG, "f32", params, {}, x, y)
    assert abs(losses[0] - float(loss)) <= TOL * abs(float(loss))
    biggest = max(float(jnp.abs(g).max()) for g in grads.values())
    assert set(grad_sq) == set(grads)
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
    np.testing.assert_allclose(losses, ref_losses, rtol=TOL)
    after = prog.params_of(net)
    assert set(after) == set(p)
    for leaf, value in p.items():
        if float(jnp.abs(grads[leaf]).max()) > 1e-6 * biggest:
            assert float(jnp.abs(after[leaf] - value).max()) <= 1e-5 * max(
                float(jnp.abs(value).max()), 1.0), leaf
    # the step's Adam, a leaf at a time, is the whole-tree `apply_updater`
    opt1, update = ref.apply_updater(CFG, ref.init_opt(CFG, params), grads)
    for leaf in grads:
        moved = np.asarray(params[leaf], np.float64) - after_one[leaf]
        assert np.abs(moved - update[leaf]).max() <= 2.4e-7 * max(
            float(jnp.abs(params[leaf]).max()), 1e-3), leaf


def test_loading_gives_up_the_drawn_weights(setup):
    """The drawn leaves' device buffers go once the net holds their values:
    the device holds the weights once (at the cell's size the copy beside
    them was the set-up's peak)."""
    params = setup[0]
    drawn = _copy(params)
    net = prog.build(CFG, drawn, 0)
    assert all(v.is_deleted() for v in drawn.values())
    after = prog.params_of(net)
    assert set(after) == set(params)
    for leaf, value in after.items():
        np.testing.assert_array_equal(np.asarray(value), np.asarray(params[leaf]))


def test_bfloat16_where_float32_is_stated_fails_the_tolerances(setup):
    params, x, y, _ = setup
    loss, grads, _ = ref.loss_and_grads(CFG, "f32", params, {}, x, y)
    net = prog.build(dict(CFG, compute_dtype="bfloat16"), _copy(params), 0)
    got = float(net.fit_on_device(x, y, steps=1)[0])
    assert abs(got - float(loss)) > 2 * TOL * abs(float(loss))
    grad_sq = jax.device_get(prog.first_gradient_sq(net, CFG))
    biggest = max(float(jnp.abs(g).max()) for g in grads.values())
    worst = max(float(np.abs(np.sqrt(np.maximum(grad_sq[k], 0.0)) - np.abs(g)).max())
                for k, g in grads.items())
    assert worst > 1000 * TOL * biggest


def test_zoo_builds_the_published_model_without_allocating_it():
    from deeplearning4j_tpu.models import Ouro
    from deeplearning4j_tpu.models.ouro import PUBLISHED
    with open(os.path.join(BENCH, "configs", "ouro_2_6b_stage1of6.json")) as f:
        cut = json.load(f)
    # the configuration file holds the published keys, the cut ones under
    # `published`
    assert PUBLISHED == {k: cut["published"].get(k, cut[k]) for k in PUBLISHED}
    conf = Ouro().conf()
    stack = conf.nodes["loop"].conf
    assert stack.passes == 4 and len(stack.blocks) == 96
    assert [b.name for b in stack.blocks[:4]] == ["l0_attn", "l0_mlp", "l1_attn", "l1_mlp"]

    def shapes_of(conf):
        types, out = conf.node_input_types(), {}
        for name, node in conf.nodes.items():
            if node.kind == "layer":
                got = jax.eval_shape(lambda n=node, t=types[name][0]: n.conf.init_params(
                    jax.random.PRNGKey(0), t, jnp.float32))
                out.update({f"{name}/{k}": v.shape for k, v in got.items()})
        return out
    total = sum(int(np.prod(s)) for s in shapes_of(conf).values())
    # 48 layers of 51.39 M, table and head 201.3 M: the 2.6 B of its name
    assert 2.66e9 < total < 2.67e9, total
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    shapes = shapes_of(ComputationGraph(prog.zoo(cut, 0).conf()).conf)
    assert shapes == ref.param_shapes(cut)
    assert 612.0e6 < sum(int(np.prod(s)) for s in shapes.values()) < 612.5e6
    with pytest.raises(ValueError, match="full attention"):
        Ouro(dict(PUBLISHED, layer_types=["sliding_attention"] * 48))


LAYERS = [
    decoder.LoopedStack(blocks=[
        decoder.PreNormResidual(name="a", layer=decoder.GatedAttention(
            n_in=8, n_out=8, n_heads=2, n_kv_heads=2, head_dim=4, rotary_dim=4,
            output_gate=False, qk_norm=False), zero_centred=False, sandwich=True),
        decoder.PreNormResidual(name="m", layer=decoder.GatedMLP(n_in=8, n_out=8, width=12),
                                zero_centred=False, sandwich=True)], passes=3),
    decoder.LoopExitHead(n_in=8, n_out=16, entropy_weight=0.1),
]


@pytest.mark.parametrize("layer", LAYERS, ids=lambda l: type(l).__name__)
def test_layer_config_round_trips_through_json(layer):
    back = BaseLayerConf.from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(back) is type(layer) and back.to_dict() == layer.to_dict()


@pytest.mark.parametrize("build", [
    lambda: decoder.PreNormResidual(layer=decoder.GatedMLP(n_in=8, n_out=8, width=12),
                                    norm_output=True, sandwich=True),
    lambda: decoder.GatedAttention(n_in=8, n_out=8, n_heads=2, n_kv_heads=2, head_dim=4,
                                   qk_norm=False, qk_norm_whole=True)],
    ids=["norm_output_and_sandwich", "qk_norm_whole_without_qk_norm"])
def test_two_orders_at_once_are_refused(build):
    with pytest.raises(ValueError):
        build()


def test_a_looped_stack_refuses_blocks_without_names_of_their_own():
    block = decoder.PreNormResidual(layer=decoder.GatedMLP(n_in=8, n_out=8, width=12))
    with pytest.raises(ValueError, match="names of their own"):
        decoder.LoopedStack(blocks=[block])


def test_scopes_name_the_loop_its_blocks_and_the_exit_head(setup):
    """What the per-layer metrics read: `dl4j.LoopedStack/loop` round every
    pass's blocks, forward and backward, with `dl4j.PreNormResidual/<block>`,
    `dl4j.GatedAttention/<block>` and `dl4j.GatedMLP/<block>` inside it;
    `dl4j.LoopExitHead/exit_head` under `dl4j.loss`."""
    _, x, y, net = setup
    from deeplearning4j_tpu.telemetry import profiler
    names = profiler.op_scopes(net.lower_train_step(x, y).compile()).values()
    inside = [n for n in names if "dl4j.LoopedStack/loop" in n]
    for scope in ("dl4j.PreNormResidual/l1_attn", "dl4j.GatedAttention/l1_attn",
                  "dl4j.PreNormResidual/l0_mlp", "dl4j.GatedMLP/l0_mlp"):
        assert any(scope in n for n in inside), scope
        assert any(scope in n and "transpose(" in n for n in inside), scope
        # every instruction of the program's computations (a reduction's
        # region is no operation of the device, and has no prefix)
        assert all("dl4j.LoopedStack/loop" in n for n in names
                   if scope in n and n.startswith("jit(")), scope
    assert any("dl4j.loss" in n and "dl4j.LoopExitHead/exit_head" in n for n in names)
    assert {profiler.scope_phase(n)[0] for n in names if "GatedMLP" in n} \
        == {"dl4j.GatedMLP/l0_mlp", "dl4j.GatedMLP/l1_mlp"}
