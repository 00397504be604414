"""Plain reference for Qwen3-Next-80B-A3B-Instruct (training step), as one
chip's share of a layer group.

Written from the keys of the published `config.json` (`model_type`
qwen3_next) and the papers the mechanisms come from: the gated delta rule
(Gated DeltaNet, arXiv:2412.06464) and softmax top-k routing beside a gated
shared expert (Qwen's). Equations, with eps = `rms_norm_eps`:

  norm     y = x rsqrt(mean(x^2) + eps) (1 + g), g from 0 (every norm but the
           DeltaNet's output norm, whose gain w multiplies and starts at 1)
  block i  h = x + mixer_i(norm(x)); x' = h + experts(norm(h)); mixer_i is
           the attention where (i + 1) % full_attention_interval == 0
  DeltaNet [q, k, v, z] = x W_qkvz, [b, a] = x W_ba; [q, k, v] <- silu(causal
           depthwise conv, width 4, no bias); beta = sigmoid(b); g = -exp(A_log)
           softplus(a + dt_bias); q, k <- x / sqrt(sum(x^2) + 1e-6) a head, q
           by d_k^-1/2 more, each key head serving n_v / n_k value heads; a
           head, from S_0 = 0: S' = exp(g_t) S_{t-1}; u_t = beta_t (v_t - S'^T
           k_t); S_t = S' + k_t u_t^T; o_t = S_t^T q_t; y = (o rsqrt(mean(o^2)
           + eps) w silu(z)) W_out
  attention [q, gate] = x W_q a head, k = x W_k, v = x W_v; the norm a head on
           q and k; rotary (half-split pairing) on the first partial_rotary_
           factor of each head; causal softmax, scale head_dim^-1/2, a k/v
           head shared by n_heads / n_kv_heads query heads; y = (attention
           sigmoid(gate)) W_o
  experts  p = softmax(u W_r) over the published experts; the top k chosen; w
           = p_sel / sum(p_sel); y = sum_e w_e expert_e(u) + sigmoid(u w_s)
           shared(u), an expert W_d(silu(W_g u) (W_u u))
  loss     mean cross-entropy of norm(x) W_head over the held rows

Float32 `jax.numpy`, `highest` precision, no kernel, no cache: the recurrence
one token at a time in a `lax.scan`, the attention a full masked softmax a
block of queries at a time. Every block is recomputed in the backward pass,
a sequence at a time, so that the step fits beside the parameters, their
gradients and Adam's two moments. Imports nothing of `deeplearning4j_tpu`.

Departures from the published description, each where it is made: the column
order inside W_qkvz, W_ba and W_q (permutations of random weights); where the
configuration says `train_gate` false, the chosen experts' weights w are
constants of the backward pass (`experts`, below).

The share: the configuration says how many routed experts and rows of the
vocabulary are held here (`num_experts`, `vocab_size`) of the published
counts (`published`), and which share this is (`share.index` of `share.of`).
The router scores all published experts and picks `num_experts_per_tok`;
this share adds its own experts' part for the tokens routed to them, and what
absent experts would have added is left out; the shared expert and the
mixers are whole on every chip. Ids, logits and loss are over the held rows.

`mode` other than "f32" is the control: every operand of a matrix product
held in the lower type (int8, `harness/refmath.py`), forward and backward,
the way the configuration holds them in bfloat16 (the recurrence's q, k, v
among them); the recurrence's state, the router, the head and the loss stay
float32.

Leaves are named `<node>/<key>`, node for node with the program's graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from harness import refmath

HI = lax.Precision.HIGHEST

# Queries a block of the attention's masked softmax (a block's scores over
# all keys are what lives at once), and tokens a recomputed segment of the
# recurrence (the backward keeps the state each segment started from: the
# arithmetic is one token at a time whatever this is).
QUERY_BLOCK = 512
SCAN_SEGMENT = 64
# Tokens a chunk of the chunked form whose required work the counts below
# take: the form a chip runs (one state update a chunk, not a token).
COUNTED_CHUNK = 64


# ------------------------------------------------------------------ shapes
def dims(cfg):
    """The sizes the equations use, from the configuration's keys."""
    pub = cfg.get("published", {})
    share = cfg.get("share", {"index": 0, "of": 1})
    experts = cfg["num_experts"]
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "period": cfg["full_attention_interval"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head": cfg["head_dim"],
        "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "n_k": cfg["linear_num_key_heads"], "n_v": cfg["linear_num_value_heads"],
        "d_k": cfg["linear_key_head_dim"], "d_v": cfg["linear_value_head_dim"],
        "conv": cfg["linear_conv_kernel_dim"],
        "expert": cfg["moe_intermediate_size"],
        "shared": cfg["shared_expert_intermediate_size"],
        "experts": experts, "router": pub.get("num_experts", experts),
        "first_expert": share["index"] * experts,
        "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def block_names(cfg):
    """[(mixer node, expert node, "attention" | "delta")] of the blocks."""
    m = dims(cfg)
    return [(f"b{i}_mix", f"b{i}_mlp",
             "attention" if (i + 1) % m["period"] == 0 else "delta")
            for i in range(m["layers"])]


def _mixer_shapes(node, kind, m):
    d = m["d"]
    if kind == "attention":
        h, hk, hd = m["heads"], m["kv_heads"], m["head"]
        return {f"{node}/w_q": (d, h * 2 * hd), f"{node}/w_k": (d, hk * hd),
                f"{node}/w_v": (d, hk * hd), f"{node}/q_norm_g": (hd,),
                f"{node}/k_norm_g": (hd,), f"{node}/w_o": (h * hd, d)}
    qk, v = 2 * m["n_k"] * m["d_k"], m["n_v"] * m["d_v"]
    return {f"{node}/w_qkvz": (d, qk + 2 * v), f"{node}/w_ba": (d, 2 * m["n_v"]),
            f"{node}/conv_w": (m["conv"], qk + v), f"{node}/a_log": (m["n_v"],),
            f"{node}/dt_bias": (m["n_v"],), f"{node}/o_norm_w": (m["d_v"],),
            f"{node}/w_out": (v, d)}


def _expert_shapes(node, m):
    d, f, e, fs = m["d"], m["expert"], m["experts"], m["shared"]
    return {f"{node}/w_r": (d, m["router"]),
            f"{node}/e_w_g": (e, d, f), f"{node}/e_w_u": (e, d, f),
            f"{node}/e_w_d": (e, f, d),
            f"{node}/s_w_g": (d, fs), f"{node}/s_w_u": (d, fs),
            f"{node}/s_w_d": (fs, d), f"{node}/s_gate": (d, 1)}


def param_shapes(cfg):
    m = dims(cfg)
    shapes = {"embed/W": (m["vocab"], m["d"])}
    for mix, mlp, kind in block_names(cfg):
        shapes[f"{mix}/norm_g"] = (m["d"],)
        shapes.update(_mixer_shapes(mix, kind, m))
        shapes[f"{mlp}/norm_g"] = (m["d"],)
        shapes.update(_expert_shapes(mlp, m))
    shapes["final_norm/g"] = (m["d"],)
    shapes["lm_head/W"] = (m["d"], m["vocab"])
    return shapes


def _init_leaf(leaf, shape, key):
    """N(0, 0.02) weights; zero-centred gains 0 and the DeltaNet's output gain
    1; A_log = ln U(1, 16) and dt_bias 1 (the Mamba-2 convention)."""
    tail = leaf.split("/")[1]
    if tail in ("norm_g", "q_norm_g", "k_norm_g", "g"):
        return jnp.zeros(shape, jnp.float32)
    if tail in ("o_norm_w", "dt_bias"):
        return jnp.ones(shape, jnp.float32)
    if tail == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shape_items, key):
    return {leaf: _init_leaf(leaf, shape, jax.random.fold_in(key, i))
            for i, (leaf, shape) in enumerate(shape_items)}


def init_params(cfg, key):
    return _init(tuple(param_shapes(cfg).items()), key)


def init_state(cfg):
    return {}


# -------------------------------------------------------------------- math
def norm(x, g, eps):
    """The zero-centred RMS norm: gain 1 + g."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HI)


def rope(x, rotary, theta):
    """x: (B, T, heads, dim); the first `rotary` entries of each head turned,
    the half-split pairing (entry i with entry i + rotary / 2), no scaling."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :rotary], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def causal_attention(qh, kh, vh, scale, q):
    """qh (B, T, H, hd), kh and vh (B, T, Hk, hd) -> (B, T, H, hd): a full
    masked softmax over all keys, `QUERY_BLOCK` queries at a time (each
    block's scores recomputed in the backward pass)."""
    b, t, h, hd = qh.shape
    hk = kh.shape[2]
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    grouped = jnp.pad(qh, ((0, 0), (0, pad), (0, 0), (0, 0))) \
        .reshape(b, (t + pad) // block, block, hk, h // hk, hd)
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        rows, first = args                       # (B, block, Hk, G, hd)
        scores = jnp.einsum("bqkgd,btkd->bkgqt", q(rows), q(kh), precision=HI) * scale
        seen = keys[None, :] <= (first + jnp.arange(block))[:, None]
        attn = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", q(attn), q(vh), precision=HI)

    out = lax.map(one_block, (jnp.moveaxis(grouped, 1, 0),
                              jnp.arange(0, t + pad, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, hd)[:, :t]


def gated_attention(cfg, p, node, u, q):
    """u: (B, T, d) -> (B, T, d)."""
    m = dims(cfg)
    h, hk, hd, eps = m["heads"], m["kv_heads"], m["head"], cfg["rms_norm_eps"]
    b, t, _ = u.shape
    # departure: the columns of W_q are [q | gate] a head (a permutation)
    qh, gate = jnp.split(mm(u, p[f"{node}/w_q"], q).reshape(b, t, h, 2 * hd), 2, axis=-1)
    kh = mm(u, p[f"{node}/w_k"], q).reshape(b, t, hk, hd)
    vh = mm(u, p[f"{node}/w_v"], q).reshape(b, t, hk, hd)
    theta = float(cfg["rope_theta"])
    qh = rope(norm(qh, p[f"{node}/q_norm_g"], eps), m["rotary"], theta)
    kh = rope(norm(kh, p[f"{node}/k_norm_g"], eps), m["rotary"], theta)
    out = causal_attention(qh, kh, vh, hd ** -0.5, q) * jax.nn.sigmoid(gate)
    return mm(out.reshape(b, t, h * hd), p[f"{node}/w_o"], q)


def delta_rule(qh, kh, vh, g, beta):
    """(B, T, H, d_k) twice, (B, T, H, d_v), (B, T, H) twice -> (B, T, H, d_v):
    the recurrence one token at a time, float32, the products written as
    sums so that no backend lowers their precision. In segments of
    `SCAN_SEGMENT` tokens only so that the backward pass keeps one state a
    segment and recomputes the rest."""
    b, t, h, d_k = qh.shape
    seg = min(SCAN_SEGMENT, t)
    pad = -t % seg

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.sum(state * k_t[..., :, None], axis=-2))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    segment = jax.checkpoint(lambda state, xs: lax.scan(token, state, xs))

    def by_segment(a):
        # padding tokens (k = v = 0, beta = 0, g = 0) write nothing
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(((t + pad) // seg, seg) + a.shape[1:])

    start = jnp.zeros((b, h, d_k, vh.shape[-1]), jnp.float32)
    _, out = lax.scan(segment, start, tuple(map(by_segment, (qh, kh, vh, g, beta))))
    return jnp.moveaxis(out.reshape((t + pad,) + out.shape[2:]), 0, 1)[:, :t]


def gated_delta_net(cfg, p, node, u, q):
    """u: (B, T, d) -> (B, T, d)."""
    m = dims(cfg)
    nk, nv, dk, dv, eps = m["n_k"], m["n_v"], m["d_k"], m["d_v"], cfg["rms_norm_eps"]
    b, t, _ = u.shape
    qk_w, v_w = 2 * nk * dk, nv * dv
    # departure: the columns of W_qkvz are [q | k | v | z], of W_ba [b | a]
    # (the published layout interleaves them a key head: a permutation)
    proj = mm(u, p[f"{node}/w_qkvz"], q)
    ba = jnp.matmul(u, p[f"{node}/w_ba"], precision=HI)
    conv_w = p[f"{node}/conv_w"]
    padded = jnp.pad(q(proj[..., :qk_w + v_w]), ((0, 0), (m["conv"] - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, i:i + t] * q(conv_w)[i]
                            for i in range(m["conv"])))
    qh, kh = jnp.split(mixed[..., :qk_w].reshape(b, t, 2 * nk, dk), 2, axis=2)
    vh = mixed[..., qk_w:].reshape(b, t, nv, dv)
    z = proj[..., qk_w + v_w:].reshape(b, t, nv, dv)

    def unit(a, scale=1.0):
        a = a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(a * scale, nv // nk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p[f"{node}/a_log"]) * jax.nn.softplus(ba[..., nv:] + p[f"{node}/dt_bias"])
    o = delta_rule(q(unit(qh, dk ** -0.5)), q(unit(kh)), q(vh), g, beta)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) \
        * p[f"{node}/o_norm_w"]
    return mm((o * jax.nn.silu(z)).reshape(b, t, v_w), p[f"{node}/w_out"], q)


def gated_mlp(x, w_g, w_u, w_d, q):
    return mm(jax.nn.silu(mm(x, w_g, q)) * mm(x, w_u, q), w_d, q)


def route(cfg, p, node, u):
    """(indices (.., k) over the published experts, weights (.., k))."""
    m = dims(cfg)
    scores = jax.nn.softmax(jnp.matmul(u, p[f"{node}/w_r"], precision=HI), axis=-1)
    w, sel = lax.top_k(scores, m["top_k"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # departure, where the configuration says so: under the cut one chip's
    # part of the router's gradient is no stand-in for the group's sum, so
    # the chosen experts' weights are constants of the backward pass
    return sel, w if cfg.get("train_gate", True) else lax.stop_gradient(w)


def experts(cfg, p, node, u, q):
    """The held experts' part for the tokens routed to them (every token
    through every held expert, weighted by what the router gave it there:
    nought for most), plus the gated shared expert."""
    m = dims(cfg)
    sel, w = route(cfg, p, node, u)

    @jax.checkpoint
    def one_more(y, x):
        j, w_g, w_u, w_d = x
        w_j = jnp.sum(jnp.where(sel == m["first_expert"] + j, w, 0.0), axis=-1)
        return y + w_j[..., None] * gated_mlp(u, w_g, w_u, w_d, q), None

    y, _ = lax.scan(one_more, jnp.zeros_like(u),
                    (jnp.arange(m["experts"]), p[f"{node}/e_w_g"],
                     p[f"{node}/e_w_u"], p[f"{node}/e_w_d"]))
    open_ = jax.nn.sigmoid(jnp.matmul(u, p[f"{node}/s_gate"], precision=HI))
    return y + open_ * gated_mlp(u, p[f"{node}/s_w_g"], p[f"{node}/s_w_u"],
                                 p[f"{node}/s_w_d"], q)


def _by_sequence(f, p, x):
    """f(p, (1, T, ...)) over the batch's sequences, one at a time and each
    recomputed in the backward pass: one sequence's activations live at once
    (a token's arithmetic does not know the batch)."""
    return lax.map(jax.checkpoint(lambda row: f(p, row[None])[0]), x)


def _block(cfg, q, p, x, mix, mlp, kind):
    eps = cfg["rms_norm_eps"]
    mixer = gated_attention if kind == "attention" else gated_delta_net
    x = _by_sequence(lambda p_, x_: x_ + mixer(
        cfg, p_, mix, norm(x_, p_[f"{mix}/norm_g"], eps), q), p, x)
    return _by_sequence(lambda p_, x_: x_ + experts(
        cfg, p_, mlp, norm(x_, p_[f"{mlp}/norm_g"], eps), q), p, x)


def _xent(h, w, labels):
    """Mean of -log softmax(h W)[label]: float32 in both modes; a sequence's
    logits at a time (sequences of one length: the mean of their means)."""
    @jax.checkpoint
    def one(args):
        logits = jnp.matmul(args[0], w, precision=HI)
        picked = jnp.take_along_axis(logits, args[1][..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
    return jnp.mean(lax.map(one, (h, labels)))


def data_loss(cfg, mode, params, ids, labels):
    """ids (B, T) -> labels (B, T), both integer, over the held rows."""
    q = refmath.QUANT[mode]
    first_row = cfg.get("share", {}).get("index", 0) * cfg["vocab_size"]
    x = params["embed/W"][ids - first_row]
    for names in block_names(cfg):
        x = _block(cfg, q, params, x, *names)
    return _xent(norm(x, params["final_norm/g"], cfg["rms_norm_eps"]),
                 params["lm_head/W"], labels - first_row)


# ------------------------------------------------------------------ updater
def _hyper(cfg):
    u = cfg["updater"]
    return u["learning_rate"], u["beta1"], u["beta2"], u["epsilon"]


def init_opt(cfg, params):
    """Adam's state before the first step."""
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32)}


def _adam(hyper, opt, grads):
    """Adam as the program's updater has it: the epsilon outside the root,
    the bias corrections folded into the step size."""
    lr, b1, b2, eps = hyper
    t = opt["t"].astype(jnp.float32) + 1.0
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, opt["v"], grads)
    alpha = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    update = jax.tree_util.tree_map(lambda a, b: alpha * a / (jnp.sqrt(b) + eps), m, v)
    return {"m": m, "v": v, "t": opt["t"] + 1}, update


def _freeze(v):
    if isinstance(v, dict):
        return tuple((k, _freeze(x)) for k, x in sorted(v.items()))
    if isinstance(v, list):
        return tuple(_freeze(x) for x in v)
    return v


def _thaw(v):
    if isinstance(v, tuple) and all(isinstance(x, tuple) and len(x) == 2
                                    and isinstance(x[0], str) for x in v):
        return {k: _thaw(x) for k, x in v}
    return v


def _model_cfg(cfg):
    """The keys the equations read (the prose of the file stays out of the
    jit's key)."""
    skip = ("source", "assumed", "reduced", "why", "deployment", "reference",
            "program", "name", "input", "sample_unit", "left_out")
    return _freeze({k: v for k, v in cfg.items() if k not in skip})


def _loss_and_grads(cfg, mode, params, x, y):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(functools.partial(data_loss, cfg, mode))(
            params, x, y)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _step(cfg_key, mode, params, opt, x, y):
    cfg = _thaw(cfg_key)
    loss, grads = _loss_and_grads(cfg, mode, params, x, y)
    opt, update = _adam(_hyper(cfg), opt, grads)
    return jax.tree_util.tree_map(jnp.subtract, params, update), opt, loss


def train_step(cfg, mode, params, opt, state, x, y):
    """One training step of the reference: (parameters, updater's state,
    state, loss). `params` and `opt` are given up to the step."""
    params, opt, loss = _step(_model_cfg(cfg), mode, params, opt, x, y)
    return params, opt, state, loss


@functools.partial(jax.jit, static_argnums=(0, 1))
def _grads(cfg_key, mode, params, x, y):
    return _loss_and_grads(_thaw(cfg_key), mode, params, x, y)


def loss_and_grads(cfg, mode, params, state, x, y):
    loss, grads = _grads(_model_cfg(cfg), mode, params, x, y)
    return loss, grads, state


def apply_updater(cfg, opt, grads):
    """(new state of the updater, the update that is subtracted)."""
    return _adam(_hyper(cfg), opt, grads)


def first_gradient_sq(cfg, opt1):
    """g^2, element by element, of the first gradient as Adam got it, from
    its state after one step from zero: v = (1 - beta2) g^2."""
    scale = 1.0 / (1.0 - cfg["updater"]["beta2"])
    return jax.tree_util.tree_map(lambda v: v * scale, opt1["v"])


# --------------------------------------------------------------- operations
def _matrix_macs(shapes) -> float:
    return float(sum(s[0] * s[1] for s in shapes.values() if len(s) == 2))


def delta_rule_macs_per_token(cfg) -> float:
    """Multiply-adds a token a layer that the recurrence requires in the
    chunked form (chunks of C = `COUNTED_CHUNK`), over the n_v heads: a
    token's rows of K K^T and Q K^T (C d_k each, half of them under the
    causal mask), of the triangular solve (C (d_k + d_v) / 2) and of P U
    (C d_v / 2), and its part of the three products with the state (d_k d_v
    each: W S, Q S, K^T U)."""
    m = dims(cfg)
    c, dk, dv = COUNTED_CHUNK, m["d_k"], m["d_v"]
    return m["n_v"] * (c * dk + c * (dk + dv) / 2.0 + c * dv / 2.0 + 3.0 * dk * dv)


def routed_assignments_per_token(cfg) -> float:
    """Assignments that fall to a held expert, a token: the router's top-k
    over the published experts, taken as even."""
    m = dims(cfg)
    return m["top_k"] * m["experts"] / m["router"]


def train_macs_per_token(cfg) -> dict:
    """Forward multiply-adds a token, by part."""
    m = dims(cfg)
    d, seq = m["d"], cfg["sequence_length"]
    kinds = [kind for _, _, kind in block_names(cfg)]
    n_attn, n_delta = kinds.count("attention"), kinds.count("delta")
    # causal scores and values: each position sees half the sequence on average
    attention = _matrix_macs(_mixer_shapes("x", "attention", m)) \
        + m["heads"] * 2 * m["head"] * seq / 2.0
    delta = _matrix_macs({k: s for k, s in _mixer_shapes("x", "delta", m).items()
                          if not k.endswith("conv_w")}) \
        + m["conv"] * (2 * m["n_k"] * m["d_k"] + m["n_v"] * m["d_v"]) \
        + delta_rule_macs_per_token(cfg)
    one = 3.0 * d * m["expert"]
    expert_layer = d * m["router"] + 3.0 * d * m["shared"] + d \
        + routed_assignments_per_token(cfg) * one
    return {"head": float(d * m["vocab"]), "attention": n_attn * attention,
            "delta_net": n_delta * delta, "experts": len(kinds) * expert_layer}


def train_flops_per_sample(cfg) -> float:
    """Forward plus backward (3x the forward's multiply-adds x2) of one
    sequence; nothing recomputed counted."""
    return 6.0 * sum(train_macs_per_token(cfg).values()) * cfg["sequence_length"]


def routed_products_flops_per_sample(cfg) -> float:
    """What the held experts' three products must compute for one sequence,
    forward and backward (towards the tokens and towards the weights)."""
    m = dims(cfg)
    return 6.0 * 3.0 * m["d"] * m["expert"] * routed_assignments_per_token(cfg) \
        * cfg["sequence_length"] * m["layers"]


def routed_products_bytes_per_sample(cfg, itemsize: int) -> float:
    """The least the three products must move through HBM for one sequence at
    the compute type's width: forward reads the held experts' weights once
    and each routed row (d), writes and reads the two hidden rows (f) and
    writes the output row (d); backward reads all of that again with the
    incoming gradient and writes the gradients of rows and weights. The
    weights cross once a step, whatever its sequences: one sequence bears
    its part of the configuration's `microbatch`."""
    m = dims(cfg)
    weights = 3.0 * m["experts"] * m["d"] * m["expert"] / cfg.get("microbatch", 1)
    rows = routed_assignments_per_token(cfg) * cfg["sequence_length"]
    per_row = 2 * m["d"] + 4 * m["expert"]
    return float(itemsize * m["layers"] * 3 * (weights + rows * per_row))


def delta_rule_flops_per_sample(cfg) -> float:
    """What the recurrence of every DeltaNet layer must compute for one
    sequence, forward and backward (twice the forward's)."""
    n_delta = sum(kind == "delta" for _, _, kind in block_names(cfg))
    return 6.0 * delta_rule_macs_per_token(cfg) * cfg["sequence_length"] * n_delta


def delta_rule_bytes_per_sample(cfg, itemsize: int) -> float:
    """The least the recurrence must move through HBM for one sequence: the
    forward reads q, k, v a value head at the compute type's width and g,
    beta in float32 and writes o; the backward reads them again with o's
    gradient and writes the five gradients. The state need not cross: a
    chunk's state fits the chip's fast memory."""
    m = dims(cfg)
    n_delta = sum(kind == "delta" for _, _, kind in block_names(cfg))
    qkv = m["n_v"] * (2 * m["d_k"] + m["d_v"]) * itemsize
    o = m["n_v"] * m["d_v"] * itemsize
    gates = 2 * m["n_v"] * 4
    per_token = (qkv + gates + o) + (qkv + gates + o) + (qkv + gates)
    return float(per_token * cfg["sequence_length"] * n_delta)
