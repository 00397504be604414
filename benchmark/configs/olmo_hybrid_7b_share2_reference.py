"""Plain reference for Olmo-Hybrid-7B (training step), as one chip's share of
a layer group.

Written from the keys of the published `config.json` (`model_type`
olmo_hybrid), the Gated DeltaNet paper (arXiv:2412.06464) as the FLA layer
has it, and the Olmo family's conventions (Olmo 2 and 3: the norm on each
sublayer's output, a norm over the whole width of q and of k). Equations,
with eps = `rms_norm_eps` and every gain g from 1:

  norm      y = x rsqrt(mean(x^2) + eps) g
  layer i   h = x + norm(mixer_i(x)); x' = h + norm(MLP(h)); MLP(h) =
            (silu(h W_g) (h W_u)) W_d; mixer_i by `layer_types[i]`
  DeltaNet  (linear_attention) q = silu(conv4(x W_q)), k = silu(conv4(x W_k)),
            v = silu(conv4(x W_v)), causal depthwise convolutions, no bias;
            q, k <- x / sqrt(sum(x^2) + 1e-6) a head, q by d_k^-1/2 more; beta
            = 2 sigmoid(x W_b) (`linear_allow_neg_eigval`; sigmoid without
            it); g = -exp(A_log) softplus(x W_a + dt_bias); a head, from S_0
            = 0 of (d_k, d_v): S' = exp(g_t) S_{t-1}; u_t = beta_t (v_t -
            S'^T k_t); S_t = S' + k_t u_t^T; o_t = S_t^T q_t; y = (o rsqrt(
            mean(o^2) + eps) w silu(x W_z)) W_o, w one gain of d_v
  attention (full_attention) q = norm_q(x W_q), k = norm_k(x W_k), each norm
            over the projection's whole held width with its own gain; v = x
            W_v; heads of hidden_size / num_attention_heads; no rotary
            (`rope_theta` null); causal softmax at head^-1/2; y = o W_o
  loss      mean cross-entropy of norm(x) W_head over the held rows

Float32 `jax.numpy`, `highest` precision, no kernel, no cache: the recurrence
one token at a time in a `lax.scan`, the attention a full masked softmax a
block of queries at a time. Every sublayer is recomputed in the backward
pass, and Adam takes the leaves one at a time (each leaf's gradient is given
up to its update), so that the step fits beside the parameters, their
gradients and Adam's two moments. Imports nothing of `deeplearning4j_tpu`.

Departures from the published description, each where it is made: the
mixer's separate projections W_q, W_k, W_v, W_z are the columns [q | k | v |
z] of one matrix, W_b and W_a the columns [b | a] of another, the three
convolutions' taps side by side (the same products on random weights).

The share: the configuration says how many heads of every mixer and rows of
the vocabulary are held here (`num_attention_heads`, `num_key_value_heads`,
`linear_num_key_heads`, `linear_num_value_heads`, `vocab_size`) of the
published counts (`published`), and which share this is (`share.index` of
`share.of`). A mixer computes its held heads' part of the sum over heads,
and what absent heads would have added is left out; the norm over q's and
k's whole width runs over the held columns; the MLP is whole on every chip.
Ids, logits and loss are over the held rows.

`mode` other than "f32" is the control: every operand of a matrix product
held in the lower type (int8, `harness/refmath.py`), forward and backward,
the way the configuration holds them in bfloat16 (the recurrence's q, k, v
among them); the recurrence's state, the gates, the head and the loss stay
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
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "mlp": cfg["intermediate_size"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head": cfg["hidden_size"] // pub.get("num_attention_heads",
                                              cfg["num_attention_heads"]),
        "n_k": cfg["linear_num_key_heads"], "n_v": cfg["linear_num_value_heads"],
        "d_k": cfg["linear_key_head_dim"], "d_v": cfg["linear_value_head_dim"],
        "conv": cfg["linear_conv_kernel_dim"],
        "beta_scale": 2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
        "vocab": cfg["vocab_size"],
    }


def block_names(cfg):
    """[(mixer node, MLP node, "attention" | "delta")] of the layers held."""
    kinds = {"full_attention": "attention", "linear_attention": "delta"}
    return [(f"b{i}_mix", f"b{i}_mlp", kinds[kind]) for i, kind in
            enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]])]


def _mixer_shapes(node, kind, m):
    d = m["d"]
    if kind == "attention":
        q, kv = m["heads"] * m["head"], m["kv_heads"] * m["head"]
        return {f"{node}/w_q": (d, q), f"{node}/w_k": (d, kv),
                f"{node}/w_v": (d, kv), f"{node}/q_norm_g": (q,),
                f"{node}/k_norm_g": (kv,), f"{node}/w_o": (q, d)}
    qk, v = 2 * m["n_k"] * m["d_k"], m["n_v"] * m["d_v"]
    return {f"{node}/w_qkvz": (d, qk + 2 * v), f"{node}/w_ba": (d, 2 * m["n_v"]),
            f"{node}/conv_w": (m["conv"], qk + v), f"{node}/a_log": (m["n_v"],),
            f"{node}/dt_bias": (m["n_v"],), f"{node}/o_norm_w": (m["d_v"],),
            f"{node}/w_out": (v, d)}


def _mlp_shapes(node, m):
    d, f = m["d"], m["mlp"]
    return {f"{node}/w_g": (d, f), f"{node}/w_u": (d, f), f"{node}/w_d": (f, d)}


def param_shapes(cfg):
    m = dims(cfg)
    shapes = {"embed/W": (m["vocab"], m["d"])}
    for mix, mlp, kind in block_names(cfg):
        shapes.update(_mixer_shapes(mix, kind, m))
        shapes[f"{mix}/norm_g"] = (m["d"],)
        shapes.update(_mlp_shapes(mlp, m))
        shapes[f"{mlp}/norm_g"] = (m["d"],)
    shapes["final_norm/g"] = (m["d"],)
    shapes["lm_head/W"] = (m["d"], m["vocab"])
    return shapes


def _init_leaf(leaf, shape, key):
    """N(0, 0.02) weights; every gain and dt_bias 1; A_log = ln U(1, 16) (the
    Mamba-2 convention the FLA layer keeps)."""
    tail = leaf.split("/")[1]
    if tail in ("norm_g", "q_norm_g", "k_norm_g", "g", "o_norm_w", "dt_bias"):
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
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HI)


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


def attention(cfg, p, node, u, q):
    """u: (B, T, d) -> (B, T, d), the held heads' part."""
    m = dims(cfg)
    h, hk, hd, eps = m["heads"], m["kv_heads"], m["head"], cfg["rms_norm_eps"]
    b, t, _ = u.shape
    qh = norm(mm(u, p[f"{node}/w_q"], q), p[f"{node}/q_norm_g"], eps)
    kh = norm(mm(u, p[f"{node}/w_k"], q), p[f"{node}/k_norm_g"], eps)
    vh = mm(u, p[f"{node}/w_v"], q)
    out = causal_attention(qh.reshape(b, t, h, hd), kh.reshape(b, t, hk, hd),
                           vh.reshape(b, t, hk, hd), hd ** -0.5, q)
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
    """u: (B, T, d) -> (B, T, d), the held heads' part."""
    m = dims(cfg)
    nk, nv, dk, dv, eps = m["n_k"], m["n_v"], m["d_k"], m["d_v"], cfg["rms_norm_eps"]
    b, t, _ = u.shape
    qk_w, v_w = 2 * nk * dk, nv * dv
    # departure: the separate projections are the columns [q | k | v | z] of
    # one matrix, [b | a] of another, the convolutions' taps side by side
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
    beta = m["beta_scale"] * jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p[f"{node}/a_log"]) * jax.nn.softplus(ba[..., nv:] + p[f"{node}/dt_bias"])
    o = delta_rule(q(unit(qh, dk ** -0.5)), q(unit(kh)), q(vh), g, beta)
    o = norm(o, p[f"{node}/o_norm_w"], eps)
    return mm((o * jax.nn.silu(z)).reshape(b, t, v_w), p[f"{node}/w_out"], q)


def gated_mlp(cfg, p, node, u, q):
    return mm(jax.nn.silu(mm(u, p[f"{node}/w_g"], q)) * mm(u, p[f"{node}/w_u"], q),
              p[f"{node}/w_d"], q)


def _sublayer(cfg, q, f, node, p, x):
    """x + norm(f(x)), a sequence at a time and each recomputed in the
    backward pass: one sequence's activations of one sublayer live at once
    (a token's arithmetic does not know the batch)."""
    def one(row):
        y = f(cfg, p, node, row[None], q)[0]
        return row + norm(y, p[f"{node}/norm_g"], cfg["rms_norm_eps"])
    return lax.map(jax.checkpoint(one), x)


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
    for mix, mlp, kind in block_names(cfg):
        mixer = attention if kind == "attention" else gated_delta_net
        x = _sublayer(cfg, q, mixer, mix, params, x)
        x = _sublayer(cfg, q, gated_mlp, mlp, params, x)
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


def _adam_leaf(hyper, t, m, v, g):
    """Adam on one leaf as the program's updater has it: the epsilon outside
    the root, the bias corrections folded into the step size. (m, v, the
    update that is subtracted)."""
    lr, b1, b2, eps = hyper
    t = t.astype(jnp.float32)
    m, v = b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g
    alpha = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return m, v, alpha * m / (jnp.sqrt(v) + eps)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def _step_leaf(hyper, t, p, m, v, g):
    m, v, update = _adam_leaf(hyper, t, m, v, g)
    return p - update, m, v


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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _grads(cfg_key, mode, params, x, y):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(functools.partial(
            data_loss, _thaw(cfg_key), mode))(params, x, y)


def train_step(cfg, mode, params, opt, state, x, y):
    """One training step of the reference: (parameters, updater's state,
    state, loss). `params` and `opt` are given up to the step, which hands
    Adam the leaves one at a time: a leaf's gradient goes as its update is
    made, and no second copy of the parameters or the moments ever lives."""
    loss, grads = _grads(_model_cfg(cfg), mode, params, x, y)
    hyper, t = _hyper(cfg), opt["t"] + 1
    for leaf in list(params):
        params[leaf], opt["m"][leaf], opt["v"][leaf] = _step_leaf(
            hyper, t, params[leaf], opt["m"][leaf], opt["v"][leaf],
            grads.pop(leaf))
    return params, dict(opt, t=t), state, loss


def loss_and_grads(cfg, mode, params, state, x, y):
    loss, grads = _grads(_model_cfg(cfg), mode, params, x, y)
    return loss, grads, state


def apply_updater(cfg, opt, grads):
    """(new state of the updater, the update that is subtracted)."""
    hyper, t = _hyper(cfg), opt["t"] + 1
    out = {leaf: _adam_leaf(hyper, t, opt["m"][leaf], opt["v"][leaf], g)
           for leaf, g in grads.items()}
    return {"m": {k: o[0] for k, o in out.items()},
            "v": {k: o[1] for k, o in out.items()}, "t": t}, \
        {k: o[2] for k, o in out.items()}


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
    chunked form (chunks of C = `COUNTED_CHUNK`), over the held value heads
    at their published widths (96 and 192: lanes a kernel fills up to whole
    tiles are no required work): a token's rows of K K^T and Q K^T (C d_k
    each, half of them under the causal mask), of the triangular solve (C
    (d_k + d_v) / 2) and of P U (C d_v / 2), and its part of the three
    products with the state (d_k d_v each: W S, Q S, K^T U)."""
    m = dims(cfg)
    c, dk, dv = COUNTED_CHUNK, m["d_k"], m["d_v"]
    return m["n_v"] * (c * dk + c * (dk + dv) / 2.0 + c * dv / 2.0 + 3.0 * dk * dv)


def train_macs_per_token(cfg) -> dict:
    """Forward multiply-adds a token, by part."""
    m = dims(cfg)
    seq = cfg["sequence_length"]
    kinds = [kind for _, _, kind in block_names(cfg)]
    # causal scores and values: each position sees half the sequence on average
    attention_ = _matrix_macs(_mixer_shapes("x", "attention", m)) \
        + m["heads"] * 2 * m["head"] * seq / 2.0
    delta = _matrix_macs({k: s for k, s in _mixer_shapes("x", "delta", m).items()
                          if not k.endswith("conv_w")}) \
        + m["conv"] * (2 * m["n_k"] * m["d_k"] + m["n_v"] * m["d_v"]) \
        + delta_rule_macs_per_token(cfg)
    return {"head": float(m["d"] * m["vocab"]),
            "attention": kinds.count("attention") * attention_,
            "delta_net": kinds.count("delta") * delta,
            "mlp": len(kinds) * _matrix_macs(_mlp_shapes("x", m))}


def train_flops_per_sample(cfg) -> float:
    """Forward plus backward (3x the forward's multiply-adds x2) of one
    sequence; nothing recomputed counted."""
    return 6.0 * sum(train_macs_per_token(cfg).values()) * cfg["sequence_length"]


def _delta_layers(cfg) -> int:
    return sum(kind == "delta" for _, _, kind in block_names(cfg))


def delta_rule_flops_per_sample(cfg) -> float:
    """What the recurrence of every DeltaNet layer must compute for one
    sequence, forward and backward (twice the forward's)."""
    return 6.0 * delta_rule_macs_per_token(cfg) * cfg["sequence_length"] \
        * _delta_layers(cfg)


def delta_rule_bytes_per_sample(cfg, itemsize: int) -> float:
    """The least the recurrence must move through HBM for one sequence: the
    forward reads q, k, v a value head at the compute type's width and g,
    beta in float32 and writes o; the backward reads them again with o's
    gradient and writes the five gradients; all at the published widths. The
    state need not cross: a chunk's state fits the chip's fast memory."""
    m = dims(cfg)
    qkv = m["n_v"] * (2 * m["d_k"] + m["d_v"]) * itemsize
    o = m["n_v"] * m["d_v"] * itemsize
    gates = 2 * m["n_v"] * 4
    per_token = (qkv + gates + o) + (qkv + gates + o) + (qkv + gates)
    return float(per_token * cfg["sequence_length"] * _delta_layers(cfg))
