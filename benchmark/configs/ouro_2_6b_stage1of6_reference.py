"""Plain reference for Ouro-2.6B (training step), as one stage of a pipeline.

Written from the keys of the published `config.json` (`model_type` ouro), the
LoopLM report (arXiv:2510.25741) and the layer order of the published modeling
code: the whole stack of layers runs `total_ut_steps` times with the same
weights, a layer has a norm before and another after each sublayer, and after
each pass come the final norm (whose output is the next pass's input) and the
early-exit gate. Equations, with eps = `rms_norm_eps` and every gain g from 1:

  norm      y = x rsqrt(mean(x^2) + eps) g
  layer     a = x + norm_a2(attn(norm_a1(x))); x' = a + norm_m2(MLP(norm_m1(a)))
            MLP(u) = (silu(u W_g) (u W_u)) W_d
  attention q = u W_q, k = u W_k, v = u W_v on heads of `head_dim`, rotary
            (the half-split pairing: entry i with entry i + head/2) over the
            whole head at angles pos theta^(-2i/head), theta = `rope_theta`;
            causal softmax at head^-1/2; y = o W_o; no bias, no q/k norm
  pass t    x <- every held layer in order; h_t = norm_f(x); x <- h_t
  gate      lambda_t = sigmoid(h_t w_gate + b_gate), a token
  exit      p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < P, p_P =
            prod_{j<P} (1 - lambda_j), P = `total_ut_steps` (in logs)
  loss      mean over positions of sum_t p_t CE_t + beta sum_t p_t log p_t,
            CE_t = -log softmax(h_t W_head)[label], beta = `entropy_weight`

Float32 `jax.numpy`, `highest` precision, no kernel, no cache: the P passes
written out one after the other over the same weights, the attention a full
masked softmax a block of queries at a time. Each pass is recomputed in the
backward pass, and inside it each sublayer again, a sequence at a time; the
heads score a pass and a sequence at a time. Adam takes the leaves one at a
time (each leaf's gradient is given up to its update), so that the step fits
beside the parameters, their gradients and Adam's two moments. Imports nothing
of `deeplearning4j_tpu`.

The cut: `num_hidden_layers` of the published layers are held (one stage of
a pipeline that runs its layers at every pass); the table, the final norm,
the gate and the head are whole.

`mode` other than "f32" is the control: every operand of a block's matrix
product held in the lower type (int8, `harness/refmath.py`), forward and
backward, the way the configuration holds them in bfloat16; the norms, the
gate, the heads and the loss stay float32.

Leaves are named `<node>/<key>`, node for node with the program's graph:
`embed/W`; `loop/l<i>_attn.<key>`, `loop/l<i>_mlp.<key>` (a block's
sublayer's leaves, `norm_g` before it and `norm_out_g` after it),
`loop/norm_g` (the final norm); `exit_head/W`, `exit_head/w_gate`,
`exit_head/b_gate`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from harness import refmath

HI = lax.Precision.HIGHEST

# Queries a block of the attention's masked softmax (a block's scores over
# all keys are what lives at once).
QUERY_BLOCK = 512
GAINS = ("norm_g", "norm_out_g")


# ------------------------------------------------------------------ shapes
def dims(cfg):
    """The sizes the equations use, from the configuration's keys."""
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "mlp": cfg["intermediate_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
        "vocab": cfg["vocab_size"], "passes": cfg["total_ut_steps"],
    }


def block_names(cfg):
    """[(attention block, MLP block)] of the layers held."""
    return [(f"l{i}_attn", f"l{i}_mlp") for i in range(cfg["num_hidden_layers"])]


def _attention_shapes(node, m):
    d, q, kv = m["d"], m["heads"] * m["head"], m["kv_heads"] * m["head"]
    return {f"{node}.w_q": (d, q), f"{node}.w_k": (d, kv),
            f"{node}.w_v": (d, kv), f"{node}.w_o": (q, d)}


def _mlp_shapes(node, m):
    d, f = m["d"], m["mlp"]
    return {f"{node}.w_g": (d, f), f"{node}.w_u": (d, f), f"{node}.w_d": (f, d)}


def param_shapes(cfg):
    m = dims(cfg)
    d = m["d"]
    shapes = {"embed/W": (m["vocab"], d)}
    for attn, mlp in block_names(cfg):
        for node, own in ((attn, _attention_shapes(attn, m)),
                          (mlp, _mlp_shapes(mlp, m))):
            shapes.update({f"loop/{k}": s for k, s in own.items()})
            shapes.update({f"loop/{node}.{g}": (d,) for g in GAINS})
    shapes["loop/norm_g"] = (d,)
    shapes["exit_head/W"] = (d, m["vocab"])
    shapes["exit_head/w_gate"] = (d, 1)
    shapes["exit_head/b_gate"] = (1,)
    return shapes


def _init_leaf(leaf, shape, key):
    """N(0, 0.02) weights; every gain 1; the gate's bias 0."""
    tail = leaf.split("/")[1].split(".")[-1]
    if tail in GAINS:
        return jnp.ones(shape, jnp.float32)
    if tail == "b_gate":
        return jnp.zeros(shape, jnp.float32)
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


def rope(x, theta):
    """x (B, T, H, head): rotated over the whole head, the half-split pairing."""
    head = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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
    """u: (B, T, d) -> (B, T, d)."""
    m = dims(cfg)
    h, hk, hd = m["heads"], m["kv_heads"], m["head"]
    b, t, _ = u.shape
    theta = float(cfg["rope_theta"])
    qh = rope(mm(u, p[f"loop/{node}.w_q"], q).reshape(b, t, h, hd), theta)
    kh = rope(mm(u, p[f"loop/{node}.w_k"], q).reshape(b, t, hk, hd), theta)
    vh = mm(u, p[f"loop/{node}.w_v"], q).reshape(b, t, hk, hd)
    out = causal_attention(qh, kh, vh, hd ** -0.5, q)
    return mm(out.reshape(b, t, h * hd), p[f"loop/{node}.w_o"], q)


def gated_mlp(cfg, p, node, u, q):
    w = lambda k: p[f"loop/{node}.{k}"]
    return mm(jax.nn.silu(mm(u, w("w_g"), q)) * mm(u, w("w_u"), q), w("w_d"), q)


def _sublayer(cfg, q, f, node, p, x):
    """x + norm'(f(norm(x))), a sequence at a time and each recomputed in
    the backward pass."""
    eps = cfg["rms_norm_eps"]

    def one(row):
        u = norm(row, p[f"loop/{node}.norm_g"], eps)
        y = f(cfg, p, node, u[None], q)[0]
        return row + norm(y, p[f"loop/{node}.norm_out_g"], eps)
    return lax.map(jax.checkpoint(one), x)


def one_pass(cfg, q, p, x):
    """Every held layer, then the final norm: the next pass's input."""
    for attn, mlp in block_names(cfg):
        x = _sublayer(cfg, q, attention, attn, p, x)
        x = _sublayer(cfg, q, gated_mlp, mlp, p, x)
    return norm(x, p["loop/norm_g"], cfg["rms_norm_eps"])


def _scored(h, w, w_gate, b_gate, labels):
    """A pass's (CE, gate logit), each (B, T): float32 in both modes; a
    sequence's logits at a time."""
    @jax.checkpoint
    def one(args):
        row, lab = args
        logits = jnp.matmul(row, w, precision=HI)
        picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        z = jnp.matmul(row, w_gate, precision=HI)[..., 0] + b_gate[0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, z
    return lax.map(one, (h, labels))


def exit_loss(ce, z, beta):
    """ce, z (P, B, T) -> the loss: the exit distribution's expected
    cross-entropy less beta times its entropy, a position, averaged. The
    distribution is taken in logs, log p_t = log lambda_t + sum_{j<t} log(1
    - lambda_j), each log of a sigmoid as `log_sigmoid`: where training
    saturates a gate, 1 - lambda rounds to 0 in float32 and the product form
    reads p log p = 0 * -inf (at the cell's size on a v5e it read NaN within
    eleven steps on three seeds of nine)."""
    log_p, stay = [], jnp.zeros_like(z[0])
    for t in range(ce.shape[0] - 1):
        log_p.append(jax.nn.log_sigmoid(z[t]) + stay)
        stay = stay + jax.nn.log_sigmoid(-z[t])
    log_p = jnp.stack(log_p + [stay])
    exit_p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(exit_p * ce, axis=0)
                    + beta * jnp.sum(exit_p * log_p, axis=0))


def data_loss(cfg, mode, params, ids, labels):
    """ids (B, T) -> labels (B, T), both integer."""
    q = refmath.QUANT[mode]
    x = params["embed/W"][ids]
    ce, z = [], []
    step = jax.checkpoint(functools.partial(one_pass, cfg, q))
    for _ in range(cfg["total_ut_steps"]):
        x = step(params, x)
        c, g = _scored(x, params["exit_head/W"], params["exit_head/w_gate"],
                       params["exit_head/b_gate"], labels)
        ce.append(c)
        z.append(g)
    return exit_loss(jnp.stack(ce), jnp.stack(z), cfg["entropy_weight"])


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
    keep = ("hidden_size", "num_hidden_layers", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "total_ut_steps", "rms_norm_eps", "rope_theta",
            "entropy_weight", "sequence_length", "note")
    return _freeze({k: v for k, v in cfg.items() if k in keep})


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


def train_macs_per_token(cfg) -> dict:
    """Forward multiply-adds a token, by part: every pass of every held
    layer, and every pass's head and gate."""
    m = dims(cfg)
    per_pass = m["passes"] * m["layers"]
    # causal scores and values: each position sees half the sequence on average
    attention_ = _matrix_macs(_attention_shapes("x", m)) \
        + m["heads"] * 2 * m["head"] * cfg["sequence_length"] / 2.0
    return {"attention": per_pass * attention_,
            "mlp": per_pass * _matrix_macs(_mlp_shapes("x", m)),
            "exit_heads": m["passes"] * float(m["d"] * m["vocab"] + m["d"])}


def train_flops_per_sample(cfg) -> float:
    """Forward plus backward (3x the forward's multiply-adds x2) of one
    sequence; nothing recomputed counted."""
    return 6.0 * sum(train_macs_per_token(cfg).values()) * cfg["sequence_length"]
