"""Plain reference for Xing4.0-29B-A4B (training step), as one chip's share
of a layer group.

Written from the keys of the published `config.json` (`model_type` xing4_0)
and the papers the mechanisms come from: latent attention with a decoupled
rotary key and YaRN frequencies and sigmoid top-k routing with a shared
expert (DeepSeek-V3, arXiv:2412.19437), manifold-constrained
hyper-connections (arXiv:2512.24880) and the depth-1 multi-token-prediction
module (DeepSeek-V3 section 2.2). What the config has no key for is listed
under `assumed` in the configuration's file. Float32 `jax.numpy`, no kernel,
no cache; every block is recomputed in the backward pass so that the step
fits beside the parameters, their gradients and Adam's two moments. Imports
nothing of `deeplearning4j_tpu`.

The share: the configuration says how many heads, routed experts and rows of
the vocabulary are held here (`num_attention_heads`, `n_routed_experts`,
`vocab_size`) of the published counts (`published`), and which share this is
(`share.index` of `share.of`). The router scores all published experts and
picks `num_experts_per_tok` of them; this share adds its own experts' part
for the tokens routed to them, and what absent heads and experts would have
added is left out. Ids, logits and loss are over the held rows.

`mode` other than "f32" is the control: every operand of a matrix product
held in the lower type (int8, `harness/refmath.py`), forward and backward,
the way the configuration holds them in bfloat16; the heads and the loss
stay float32.

Leaves are named `<node>/<key>`, node for node with the program's graph.
`<node>/router_bias` is the router's selection bias: a buffer drawn from the
seed that no gradient reaches (its gradient reads zero).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from harness import refmath

HI = lax.Precision.HIGHEST


# ------------------------------------------------------------------ shapes
def dims(cfg):
    """The sizes the equations use, from the configuration's keys."""
    pub = cfg.get("published", {})
    share = cfg.get("share", {"index": 0, "of": 1})
    heads = cfg["num_attention_heads"]
    experts = cfg["n_routed_experts"]
    return {
        "d": cfg["hidden_size"], "n": cfg["hc_mult"],
        "heads": heads, "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "dense": cfg["intermediate_size"], "expert": cfg["moe_intermediate_size"],
        "experts": experts,
        "router": pub.get("n_routed_experts", experts),
        "first_expert": share["index"] * experts,
        "top_k": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "mtp": cfg["num_nextn_predict_layers"],
    }


def block_names(cfg):
    """[(attention node, MLP node, "dense" | "experts")] of the main model's
    blocks, then of the MTP module's."""
    m = dims(cfg)
    blocks = [(f"b{i}_attn", f"b{i}_mlp",
               "dense" if i < m["dense_layers"] else "experts")
              for i in range(m["layers"])]
    mtp = [("mtp_attn", "mtp_mlp", "experts")] if m["mtp"] else []
    return blocks, mtp


def _hc_shapes(node, m):
    nd, n = m["n"] * m["d"], m["n"]
    return {f"{node}/hc_phi_pre": (nd, n), f"{node}/hc_phi_post": (nd, n),
            f"{node}/hc_phi_res": (nd, n * n), f"{node}/hc_a": (3,),
            f"{node}/hc_b_pre": (n,), f"{node}/hc_b_post": (n,),
            f"{node}/hc_b_res": (n, n), f"{node}/norm_g": (m["d"],)}


def _attn_shapes(node, m):
    d, h = m["d"], m["heads"]
    return {f"{node}/w_qa": (d, m["q_rank"]), f"{node}/q_norm_g": (m["q_rank"],),
            f"{node}/w_qb": (m["q_rank"], h * (m["nope"] + m["rope"])),
            f"{node}/w_kva": (d, m["kv_rank"] + m["rope"]),
            f"{node}/kv_norm_g": (m["kv_rank"],),
            f"{node}/w_kvb": (m["kv_rank"], h * (m["nope"] + m["v"])),
            f"{node}/w_o": (h * m["v"], d)}


def _mlp_shapes(node, kind, m):
    d = m["d"]
    if kind == "dense":
        f = m["dense"]
        return {f"{node}/w_g": (d, f), f"{node}/w_u": (d, f), f"{node}/w_d": (f, d)}
    f, e = m["expert"], m["experts"]
    return {f"{node}/w_r": (d, m["router"]), f"{node}/router_bias": (m["router"],),
            f"{node}/e_w_g": (e, d, f), f"{node}/e_w_u": (e, d, f),
            f"{node}/e_w_d": (e, f, d),
            f"{node}/s_w_g": (d, f), f"{node}/s_w_u": (d, f), f"{node}/s_w_d": (f, d)}


def param_shapes(cfg):
    m = dims(cfg)
    shapes = {"embed/W": (m["vocab"], m["d"])}
    blocks, mtp = block_names(cfg)
    if mtp:
        shapes.update({"mtp_in/norm_e_g": (m["d"],), "mtp_in/norm_h_g": (m["d"],),
                       "mtp_in/W": (2 * m["d"], m["d"])})
    for attn, mlp, kind in blocks + mtp:
        shapes.update(_hc_shapes(attn, m))
        shapes.update(_attn_shapes(attn, m))
        shapes.update(_hc_shapes(mlp, m))
        shapes.update(_mlp_shapes(mlp, kind, m))
    shapes["final_norm/g"] = (m["d"],)
    shapes["lm_head/W"] = (m["d"], m["vocab"])
    if mtp:
        shapes["mtp_norm/g"] = (m["d"],)
    return shapes


def is_buffer(leaf: str) -> bool:
    return leaf.endswith("/router_bias")


def _init_leaf(leaf, shape, n, key):
    """N(0, 0.02) weights; unit gains; hyper-connection scalars 0.1, zero
    pre/post biases (H_pre 0.5, H_post 1) and 4 on H_res' diagonal (near the
    identity after Sinkhorn): a pre-norm residual net at the start."""
    tail = leaf.split("/")[1]
    if tail in ("norm_g", "q_norm_g", "kv_norm_g", "g", "norm_e_g", "norm_h_g"):
        return jnp.ones(shape, jnp.float32)
    if tail == "hc_a":
        return jnp.full(shape, 0.1, jnp.float32)
    if tail in ("hc_b_pre", "hc_b_post"):
        return jnp.zeros(shape, jnp.float32)
    if tail == "hc_b_res":
        return 4.0 * jnp.eye(n, dtype=jnp.float32)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(shape_items, n, key):
    return {leaf: _init_leaf(leaf, shape, n, jax.random.fold_in(key, i))
            for i, (leaf, shape) in enumerate(shape_items)}


def init_params(cfg, key):
    return _init(tuple(param_shapes(cfg).items()), cfg["hc_mult"], key)


def init_state(cfg):
    return {}


# -------------------------------------------------------------------- math
def rms_norm(x, g, eps):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if g is None else y * g


def mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HI)


def yarn_inv_freq(cfg):
    """YaRN's frequencies: interpolated (divided by `factor`) below the
    `beta_slow` rotation count, extrapolated (as trained) above `beta_fast`,
    a linear ramp between."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (factor * pos)) * (1.0 - extrapolated) + (1.0 / pos) * extrapolated


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0 \
        if rs["factor"] > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, inv_freq):
    """x: (..., T, heads, dim); the half-split pairing."""
    t = jnp.arange(x.shape[-3], dtype=jnp.float32)
    ang = t[:, None] * inv_freq[None, :]                    # (T, dim/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_attention(cfg, p, node, u, q):
    """u: (B, T, d) -> (B, T, d), the held heads' part."""
    m = dims(cfg)
    h = m["heads"]
    eps = cfg["rms_norm_eps"]
    b, t, _ = u.shape
    c_q = rms_norm(mm(u, p[f"{node}/w_qa"], q), p[f"{node}/q_norm_g"], eps)
    qh = mm(c_q, p[f"{node}/w_qb"], q).reshape(b, t, h, m["nope"] + m["rope"])
    q_nope, q_rope = qh[..., :m["nope"]], qh[..., m["nope"]:]
    kva = mm(u, p[f"{node}/w_kva"], q)
    c_kv = rms_norm(kva[..., :m["kv_rank"]], p[f"{node}/kv_norm_g"], eps)
    k_r = kva[..., m["kv_rank"]:].reshape(b, t, 1, m["rope"])
    kv = mm(c_kv, p[f"{node}/w_kvb"], q).reshape(b, t, h, m["nope"] + m["v"])
    k_nope, v = kv[..., :m["nope"]], kv[..., m["nope"]:]
    inv_freq = yarn_inv_freq(cfg)
    q_rope, k_rope = rope(q_rope, inv_freq), rope(k_r, inv_freq)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q(qq), q(kk), precision=HI) \
        * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", q(attn), q(v), precision=HI)
    return mm(out.reshape(b, t, h * m["v"]), p[f"{node}/w_o"], q)


def gated_mlp(x, w_g, w_u, w_d, q):
    return mm(jax.nn.silu(mm(x, w_g, q)) * mm(x, w_u, q), w_d, q)


def route(cfg, p, node, u):
    """(indices (.., k) over the published experts, weights (.., k))."""
    m = dims(cfg)
    s = jax.nn.sigmoid(jnp.matmul(u, p[f"{node}/w_r"], precision=HI))
    _, sel = lax.top_k(s + lax.stop_gradient(p[f"{node}/router_bias"]), m["top_k"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w * cfg["routed_scaling_factor"]


def routed_experts(cfg, p, node, u, q):
    """The held experts' part for the tokens routed to them (every token
    through every held expert, weighted by what the router gave it there:
    nought for most), plus the shared expert."""
    m = dims(cfg)
    sel, w = route(cfg, p, node, u)
    y = jnp.zeros_like(u)
    for j in range(m["experts"]):
        w_j = jnp.sum(jnp.where(sel == m["first_expert"] + j, w, 0.0), axis=-1)
        y = y + w_j[..., None] * gated_mlp(
            u, p[f"{node}/e_w_g"][j], p[f"{node}/e_w_u"][j], p[f"{node}/e_w_d"][j], q)
    return y + gated_mlp(u, p[f"{node}/s_w_g"], p[f"{node}/s_w_u"],
                         p[f"{node}/s_w_d"], q)


def sinkhorn(logits, iters, eps):
    """exp, then `iters` rounds of column then row normalisation. The two
    axes of the matrix lead while it is iterated (the tokens stay the minor
    axis, which the chip's tiles want; the sums are the same sums)."""
    def one_round(mat, _):
        mat = mat / (jnp.sum(mat, axis=0, keepdims=True) + eps)     # columns
        return mat / (jnp.sum(mat, axis=1, keepdims=True) + eps), None   # rows

    mat = jnp.moveaxis(jnp.exp(logits), (-2, -1), (0, 1))
    mat, _ = lax.scan(one_round, mat, None, length=iters)
    return jnp.moveaxis(mat, (0, 1), (-2, -1))


def hyper_maps(cfg, p, node, x):
    """x: (B, T, n, d) -> H_pre (B, T, n), H_post (B, T, n), H_res (B, T, n, n)."""
    n = cfg["hc_mult"]
    flat = rms_norm(x.reshape(x.shape[:-2] + (-1,)), None, cfg["rms_norm_eps"])
    a = p[f"{node}/hc_a"]
    dot = lambda phi: jnp.matmul(flat, phi, precision=HI)
    h_pre = jax.nn.sigmoid(a[0] * dot(p[f"{node}/hc_phi_pre"]) + p[f"{node}/hc_b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * dot(p[f"{node}/hc_phi_post"])
                                  + p[f"{node}/hc_b_post"])
    res = a[2] * dot(p[f"{node}/hc_phi_res"]).reshape(x.shape[:-2] + (n, n)) \
        + p[f"{node}/hc_b_res"]
    res = jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(res, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])


def hyper_connection(cfg, p, node, x, sublayer):
    """X_next = H_res X + H_post^T F(RMSNorm(H_pre X)), token by token."""
    h_pre, h_post, h_res = hyper_maps(cfg, p, node, x)
    u = jnp.einsum("btn,btnd->btd", h_pre, x, precision=HI)
    y = sublayer(rms_norm(u, p[f"{node}/norm_g"], cfg["rms_norm_eps"]))
    return jnp.einsum("btij,btjd->btid", h_res, x, precision=HI) \
        + h_post[..., None] * y[..., None, :]


def _block(cfg, q, p, x, attn, mlp, kind):
    x = jax.checkpoint(lambda p_, x_: hyper_connection(
        cfg, p_, attn, x_, lambda u: latent_attention(cfg, p_, attn, u, q)))(p, x)
    if kind == "dense":
        f = lambda p_, u: gated_mlp(u, p_[f"{mlp}/w_g"], p_[f"{mlp}/w_u"],
                                    p_[f"{mlp}/w_d"], q)
    else:
        f = lambda p_, u: routed_experts(cfg, p_, mlp, u, q)
    return jax.checkpoint(lambda p_, x_: hyper_connection(
        cfg, p_, mlp, x_, lambda u: f(p_, u)))(p, x)


def _streams(cfg, h):
    return jnp.broadcast_to(h[..., None, :], h.shape[:-1] + (cfg["hc_mult"], h.shape[-1]))


@jax.checkpoint
def _xent(h, w, labels):
    """Mean of -log softmax(h W)[label]: float32 in both modes."""
    logits = jnp.matmul(h, w, precision=HI)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def losses(cfg, mode, params, ids, labels):
    """(main loss, MTP loss) of ids (B, T) -> labels (B, T), both integer."""
    q = refmath.QUANT[mode]
    eps = cfg["rms_norm_eps"]
    blocks, mtp = block_names(cfg)
    x = _streams(cfg, params["embed/W"][ids])
    for names in blocks:
        x = _block(cfg, q, params, x, *names)
    h = jnp.sum(x, axis=-2)
    main = _xent(rms_norm(h, params["final_norm/g"], eps), params["lm_head/W"],
                 labels)
    if not mtp:
        return main, jnp.float32(0.0)
    both = jnp.concatenate(
        [rms_norm(params["embed/W"][labels], params["mtp_in/norm_e_g"], eps),
         rms_norm(h, params["mtp_in/norm_h_g"], eps)], axis=-1)
    x = _streams(cfg, mm(both, params["mtp_in/W"], q))
    x = _block(cfg, q, params, x, *mtp[0])
    h2 = rms_norm(jnp.sum(x, axis=-2), params["mtp_norm/g"], eps)
    # position i read t_{i+1} and predicts t_{i+2}; the last has no target
    return main, _xent(h2[:, :-1], params["lm_head/W"], labels[:, 1:])


def data_loss(cfg, mode, params, ids, labels):
    main, second = losses(cfg, mode, params, ids, labels)
    return main + cfg["mtp_loss_weight"] * second


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
            "program", "name", "input", "sample_unit")
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
def _attention_macs_per_token(cfg):
    m = dims(cfg)
    proj = sum(s[0] * s[1] for s in _attn_shapes("x", m).values() if len(s) == 2)
    # causal scores and values: each position sees half the sequence on average
    seq = cfg["sequence_length"]
    return proj + m["heads"] * (m["nope"] + m["rope"] + m["v"]) * seq / 2.0


def _hc_macs_per_token(cfg):
    m = dims(cfg)
    n, d = m["n"], m["d"]
    return n * d * (n * n + 2 * n) + n * d + n * n * d + n * d


def routed_assignments_per_token(cfg) -> float:
    """Assignments that fall to a held expert, a token: the router's top-k
    over the published experts, taken as even."""
    m = dims(cfg)
    return m["top_k"] * m["experts"] / m["router"]


def _mlp_macs_per_token(cfg, kind):
    m = dims(cfg)
    if kind == "dense":
        return 3.0 * m["d"] * m["dense"]
    one = 3.0 * m["d"] * m["expert"]
    return m["d"] * m["router"] + cfg["n_shared_experts"] * one \
        + routed_assignments_per_token(cfg) * one


def train_macs_per_token(cfg) -> dict:
    """Forward multiply-adds a token, by part."""
    m = dims(cfg)
    blocks, mtp = block_names(cfg)
    out = {"head": float(m["d"] * m["vocab"]) * (2 if mtp else 1),
           "attention": 0.0, "hyper_connection": 0.0, "dense_mlp": 0.0,
           "experts": 0.0, "mtp_in": 2.0 * m["d"] * m["d"] if mtp else 0.0}
    for _, _, kind in blocks + mtp:
        out["attention"] += _attention_macs_per_token(cfg)
        out["hyper_connection"] += 2 * _hc_macs_per_token(cfg)
        out["dense_mlp" if kind == "dense" else "experts"] += \
            _mlp_macs_per_token(cfg, kind)
    return out


def train_flops_per_sample(cfg) -> float:
    """Forward plus backward (3x the forward's multiply-adds x2) of one
    sequence; nothing recomputed counted."""
    return 6.0 * sum(train_macs_per_token(cfg).values()) * cfg["sequence_length"]


def routed_products_flops_per_sample(cfg) -> float:
    """What the held experts' three products must compute for one sequence,
    forward and backward (towards the tokens and towards the weights)."""
    m = dims(cfg)
    blocks, mtp = block_names(cfg)
    layers = sum(1 for _, _, kind in blocks + mtp if kind == "experts")
    return 6.0 * 3.0 * m["d"] * m["expert"] * routed_assignments_per_token(cfg) \
        * cfg["sequence_length"] * layers


def routed_products_bytes_per_sample(cfg, itemsize: int) -> float:
    """The least the three products must move through HBM for one sequence at
    the compute type's width: forward reads the held experts' weights once
    and each routed row (d), writes and reads the two hidden rows (f) and
    writes the output row (d); backward reads all of that again with the
    incoming gradient and writes the gradients of rows and weights."""
    m = dims(cfg)
    blocks, mtp = block_names(cfg)
    layers = sum(1 for _, _, kind in blocks + mtp if kind == "experts")
    weights = 3.0 * m["experts"] * m["d"] * m["expert"]
    rows = routed_assignments_per_token(cfg) * cfg["sequence_length"]
    per_row = 2 * m["d"] + 4 * m["expert"]
    return float(itemsize * layers * 3 * (weights + rows * per_row))
