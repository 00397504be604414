"""Plain reference for the DL4J zoo TextGenerationLSTM (training step).

Written from deeplearning4j-zoo 0.9.1 `TextGenerationLSTM.java:75-87` and
`GravesLSTM` (Graves 2013, peephole connections): two GravesLSTM layers of
256 units, tanh cell and sigmoid gates, a per-timestep dense softmax over the
characters, multi-class cross-entropy averaged over rows and timesteps, l2
1e-3 on the weight matrices, Xavier initialisation, forget-gate bias 1,
RmsProp(0.01). Float32 `jax.numpy`, a `lax.scan` over time, no kernel.
Imports nothing of `deeplearning4j_tpu`.

`mode` other than "f32" is the control: every operand of a matrix product
and every layer's state and output held in the lower type (int8,
`harness/refmath.py`), forward and backward, the way the configuration holds
them in bfloat16; the head and the loss stay float32.

Gate order in the 4H axis: input, forget, output, cell candidate. The input
and forget gates see the previous cell through their peepholes, the output
gate the new one.

The rows of a batch do not interact, so the gradient is taken in blocks of
rows (`cfg["reference_row_block"]`) and averaged: 8192 sequences in float32
do not fit in one piece.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from harness import refmath


def param_shapes(cfg):
    v, h = cfg["vocab"], cfg["hidden"]
    shapes, n_in = {}, v
    for i in range(cfg["layers"]):
        shapes[f"lstm{i}/W"] = (n_in, 4 * h)
        shapes[f"lstm{i}/RW"] = (h, 4 * h)
        shapes[f"lstm{i}/b"] = (4 * h,)
        for peep in ("pi", "pf", "po"):
            shapes[f"lstm{i}/{peep}"] = (h,)
        n_in = h
    shapes["output/W"] = (h, v)
    shapes["output/b"] = (v,)
    return shapes


def is_weight(leaf: str) -> bool:
    return leaf.endswith("/W") or leaf.endswith("/RW")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(shape_items, hidden, key):
    params = {}
    for i, (leaf, shape) in enumerate(shape_items):
        if is_weight(leaf):
            # Xavier: N(0, 2/(fan_in+fan_out)); an LSTM's fan_out is its width
            fan_in = shape[0]
            fan_out = hidden if leaf.startswith("lstm") else shape[1]
            std = (2.0 / (fan_in + fan_out)) ** 0.5
            params[leaf] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif leaf.startswith("lstm") and leaf.endswith("/b"):
            params[leaf] = jnp.zeros(shape, jnp.float32).at[
                hidden:2 * hidden].set(1.0)          # forget-gate bias 1
        else:
            params[leaf] = jnp.zeros(shape, jnp.float32)
    return params


def init_params(cfg, key):
    return _init(tuple(param_shapes(cfg).items()), cfg["hidden"], key)


def init_state(cfg):
    return {}


def _lstm(p, name, x_tbf, q):
    """x: (time, batch, features) -> (time, batch, hidden)."""
    n = p[name + "/RW"].shape[0]
    rw = q(p[name + "/RW"])
    xw = q(jnp.dot(q(x_tbf), q(p[name + "/W"]),
                   precision=lax.Precision.HIGHEST) + p[name + "/b"])
    pi, pf, po = p[name + "/pi"], p[name + "/pf"], p[name + "/po"]

    @jax.checkpoint
    def step(carry, xw_t):
        h, c = carry
        z = q(xw_t + jnp.dot(q(h), rw, precision=lax.Precision.HIGHEST))
        i = jax.nn.sigmoid(z[:, :n] + c * pi)
        f = jax.nn.sigmoid(z[:, n:2 * n] + c * pf)
        g = jnp.tanh(z[:, 3 * n:])
        c_new = q(f * c + i * g)
        o = jax.nn.sigmoid(z[:, 2 * n:3 * n] + c_new * po)
        h_new = q(o * jnp.tanh(c_new))
        return (h_new, c_new), h_new

    zeros = jnp.zeros((x_tbf.shape[1], n), jnp.float32)
    _, hs = lax.scan(step, (zeros, zeros), xw)
    return hs


def data_loss(cfg, mode, params, x, y):
    """x, y: (batch, vocab, time), DL4J's recurrent layout."""
    q = refmath.QUANT[mode]
    h = jnp.transpose(x, (2, 0, 1))                      # (time, batch, vocab)
    for i in range(cfg["layers"]):
        h = _lstm(params, f"lstm{i}", h, q)
    # the head and the loss stay float32, as the configuration states them
    logits = jnp.dot(h, params["output/W"],
                     precision=lax.Precision.HIGHEST) + params["output/b"]
    return refmath.softmax_xent(logits.reshape(-1, cfg["vocab"]),
                                jnp.transpose(y, (2, 0, 1)).reshape(
                                    -1, cfg["vocab"]))


def _loss_and_grads(cfg, mode, params, x, y):
    block = min(cfg["reference_row_block"], x.shape[0])
    n_blocks = x.shape[0] // block
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.value_and_grad(functools.partial(data_loss, cfg, mode))

        def one(acc, xy):
            loss, grads = grad_fn(params, *xy)
            return jax.tree_util.tree_map(jnp.add, acc, (loss, grads)), None

        zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
        blocks = tuple(a[:n_blocks * block].reshape((n_blocks, block) + a.shape[1:])
                       for a in (x, y))
        (loss, grads), _ = lax.scan(one, zero, blocks)
        loss, grads = jax.tree_util.tree_map(lambda a: a / n_blocks,
                                             (loss, grads))
        weights = [v for k, v in params.items() if is_weight(k)]
        reg_grads = jax.grad(lambda w: refmath.l1_l2(w, cfg["l1"], cfg["l2"]))(
            weights)
        loss = loss + refmath.l1_l2(weights, cfg["l1"], cfg["l2"])
        names = [k for k in params if is_weight(k)]
        grads = dict(grads, **{k: grads[k] + g for k, g in zip(names, reg_grads)})
    return loss, grads


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _step(cfg_items, mode, hyper, params, g2, x, y):
    lr, decay, eps = hyper
    loss, grads = _loss_and_grads(dict(cfg_items), mode, params, x, y)
    params, g2 = refmath.rmsprop(params, g2, grads, lr, decay, eps)
    return params, g2, loss


def _items(cfg):
    return tuple((k, cfg[k]) for k in ("vocab", "hidden", "layers", "l1", "l2",
                                       "reference_row_block"))


def train_step(cfg, mode, params, opt, state, x, y):
    """One training step of the reference: (parameters, updater's state,
    state, loss)."""
    params, opt, loss = _step(_items(cfg), mode, _hyper(cfg), params, opt, x, y)
    return params, opt, state, loss


def _hyper(cfg):
    u = cfg["updater"]
    return u["learning_rate"], u["rms_decay"], u["epsilon"]


def init_opt(cfg, params):
    """The updater's state before the first step: RmsProp's cache, zero."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def apply_updater(cfg, opt, grads):
    """(new state of the updater, the update that is subtracted from the
    parameters), for the data-parallel reference."""
    return refmath.rmsprop_update(opt, grads, *_hyper(cfg))


def first_gradient_sq(cfg, opt1):
    """g^2, element by element, of the first gradient as the updater got it,
    from its state after one step."""
    return refmath.rmsprop_first_gradient_sq(opt1, cfg["updater"]["rms_decay"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _grads(cfg_items, mode, params, x, y):
    return _loss_and_grads(dict(cfg_items), mode, params, x, y)


def loss_and_grads(cfg, mode, params, state, x, y):
    """(loss, gradients, state) of one replica's rows, for the data-parallel
    reference, which applies the updater itself."""
    loss, grads = _grads(_items(cfg), mode, params, x, y)
    return loss, grads, state


# --------------------------------------------------------------- operations
def _layer_dims(cfg):
    n_in = cfg["vocab"]
    for _ in range(cfg["layers"]):
        yield n_in, cfg["hidden"]
        n_in = cfg["hidden"]


def train_flops_per_token(cfg) -> float:
    """Forward plus backward (3x the forward's multiply-adds x2): the input
    and recurrent products of each layer and the softmax head."""
    h, v = cfg["hidden"], cfg["vocab"]
    fwd = sum(2.0 * 4 * h * (n_in + n) for n_in, n in _layer_dims(cfg))
    return 3.0 * (fwd + 2.0 * h * v)


def train_flops_per_sample(cfg) -> float:
    return train_flops_per_token(cfg) * cfg["sequence_length"]


def scan_kernel_flops_per_sample(cfg) -> float:
    """What the fused scan kernels (forward and backward) must compute for one
    sequence: the recurrent product h.RW of every layer and timestep, once
    forward and twice backward (towards h and towards RW). The input
    projection and the head are XLA's and are not counted here."""
    h = cfg["hidden"]
    return 3.0 * 2.0 * 4 * h * h * cfg["layers"] * cfg["sequence_length"]


def scan_kernel_bytes_per_sample(cfg, itemsize: int) -> float:
    """The least the scan kernels must move through HBM for one sequence, at
    the compute type's width: forward reads the projected inputs (4H) and
    writes h and c (2H) per step; backward reads them again with the incoming
    gradient (H) and writes the gradient of the projected inputs (4H)."""
    h = cfg["hidden"]
    per_step = (4 * h + 2 * h) + (4 * h + 2 * h + h + 4 * h)
    return float(per_step * itemsize * cfg["layers"] * cfg["sequence_length"])
