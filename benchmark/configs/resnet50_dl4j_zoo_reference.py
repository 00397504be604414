"""Plain reference for the DL4J zoo ResNet50 (training step), float32 jax.numpy.

Written from deeplearning4j-zoo 0.9.1 `ResNet50.java` (graphBuilder :174-218,
identityBlock :90-125, convBlock :127-172): zero-pad 3, conv 7x7/2, batch
norm, ReLU, max-pool 3x3/2; stages 2-5 of bottleneck blocks (every stage's
first block strides by 2, also stage 2: the zoo's variant), shortcut by
addition; max-pool 3x3/2 head, dense softmax, negative log-likelihood;
Truncate convolution mode (3x3 convolutions `Same`); l1 1e-7, l2 5e-5 on
weights; RmsProp. Imports nothing of `deeplearning4j_tpu` and takes nothing
the program made. Sizes come from the configuration's JSON (`cfg`).

`mode` other than "f32" is the control: the same network with every operand
of a convolution and every layer's output held in the lower type (int8,
`harness/refmath.py`), forward and backward, the way the configuration
holds them in bfloat16; the dense head and the loss stay float32 as the
configuration states.

Departures, each also under `assumed` in the JSON: batch norm's scale counts
as a weight for l1/l2 (as in the program), the running variance is the
biased batch variance blended with decay 0.9, and RmsProp's epsilon (0.001,
`ResNet50.java`'s third argument) stands under the root as in the program's
updater.

Memory: each bottleneck block is rematerialised (`jax.checkpoint`), so the
float32 backward pass of 512 images fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from harness import refmath

STAGES = (("2", (64, 64, 256), "abc"), ("3", (128, 128, 512), "abcd"),
          ("4", (256, 256, 1024), "abcdef"), ("5", (512, 512, 2048), "abc"))
BN_DECAY, BN_EPS = 0.9, 1e-5
_DN = ("NCHW", "OIHW", "NCHW")


def _blocks(cfg):
    """[(block, [(conv, bn, cin, cout, k)...], shortcut-or-None)] in graph order."""
    cin, out = 64, []
    for stage, f, names in STAGES:
        for blk in names:
            c, b = f"res{stage}{blk}_branch", f"bn{stage}{blk}_branch"
            main = [(c + "2a", b + "2a", cin, f[0], 1),
                    (c + "2b", b + "2b", f[0], f[1], 3),
                    (c + "2c", b + "2c", f[1], f[2], 1)]
            short = (c + "1", b + "1", cin, f[2], 1) if blk == "a" else None
            out.append((stage + blk, main, short))
            cin = f[2]
    return out


def param_shapes(cfg):
    """{"layer/param": shape}, DL4J's parameter names (W, b, gamma, beta)."""
    shapes = {}

    def conv_bn(conv, bn, cin, cout, k):
        shapes[conv + "/W"] = (cout, cin, k, k)
        shapes[conv + "/b"] = (cout,)
        shapes[bn + "/gamma"] = (cout,)
        shapes[bn + "/beta"] = (cout,)

    conv_bn("stem-cnn1", "stem-batch1", cfg["input_shape"][0], 64, 7)
    for _, main, short in _blocks(cfg):
        for spec in main + ([short] if short else []):
            conv_bn(*spec)
    shapes["output/W"] = (2048, cfg["num_labels"])
    shapes["output/b"] = (cfg["num_labels"],)
    return shapes


def is_weight(leaf: str) -> bool:
    return leaf.endswith("/W") or leaf.endswith("/gamma")


@functools.partial(jax.jit, static_argnums=0)
def _init(shape_items, key):
    params = {}
    for i, (leaf, shape) in enumerate(shape_items):
        if leaf.endswith("/W"):  # ResNet50.java: WeightInit.DISTRIBUTION N(0, 0.5)
            params[leaf] = 0.5 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif leaf.endswith("/gamma"):
            params[leaf] = jnp.ones(shape, jnp.float32)
        else:
            params[leaf] = jnp.zeros(shape, jnp.float32)
    return params


def init_params(cfg, key):
    """The weights as the zoo draws them, from the benchmark's seed, on the
    device in one jitted call."""
    return _init(tuple(param_shapes(cfg).items()), key)


def init_state(cfg):
    return {leaf[:-len("gamma")] + stat: jnp.full(shape, fill, jnp.float32)
            for leaf, shape in param_shapes(cfg).items()
            if leaf.endswith("/gamma")
            for stat, fill in (("mean", 0.0), ("var", 1.0))}


def _conv(x, p, name, stride, pad, q):
    z = lax.conv_general_dilated(
        q(x), q(p[name + "/W"]), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=_DN, precision=lax.Precision.HIGHEST)
    return q(z + p[name + "/b"][None, :, None, None])


def _bn(x, p, st, name, relu, q):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.var(x, axis=(0, 2, 3))
    xhat = (x - mean[None, :, None, None]) / jnp.sqrt(
        var[None, :, None, None] + BN_EPS)
    y = p[name + "/gamma"][None, :, None, None] * xhat \
        + p[name + "/beta"][None, :, None, None]
    new = {name + "/mean": BN_DECAY * st[name + "/mean"] + (1 - BN_DECAY) * mean,
           name + "/var": BN_DECAY * st[name + "/var"] + (1 - BN_DECAY) * var}
    return q(jnp.maximum(y, 0.0) if relu else y), new


def _max_pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             "VALID")


def _block(main, short, q, p, st, x):
    new, h = {}, x
    for i, (conv, bn, _, _, k) in enumerate(main):
        # the block's first 1x1 convolution carries the stride (convBlock)
        stride = 2 if (i == 0 and short) else 1
        h = _conv(h, p, conv, stride, 1 if k == 3 else 0, q)
        h, s = _bn(h, p, st, bn, relu=i < 2, q=q)
        new.update(s)
    if short:
        conv, bn = short[0], short[1]
        x, s = _bn(_conv(x, p, conv, 2, 0, q), p, st, bn, relu=False, q=q)
        new.update(s)
    return q(jnp.maximum(h + x, 0.0)), new


def loss_fn(cfg, mode, params, state, x, y):
    q = refmath.QUANT[mode]
    new_state = {}
    h = jnp.pad(x, ((0, 0), (0, 0), (3, 3), (3, 3)))
    h, s = _bn(_conv(h, params, "stem-cnn1", 2, 0, q), params, state,
               "stem-batch1", relu=True, q=q)
    new_state.update(s)
    h = _max_pool(h)
    for _, main, short in _blocks(cfg):
        block = jax.checkpoint(functools.partial(_block, main, short, q))
        h, s = block(params, state, h)
        new_state.update(s)
    feats = _max_pool(h).reshape(h.shape[0], -1)
    # the head and the loss stay float32, as the configuration states them
    logits = jnp.dot(feats, params["output/W"],
                     precision=lax.Precision.HIGHEST) + params["output/b"]
    loss = refmath.softmax_xent(logits, y) + refmath.l1_l2(
        [v for k, v in params.items() if is_weight(k)], cfg["l1"], cfg["l2"])
    return loss, new_state


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4, 5))
def _step(cfg_items, mode, hyper, params, g2, state, x, y):
    cfg = dict(cfg_items)
    lr, decay, eps = hyper
    with jax.default_matmul_precision("highest"):
        (loss, new_state), grads = jax.value_and_grad(
            functools.partial(loss_fn, cfg, mode), has_aux=True)(
                params, state, x, y)
        params, g2 = refmath.rmsprop(params, g2, grads, lr, decay, eps)
    return params, g2, new_state, loss


def _items(cfg):
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in cfg.items() if k in ("input_shape", "num_labels",
                                                  "l1", "l2"))


def train_step(cfg, mode, params, opt, state, x, y):
    """One training step of the reference: (parameters, updater's state,
    running statistics, loss)."""
    return _step(_items(cfg), mode, _hyper(cfg), params, opt, state, x, y)


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
def _grads(cfg_items, mode, params, state, x, y):
    with jax.default_matmul_precision("highest"):
        (loss, new_state), grads = jax.value_and_grad(
            functools.partial(loss_fn, dict(cfg_items), mode), has_aux=True)(
                params, state, x, y)
    return loss, grads, new_state


def loss_and_grads(cfg, mode, params, state, x, y):
    """(loss, gradients, running statistics) of one replica's rows, for the
    data-parallel reference, which applies the updater itself."""
    return _grads(_items(cfg), mode, params, state, x, y)


# --------------------------------------------------------------- operations
def _spatial(cfg):
    """Output side of every convolution, by name, from the input's side."""
    side = (cfg["input_shape"][1] + 6 - 7) // 2 + 1
    sides = {"stem-cnn1": side}
    side = (side - 3) // 2 + 1
    for _, main, short in _blocks(cfg):
        if short:
            side = (side - 1) // 2 + 1
            sides[short[0]] = side
        for conv, *_ in main:
            sides[conv] = side
    return sides, side


def _taps(n_in: int, n_out: int, k: int, stride: int, pad: int) -> int:
    """Along one side, the kernel taps that land on the input and not on
    padding, summed over the output positions."""
    return sum(1 for o in range(n_out) for t in range(k)
               if 0 <= o * stride + t - pad < n_in)


def conv_flops_per_sample(cfg) -> float:
    """Multiply-adds x2 of every convolution and of the dense head, forward
    plus backward (the backward pass costs twice the forward; the first
    convolution needs no gradient towards its input). Products with padding
    are not needed and not counted: in the 3x3 convolutions of stage 5, on a
    4x4 map, they would be three tenths of the taps."""
    sides, _ = _spatial(cfg)
    side_in = cfg["input_shape"][1]
    total = 0.0
    for leaf, (cout, cin, k, _) in ((n, s) for n, s in param_shapes(cfg).items()
                                    if n.endswith("/W") and len(s) == 4):
        conv = leaf[:-2]
        out = sides[conv]
        if conv == "stem-cnn1":
            taps = _taps(side_in, out, 7, 2, 3) ** 2
        elif k == 3:
            taps = _taps(out, out, 3, 1, 1) ** 2
        else:
            taps = out * out
        fwd = 2.0 * cout * cin * taps
        total += fwd * (2.0 if conv == "stem-cnn1" else 3.0)
    total += 3.0 * 2.0 * 2048 * cfg["num_labels"]
    return total


def train_flops_per_sample(cfg) -> float:
    """What forward and backward require for one image: the matrix work. The
    elementwise work (batch norm, ReLU, additions, pooling, the updater) is
    left out, as is usual for a model's utilisation, so `step_mfu` reads a
    little under what the chip really did."""
    return conv_flops_per_sample(cfg)
