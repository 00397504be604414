"""Arithmetic the plain references share. Imports nothing of the program.

Everything here is float32 `jax.numpy`; callers wrap the whole computation in
`jax.default_matmul_precision("highest")`, because on a TPU a float32 matrix
product otherwise runs as one bfloat16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def identity(x):
    return x


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def fake_int8(x):
    """The control's precision: symmetric int8 with one scale per tensor, the
    step below bfloat16, on every operand of a matrix product or convolution,
    forward and backward: the value is rounded to 255 levels on the way in,
    and so is the gradient that comes back through it. The arithmetic between
    stays float32."""
    return _round_int8(x)


fake_int8.defvjp(lambda x: (_round_int8(x), None),
                 lambda _, g: (_round_int8(g),))


QUANT = {"f32": identity, "int8": fake_int8}


@jax.jit
def rmsprop_update(cache, grads, lr, decay, eps):
    """RmsProp as the program's updater has it: cache = d*cache + (1-d)*g^2;
    update = lr*g/sqrt(cache + eps), the epsilon under the root (nd4j 0.9.1
    adds it outside the root; see the configurations' `assumed`)."""
    cache = jax.tree_util.tree_map(
        lambda s, g: decay * s + (1.0 - decay) * g * g, cache, grads)
    return cache, jax.tree_util.tree_map(
        lambda s, g: lr * g / jnp.sqrt(s + eps), cache, grads)


def rmsprop(params, cache, grads, lr, decay, eps):
    """One RmsProp step: (new parameters, new cache)."""
    cache, update = rmsprop_update(cache, grads, lr, decay, eps)
    return jax.tree_util.tree_map(jnp.subtract, params, update), cache


@jax.jit
def rmsprop_first_gradient_sq(cache1, decay):
    """g^2, element by element, of the first gradient as RmsProp got it, from
    its cache after one step from zero: cache = (1-d) g^2."""
    return jax.tree_util.tree_map(lambda s: s / (1.0 - decay), cache1)


def l1_l2(weights, l1, l2):
    """DL4J's regularisation score: l1*sum|w| + 0.5*l2*sum w^2 over weights."""
    s = jnp.float32(0.0)
    for w in weights:
        if l1:
            s = s + l1 * jnp.sum(jnp.abs(w))
        if l2:
            s = s + 0.5 * l2 * jnp.sum(jnp.square(w))
    return s


def softmax_xent(logits, onehot):
    """Mean over rows of -sum(y * log_softmax(z))."""
    return jnp.mean(-jnp.sum(onehot * jax.nn.log_softmax(logits, axis=-1),
                             axis=-1))


@jax.jit
def diff_norms(a, b):
    """{leaf: ||a - b||_2} over the leaves of `a`."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a}


@jax.jit
def leaf_sums(tree):
    return {k: jnp.sum(v.astype(jnp.float32)) for k, v in tree.items()}
