"""The one general generator of training traffic.

A traffic mix is a data file `traffic/<name>.json`: the driver it is for, the
batch, the steps of one call. The kind of input is the configuration's
(`input.kind`). Every batch is drawn from `--seed` on the device in one jitted
call; the same seed gives the same batch, and all rows of a batch differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int):
    """A JAX key from any whole number the driver may give (its seeds pass
    32 signed bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _images(batch, shape, classes, key):
    kx, ky = jax.random.split(key)
    x = jax.random.uniform(kx, (batch,) + shape, jnp.float32)
    y = jax.nn.one_hot(jax.random.randint(ky, (batch,), 0, classes), classes,
                       dtype=jnp.float32)
    return x, y


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _char_sequences(batch, vocab, length, key):
    """One-hot characters in DL4J's recurrent layout (batch, vocab, time), the
    label of each position the next character."""
    idx = jax.random.randint(key, (batch, length + 1), 0, vocab)
    hot = jax.nn.one_hot(idx, vocab, dtype=jnp.float32)      # (b, t+1, v)
    hot = jnp.transpose(hot, (0, 2, 1))
    return hot[:, :, :-1], hot[:, :, 1:]


def make_batch(cfg: dict, traffic: dict, key):
    """(features, labels) for one step of `traffic["batch"]` samples."""
    kind = cfg["input"]["kind"]
    batch = int(traffic["batch"])
    if kind == "images":
        return _images(batch, tuple(cfg["input_shape"]), cfg["num_labels"], key)
    if kind == "char_sequences":
        return _char_sequences(batch, cfg["vocab"], cfg["sequence_length"], key)
    raise ValueError(f"unknown input kind {kind!r}")
