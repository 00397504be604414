"""Mixed-precision helpers.

TPU-first design: params and updater state live in the storage dtype (fp32 by
default); layer compute can run in a lower `compute_dtype` (bfloat16 on TPU hits the
MXU at 2x fp32 throughput with the same exponent range, so no loss scaling is
needed). The output-layer score and regularization always run in the storage dtype.
The reference is fp32-only (nd4j DataBuffer.Type.FLOAT); this is a capability the
TPU build adds on top.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# A parameter leaf of at least this many elements has its gradient held apart
# from its update (`cast_params`). Left alone, XLA fuses the gradient's upcast
# and the updater's float32 streams into the weight-gradient product's output
# fusion. On a v5e such a fusion of Olmo-Hybrid's 3840 x 11008 MLP matrices
# took 7.8-11.2 ms where the plain product takes 3.7-4.7 and the update apart
# 1.6. Smaller leaves split no such way: Olmo-Hybrid's projections (7.4-33.2 M
# elements) broke even, Qwen3-Next's (8.4-25.2 M) gained 1.2% of the step and
# Xing4's dense and MTP matrices (25.7 and 33.0 M) lost 0.9%, their fused
# updates being cheaper than apart (PERF.md, PR 36). No count of elements
# parts those, and 2**25 holds apart the MLP matrices and none of them: the
# other decoders' programs compile as before. The zoo's ResNet50 and LSTM
# leaves (2,359,296 at the most) are far under it.
GRAD_HELD_APART_MIN = 2 ** 25


def cast_floats(tree, dtype):
    """Cast every floating-point leaf of a pytree to `dtype`; leave ints/bools."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _held_apart(a, storage, compute):
    return a.astype(compute)


def _held_apart_fwd(a, storage, compute):
    return a.astype(compute), None


def _held_apart_bwd(storage, compute, _, ct):
    # the product writes its gradient in the compute type; the barrier keeps
    # the upcast, and with it the update, out of the product's fusion
    from deeplearning4j_tpu import telemetry
    telemetry.registry().counter(
        "train.grad_held_apart",
        "parameter leaves whose gradient is held apart from its update "
        "(counted at trace time)").inc()
    return (jax.lax.optimization_barrier(ct).astype(storage),)


_held_apart.defvjp(_held_apart_fwd, _held_apart_bwd)


def cast_params(tree, dtype):
    """`cast_floats` for a layer's parameters in a train step: the same
    values, and a leaf of at least `GRAD_HELD_APART_MIN` elements hands its
    gradient back through an optimization barrier, so that the updater's
    fusion starts from the product's output instead of joining it."""
    def cast(a):
        src = jnp.asarray(a).dtype
        if not jnp.issubdtype(src, jnp.floating):
            return a
        if a.size >= GRAD_HELD_APART_MIN and src != jnp.dtype(dtype):
            return _held_apart(a, src, jnp.dtype(dtype))
        return a.astype(dtype)
    return jax.tree_util.tree_map(cast, tree)
