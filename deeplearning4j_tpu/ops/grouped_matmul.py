"""Grouped matrix product: rows sorted by group, one weight matrix a group.

`grouped_matmul(x, w, group_sizes)` with x (M, K) whose first
`sum(group_sizes)` rows are sorted by group, w (G, K, N) and group_sizes (G,)
gives (M, N): row r of group g is `x[r] @ w[g]`; rows past the groups' sum
are zero. It is what an expert layer needs for the experts it holds: the
work follows the rows that were routed, not a capacity. M is the caller's:
`RoutedExperts` hands over one block of `row_bound` rows of its sorted
assignments at a time (4096 of 16384 at the decoder cell's shapes) with the
groups' sizes inside that block, so the masks below, which run over all M
rows whatever the groups' sum, cost a block and not every assignment.

Behind the helper seam: the grouped Mosaic kernels that JAX ships
(`jax.experimental.pallas.ops.tpu.megablox`: `gmm` for the product and for the
gradient towards the rows, `tgmm` for the gradient towards the weights), walked
in tiles of 128 rows, each visit of one group, the tiles past the groups' sum
never visited, so the work follows the rows in steps of 128. At the decoder
cell's shapes and M = 16384 a product with both gradients takes 1.46-1.59 ms
against 1.99-2.15 for `jax.lax.ragged_dot` (tiles of 512 rows), whose kernels
also reach the profile without the layer's scope (PERF.md, PR 27). The fallback is
one masked dense product a group, which every backend lowers and which costs
G times the work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.helpers import helper_for, register_helper

# Rows a visit: one tile of the matrix unit. The visits a product makes are
# ceil(rows held / 128) + a visit more a group boundary inside a tile.
TILE_ROWS = 128
# The widest block of a weight matrix a visit holds in VMEM (both ways): with
# the whole contraction in one block a group's weights are read once, not once
# a visit.
_MAX_CONTRACTION = 4096
_MAX_COLUMNS = 512


def _call(kernel, *args, **kwargs):
    if not helpers.interpret_mode():
        return kernel(*args, **kwargs)
    # off the chip the kernels are interpreted; the library's interpreter
    # subtracts int32 grid indices from what x64 (the tests' setting) makes
    # int64, so the call is traced with x64 off
    with jax.enable_x64(False):
        return kernel(*args, interpret=True, **kwargs)


def _row_groups(m: int, group_sizes):
    """(M,) the group of each row; G for the rows past the groups' sum."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def grouped_matmul_xla(x, w, group_sizes):
    """The fallback: every row through every group's matrix, masked."""
    groups = _row_groups(x.shape[0], group_sizes)
    out = jnp.zeros((x.shape[0], w.shape[-1]), x.dtype)
    for g in range(w.shape[0]):
        rows = (groups == g)[:, None]
        out = out + jnp.where(rows, jnp.dot(jnp.where(rows, x, 0), w[g]), 0)
    return out


def _block(size: int, most: int) -> int:
    return size if size <= most else most


def _product_tiles(k: int, n: int):
    return TILE_ROWS, _block(k, _MAX_CONTRACTION), _block(n, _MAX_COLUMNS)


@jax.custom_vjp
def _grouped(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return _call(gmm, x, w, sizes, x.dtype, _product_tiles(*w.shape[1:]))


def _grouped_fwd(x, w, sizes):
    return _grouped(x, w, sizes), (x, w, sizes)


def _grouped_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    x, w, sizes = res
    k, n = w.shape[1:]
    dx = _call(gmm, g, w, sizes, x.dtype, _product_tiles(n, k),
               transpose_rhs=True)
    dw = _call(tgmm, x.swapaxes(0, 1), g, sizes, w.dtype,
               (TILE_ROWS, _block(k, _MAX_COLUMNS), _block(n, _MAX_COLUMNS)),
               num_actual_groups=w.shape[0])
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@register_helper("grouped_matmul")
def grouped_matmul_kernel(x, w, group_sizes):
    m = x.shape[0]
    pad = -m % TILE_ROWS
    sizes = group_sizes.astype(jnp.int32)
    # the rows past the groups' sum belong to no group: the kernels never
    # visit their tiles and leave what was there, in the product and in the
    # gradient towards those rows: both are masked, the second by masking the
    # rows on the way in
    live = (jnp.arange(m + pad) < jnp.sum(sizes))[:, None]
    rows = jnp.where(live, jnp.pad(x, ((0, pad), (0, 0))), 0)
    out = jnp.where(live, _grouped(rows, w.astype(x.dtype), sizes), 0)
    return out[:m]


def grouped_matmul(x, w, group_sizes):
    return helper_for("grouped_matmul", grouped_matmul_xla)(x, w, group_sizes)
