"""The hyper-connection round a sublayer as four Pallas calls.

`HyperConnection` (nn/conf/layers/decoder.py) mixes `n` streams of a
`(B, n, T, d)` state with maps a token: 2n + n^2 numbers from a product of
the whole row with `phi`, a mean square, sigmoids, clip-exp and Sinkhorn's
rounds. It does almost no arithmetic and, written as plain XLA, reads and
writes the state four to five times more often than it has to (ISSUE 30:
6.4 GB a layer a step at the decoder cell's shape against 1.2). Here each
direction passes over the state once, in tiles of tokens with the whole
`(n, tile, d)` row of a tile in VMEM:

- `dl4j_hc_pre` (forward, and again where the layer is recomputed): the
  maps' product on the MXU with float32 accumulation, the mean square, the
  three maps, Sinkhorn's rounds with the tokens as the minor axis, the
  pre-mix and the RMS norm. Writes `u`, the maps a token (float32, the tokens
  leading: `(B, T, 32)`) and, for the backward, the normalised products with
  the tokens as the minor axis (`(B, 32, T)`).
- `dl4j_hc_post` (forward only; a call of its own so that XLA drops it from
  a recomputed forward, whose `out` nothing reads): all n output streams in
  one pass, `H_res X + H_post^T y`.
- `dl4j_hc_post_bwd`: `dy`, and a token `dH_res[i, j] = <dout_i, X_j>`,
  `dH_post[i] = <dout_i, y>`.
- `dl4j_hc_pre_bwd`: the norm's, the pre-mix's, Sinkhorn's (its rounds run
  again and kept in VMEM) and the maps' backward, then `dX` written once, and
  `dphi`, the maps' scales and biases and `dnorm_g` summed in float32 across
  the grid.

The mathematics is the layer's own (maps, Sinkhorn, mixing and norm in
float32, the maps' product on operands of the state's type with float32
accumulation, clip before exp, column before row, `hc_eps` in both
denominators, every round): the layer's plain body is the fallback and the
tests' reference.

The sublayer runs between `pre` and `post`, so there are two `custom_vjp`s,
and they share one private arrangement that keeps `dX` a single write: `pre`
hands `X` on to `post` as an output of its own, and `post`'s backward
returns, in the place of that argument's cotangent, `dout` as it came.
`pre`'s backward mixes it with `H_res` itself, in the pass that writes `dX`.
Neither function is of use alone; `hyper_connection` is the one entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.helpers import register_helper

_F32 = jnp.float32
# Rows of a token's block of maps: H_pre (n), H_post (n), H_res (n n, row i
# column j at n i + j), then the state's and the pre-mix's 1 / rms. With the
# tokens as the minor axis the block's last sublane tile is kept for the one
# number a token that rides along: the state's 1 / rms into the backward, the
# mean square's factor on X out of the maps' backward.
MAP_ROWS = 32
_RIDER = MAP_ROWS - 8
# Rows an inner step works on: one packed bf16 register of 16 sublanes.
_GROUP = 16
# Tokens a grid step holds, largest first. What a call keeps in VMEM is
# reckoned by `_vmem_bytes` and asked for by name (`vmem_limit_bytes`):
# Mosaic's default scope of 16 MB holds no tile of the decoder cell's row.
_TILES = (256, 128)
# The chip has 128 MiB of VMEM; the reckoning leaves out the compiler's own
# scratch (the maps' block, spilled registers), which `_HEADROOM` covers.
_VMEM_MOST = 100 * 1024 * 1024
_HEADROOM = 12 * 1024 * 1024


def _vmem_bytes(n: int, tile: int, d: int, itemsize: int) -> int:
    """What the largest of the four calls, `pre`'s backward, holds: X, dout
    and dX blocks and the du block, each twice (the pipeline's two buffers);
    two float32 tiles of scratch; phi's block twice, dphi's block twice and
    its float32 accumulator."""
    state = n * tile * d * itemsize
    return (3 * 2 * state + 2 * tile * d * itemsize + 2 * tile * d * 4
            + MAP_ROWS * n * d * (2 * itemsize + 3 * 4))


def token_tile(n: int, t: int, d: int, itemsize: int):
    """The tokens a grid step takes for a `(B, n, t, d)` state, or None where
    the kernels refuse the shape: the maps have to fit their block, d has to
    be whole lanes, t whole tiles, the state no wider than the float32 the
    kernels reckon in, and a tile's row has to fit in VMEM."""
    if 2 * n + n * n > _RIDER or d % 128 or itemsize > 4:
        return None
    for tile in _TILES:
        if t % tile == 0 and \
                _vmem_bytes(n, tile, d, itemsize) + _HEADROOM <= _VMEM_MOST:
            return tile
    return None


def _lane_chunk(d: int) -> int:
    return next(c for c in (512, 256, 128) if d % c == 0)


def _fold_lanes(v):
    """(rows, c) -> (rows, 128): the sum of its 128-lane columns."""
    return sum(v[:, k:k + 128] for k in range(0, v.shape[1], 128))


def _groups(tile: int, body):
    """body(rows) for each group of `_GROUP` rows of a tile."""
    from jax.experimental import pallas as pl

    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP))
        return carry
    lax.fori_loop(0, tile // _GROUP, step, 0)


def _place(columns, width=MAP_ROWS):
    """[(k, (rows, 1))] -> (rows, width) with column k holding its value and
    zero elsewhere (selects on a lane index: no concatenation of
    one-lane pieces)."""
    rows = columns[0][1].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), _F32)
    for k, v in columns:
        out = jnp.where(lane == k, v, out)
    return out


# ---- the maps, tokens as the minor axis

def _sinkhorn_rounds(mats, iters: int, eps: float):
    """mats: n arrays (n, tile), row i of the matrices with its columns j on
    the sublanes. `iters` rounds of column then row normalisation; returns
    the rows after the last round and, for the backward, each round's input."""
    kept = []
    for _ in range(iters):
        kept.append(mats)
        col = 1.0 / (sum(mats) + eps)                            # (n, tile) by j
        mats = [m * col for m in mats]
        mats = [m * (1.0 / (jnp.sum(m, axis=0, keepdims=True) + eps))
                for m in mats]
    return mats, kept


def _sinkhorn_rounds_bwd(kept, d_mats, eps: float):
    """The cotangent of the rounds' first input from that of their output."""
    for mats in reversed(kept):
        col = 1.0 / (sum(mats) + eps)
        mid = [m * col for m in mats]                             # after columns
        new = []
        for a, db in zip(mid, d_mats):
            s = 1.0 / (jnp.sum(a, axis=0, keepdims=True) + eps)
            new.append(s * (db - jnp.sum(db * a * s, axis=0, keepdims=True)))
        inner = sum(da * a for da, a in zip(new, mid))            # (n, tile) by j
        d_mats = [col * (da - inner) for da in new]
    return d_mats


def _maps_fwd(cfg, z, scale, bias):
    """z (MAP_ROWS, tile) the normalised products -> H_pre (n, tile), H_post
    (n, tile), the rows of H_res, and what the backward reuses."""
    n, iters, hc_eps, lo, hi, _ = cfg
    p = z * scale + bias
    h_pre = jax.nn.sigmoid(p[:n])
    h_post = 2.0 * jax.nn.sigmoid(p[n:2 * n])
    res = p[2 * n:2 * n + n * n]
    first = [jnp.exp(jnp.clip(res[i * n:(i + 1) * n], lo, hi)) for i in range(n)]
    h_res, kept = _sinkhorn_rounds(first, iters, hc_eps)
    return h_pre, h_post, h_res, (res, first, kept)


def _pad_rows(pieces):
    """(k, tile) pieces one under the other, zero rows up to MAP_ROWS."""
    rows = sum(p.shape[0] for p in pieces)
    return jnp.concatenate(
        pieces + [jnp.zeros((MAP_ROWS - rows, pieces[0].shape[1]), _F32)], axis=0)


# ---- the four kernels

def _pre_kernel(cfg, x_ref, phit_ref, scale_ref, bias_ref, g_ref,
                u_ref, maps_ref, zt_ref, sq_scr, u0_scr):
    n, _, _, _, _, eps = cfg
    _, tile, d = x_ref.shape
    chunk = _lane_chunk(d)
    cols = range(0, d, chunk)

    def mean_square(rows):
        acc = jnp.zeros((_GROUP, 128), _F32)
        for c in cols:
            for j in range(n):
                v = x_ref[j, rows, c:c + chunk].astype(_F32)
                acc = acc + _fold_lanes(v * v)
        sq_scr[rows, :] = jnp.broadcast_to(
            jnp.sum(acc, axis=1, keepdims=True) * (1.0 / (n * d)), (_GROUP, 128))
    _groups(tile, mean_square)

    raw = sum(lax.dot_general(phit_ref[:, j * d:(j + 1) * d], x_ref[j],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=_F32) for j in range(n))
    r = lax.rsqrt(sq_scr[...].T[0:1] + eps)                       # (1, tile)
    z = raw * r
    h_pre, h_post, h_res, _ = _maps_fwd(cfg, z, scale_ref[...], bias_ref[...])
    zt_ref[...] = jnp.concatenate([z[:_RIDER],
                                   jnp.broadcast_to(r, (8, tile))], axis=0)
    maps_ref[...] = _pad_rows([h_pre, h_post] + h_res + [r]).T

    def pre_mix(rows):
        h = maps_ref[rows, :]
        acc = jnp.zeros((_GROUP, 128), _F32)
        for c in cols:
            u0 = sum(h[:, j:j + 1] * x_ref[j, rows, c:c + chunk].astype(_F32)
                     for j in range(n))
            u0_scr[:, c:c + chunk] = u0
            acc = acc + _fold_lanes(u0 * u0)
        ru = lax.rsqrt(jnp.sum(acc, axis=1, keepdims=True) * (1.0 / d) + eps)
        maps_ref[rows, 2 * n + n * n + 1:2 * n + n * n + 2] = ru
        for c in cols:
            u_ref[rows, c:c + chunk] = (
                u0_scr[:, c:c + chunk] * ru * g_ref[:, c:c + chunk]
            ).astype(u_ref.dtype)
    _groups(tile, pre_mix)


def _post_kernel(n, x_ref, y_ref, maps_ref, out_ref):
    _, tile, d = x_ref.shape
    chunk = _lane_chunk(d)

    def mix(rows):
        h = maps_ref[rows, :]
        for c in range(0, d, chunk):
            xs = [x_ref[j, rows, c:c + chunk].astype(_F32) for j in range(n)]
            y = y_ref[rows, c:c + chunk].astype(_F32)
            for i in range(n):
                res = 2 * n + n * i
                out = sum(h[:, res + j:res + j + 1] * xs[j] for j in range(n))
                out_ref[i, rows, c:c + chunk] = (
                    out + h[:, n + i:n + i + 1] * y).astype(out_ref.dtype)
    _groups(tile, mix)


def _post_bwd_kernel(n, dout_ref, x_ref, y_ref, maps_ref, dy_ref, dmaps_ref):
    _, tile, d = x_ref.shape
    chunk = _lane_chunk(d)

    def back(rows):
        h = maps_ref[rows, :]
        sums = [jnp.zeros((_GROUP, 128), _F32) for _ in range(n + n * n)]
        for c in range(0, d, chunk):
            xs = [x_ref[j, rows, c:c + chunk].astype(_F32) for j in range(n)]
            y = y_ref[rows, c:c + chunk].astype(_F32)
            dy = jnp.zeros((_GROUP, chunk), _F32)
            for i in range(n):
                dout = dout_ref[i, rows, c:c + chunk].astype(_F32)
                dy = dy + h[:, n + i:n + i + 1] * dout
                sums[i] = sums[i] + _fold_lanes(dout * y)
                for j in range(n):
                    k = n + n * i + j
                    sums[k] = sums[k] + _fold_lanes(dout * xs[j])
            dy_ref[rows, c:c + chunk] = dy.astype(dy_ref.dtype)
        dmaps_ref[rows, :] = _place(
            [(n + k, jnp.sum(s, axis=1, keepdims=True))
             for k, s in enumerate(sums)])
    _groups(tile, back)


def _pre_bwd_kernel(cfg, du_ref, x_ref, dout_ref, maps_ref, dmaps_ref, zt_ref,
                    phit_ref, scale_ref, bias_ref, g_ref,
                    dx_ref, dphit_ref, dscale_ref, dbias_ref, dg_ref,
                    du0_scr, thr_scr, mix_scr, dm_scr, dphit_scr, dg_scr):
    from jax.experimental import pallas as pl
    n, _, hc_eps, lo, hi, _ = cfg
    _, tile, d = x_ref.shape
    chunk = _lane_chunk(d)
    cols = range(0, d, chunk)
    ru_col = 2 * n + n * n + 1
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    last = (pl.program_id(0) == pl.num_programs(0) - 1) \
        & (pl.program_id(1) == pl.num_programs(1) - 1)

    @pl.when(first)
    def _():
        dphit_scr[...] = jnp.zeros_like(dphit_scr)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    # the norm's and the pre-mix's backward: du0 a tile, dH_pre a token
    def norm_back(rows):
        h = maps_ref[rows, :]
        ru = h[:, ru_col:ru_col + 1]
        acc = jnp.zeros((_GROUP, 128), _F32)
        for c in cols:
            xs = [x_ref[j, rows, c:c + chunk].astype(_F32) for j in range(n)]
            u0 = sum(h[:, j:j + 1] * xs[j] for j in range(n))
            du = du_ref[rows, c:c + chunk].astype(_F32)
            du0_scr[rows, c:c + chunk] = u0
            acc = acc + _fold_lanes(du * g_ref[:, c:c + chunk] * u0)
            folded = du * u0 * ru
            dg_scr[:, c:c + chunk] += folded[:8] + folded[8:]
        pull = jnp.sum(acc, axis=1, keepdims=True) * (ru * ru * ru * (1.0 / d))
        sums = [jnp.zeros((_GROUP, 128), _F32) for _ in range(n)]
        for c in cols:
            du = du_ref[rows, c:c + chunk].astype(_F32)
            du0 = ru * g_ref[:, c:c + chunk] * du \
                - pull * du0_scr[rows, c:c + chunk]
            du0_scr[rows, c:c + chunk] = du0
            for j in range(n):
                sums[j] = sums[j] + _fold_lanes(
                    du0 * x_ref[j, rows, c:c + chunk].astype(_F32))
        dm_scr[rows, :] = dmaps_ref[rows, :] + _place(
            [(j, jnp.sum(s, axis=1, keepdims=True)) for j, s in enumerate(sums)])
    _groups(tile, norm_back)

    # the maps' backward, tokens as the minor axis
    z = zt_ref[...]
    r = z[_RIDER:_RIDER + 1]
    scale, bias = scale_ref[...], bias_ref[...]
    h_pre, h_post, _, (res, exps, kept) = _maps_fwd(cfg, z, scale, bias)
    dm = dm_scr[...].T                                            # (32, tile)
    d_res = _sinkhorn_rounds_bwd(
        kept, [dm[2 * n + n * i:2 * n + n * (i + 1)] for i in range(n)], hc_eps)
    inside = (res >= lo) & (res <= hi)
    dp = _pad_rows(
        [dm[:n] * h_pre * (1.0 - h_pre),
         dm[n:2 * n] * h_post * (1.0 - 0.5 * h_post),
         jnp.where(inside, jnp.concatenate(
             [g * e for g, e in zip(d_res, exps)], axis=0), 0.0)])
    dscale_ref[...] += jnp.sum(dp * z, axis=1, keepdims=True)
    dbias_ref[...] += jnp.sum(dp, axis=1, keepdims=True)
    dz = dp * scale
    # the mean square's share of dX is a factor a token on X: it rides
    # through the transposition with the maps' rows
    pull = jnp.sum(dz * z, axis=0, keepdims=True) * (r * r * (-1.0 / (n * d)))
    row = lax.broadcasted_iota(jnp.int32, (MAP_ROWS, tile), 0)
    draw = dz * r
    mix_scr[...] = jnp.where(row == _RIDER, pull, draw).T
    draw = draw.astype(phit_ref.dtype)
    for j in range(n):
        dphit_scr[:, j * d:(j + 1) * d] += jnp.dot(
            draw, x_ref[j], preferred_element_type=_F32)

    # dX, one stream at a time: the maps' product's share comes through the
    # MXU a whole tile at once, the rest is mixed a group of rows at a time
    for j in range(n):
        thr_scr[...] = jnp.dot(mix_scr[...].astype(phit_ref.dtype),
                               phit_ref[:, j * d:(j + 1) * d],
                               preferred_element_type=_F32)

        def write(rows, j=j):
            h = maps_ref[rows, :]
            pull = mix_scr[rows, _RIDER:_RIDER + 1]
            for c in cols:
                dx = thr_scr[rows, c:c + chunk] \
                    + h[:, j:j + 1] * du0_scr[rows, c:c + chunk] \
                    + pull * x_ref[j, rows, c:c + chunk].astype(_F32)
                for i in range(n):
                    k = 2 * n + n * i + j
                    dx = dx + h[:, k:k + 1] \
                        * dout_ref[i, rows, c:c + chunk].astype(_F32)
                dx_ref[j, rows, c:c + chunk] = dx.astype(dx_ref.dtype)
        _groups(tile, write)

    @pl.when(last)
    def _():
        dphit_ref[...] = dphit_scr[...]
        dg_ref[...] = jnp.sum(dg_scr[...], axis=0, keepdims=True)


# ---- the calls

def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, vmem,
          interpret, args, accumulates=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    call = pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if accumulates else "parallel",) * 2,
            vmem_limit_bytes=vmem))
    if not interpret:
        return call(*args)
    # interpreted off the chip; traced with x64 off, as the chip has it (the
    # tests' x64 makes the loops' counters int64)
    with jax.enable_x64(False):
        return call(*args)


def _specs(n, tile, d):
    """Block specs over a (B, T / tile) grid: the state's, a (B, T, d)
    array's, the maps' (tokens leading), the maps' (tokens the minor axis)
    and a small operand's that stays where it is."""
    from jax.experimental import pallas as pl
    state = pl.BlockSpec((None, n, tile, d), lambda b, t: (b, 0, t, 0))
    tokens = pl.BlockSpec((None, tile, d), lambda b, t: (b, t, 0))
    maps = pl.BlockSpec((None, tile, MAP_ROWS), lambda b, t: (b, t, 0))
    maps_t = pl.BlockSpec((None, MAP_ROWS, tile), lambda b, t: (b, 0, t))
    whole = lambda shape: pl.BlockSpec(shape, lambda b, t: (0,) * len(shape))
    return state, tokens, maps, maps_t, whole


def _shape_of(x):
    b, n, t, d = x.shape
    tile = token_tile(n, t, d, x.dtype.itemsize)
    vmem = _vmem_bytes(n, tile, d, x.dtype.itemsize) + _HEADROOM
    return b, n, t, d, tile, vmem


# Each call is a jit of its own: a net's layers of one shape then share one
# trace of the kernel and one lowering to Mosaic, where twelve hyper-
# connections each traced four kernels of some thousand operations (34 s more
# of tracing and lowering a program at the decoder cell's shape, PERF.md PR 30).
# Whether the kernels are interpreted (ops/helpers.interpret_mode) is asked
# outside and is part of each jit's key.
@functools.partial(jax.jit, static_argnums=(0, 1))
def _pre_call(cfg, interpret, x, phit, scale, bias, g):
    from jax.experimental.pallas import tpu as pltpu
    b, n, t, d, tile, vmem = _shape_of(x)
    state, tokens, maps, maps_t, whole = _specs(n, tile, d)
    small = whole((MAP_ROWS, 1))
    return _call(
        functools.partial(_pre_kernel, cfg), "dl4j_hc_pre", (b, t // tile),
        [state, whole((MAP_ROWS, n * d)), small, small, whole((1, d))],
        (tokens, maps, maps_t),
        (jax.ShapeDtypeStruct((b, t, d), x.dtype),
         jax.ShapeDtypeStruct((b, t, MAP_ROWS), _F32),
         jax.ShapeDtypeStruct((b, MAP_ROWS, t), _F32)),
        [pltpu.VMEM((tile, 128), _F32), pltpu.VMEM((_GROUP, d), _F32)],
        vmem, interpret, (x, phit, scale, bias, g))


@functools.partial(jax.jit, static_argnums=0)
def _post_call(interpret, x, y, maps):
    b, n, t, d, tile, vmem = _shape_of(x)
    state, tokens, maps_spec, _, _ = _specs(n, tile, d)
    return _call(
        functools.partial(_post_kernel, n), "dl4j_hc_post", (b, t // tile),
        [state, tokens, maps_spec], state,
        jax.ShapeDtypeStruct(x.shape, x.dtype), [], vmem, interpret,
        (x, y, maps))


@functools.partial(jax.jit, static_argnums=0)
def _post_bwd_call(interpret, dout, x, y, maps):
    b, n, t, d, tile, vmem = _shape_of(x)
    state, tokens, maps_spec, _, _ = _specs(n, tile, d)
    return _call(
        functools.partial(_post_bwd_kernel, n), "dl4j_hc_post_bwd",
        (b, t // tile), [state, state, tokens, maps_spec], (tokens, maps_spec),
        (jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(maps.shape, _F32)), [], vmem, interpret,
        (dout, x, y, maps))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pre_bwd_call(cfg, interpret, du, x, dout, maps, dmaps, zt, phit, scale, bias,
                  g):
    from jax.experimental.pallas import tpu as pltpu
    b, n, t, d, tile, vmem = _shape_of(x)
    state, tokens, maps_spec, maps_t, whole = _specs(n, tile, d)
    small, wide = whole((MAP_ROWS, 1)), whole((MAP_ROWS, n * d))
    return _call(
        functools.partial(_pre_bwd_kernel, cfg), "dl4j_hc_pre_bwd",
        (b, t // tile),
        [tokens, state, state, maps_spec, maps_spec, maps_t, wide, small, small,
         whole((1, d))],
        (state, wide, small, small, whole((1, d))),
        (jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((MAP_ROWS, n * d), _F32),
         jax.ShapeDtypeStruct((MAP_ROWS, 1), _F32),
         jax.ShapeDtypeStruct((MAP_ROWS, 1), _F32),
         jax.ShapeDtypeStruct((1, d), _F32)),
        [pltpu.VMEM((tile, d), _F32), pltpu.VMEM((tile, d), _F32),
         pltpu.VMEM((tile, MAP_ROWS), _F32), pltpu.VMEM((tile, MAP_ROWS), _F32),
         pltpu.VMEM((MAP_ROWS, n * d), _F32), pltpu.VMEM((8, d), _F32)],
        vmem, interpret, (du, x, dout, maps, dmaps, zt, phit, scale, bias, g),
        accumulates=True)


# ---- the two halves and the arrangement between them (module docstring)

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pre(cfg, x, phit, scale, bias, g):
    u, maps, _ = _pre_call(cfg, helpers.interpret_mode(), x, phit, scale, bias, g)
    return u, maps, x


def _pre_fwd(cfg, x, phit, scale, bias, g):
    u, maps, zt = _pre_call(cfg, helpers.interpret_mode(), x, phit, scale, bias, g)
    return (u, maps, x), (x, phit, scale, bias, g, maps, zt)


def _pre_bwd(cfg, saved, cotangents):
    x, phit, scale, bias, g, maps, zt = saved
    du, dmaps, dout = cotangents              # dout: what `_post_bwd` handed on
    dx, dphit, dscale, dbias, dg = _pre_bwd_call(
        cfg, helpers.interpret_mode(), du, x, dout, maps, dmaps, zt, phit, scale,
        bias, g)
    return dx, dphit.astype(phit.dtype), dscale, dbias, dg


_pre.defvjp(_pre_fwd, _pre_bwd)


@jax.custom_vjp
def _post(x, y, maps):
    return _post_call(helpers.interpret_mode(), x, y, maps)


def _post_fwd(x, y, maps):
    return _post(x, y, maps), (x, y, maps)


def _post_bwd(saved, dout):
    dy, dmaps = _post_bwd_call(helpers.interpret_mode(), dout, *saved)
    return dout, dy, dmaps                    # dout in dX's place: `_pre_bwd` mixes it


_post.defvjp(_post_fwd, _post_bwd)


@register_helper("hyper_connection")
def hyper_connection(x, phi, a, b_pre, b_post, b_res, norm_g, sublayer, *,
                     sinkhorn_iters, hc_eps, clamp_min, clamp_max, eps):
    """X (B, n, T, d) -> (X_next, what `sublayer` returned beside y), for a
    shape `token_tile` takes. phi (n d, 2n + n n) the maps' weights, pre,
    post, res side by side; a (3,), b_pre (n,), b_post (n,), b_res (n, n) their
    scales and biases; norm_g (d,); `sublayer(u) -> (y, rest)` with u and y
    (B, T, d)."""
    n = x.shape[1]
    cfg = (n, int(sinkhorn_iters), float(hc_eps), float(clamp_min),
           float(clamp_max), float(eps))
    used = 2 * n + n * n
    column = lambda v: jnp.pad(v.astype(_F32), (0, MAP_ROWS - used))[:, None]
    scale = column(jnp.repeat(a, np.array([n, n, n * n]),
                              total_repeat_length=used))
    bias = column(jnp.concatenate([b_pre, b_post, b_res.reshape(-1)]))
    phit = jnp.pad(phi.astype(x.dtype).T, ((0, MAP_ROWS - used), (0, 0)))
    u, maps, x = _pre(cfg, x, phit, scale, bias,
                      norm_g.astype(_F32)[None])
    y, rest = sublayer(u)
    return _post(x, y.astype(x.dtype), maps), rest
