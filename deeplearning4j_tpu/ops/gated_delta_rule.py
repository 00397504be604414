"""The gated delta rule: a recurrent layer whose state is a matrix a head.

Per value head, with S_0 = 0 of shape (d_k, d_v), a log-decay g_t <= 0 and a
write strength beta_t:

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t.

`gated_delta_rule_scan` is those four lines, token by token in a `lax.scan`:
what runs off a TPU, what a call the kernels refuse gets, and what the tests
hold the kernels to. `gated_delta_rule` is the same mathematics in chunks of
`CHUNK` tokens (Gated DeltaNet, arXiv:2412.06464, section 3.3) as two Mosaic
kernels, `dl4j_gdr_fwd` and `dl4j_gdr_bwd`, under one `custom_vjp`. Inside a
chunk, with G the cumulative sum of g from the chunk's start and D_ij =
exp(G_i - G_j) for i >= j (a decay is always the `exp` of the sum of the g
between two tokens, which is not positive: never of a cumulative sum's
negative, nor of the difference of two), the chunk's u solve the unit lower
triangular system

    (I + A) U = beta (V - exp(G) K S_0),  A = tril(beta_i D_ij k_i.k_j, -1),

which is linear in the state the chunk starts from: with T = (I + A)^-1, W =
T (beta exp(G) K) and U_0 = T (beta V), U = U_0 - W S_0, O = exp(G) (Q S_0) +
tril(Q K^T D) U and S_C = exp(G_C) S_0 + K^T (exp(G_C - G) U). T is worked
out by blocked forward substitution: the diagonal blocks of `_BASE` tokens a
row at a time on the vector unit, then merged by products on the matrix unit
up to the chunk (`_inverse`).

The kernels. A grid step holds `_TILE_CHUNKS` chunks of a few heads (one or
more key heads with the value heads they serve) and walks the chunks in a
loop; the token tiles of a sequence are the grid's last, sequential axis, and
the heads' (d_k, d_v) float32 states stay in VMEM scratch across it, zeroed at
a sequence's first tile. Everything of a chunk is made in VMEM from q, k, v,
g, beta: what crosses HBM a pass is those five in and o out. The forward
under differentiation also writes the state each chunk starts from (float32);
the backward reads them, walks the tiles and their chunks from the last to
the first carrying the state's cotangent in VMEM, makes each chunk's operands
again, takes the solve's pull-back through T itself (dR = T^T dU, dA =
-tril(dR U^T, -1)), sums dq and dk over a key head's value heads, and writes
the five cotangents once.

Layout: q, k (B, T, H_k, d_k), v (B, T, H_v, d_v) with H_v a multiple of
H_k (value head h reads key head h // (H_v / H_k)), g and beta (B, T, H_v);
o (B, T, H_v, d_v). q and k come normalised and scaled. The kernels read q,
k, v as they are, viewed (B, T, H d): no transpose and no repeat round the
call; only g and beta (small) are laid out a chunk a row outside.

Precision. The products of q, k, v, the state, U, W, P and the cotangents
take their operands in the type q, k, v come in (bfloat16 in a
mixed-precision net) and add up in float32; g, beta, the decays, the system,
its inverse and the state are float32, and a product one of whose operands is
such a float32 matrix (the decays' sums, the merges of the inverse, T against
K, V, dW and dU) is a float32 product at `Precision.HIGHEST` (`_mm`); the
substitution is float32 arithmetic of the vector unit. The kernels take the
same steps for bfloat16 q, k, v as for float32 ones.

Behind the helper seam (`register_helper("gated_delta_rule")`): the kernels
on a TPU for the shapes `heads_a_step` takes, interpreted off a TPU under the
override at any widths, the scan elsewhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.helpers import register_helper

_F32 = jnp.float32
# Tokens a chunk: a 128 x 128 system a chunk a head, one tile of the matrix
# unit and whole registers of 128 lanes (at 64 half of each lane is empty and
# a pass costs more: 19.6 ms a forward pass against 17.3 at the Qwen3-Next
# share's shape with every level of the inverse on the matrix unit, PERF.md
# PR 34); the state is touched T / 128 times instead of T.
CHUNK = 128
# Chunks a grid step walks (a token tile of 1024 at CHUNK): eight rows of the
# gates' block, one sublane tile.
_TILE_CHUNKS = 8
# Value heads a grid step takes where the heads' grouping allows it (2 read
# 4% slower on the chip, 8 no faster: PR 34's first round).
_HEADS = 4
# Tokens a diagonal block of a chunk's system that the vector unit inverts
# (`_inverse`) while the matrix unit merges another head's: at 1, 4, 8, 16,
# 32, 64 a forward pass at the Qwen3-Next share's shape read 17.3, 15.6,
# 13.2, 11.8, 11.4, 12.8 ms on the chip (PERF.md, PR 34).
_BASE = 32
# What a call asks of VMEM beyond its blocks (`vmem_limit_bytes`, by name):
# the chunk's operands of every head in flight, spilled by the compiler.
_HEADROOM = 16 * 1024 * 1024
_VMEM_MOST = 100 * 1024 * 1024
_LANES = 128

_NN = (((1,), (0,)), ((), ()))       # a @ b
_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_TN = (((0,), (0,)), ((), ()))       # a.T @ b


def _wide(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def gated_delta_rule_scan(q, k, v, g, beta):
    """The recurrence as written, one token a step."""
    wide = _wide(q.dtype)
    b, _, h, d_v = v.shape
    group = h // q.shape[2]
    q, k = (jnp.repeat(a, group, axis=2) for a in (q, k))
    time_major = lambda a: jnp.moveaxis(a.astype(wide), 1, 0)

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros((b, h, q.shape[-1], d_v), wide)
    _, out = lax.scan(token, start, tuple(map(time_major, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# ---- one chunk of one head, on values (float32 but the products' operands)

def _mm(a, b, dims):
    """A product that adds up in float32: one pass of the matrix unit where
    both operands come in a narrower type (q, k, v and what is rounded to
    their type), a float32 product at `Precision.HIGHEST` where either is a
    float32 matrix (the gates' sums, the system, its inverse and what the
    inverse meets)."""
    if _F32 in (a.dtype, b.dtype):
        return lax.dot_general(a.astype(_F32), b.astype(_F32), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _masks(chunk):
    """Over a chunk's (row i, column j): i == j, i >= j, i > j; i ^ j, whose
    highest bit says at which size of block, halving the chunk again and
    again, i and j part; and j's place in its block of `_base(chunk)`."""
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return i == j, i >= j, i > j, i ^ j, j & (_base(chunk) - 1)


def _base(chunk):
    """Tokens a diagonal block that the vector unit inverts: `_BASE`, or the
    largest power of two under it that divides the chunk."""
    return math.gcd(chunk, _BASE)


def _col(row, eye):
    """(1, C) -> (C, 1)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """(C, 1) -> (1, C)."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decays(g_row, masks):
    """From a chunk's g (1, C): D (C, C); exp(G) and exp(G_C - G) (C, 1);
    exp(G_C) (1, 1). Each exponent is summed from the g it spans (D's as a
    product with the mask j < m), never taken as the difference of two
    cumulative sums, which loses what the larger one's size rounds away."""
    _, later, before = masks[:3]
    upto = jnp.where(later, g_row, 0.0)                  # g_m for m <= i
    total = jnp.sum(upto, axis=1, keepdims=True)
    spans = _mm(upto, jnp.where(before, 1.0, 0.0).astype(jnp.bfloat16),
                _NN)                                     # sum of g_m, j < m <= i
    after = jnp.sum(jnp.where(later, 0.0, g_row), axis=1, keepdims=True)
    return (jnp.where(later, jnp.exp(spans), 0.0), jnp.exp(total),
            jnp.exp(after), jnp.exp(total[-1:]))


def _inverse(system, masks):
    """(I + A)^-1 for A strictly lower triangular, float32: blocked forward
    substitution. The diagonal blocks of `_BASE` tokens
    on the vector unit, a row at a time, every block at once: once a block's
    row p is done, each row under it takes its A_ip times row p off. Then the
    blocks merged by products on the matrix unit: the inverse of a diagonal
    block of 2b tokens is the inverses T_1, T_2 of its halves on the diagonal
    and -T_2 A_21 T_1 below, A_21 the entries of A between the halves, up to
    the chunk. Every intermediate is the inverse of a block, as large as T
    and no larger (the product (I - A)(I + A^2)(I + A^4) ... takes the same
    count of products and cancels powers of A of any size where keys are
    alike and decays weak)."""
    eye, _, _, apart, place = masks
    chunk = system.shape[0]
    base = _base(chunk)
    blocks = (chunk // base, base, chunk)
    inverse = jnp.where(eye, 1.0, 0.0)
    for pivot in range(base - 1):
        factor = jnp.sum(jnp.where((apart < base) & (place == pivot), system,
                                   0.0), axis=1, keepdims=True)
        done = inverse.reshape(blocks)[:, pivot:pivot + 1]
        inverse = inverse - factor * jnp.broadcast_to(done, blocks).reshape(
            chunk, chunk)
    half = base
    while half < chunk:
        between = jnp.where((apart >= half) & (apart < 2 * half), system, 0.0)
        inverse = inverse - _mm(inverse, _mm(between, inverse, _NN), _NN)
        half *= 2
    return inverse


def _solved(k, v, kk, beta_row, decays, masks):
    """beta (C, 1) and T, W, U_0 of a chunk (module docstring), float32; W
    and U_0 as one product of T, side by side."""
    eye, _, before = masks[:3]
    decay, grown = decays[:2]
    beta = _col(beta_row, eye)
    inverse = _inverse(jnp.where(before, beta * decay * kk, 0.0), masks)
    both = _mm(inverse, jnp.concatenate([beta * grown * k, beta * v], axis=1),
               _NN)
    return beta, inverse, both[:, :k.shape[1]], both[:, k.shape[1]:]


def _chunk_fwd(q, k, v, kk, qk, g_row, beta_row, state, masks):
    """One chunk of one head from the state it starts from: (o (C, d_v), the
    state it leaves), float32."""
    op = q.dtype
    decays = decay, grown, to_end, whole = _decays(g_row, masks)
    _, _, w, u0 = _solved(k, v, kk, beta_row, decays, masks)
    s = state.astype(op)
    u = u0 - _mm(w.astype(op), s, _NN)
    p = jnp.where(masks[1], qk * decay, 0.0).astype(op)
    out = grown * _mm(q, s, _NN) + _mm(p, u.astype(op), _NN)
    return out, whole * state + _mm(k, (to_end * u).astype(op), _TN)


def _chunk_bwd(q, k, v, kk, qk, g_row, beta_row, state, d_out, d_state, masks):
    """The cotangents of one chunk of one head from the state it started
    from, the cotangent of its outputs and of the state it left: (dq, dk
    (C, d_k), dv (C, d_v), dg, dbeta (1, C), the cotangent of the state it
    started from), float32. A cotangent enters a product in the products'
    type, as q, k, v do, but where it meets T."""
    op = q.dtype
    narrow = lambda x: x.astype(op)
    eye, later, before = masks[:3]
    chunk = q.shape[0]
    # the forward again
    decays = decay, grown, to_end, whole = _decays(g_row, masks)
    beta, inverse, w, u0 = _solved(k, v, kk, beta_row, decays, masks)
    s, w_op = narrow(state), narrow(w)
    u = u0 - _mm(w_op, s, _NN)
    read = _mm(q, s, _NN)                                   # Q S_0
    p = narrow(jnp.where(later, qk * decay, 0.0))
    # the outputs' and the left state's pull-back
    d_out_wide = d_out.astype(_F32)
    d_read = narrow(grown * d_out_wide)
    dq = _mm(d_read, s, _NT)
    d_start = whole * d_state + _mm(q, d_read, _TN)
    dp = jnp.where(later, _mm(d_out, narrow(u), _NT), 0.0)
    d_left = narrow(d_state)
    dk = _mm(narrow(to_end * u), d_left, _NT)
    du_end = _mm(k, d_left, _NN)
    du = _mm(p, d_out, _TN) + to_end * du_end
    d_to_end = jnp.sum(du_end * u, axis=1, keepdims=True)
    d_whole = jnp.sum(jnp.sum(d_state * state, axis=1, keepdims=True),
                      axis=0, keepdims=True)
    du_op = narrow(du)
    dw = -_mm(du_op, s, _NT)
    d_start = d_start - _mm(w_op, du_op, _TN)
    # the solve's: dR = T^T dU, dA = -tril(dR U^T, -1)
    dr = _mm(inverse, jnp.concatenate([dw, du], axis=1), _TN)
    dr_w, dr_u = dr[:, :dw.shape[1]], dr[:, dw.shape[1]:]
    da = -jnp.where(before, _mm(narrow(dr_w), w_op, _NT)
                    + _mm(narrow(dr_u), narrow(u0), _NT), 0.0)
    # A = beta D K K^T below the diagonal, P = tril(Q K^T D)
    d_decay = da * (beta * kk) + dp * qk
    dkk = da * (beta * decay)
    dqk = narrow(dp * decay)
    dq = dq + _mm(dqk, k, _NN)
    scale = beta * grown
    dk = dk + _mm(dqk, q, _TN) + _mm(narrow(dkk + dkk.T), k, _NN) + scale * dr_w
    d_scale = jnp.sum(dr_w * k.astype(_F32), axis=1, keepdims=True)
    dv = beta * dr_u
    d_beta = jnp.sum(da * decay * kk, axis=1, keepdims=True) \
        + jnp.sum(dr_u * v.astype(_F32), axis=1, keepdims=True) + d_scale * grown
    # the cumulative sum's: G enters exp(G), exp(G_C - G), exp(G_C) and D
    pulled = d_decay * decay
    at_end = jnp.sum(d_to_end * to_end, axis=0, keepdims=True) + d_whole * whole
    last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_total = d_scale * scale \
        + jnp.sum(d_out_wide * read, axis=1, keepdims=True) * grown \
        - d_to_end * to_end + jnp.sum(pulled, axis=1, keepdims=True) \
        - _col(jnp.sum(pulled, axis=0, keepdims=True), eye) \
        + jnp.where(last, at_end, 0.0)
    dg = jnp.sum(jnp.where(later, d_total, 0.0), axis=0, keepdims=True)
    return dq, dk, dv, dg, _row(d_beta, eye), d_start


# ---- the kernels: a grid step is `_TILE_CHUNKS` chunks of a few heads

def _heads_of(q_ref, v_ref, g_ref, group):
    """(value heads, d_k, d_v) of a grid step's blocks."""
    heads = g_ref.shape[0]
    return heads, q_ref.shape[-1] * group // heads, v_ref.shape[-1] // heads


def _lanes(width: int) -> int:
    """A head's width in whole lane tiles."""
    return -(-width // _LANES) * _LANES


def _head(ref, rows, h, width):
    """Head h's (C, width) of a block's rows, its lanes filled up with zeros
    to whole tiles: a width that is no whole tile (96, 192) crosses HBM as it
    is and is widened here, in VMEM, which changes no sum."""
    x = ref[rows, h * width:(h + 1) * width]
    fill = _lanes(width) - width
    if not fill:
        return x
    return jnp.concatenate([x, jnp.zeros((x.shape[0], fill), x.dtype)], axis=1)


def _put(ref, rows, h, width, x):
    """`_head` the other way: the first `width` lanes of x to head h's place."""
    ref[rows, h * width:(h + 1) * width] = x[:, :width].astype(ref.dtype)


def _present(keys, n_k):
    """Runs a body for the grid step's key head `key` if it is one of the
    `n_k`: where the step's heads do not divide them its last block reaches
    past the end, and the heads there are skipped (what their lanes hold is
    not read)."""
    from jax.experimental import pallas as pl
    if n_k % keys == 0:
        return lambda key: lambda body: body()
    first = pl.program_id(1) * keys
    return lambda key: pl.when(first + key < n_k)


def _fwd_kernel(chunk, group, n_k, q_ref, k_ref, v_ref, g_ref, beta_ref,
                o_ref, *rest):
    """`rest`: the block of the chunks' starting states where the call keeps
    them, then the states' scratch."""
    from jax.experimental import pallas as pl
    *starts_ref, state_scr = rest
    heads, d_k, d_v = _heads_of(q_ref, v_ref, g_ref, group)
    keys = heads // group

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros_like(state_scr)

    masks, present = _masks(chunk), _present(keys, n_k)

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        for key in range(keys):
            @present(key)
            def _():
                q, k = _head(q_ref, rows, key, d_k), _head(k_ref, rows, key, d_k)
                kk, qk = _mm(k, k, _NT), _mm(q, k, _NT)
                for h in range(key * group, (key + 1) * group):
                    state = state_scr[h]
                    for kept in starts_ref:
                        kept[h, c] = state
                    out, state_scr[h] = _chunk_fwd(
                        q, k, _head(v_ref, rows, h, d_v), kk, qk,
                        g_ref[h, pl.ds(c, 1), :], beta_ref[h, pl.ds(c, 1), :],
                        state, masks)
                    _put(o_ref, rows, h, d_v, out)
        return carry
    lax.fori_loop(0, g_ref.shape[1], one_chunk, 0)


def _bwd_kernel(chunk, group, n_k, q_ref, k_ref, v_ref, g_ref, beta_ref,
                starts_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                d_state_scr):
    from jax.experimental import pallas as pl
    heads, d_k, d_v = _heads_of(q_ref, v_ref, g_ref, group)
    keys = heads // group
    chunks = g_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_scr[...] = jnp.zeros_like(d_state_scr)

    masks, present = _masks(chunk), _present(keys, n_k)

    def one_chunk(step, carry):
        c = chunks - 1 - step
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        for key in range(keys):
            @present(key)
            def _():
                q, k = _head(q_ref, rows, key, d_k), _head(k_ref, rows, key, d_k)
                kk, qk = _mm(k, k, _NT), _mm(q, k, _NT)
                dq = dk = 0.0
                for h in range(key * group, (key + 1) * group):
                    dq_h, dk_h, dv, dg, dbeta, d_state_scr[h] = _chunk_bwd(
                        q, k, _head(v_ref, rows, h, d_v), kk, qk,
                        g_ref[h, pl.ds(c, 1), :], beta_ref[h, pl.ds(c, 1), :],
                        starts_ref[h, c], _head(do_ref, rows, h, d_v),
                        d_state_scr[h], masks)
                    dq, dk = dq + dq_h, dk + dk_h
                    _put(dv_ref, rows, h, d_v, dv)
                    dg_ref[h, pl.ds(c, 1), :] = dg
                    dbeta_ref[h, pl.ds(c, 1), :] = dbeta
                _put(dq_ref, rows, key, d_k, dq)
                _put(dk_ref, rows, key, d_k, dk)
        return carry
    lax.fori_loop(0, chunks, one_chunk, 0)


# ---- the calls

def heads_a_step(n_k: int, n_v: int, d_k: int, d_v: int, itemsize: int,
                 chunk: int = CHUNK):
    """The value heads a grid step takes, or None where the kernels refuse
    the shape: the value heads have to be whole groups of the key heads, q,
    k, v no wider than the float32 the kernels reckon in, and compiled for
    the chip a step's heads whole lane tiles together (a head's own width
    need not be one: 4 heads of 96 are 384 lanes, and `_head` fills each up
    in VMEM) and the step's blocks within VMEM. Up to `_HEADS` value heads a
    step, in whole groups: the most that divide the key heads, or, where no
    such count is whole tiles wide, the most that are, the last block then
    reaching past the heads (`_present`)."""
    if n_v % n_k or itemsize > 4:
        return None
    group = n_v // n_k
    on_chip = not helpers.interpret_mode()
    whole = lambda kb: not (kb * d_k % _LANES or kb * group * d_v % _LANES)
    counts = [kb for kb in range(1, n_k + 1) if kb == 1 or kb * group <= _HEADS]
    divide = [kb for kb in counts if n_k % kb == 0]
    # interpreted, any widths do
    fit = [kb for kb in divide if whole(kb)] or [kb for kb in counts if whole(kb)] \
        or ([] if on_chip else divide)
    if not fit:
        return None
    keys = fit[-1]
    if on_chip and _vmem_bytes(keys, keys * group, d_k, d_v, itemsize,
                               chunk) > _VMEM_MOST:
        return None
    return keys * group


def _vmem_bytes(keys, heads, d_k, d_v, itemsize, chunk):
    """What the larger call, the backward, holds: the blocks of q, k, v, do,
    dq, dk, dv and of the chunks' starting states, each twice (the
    pipeline's two buffers), the states' cotangents, and `_HEADROOM`."""
    tile = _TILE_CHUNKS * chunk
    tokens = tile * (4 * keys * d_k + 3 * heads * d_v) * itemsize
    states = heads * _lanes(d_k) * _lanes(d_v) * 4
    return 2 * (tokens + _TILE_CHUNKS * states) + states + _HEADROOM


def _laid_out(chunk, back, q, k, v, g, beta, *more):
    """The kernels' operands from the layer's: the length padded to whole
    tiles with tokens that write nothing (k = v = 0, beta = 0, g = 0), q, k,
    v (and what `more` holds) viewed (B, T, H d), g and beta (B, H_v, T /
    chunk, chunk) float32; and the grid and block specs of a call, the tiles
    of a sequence from its last to its first where `back`."""
    from jax.experimental import pallas as pl
    b, t, n_k, d_k = q.shape
    n_v, d_v = v.shape[2:]
    heads = heads_a_step(n_k, n_v, d_k, d_v, q.dtype.itemsize, chunk)
    keys = heads * n_k // n_v
    chunks = min(_TILE_CHUNKS, -(-t // chunk))
    tile = chunks * chunk
    pad = -t % tile
    padded = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    flat = lambda a: padded(a).reshape(b, t + pad, -1)
    gate = lambda a: jnp.moveaxis(padded(a.astype(_F32)), 1, 2).reshape(
        b, n_v, -1, chunk)
    tiles = (t + pad) // tile
    at = (lambda n: tiles - 1 - n) if back else (lambda n: n)
    specs = (                       # of q or k, of v, of a gate, of the states
        pl.BlockSpec((None, tile, keys * d_k), lambda i, j, n: (i, at(n), j)),
        pl.BlockSpec((None, tile, heads * d_v), lambda i, j, n: (i, at(n), j)),
        pl.BlockSpec((None, heads, chunks, chunk),
                     lambda i, j, n: (i, j, at(n), 0)),
        pl.BlockSpec((None, heads, chunks, _lanes(d_k), _lanes(d_v)),
                     lambda i, j, n: (i, j, at(n), 0, 0)))
    grid = (b, -(-n_k // keys), tiles)
    vmem = _vmem_bytes(keys, heads, d_k, d_v, q.dtype.itemsize, chunk)
    return (tuple(map(flat, (q, k, v))) + (gate(g), gate(beta))
            + tuple(map(flat, more))), grid, specs, heads, vmem


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, vmem,
          interpret, args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    call = pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem))
    if not interpret:
        return call(*args)
    # interpreted off the chip; traced with x64 off, as the chip has it (the
    # tests' x64 makes the loops' counters int64)
    with jax.enable_x64(False):
        return call(*args)


# Each call is a jit of its own: a net's layers of one shape, and a block's
# forward and its recomputation, then share one trace of the kernel and one
# lowering to Mosaic (PERF.md, PR 30). Whether the kernels are interpreted
# (ops/helpers.interpret_mode) is asked outside and is part of each jit's key.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fwd_call(chunk, interpret, keep, q, k, v, g, beta):
    """o, and with `keep` the state each chunk starts from (B, H_v, T /
    chunk, d_k, d_v) float32, of the padded length and of whole lane tiles."""
    from jax.experimental.pallas import tpu as pltpu
    b, t, n_k, d_k = q.shape
    n_v, d_v = v.shape[2:]
    args, grid, (key, value, gate, state), heads, vmem = _laid_out(
        chunk, False, q, k, v, g, beta)
    length = args[0].shape[1]
    out_specs = [value]
    out_shape = [jax.ShapeDtypeStruct((b, length, n_v * d_v), v.dtype)]
    if keep:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct(
            (b, n_v, length // chunk, _lanes(d_k), _lanes(d_v)), _F32))
    out = _call(
        functools.partial(_fwd_kernel, chunk, n_v // n_k, n_k), "dl4j_gdr_fwd",
        grid, [key, key, value, gate, gate], out_specs, out_shape,
        [pltpu.VMEM((heads, _lanes(d_k), _lanes(d_v)), _F32)], vmem, interpret,
        args)
    o = out[0][:, :t].reshape(v.shape)
    return (o, out[1]) if keep else o


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bwd_call(chunk, interpret, q, k, v, g, beta, starts, d_out):
    from jax.experimental.pallas import tpu as pltpu
    b, t, n_k, d_k = q.shape
    n_v, d_v = v.shape[2:]
    args, grid, (key, value, gate, state), heads, vmem = _laid_out(
        chunk, True, q, k, v, g, beta, d_out)
    length = args[0].shape[1]
    gates = jax.ShapeDtypeStruct(args[3].shape, _F32)
    dq, dk, dv, dg, dbeta = _call(
        functools.partial(_bwd_kernel, chunk, n_v // n_k, n_k), "dl4j_gdr_bwd",
        grid,
        [key, key, value, gate, gate, state, value],
        [key, key, value, gate, gate],
        [jax.ShapeDtypeStruct((b, length, n_k * d_k), q.dtype),
         jax.ShapeDtypeStruct((b, length, n_k * d_k), k.dtype),
         jax.ShapeDtypeStruct((b, length, n_v * d_v), v.dtype), gates, gates],
        [pltpu.VMEM((heads, _lanes(d_k), _lanes(d_v)), _F32)], vmem, interpret,
        args[:5] + (starts, args[5]))
    tokens = lambda a, like: a[:, :t].reshape(like.shape)
    gate_back = lambda a, like: jnp.moveaxis(
        a.reshape(b, n_v, length), 1, 2)[:, :t].astype(like.dtype)
    return (tokens(dq, q), tokens(dk, k), tokens(dv, v), gate_back(dg, g),
            gate_back(dbeta, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, chunk):
    return _fwd_call(chunk, helpers.interpret_mode(), False, q, k, v, g, beta)


def _rule_fwd(q, k, v, g, beta, chunk):
    o, starts = _fwd_call(chunk, helpers.interpret_mode(), True, q, k, v, g, beta)
    return o, (q, k, v, g, beta, starts)


def _rule_bwd(chunk, kept, d_out):
    return _bwd_call(chunk, helpers.interpret_mode(), *kept, d_out)


_rule.defvjp(_rule_fwd, _rule_bwd)


@register_helper("gated_delta_rule")
def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The recurrence in chunks of `chunk` tokens, as kernels, for a shape
    `heads_a_step` takes."""
    return _rule(q, k, v, g, beta, chunk)
