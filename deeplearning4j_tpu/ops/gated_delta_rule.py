"""The gated delta rule: a recurrent layer whose state is a matrix a head.

Per head, with S_0 = 0 of shape (d_k, d_v), a log-decay g_t <= 0 and a write
strength beta_t:

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t.

`gated_delta_rule_scan` is those four lines, token by token in a `lax.scan`:
what a backend without a reason to do better runs, and what the tests hold
the other form to. `gated_delta_rule_chunked` is the same mathematics in
chunks of `CHUNK` tokens (Gated DeltaNet, arXiv:2412.06464, section 3.3),
every product a matrix product. Inside a chunk, with G the cumulative sum of
g from the chunk's start and D_ij = exp(G_i - G_j) for i >= j (a decay is
always the `exp` of a difference that is not positive, never of a cumulative
sum's negative), the chunk's u solve the unit lower triangular system

    (I + tril(beta_i D_ij k_i.k_j, -1)) U = beta (V - exp(G) K S_0),

which is linear in the state the chunk starts from: with [W | U_0] the
solutions for the right sides [beta exp(G) K | beta V] (float32), U = U_0 -
W S_0, O = exp(G) Q S_0 + tril(Q K^T D) U and S_C = exp(G_C) S_0 + (exp(G_C -
G) K)^T U. So everything but those last three lines is worked out for all
chunks at once, and a `lax.scan` over the chunks carries one (d_k, d_v) state
in float32 through four small products a chunk.

The backward keeps the state each chunk started from and nothing else of the
forward: it works the chunks' operands out again, walks the chunks backwards
carrying the state's cotangent, takes each chunk's step apart again from its
starting state, and pulls the operands' cotangents back to q, k, v, g, beta.

Layout: q, k (B, T, H, d_k), v (B, T, H, d_v), g and beta (B, T, H); o (B, T,
H, d_v). q and k come normalised and scaled, one head for each head of v.
The products take their operands in the type q, k, v come in (bfloat16 in a
mixed-precision net) and add up in float32; g, beta, the solve and the state
are float32.

It ships as JAX behind the helper seam (`register_helper("gated_delta_rule")`:
the chunked form on a TPU, the scan elsewhere); whether a Mosaic kernel takes
its place is for a trace of the cell that runs it to say.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.helpers import register_helper

# Tokens a chunk: a 64 x 64 system a chunk a head, half a tile of the matrix
# unit; the state is touched T / 64 times instead of T times.
CHUNK = 64


def _wide(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def gated_delta_rule_scan(q, k, v, g, beta):
    """The recurrence as written, one token a step."""
    wide = _wide(q.dtype)
    b, _, h, d_k = q.shape
    time_major = lambda a: jnp.moveaxis(a.astype(wide), 1, 0)

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros((b, h, d_k, v.shape[-1]), wide)
    _, out = lax.scan(token, start, tuple(map(time_major, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


def _by_chunk(a, chunk):
    """(B, T, H, ...) -> (T / chunk, B, H, chunk, ...)."""
    b, t, h = a.shape[:3]
    a = a.reshape((b, t // chunk, chunk, h) + a.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)


def _by_token(a):
    """`_by_chunk` undone: (N, B, H, chunk, d) -> (B, N * chunk, H, d)."""
    n, b, h, chunk, d = a.shape
    return jnp.moveaxis(jnp.moveaxis(a, 0, 1), 2, 3).reshape(b, n * chunk, h, d)


def _operands(q, k, v, g, beta, chunk):
    """What each chunk's step needs and no state enters, for all chunks at
    once: (W, U_0, P = tril(Q K^T D), exp(G_C - G) K, exp(G) Q, exp(G_C)),
    chunk first. The solve and the decays float32, the products' operands in
    the type they came in."""
    op, wide = q.dtype, _wide(q.dtype)
    q, k, v = (_by_chunk(a, chunk) for a in (q, k, v))
    g, beta = (_by_chunk(a.astype(wide), chunk) for a in (g, beta))
    total = jnp.cumsum(g, axis=-1)                          # G: (N, B, H, C)
    rows = jnp.arange(chunk)
    later = rows[:, None] >= rows[None, :]                  # i >= j
    gap = jnp.where(later, total[..., :, None] - total[..., None, :], 0.0)
    decay = jnp.where(later, jnp.exp(gap), 0.0)             # D
    kk = jnp.einsum("nbhid,nbhjd->nbhij", k, k, preferred_element_type=wide)
    system = jnp.where(rows[:, None] > rows[None, :],
                       beta[..., :, None] * decay * kk, 0.0)
    grown = jnp.exp(total)
    sides = jnp.concatenate(
        [(beta * grown)[..., None] * k.astype(wide), beta[..., None] * v.astype(wide)],
        axis=-1)
    solved = jax.scipy.linalg.solve_triangular(system, sides, lower=True,
                                               unit_diagonal=True)
    w, u0 = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    qk = jnp.einsum("nbhid,nbhjd->nbhij", q, k, preferred_element_type=wide)
    to_end = jnp.exp(total[..., -1:] - total)
    return (w.astype(op), u0, (qk * decay).astype(op),
            (to_end[..., None] * k.astype(wide)).astype(op),
            (grown[..., None] * q.astype(wide)).astype(op), grown[..., -1])


def _step(state, operands):
    """One chunk: (the state it leaves, its outputs (B, H, C, d_v)), both
    float32."""
    w, u0, p, k_end, q_in, decay = operands
    wide = state.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=wide)
    s = state.astype(w.dtype)
    u = u0 - dot("bhck,bhkv->bhcv", w, s)
    u_op = u.astype(w.dtype)
    out = dot("bhck,bhkv->bhcv", q_in, s) + dot("bhij,bhjv->bhiv", p, u_op)
    return decay[..., None, None] * state + dot("bhck,bhcv->bhkv", k_end, u_op), out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunks(q, k, v, g, beta, chunk):
    return _chunks_fwd(q, k, v, g, beta, chunk)[0]


def _chunks_fwd(q, k, v, g, beta, chunk):
    b, _, h, d_k = q.shape
    start = jnp.zeros((b, h, d_k, v.shape[-1]), _wide(q.dtype))

    def one_more(state, operands):
        left, out = _step(state, operands)
        return left, (out, state)
    _, (out, starts) = lax.scan(one_more, start,
                                _operands(q, k, v, g, beta, chunk))
    return _by_token(out).astype(v.dtype), (q, k, v, g, beta, starts)


def _chunks_bwd(chunk, kept, d_out):
    q, k, v, g, beta, starts = kept
    operands, to_inputs = jax.vjp(
        lambda *inputs: _operands(*inputs, chunk), q, k, v, g, beta)
    d_out = _by_chunk(d_out.astype(starts.dtype), chunk)

    def one_back(d_state, x):
        state, ops, d_chunk = x
        _, pull = jax.vjp(_step, state, ops)
        return pull((d_state, d_chunk))
    _, d_operands = lax.scan(one_back, jnp.zeros_like(starts[0]),
                             (starts, operands, d_out), reverse=True)
    return to_inputs(d_operands)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


@register_helper("gated_delta_rule")
def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The recurrence in chunks of `chunk` tokens. A length that is no whole
    number of chunks is padded with tokens that write nothing (k = v = 0,
    beta = 0, g = 0), whose outputs are dropped."""
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    return _chunks(q, k, v, g, beta, chunk)[:, :t]
