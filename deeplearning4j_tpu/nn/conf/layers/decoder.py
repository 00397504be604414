"""The layers of a sparse decoder block, each trained through the normal walk.

Beyond-reference (the 2017 reference has no attention, no norm of this kind
and no experts): RMS norm, a token table over `(batch, time)` ids, latent
attention with a decoupled rotary key and YaRN frequencies, the gated MLP,
dropless sigmoid top-k routed experts beside a shared one, the
manifold-constrained hyper-connection around a sublayer (a wrapping layer
config, as `Bidirectional` is), the multi-token-prediction module's input
and a softmax cross-entropy head over integer labels; the plain pre-norm
residual block round a sublayer (the norm on the sublayer's input, on its
output as the Olmo family has it, or on both sides as Ouro has it), the Gated
DeltaNet mixer (a recurrent layer whose state is a matrix a head,
`ops/gated_delta_rule.py`) and the softmax attention mixer on grouped k/v
heads (with an output gate and rotary on part of each head, or with neither;
with or without a norm on q and k); the looped stack (`LoopedStack`: a stack
of blocks run several times with one set of weights, one `lax.scan` over the
passes) and its exit head (`LoopExitHead`: every pass scored by one head and
weighted by an early-exit gate).

Layout: these layers pass `(batch, time, features)` between them (features
last, as the matrix unit wants them), not DL4J's `(batch, features, time)`;
the residual state of a hyper-connected stack is `(batch, streams, time,
features)`. Their `InputType` is `recurrent(features, time)` all the same.

The chip's share is a property of the layers: the table and the head are told
which rows of the vocabulary they hold, the attention which heads, the expert
layer which experts, of the published counts. The router still scores every
published expert and picks its k; the layer adds its own experts' part for the
tokens routed to them, and what absent heads and experts would have added is
left out. On one chip a layer runs without its exchange.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    BaseLayerConf, FeedForwardLayerConf, layer_scope, register_layer)

_F32 = jnp.float32

# A sequence no longer than one tile of the matrix unit is one dense product;
# longer ones go through the flash kernel (`SelfAttentionLayer`'s rule, which
# has the same number as its default): the kernel would pad a short one to its
# own tile of 512.
_DENSE_ATTENTION_MAX_T = 128

# mHC's initial maps (arXiv:2512.24880; the plain reference has the same two
# numbers): the learned part of each map starts small against its bias, and the
# residual map's bias is a heavy diagonal, so that Sinkhorn starts it near the
# identity and a fresh hyper-connection is nearly a plain residual.
_HC_MAP_SCALE_INIT = 0.1
_HC_RES_DIAGONAL_INIT = 4.0


def rms_norm(x, g, eps, zero_centred=False):
    """x / rms(x) * g over the last axis, worked out in float32; with
    `zero_centred` the gain is 1 + g (g starts at 0)."""
    x32 = x.astype(_F32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    if g is not None:
        y = y * (1.0 + g.astype(_F32) if zero_centred else g.astype(_F32))
    return y.astype(x.dtype)


def _gain(shape, zero_centred, dtype):
    return (jnp.zeros if zero_centred else jnp.ones)(shape, dtype)


def _time_of(input_type) -> int:
    return getattr(input_type, "timeseries_length", -1)


class _TokenLayer(FeedForwardLayerConf):
    """(batch, time, n_in) -> (batch, time, n_out)."""

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, _time_of(input_type))


@register_layer
@dataclass
class RMSNorm(_TokenLayer):
    eps: float = 1e-6
    zero_centred: bool = False      # y = x / rms(x) * (1 + g), g from 0

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {"g": _gain((self.n_in,), self.zero_centred, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return rms_norm(x, params["g"], self.eps, self.zero_centred), state, mask


@register_layer
@dataclass
class TokenEmbedding(FeedForwardLayerConf):
    """(batch, time) integer ids -> (batch, time, n_out). `n_in` is the
    published vocabulary; the table keeps rows `[first_row, first_row +
    rows_held)` of it (all where `rows_held` is 0) and ids are of those."""
    rows_held: int = 0
    first_row: int = 0
    integer_input = True       # the walk hands it the ids as they are

    @property
    def rows(self) -> int:
        return self.rows_held or self.n_in

    def set_n_in(self, input_type, override=False):
        return None

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.size)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {"W": self._winit(key, (self.rows, self.n_out), self.n_in,
                                 self.n_out, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return params["W"][x.astype(jnp.int32) - self.first_row], state, mask


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Rotary frequencies; with YaRN `scaling` (`factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`) those that
    turn fewer than `beta_slow` times over the original window are divided by
    `factor`, those over `beta_fast` kept, a linear ramp between."""
    pos = theta ** (jnp.arange(0, dim, 2, dtype=_F32) / dim)
    if not scaling or float(scaling.get("factor", 1)) <= 1:
        return 1.0 / pos
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low) / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def apply_rope(x, inv_freq):
    """x: (batch, time, heads, dim), rotated in float32, the half-split
    pairing (entry i with entry i + dim/2)."""
    t = jnp.arange(x.shape[1], dtype=_F32)
    ang = t[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _dense_causal_attention(q, k, v, scale):
    """(B, H, T, qk), (B, H, T, qk), (B, H, T, v) -> (B, H, T, v): one masked
    softmax over all T keys, for a sequence of a tile or less."""
    t = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=_F32) * scale
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v)


@register_layer
@dataclass
class LatentAttention(_TokenLayer):
    """Multi-head latent attention (DeepSeek-V2/V3), causal: queries through a
    `q_lora_rank` latent, keys and values through a `kv_lora_rank` latent with
    a rotary key of `qk_rope_head_dim` shared by all heads; heads of
    `qk_nope_head_dim + qk_rope_head_dim` against values of `v_head_dim`. In
    training it is its low-rank products and an attention whose QK width
    differs from its V width. `heads_held` of the `n_heads` published heads
    are computed here (0: all); the output is their part of the sum."""
    n_heads: int = 32
    heads_held: int = 0
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    eps: float = 1e-6

    @property
    def heads(self) -> int:
        return self.heads_held or self.n_heads

    @property
    def softmax_scale(self) -> float:
        rs = self.rope_scaling or {}
        m = 1.0
        if float(rs.get("factor", 1)) > 1:
            m = 0.1 * rs.get("mscale_all_dim", 0) * math.log(rs["factor"]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def init_params(self, key, input_type, dtype=jnp.float32):
        d, h = self.n_in, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        shapes = {"w_qa": (d, self.q_lora_rank), "w_qb": (self.q_lora_rank, h * qk),
                  "w_kva": (d, self.kv_lora_rank + self.qk_rope_head_dim),
                  "w_kvb": (self.kv_lora_rank,
                            h * (self.qk_nope_head_dim + self.v_head_dim)),
                  "w_o": (h * self.v_head_dim, self.n_out)}
        keys = jax.random.split(key, len(shapes))
        p = {name: self._winit(k, s, s[0], s[1], dtype)
             for k, (name, s) in zip(keys, shapes.items())}
        p["q_norm_g"] = jnp.ones((self.q_lora_rank,), dtype)
        p["kv_norm_g"] = jnp.ones((self.kv_lora_rank,), dtype)
        return p

    def _attend(self, q, k, v):
        """(B, H, T, qk), (B, H, T, qk), (B, H, T, v) -> (B, H, T, v)."""
        t = q.shape[2]
        from deeplearning4j_tpu.ops.helpers import helper_for
        flash = helper_for("flash_attention", None) \
            if t > _DENSE_ATTENTION_MAX_T else None
        if flash is not None:
            # the kernel wants one width: V is padded to QK's with zeros,
            # whose columns of the result are dropped (PERF.md: what it costs)
            pad = q.shape[-1] - v.shape[-1]
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
            out = flash(q, k, vp, None, True, self.softmax_scale)
            return out[..., :v.shape[-1]]
        return _dense_causal_attention(q, k, v, self.softmax_scale)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        b, t, _ = x.shape
        h, nope, rd = self.heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        c_q = rms_norm(x @ params["w_qa"], params["q_norm_g"], self.eps)
        q = (c_q @ params["w_qb"]).reshape(b, t, h, nope + rd)
        kva = x @ params["w_kva"]
        c_kv = rms_norm(kva[..., :self.kv_lora_rank], params["kv_norm_g"], self.eps)
        kv = (c_kv @ params["w_kvb"]).reshape(b, t, h, nope + self.v_head_dim)
        inv_freq = yarn_inv_freq(rd, self.rope_theta, self.rope_scaling)
        q_rope = apply_rope(q[..., nope:], inv_freq)
        k_rope = apply_rope(kva[..., self.kv_lora_rank:].reshape(b, t, 1, rd),
                            inv_freq)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_rope, (b, t, h, rd))], axis=-1)
        heads_first = lambda a: jnp.swapaxes(a, 1, 2)
        out = self._attend(heads_first(q), heads_first(k),
                           heads_first(kv[..., nope:]))
        out = jnp.swapaxes(out, 1, 2).reshape(b, t, h * self.v_head_dim)
        return out @ params["w_o"], state, mask


@register_layer
@dataclass
class GatedAttention(_TokenLayer):
    """Causal softmax attention on grouped k/v heads, with an output gate
    (Qwen3-Next's full-attention layer) or without (`output_gate` False:
    Olmo's): [q, gate] = x W_q a head (q = x W_q without the gate), k = x W_k,
    v = x W_v on `n_kv_heads` heads, each shared by n_heads / n_kv_heads
    query heads; an RMS norm on q and on k, a head with one gain of
    `head_dim` or, with `qk_norm_whole`, over the projection's whole width
    with a gain of that width (Olmo 2's), the gains zero-centred or from 1;
    with `qk_norm` False no norm at all (Ouro's), and then the layer has no
    `q_norm_g` or `k_norm_g` leaves (and `qk_norm_whole` is refused);
    rotary (the half-split pairing) on the first `rotary_dim` entries of
    each head (0: none); y = (attention * sigmoid(gate)) W_o. No bias. Past a
    tile's length the attention is the flash kernel, which reads a query
    head's k/v at its group's row and sums dk, dv over the group: no repeat
    is written. Where a chip holds a share of a model's heads, `n_heads` and
    `n_kv_heads` are the held ones: the output is their part of the sum over
    heads, and a norm over the whole width runs over the held columns."""
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    eps: float = 1e-6
    output_gate: bool = True
    qk_norm_whole: bool = False
    zero_centred: bool = True
    qk_norm: bool = True

    def __post_init__(self):
        if self.qk_norm_whole and not self.qk_norm:
            raise ValueError("qk_norm_whole says how q and k are normed; "
                             "with qk_norm False they are not")

    def init_params(self, key, input_type, dtype=jnp.float32):
        d, h, hk, hd = self.n_in, self.n_heads, self.n_kv_heads, self.head_dim
        shapes = {"w_q": (d, h * (2 if self.output_gate else 1) * hd),
                  "w_k": (d, hk * hd), "w_v": (d, hk * hd),
                  "w_o": (h * hd, self.n_out)}
        keys = jax.random.split(key, len(shapes))
        p = {name: self._winit(k, s, s[0], s[1], dtype)
             for k, (name, s) in zip(keys, shapes.items())}
        if not self.qk_norm:
            return p
        whole = self.qk_norm_whole
        p["q_norm_g"] = _gain((h * hd if whole else hd,), self.zero_centred, dtype)
        p["k_norm_g"] = _gain((hk * hd if whole else hd,), self.zero_centred, dtype)
        return p

    def _attend(self, q, k, v):
        """(B, H, T, hd), (B, Hk, T, hd) twice -> (B, H, T, hd)."""
        scale = self.head_dim ** -0.5
        from deeplearning4j_tpu.ops.helpers import helper_for
        flash = helper_for("flash_attention", None) \
            if q.shape[2] > _DENSE_ATTENTION_MAX_T else None
        if flash is not None:
            return flash(q, k, v, None, True, scale)
        group = q.shape[1] // k.shape[1]
        return _dense_causal_attention(q, jnp.repeat(k, group, axis=1),
                                       jnp.repeat(v, group, axis=1), scale)

    def _normed(self, x, g, inv_freq):
        """x (B, T, heads, hd): the norm, then the rotary part."""
        if self.qk_norm and self.qk_norm_whole:
            flat = x.reshape(x.shape[:2] + (-1,))
            x = rms_norm(flat, g, self.eps, self.zero_centred).reshape(x.shape)
        elif self.qk_norm:
            x = rms_norm(x, g, self.eps, self.zero_centred)
        rd = self.rotary_dim
        if not rd:
            return x
        return jnp.concatenate([apply_rope(x[..., :rd], inv_freq), x[..., rd:]],
                               axis=-1)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        b, t, _ = x.shape
        h, hk, hd = self.n_heads, self.n_kv_heads, self.head_dim
        q = (x @ params["w_q"]).reshape(b, t, h, -1)
        if self.output_gate:
            q, gate = jnp.split(q, 2, axis=-1)
        k = (x @ params["w_k"]).reshape(b, t, hk, hd)
        v = (x @ params["w_v"]).reshape(b, t, hk, hd)
        inv_freq = yarn_inv_freq(self.rotary_dim, self.rope_theta, None) \
            if self.rotary_dim else None
        q = self._normed(q, params.get("q_norm_g"), inv_freq)
        k = self._normed(k, params.get("k_norm_g"), inv_freq)
        heads_first = lambda a: jnp.swapaxes(a, 1, 2)
        out = jnp.swapaxes(self._attend(*map(heads_first, (q, k, v))), 1, 2)
        if self.output_gate:
            out = out * jax.nn.sigmoid(gate.astype(_F32)).astype(out.dtype)
        return out.reshape(b, t, h * hd) @ params["w_o"], state, mask


def _causal_depthwise_conv(x, w):
    """x (B, T, C), w (width, C): y_t = sum_i w[i] x_{t - width + 1 + i}, the
    positions before the first taken as zero."""
    width, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(width))


@register_layer
@dataclass
class GatedDeltaNet(_TokenLayer):
    """Gated DeltaNet mixer (arXiv:2412.06464; Qwen3-Next's linear-attention
    layer): [q, k, v, z] = x W_qkvz, [b, a] = x W_ba; q, k, v through a causal
    depthwise convolution of `conv_width` and silu; beta = `beta_scale`
    sigmoid(b) (2 where the state's transition may have negative eigenvalues,
    `allow_neg_eigval`), g = -exp(A_log) softplus(a + dt_bias) a value head
    (float32); q, k scaled to
    unit length a head, q by d_k^-1/2 more, each of the `n_k_heads` key heads
    serving n_v_heads / n_k_heads value heads; the gated delta rule
    (`ops/gated_delta_rule.py`: one (d_k, d_v) state a value head; its
    kernels where `heads_a_step` takes the heads, else the token scan); y =
    (RMSNorm(o) * silu(z)) W_out, the norm a head with its own gain. The
    columns of W_qkvz are [q | k | v | z], of W_ba [b | a] (a model that
    publishes them as separate projections, each with its convolution, has
    the same products). Where a chip holds a share of a model's heads,
    `n_k_heads` and `n_v_heads` are the held ones: the output is their part
    of the sum over heads."""
    n_k_heads: int = 16
    n_v_heads: int = 32
    d_k: int = 128
    d_v: int = 128
    conv_width: int = 4
    eps: float = 1e-6
    beta_scale: float = 1.0

    @property
    def _widths(self):
        """(q and k together, v) as the projection lays them out."""
        return 2 * self.n_k_heads * self.d_k, self.n_v_heads * self.d_v

    def init_params(self, key, input_type, dtype=jnp.float32):
        d, nv = self.n_in, self.n_v_heads
        qk, v = self._widths
        keys = jax.random.split(key, 5)
        w = lambda k, *shape: self._winit(k, shape, shape[0], shape[1], dtype)
        return {"w_qkvz": w(keys[0], d, qk + 2 * v), "w_ba": w(keys[1], d, 2 * nv),
                "conv_w": w(keys[2], self.conv_width, qk + v),
                # the decay's rate a head from ln U(1, 16), its bias 1 (the
                # Mamba-2 convention the published model keeps)
                "a_log": jnp.log(jax.random.uniform(keys[3], (nv,), dtype, 1.0, 16.0)),
                "dt_bias": jnp.ones((nv,), dtype),
                "o_norm_w": jnp.ones((self.d_v,), dtype),
                "w_out": w(keys[4], v, self.n_out)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        from deeplearning4j_tpu.ops.gated_delta_rule import (
            gated_delta_rule_scan, heads_a_step)
        from deeplearning4j_tpu.ops.helpers import helper_for
        b, t, _ = x.shape
        nk, nv, dk, dv = self.n_k_heads, self.n_v_heads, self.d_k, self.d_v
        qk_w, v_w = self._widths
        proj = x @ params["w_qkvz"]
        ba = jnp.dot(x, params["w_ba"], preferred_element_type=_F32)
        mixed = jax.nn.silu(_causal_depthwise_conv(proj[..., :qk_w + v_w],
                                                   params["conv_w"]))
        q, k = jnp.split(mixed[..., :qk_w].reshape(b, t, 2 * nk, dk), 2, axis=2)
        v = mixed[..., qk_w:].reshape(b, t, nv, dv)
        z = proj[..., qk_w + v_w:].reshape(b, t, nv, dv)

        def unit(a, scale=1.0):
            a32 = a.astype(_F32)
            norm = lax.rsqrt(jnp.sum(jnp.square(a32), axis=-1, keepdims=True) + 1e-6)
            return (a32 * norm * scale).astype(a.dtype)
        beta = jax.nn.sigmoid(ba[..., :nv])
        if self.beta_scale != 1.0:
            beta = self.beta_scale * beta
        g = -jnp.exp(params["a_log"].astype(_F32)) \
            * jax.nn.softplus(ba[..., nv:] + params["dt_bias"].astype(_F32))
        with jax.named_scope("delta_rule"):
            rule = gated_delta_rule_scan
            if heads_a_step(nk, nv, dk, dv, q.dtype.itemsize) is not None:
                rule = helper_for("gated_delta_rule", rule)
            o = rule(unit(q, dk ** -0.5), unit(k), v, g, beta)
        y = rms_norm(o, params["o_norm_w"], self.eps).astype(_F32) \
            * jax.nn.silu(z.astype(_F32))
        return y.astype(x.dtype).reshape(b, t, nv * dv) @ params["w_out"], state, mask


def _gated(x, w_g, w_u, w_d):
    return (jax.nn.silu(x @ w_g) * (x @ w_u)) @ w_d


@register_layer
@dataclass
class GatedMLP(_TokenLayer):
    """SwiGLU: (silu(x W_g) * x W_u) W_d, no biases."""
    width: int = 0

    def init_params(self, key, input_type, dtype=jnp.float32):
        kg, ku, kd = jax.random.split(key, 3)
        d, f = self.n_in, self.width
        return {"w_g": self._winit(kg, (d, f), d, f, dtype),
                "w_u": self._winit(ku, (d, f), d, f, dtype),
                "w_d": self._winit(kd, (f, self.n_out), f, self.n_out, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return _gated(x, params["w_g"], params["w_u"], params["w_d"]), state, mask


@jax.custom_vjp
def _rows_of_tokens(x, token, place, live):
    """x (N, d) -> (M, d), row p the row `token[p]` of x. (`place`, `live`)
    say the same the other way round, for the gradient: the j-th of token
    n's k assignments sits at row `place[j, n]` of the block where
    `live[j, n]`, and is not in it elsewhere."""
    return x[token]


@jax.custom_vjp
def _sum_to_tokens(rows, token, place, live):
    """rows (M, d) -> (N, d), row n the sum of the block's rows that are
    token n's: `_rows_of_tokens` transposed, as k gathers of N rows through
    the inverse permutation (XLA's scatter-add walks its rows one by one)."""
    wide = jnp.promote_types(rows.dtype, _F32)
    total = sum(jnp.where(live[j][:, None], rows[place[j]], 0).astype(wide)
                for j in range(place.shape[0]))
    return total.astype(rows.dtype)


_rows_of_tokens.defvjp(
    lambda x, *where: (_rows_of_tokens(x, *where), where),
    lambda where, g: (_sum_to_tokens(g, *where), None, None, None))
_sum_to_tokens.defvjp(
    lambda rows, *where: (_sum_to_tokens(rows, *where), where),
    lambda where, g: (_rows_of_tokens(g, *where), None, None, None))


def _experts(e_w_g, e_w_u, e_w_d, rows, sizes):
    """rows sorted by held expert (M, d) -> their experts' answers (M, d)."""
    from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul
    hidden = jax.nn.silu(grouped_matmul(rows, e_w_g, sizes)) \
        * grouped_matmul(rows, e_w_u, sizes)
    return grouped_matmul(hidden, e_w_d, sizes)


def _block(m, lo, u, weights, e_w_g, e_w_u, e_w_d, order, back, sizes):
    """The held experts' part of the assignments at places [lo, lo + m) of
    the sorted order, summed to their tokens (N, d): `m` rows gathered,
    multiplied (each group's rows inside the block; the products leave zeros
    in the rows past them, which add nothing) and scaled."""
    k = back.shape[0]
    first = lax.dynamic_slice_in_dim(order, lo, m)
    place = back - lo
    where = (first // k, jnp.clip(place, 0, m - 1), (place >= 0) & (place < m))
    ends = jnp.cumsum(sizes)
    inside = jnp.clip(ends, lo, lo + m) - jnp.clip(ends - sizes, lo, lo + m)
    out = _experts(e_w_g, e_w_u, e_w_d, _rows_of_tokens(u, *where), inside)
    out = out * weights[first][:, None].astype(out.dtype)
    return _sum_to_tokens(out, *where)


def _blocks_needed(m, sizes):
    return (jnp.sum(sizes) + (m - 1)) // m


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blocks(m, u, weights, e_w_g, e_w_u, e_w_d, order, back, sizes):
    """`_block` over as many blocks of `m` rows as the held assignments
    fill, one after the other (a loop whose length the device decides each
    step; its gradient, below, is the same loop over each block's own)."""
    wide = jnp.promote_types(u.dtype, _F32)

    def one_more(c, y):
        return y + _block(m, c * m, u, weights, e_w_g, e_w_u, e_w_d,
                          order, back, sizes).astype(wide)
    with jax.named_scope("blocks"):
        return lax.fori_loop(0, _blocks_needed(m, sizes), one_more,
                             jnp.zeros(u.shape, wide)).astype(u.dtype)


def _blocks_fwd(m, *operands):
    return _blocks(m, *operands), operands


def _blocks_bwd(m, operands, g):
    towards, places = operands[:5], operands[5:]
    wide = jnp.promote_types(g.dtype, _F32)

    def one_more(c, sums):
        _, pull = jax.vjp(lambda *t: _block(m, c * m, *t, *places), *towards)
        return tuple(s + d.astype(s.dtype) for s, d in zip(sums, pull(g)))
    # the rows' and the router weights' sums wide, the experts' matrices'
    # in their own type (three more float32 copies of them do not fit)
    start = tuple(jnp.zeros(t.shape, wide if i < 2 else t.dtype)
                  for i, t in enumerate(towards))
    with jax.named_scope("blocks"):
        sums = lax.fori_loop(0, _blocks_needed(m, places[2]), one_more, start)
    return tuple(s.astype(t.dtype) for s, t in zip(sums, towards)) \
        + (None,) * len(places)


_blocks.defvjp(_blocks_fwd, _blocks_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _held_experts_part(first_expert, held, bound, e_w_g, e_w_u, e_w_d, u, sel, w):
    """`RoutedExperts._routed`, traced once for all layers of one shape."""
    n, k = sel.shape
    local = sel.reshape(-1) - first_expert                     # (N*k,)
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    back = jnp.argsort(order).reshape(n, k).T                  # (k, N) places
    operands = (u, w.reshape(-1), e_w_g, e_w_u, e_w_d)
    if bound >= n * k:
        return _block(n * k, 0, *operands, order, back, sizes), sizes
    # the blocks tile the order: pad it to whole blocks (with any place: the
    # padding lies past every group)
    order = jnp.pad(order, (0, -(n * k) % bound))
    return _blocks(bound, *operands, order, back, sizes), sizes


@register_layer
@dataclass
class RoutedExperts(_TokenLayer):
    """Dropless routed experts beside shared ones (DeepSeek-V3's `noaux_tc`
    gate): `scoring_func` scores (sigmoid, or a softmax over all of them) over
    the `n_experts` published experts, the `top_k` largest of score + bias
    chosen, their scores normalised and scaled; with `shared_gate` the shared
    expert's part is weighted by sigmoid(u w_s) a token (Qwen's). The
    layer holds experts `[first_expert, first_expert + experts_held)` (all
    where `experts_held` is 0) and adds their part for the tokens routed to
    them: the assignments are sorted by expert and the three products of the
    held experts are one grouped product each over those rows
    (`ops/grouped_matmul.py`), with no capacity and no dropped token.

    A layer that holds a share of the experts moves its rows in blocks of
    `row_bound` (twice the share's even part, in whole tiles: 4096 of 16384
    assignments at 4096 tokens, top 4, 8 of 64 held): a block of the sorted
    order is gathered, multiplied and summed back to its tokens, and the
    device goes on to the next only while held assignments are left, so a
    usual step works on one block and a step whose router crowds the share
    on as many as it needs, up to all N * top_k rows. The rows reach their
    tokens, and the gradient the rows, by gathers through the sort's inverse
    permutation; the gradient is the same loop, each block worked out again.

    The selection bias is a buffer (`state["router_bias"]`, no gradient leaf).
    The state also holds, written on the device by every step, the tokens each
    held expert took (`expert_load`) and the assignments that fell to absent
    experts (`assignments_absent`).

    `train_gate` False takes the chosen experts' weights as constants of the
    backward pass: nothing reaches the router's weights, nor the hidden state
    through the gate. It is for a layer that holds a share and runs without
    its exchange: in the deployment the router is trained by the group's
    summed gradient, and the held experts' part of it, applied alone, walks
    the router towards them or away from them (PERF.md section 7 (b))."""
    n_experts: int = 64
    experts_held: int = 0
    first_expert: int = 0
    top_k: int = 4
    width: int = 1024
    n_shared: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    shared_gate: bool = False
    train_gate: bool = True

    def __post_init__(self):
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r}: sigmoid or "
                             "softmax")

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    def init_params(self, key, input_type, dtype=jnp.float32):
        d, f, e = self.n_in, self.width, self.held
        keys = jax.random.split(key, 8)
        p = {"w_r": self._winit(keys[0], (d, self.n_experts), d, self.n_experts, dtype),
             "e_w_g": self._winit(keys[1], (e, d, f), d, f, dtype),
             "e_w_u": self._winit(keys[2], (e, d, f), d, f, dtype),
             "e_w_d": self._winit(keys[3], (e, f, self.n_out), f, self.n_out, dtype)}
        if self.n_shared:
            fs = f * self.n_shared
            p.update({"s_w_g": self._winit(keys[4], (d, fs), d, fs, dtype),
                      "s_w_u": self._winit(keys[5], (d, fs), d, fs, dtype),
                      "s_w_d": self._winit(keys[6], (fs, self.n_out), fs,
                                           self.n_out, dtype)})
            if self.shared_gate:
                p["s_gate"] = self._winit(keys[7], (d, 1), d, 1, dtype)
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        return {"router_bias": jnp.zeros((self.n_experts,), dtype),
                "expert_load": jnp.zeros((self.held,), jnp.int32),
                "assignments_absent": jnp.zeros((), jnp.int32)}

    def route(self, params, state, u):
        """u (N, d) -> (chosen experts (N, k), their weights (N, k) float32)."""
        logits = jnp.dot(u, params["w_r"], preferred_element_type=_F32)
        s = jax.nn.sigmoid(logits) if self.scoring_func == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, sel = lax.top_k(s + state["router_bias"].astype(_F32), self.top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        if self.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * self.routed_scaling_factor
        return sel, w if self.train_gate else lax.stop_gradient(w)

    def row_bound(self, assignments: int) -> int:
        """Rows a block of the routed part holds when a step has `assignments`
        (tokens x top_k): twice the held experts' even share, in whole tiles
        of the grouped product. At or past `assignments` (every expert held,
        or few tokens) the part is one pass over all of them."""
        from deeplearning4j_tpu.ops.grouped_matmul import TILE_ROWS
        rows = -(-2 * assignments * self.held // self.n_experts)
        return -(-rows // TILE_ROWS) * TILE_ROWS

    def _routed(self, params, u, sel, w):
        """The held experts' part: (N, d), the tokens each took (held,)."""
        return _held_experts_part(
            self.first_expert, self.held, self.row_bound(sel.size),
            params["e_w_g"], params["e_w_u"], params["e_w_d"], u, sel, w)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        b, t, d = x.shape
        u = x.reshape(b * t, d)
        sel, w = self.route(params, state, u)
        with jax.named_scope("routed"):
            y, sizes = self._routed(params, u, sel, w)
        if self.n_shared:
            with jax.named_scope("shared"):
                shared = _gated(u, params["s_w_g"], params["s_w_u"], params["s_w_d"])
                if self.shared_gate:
                    open_ = jax.nn.sigmoid(jnp.dot(u, params["s_gate"],
                                                   preferred_element_type=_F32))
                    shared = (shared * open_).astype(shared.dtype)
                y = y + shared
        new_state = dict(state, expert_load=sizes,
                         assignments_absent=(b * t * self.top_k
                                             - jnp.sum(sizes)).astype(jnp.int32))
        return y.reshape(b, t, -1), new_state, mask

    def state_gauges(self, state) -> dict:
        """What `fit_on_device` publishes of this layer's state after a call
        (host values): how uneven the held experts' load was in the last step
        and the share of all assignments that fell to a held expert; where
        the layer moves its rows in blocks, whether the last step needed one
        alone and the held assignments over a block's rows."""
        load = [float(v) for v in state["expert_load"]]
        held, absent = sum(load), float(state["assignments_absent"])
        if held <= 0:
            return {}
        gauges = {"moe.expert_load.max_over_mean": max(load) / (held / len(load)),
                  "moe.assignments_held_share": held / (held + absent)}
        bound = self.row_bound(int(held + absent))
        if bound < held + absent:
            gauges["moe.routed_rows.bounded"] = float(held <= bound)
            gauges["moe.routed_rows.held_over_bound"] = held / bound
        return gauges


def sinkhorn(logits, iters: int, eps: float):
    """logits (n, n, ...): exp, then `iters` rounds of column then row
    normalisation over the two leading axes: matrices that are nearly doubly
    stochastic, one for each entry of the trailing axes (the tokens, which
    stay the minor axis: the sums are additions of whole slabs, no
    reduction over a 4-wide minor axis)."""
    n = logits.shape[0]

    def one_round(mat, _):
        col = sum(mat[i] for i in range(n))                 # (n, ...): by column
        mat = mat / (col[None] + eps)
        row = sum(mat[:, j] for j in range(n))              # (n, ...): by row
        return mat / (row[:, None] + eps), None

    # a scan, not 2 x iters unrolled divisions a layer: the program stays small
    return lax.scan(one_round, jnp.exp(logits), None, length=iters)[0]


_HC_KEYS = ("hc_phi_pre", "hc_phi_post", "hc_phi_res", "hc_a", "hc_b_pre",
            "hc_b_post", "hc_b_res", "norm_g")


@register_layer
@dataclass
class HyperConnection(BaseLayerConf):
    """Manifold-constrained hyper-connection (mHC, arXiv:2512.24880) around a
    sublayer F: the residual state is `n_streams` streams, X (batch, n,
    time, d). Per token, from x' = RMSNorm(vec(X)): H_pre = sigmoid(a_pre x'
    phi_pre + b_pre) (1 x n), H_post = 2 sigmoid(a_post x' phi_post + b_post)
    (1 x n), H_res = Sinkhorn(clip(a_res mat(x' phi_res) + b_res)) (n x n);
    X_next = H_res X + H_post^T F(RMSNorm(H_pre X)). The maps, Sinkhorn and
    the mixing are worked out in float32 whatever the compute type, with the
    tokens as the minor axis of every map (n and n x n lead) and the mixing
    written out stream by stream: elementwise work that XLA fuses, where a
    (tokens, n, n) layout pads every 4 x 4 matrix to a whole tile. That is the
    plain body. Where the helper seam engages it (a TPU, a shape
    `ops/hyper_connection.token_tile` takes, no mask) the same mathematics
    runs as four Pallas calls that pass over the state once a direction."""
    layer: Optional[BaseLayerConf] = None
    n_streams: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    eps: float = 1e-6

    def __post_init__(self):
        if isinstance(self.layer, dict):
            self.layer = BaseLayerConf.from_dict(self.layer)

    def set_n_in(self, input_type, override=False):
        self.layer.set_n_in(self._inner_type(input_type), override)

    def _inner_type(self, input_type):
        return InputType.recurrent(input_type.size // self.n_streams,
                                   _time_of(input_type))

    def get_output_type(self, input_type):
        return input_type

    def init_params(self, key, input_type, dtype=jnp.float32):
        k_in, k1, k2, k3 = jax.random.split(key, 4)
        n, inner = self.n_streams, self._inner_type(input_type)
        d = inner.size
        p = dict(self.layer.init_params(k_in, inner, dtype))
        w = lambda k, cols: self._winit(k, (n * d, cols), n * d, cols, dtype)
        p.update({"hc_phi_pre": w(k1, n), "hc_phi_post": w(k2, n),
                  "hc_phi_res": w(k3, n * n),
                  "hc_a": jnp.full((3,), _HC_MAP_SCALE_INIT, dtype),
                  "hc_b_pre": jnp.zeros((n,), dtype),
                  "hc_b_post": jnp.zeros((n,), dtype),
                  "hc_b_res": _HC_RES_DIAGONAL_INIT * jnp.eye(n, dtype=dtype),
                  "norm_g": jnp.ones((d,), dtype)})
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        return self.layer.init_state(self._inner_type(input_type), dtype)

    @staticmethod
    def _phi(params):
        """The maps' weights side by side, pre, post, res: (n d, 2n + n n)."""
        return jnp.concatenate([params["hc_phi_pre"], params["hc_phi_post"],
                                params["hc_phi_res"]], axis=1)

    def maps(self, params, x):
        """X (B, n, T, d) -> H_pre (n, B, T), H_post (n, B, T), H_res (n, n, B, T),
        float32."""
        n, d = self.n_streams, x.shape[-1]
        phi = self._phi(params).astype(x.dtype)
        raw = sum(jnp.einsum("btd,dc->cbt", x[:, j], phi[j * d:(j + 1) * d],
                             preferred_element_type=_F32) for j in range(n))
        square = sum(jnp.mean(jnp.square(x[:, j].astype(_F32)), axis=-1)
                     for j in range(n)) / n
        # RMSNorm without a gain is one factor a token: it goes after the product
        raw = raw * lax.rsqrt(square + self.eps)[None]
        a = params["hc_a"].astype(_F32)
        bias = lambda k: params[k].astype(_F32)[..., None, None]
        h_pre = jax.nn.sigmoid(a[0] * raw[:n] + bias("hc_b_pre"))
        h_post = 2.0 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + bias("hc_b_post"))
        res = a[2] * raw[2 * n:].reshape((n, n) + raw.shape[1:]) + bias("hc_b_res")
        res = jnp.clip(res, self.clamp_min, self.clamp_max)
        return h_pre, h_post, sinkhorn(res, self.sinkhorn_iters, self.hc_eps)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        from deeplearning4j_tpu.ops.helpers import helper_for
        from deeplearning4j_tpu.ops.hyper_connection import token_tile
        n = self.n_streams
        inner = {k: v for k, v in params.items() if k not in _HC_KEYS}

        def sublayer(u, mask=mask):
            with layer_scope(self.layer, self.name):
                y, new_state, mask = self.layer.forward(
                    inner, state, u, train=train, rng=rng, mask=mask)
            return y, (new_state, mask)

        # the kernels pass over the state once a direction (ops/
        # hyper_connection.py); what this site can see itself it checks first
        fused = None
        if mask is None and token_tile(n, x.shape[2], x.shape[3],
                                       x.dtype.itemsize) is not None:
            fused = helper_for("hyper_connection", None)
        if fused is not None:
            out, (new_state, mask) = fused(
                x, self._phi(params), params["hc_a"], params["hc_b_pre"], params["hc_b_post"],
                params["hc_b_res"], params["norm_g"], sublayer,
                sinkhorn_iters=self.sinkhorn_iters, hc_eps=self.hc_eps,
                clamp_min=self.clamp_min, clamp_max=self.clamp_max, eps=self.eps)
            return out, new_state, mask
        h_pre, h_post, h_res = self.maps(params, x)
        streams = [x[:, j].astype(_F32) for j in range(n)]
        u = sum(h_pre[j][..., None] * streams[j] for j in range(n))
        u = rms_norm(u, params["norm_g"], self.eps).astype(x.dtype)
        y, (new_state, mask) = sublayer(u)
        y = y.astype(_F32)
        out = [sum(h_res[i, j][..., None] * streams[j] for j in range(n))
               + h_post[i][..., None] * y for i in range(n)]
        return jnp.stack(out, axis=1).astype(x.dtype), new_state, mask

    def state_gauges(self, state) -> dict:
        inner = getattr(self.layer, "state_gauges", None)
        return inner(state) if inner else {}


@register_layer
@dataclass
class PreNormResidual(BaseLayerConf):
    """The plain residual block round a sublayer F, in one of three orders:
    x + F(norm(x)) (the default: the norm on the sublayer's input); with
    `norm_output` x + norm(F(x)) (Olmo 2's: the norm on its output); with
    `sandwich` x + norm'(F(norm(x))) (Ouro's: a norm on both sides, each with
    its own gain, `norm_g` before and `norm_out_g` after; both flags at once
    are refused). Each norm is an RMS norm with its own gain (zero-centred by
    default, as the models that first used the block have it). A wrapping
    layer config as `HyperConnection` is, so that norms, sublayer and add are
    one recomputed block under one scope."""
    layer: Optional[BaseLayerConf] = None
    eps: float = 1e-6
    zero_centred: bool = True
    norm_output: bool = False
    sandwich: bool = False

    def __post_init__(self):
        if isinstance(self.layer, dict):
            self.layer = BaseLayerConf.from_dict(self.layer)
        if self.norm_output and self.sandwich:
            raise ValueError("one order: norm_output or sandwich, not both")

    def set_n_in(self, input_type, override=False):
        self.layer.set_n_in(input_type, override)

    def get_output_type(self, input_type):
        return input_type

    def init_params(self, key, input_type, dtype=jnp.float32):
        p = dict(self.layer.init_params(key, input_type, dtype))
        p["norm_g"] = _gain((input_type.size,), self.zero_centred, dtype)
        if self.sandwich:
            p["norm_out_g"] = _gain((input_type.size,), self.zero_centred, dtype)
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        return self.layer.init_state(input_type, dtype)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        inner = {k: v for k, v in params.items()
                 if k not in ("norm_g", "norm_out_g")}
        norm = lambda a, g="norm_g": rms_norm(a, params[g], self.eps,
                                              self.zero_centred)
        u = x if self.norm_output else norm(x)
        with layer_scope(self.layer, self.name):
            y, new_state, mask = self.layer.forward(
                inner, state, u, train=train, rng=rng, mask=mask)
        if self.norm_output:
            y = norm(y)
        elif self.sandwich:
            y = norm(y, "norm_out_g")
        return x + y.astype(x.dtype), new_state, mask

    def state_gauges(self, state) -> dict:
        inner = getattr(self.layer, "state_gauges", None)
        return inner(state) if inner else {}


@register_layer
@dataclass
class MTPInput(_TokenLayer):
    """The multi-token-prediction module's input (DeepSeek-V3 section 2.2):
    from [Emb[t_{i+1}]; h_i] (batch, time, 2d), the concatenation of the next
    token's embedding and the main model's state,
    h'_i = W [RMSNorm(Emb[t_{i+1}]); RMSNorm(h_i)] (2d -> d)."""
    eps: float = 1e-6

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in // 2

    def init_params(self, key, input_type, dtype=jnp.float32):
        d = self.n_in // 2
        return {"norm_e_g": jnp.ones((d,), dtype), "norm_h_g": jnp.ones((d,), dtype),
                "W": self._winit(key, (self.n_in, self.n_out), self.n_in,
                                 self.n_out, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        d = self.n_in // 2
        both = jnp.concatenate(
            [rms_norm(x[..., :d], params["norm_e_g"], self.eps),
             rms_norm(x[..., d:], params["norm_h_g"], self.eps)], axis=-1)
        return both @ params["W"], state, mask


@register_layer
@dataclass
class TokenCrossEntropyHead(FeedForwardLayerConf):
    """Untied output head with softmax cross-entropy over integer labels
    (batch, time): no one-hot array is ever made. `n_out` is the published
    vocabulary; the head keeps columns `[first_row, first_row + rows_held)`
    of it (all where `rows_held` is 0) and labels, logits and loss are over
    those. `shift` s scores position i against label i + s and leaves the
    last s positions out (the MTP module, which has read label i already,
    predicts the one after). `loss_weight` scales this head's loss in the
    graph's sum."""
    rows_held: int = 0
    first_row: int = 0
    shift: int = 0
    loss_weight: float = 1.0
    integer_labels = True      # the walk hands it the labels as they are

    @property
    def rows(self) -> int:
        return self.rows_held or self.n_out

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.rows, _time_of(input_type))

    def is_output_layer(self) -> bool:
        return True

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {"W": self._winit(key, (self.n_in, self.rows), self.n_in,
                                 self.n_out, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return x @ params["W"], state, mask

    def compute_score(self, params, x, labels, mask=None):
        """Mean over the scored positions of -log softmax(x W)[label]."""
        with layer_scope(self, self.name):
            return self._score(params, x, labels, mask)

    def _score(self, params, x, labels, mask):
        nll, mask = self._nll(params, x, labels, mask)
        return _masked_mean(nll, mask)

    def _nll(self, params, x, labels, mask):
        """(-log softmax(x W)[label] at each scored position, their mask)."""
        labels = labels.astype(jnp.int32) - self.first_row
        if self.shift:
            x, labels = x[:, :-self.shift], labels[:, self.shift:]
            mask = None if mask is None else mask[:, self.shift:]
        logits = jnp.dot(x, params["W"], preferred_element_type=_F32)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, mask


def _masked_mean(a, mask):
    if mask is None:
        return jnp.mean(a)
    m = mask.astype(a.dtype)
    return jnp.sum(a * m) / jnp.maximum(jnp.sum(m), 1.0)


def _count_traced(name: str, what: str, n: int = 1) -> None:
    from deeplearning4j_tpu import telemetry
    telemetry.registry().counter(
        f"loop.{what}_traced.{telemetry.sanitize_component(name or '_')}",
        f"{what} of a looped stack's scan traced (counted at trace time)").inc(n)


@register_layer
@dataclass
class LoopedStack(BaseLayerConf):
    """A stack of blocks run `passes` times with one set of weights (Ouro's
    looped decoder, arXiv:2510.25741): pass t runs every block in order on
    x_{t-1}, then an RMS norm with its own gain (`norm_g`, from 1), h_t =
    norm(...), and h_t is pass t + 1's input. The output is every pass's
    h_t, stacked on a leading axis: (passes, batch, time, features), for a
    head that scores each (`LoopExitHead`). The passes are one `lax.scan`
    whose body closes over the blocks' parameters, so the program holds one
    pass whatever `passes` is, and autodiff sums the shared weights'
    gradients over the passes. A block's leaves are `<its name>.<key>`.

    The graph hands the layer its recomputation policy a block at a time
    (`block`, where it recomputes at all: `recomputes_blocks`): each block
    and the norm are recomputed in the backward pass, their parameters cast
    to the compute type inside, so that only the blocks' inputs of every pass
    live through the step. Counters `loop.passes_traced.<node>` and
    `loop.bodies_traced.<node>` count at trace time."""
    blocks: Optional[list] = None
    passes: int = 4
    eps: float = 1e-6
    recomputes_blocks = True   # the walk leaves the recomputation to `block`

    def __post_init__(self):
        self.blocks = [BaseLayerConf.from_dict(b) if isinstance(b, dict) else b
                       for b in self.blocks or []]
        names = [b.name for b in self.blocks]
        if None in names or len(set(names)) != len(names):
            raise ValueError(f"a looped stack's blocks need names of their own: {names}")

    def set_n_in(self, input_type, override=False):
        for b in self.blocks:
            b.set_n_in(input_type, override)

    def get_output_type(self, input_type):
        return input_type

    def init_params(self, key, input_type, dtype=jnp.float32):
        p = {}
        for b, k in zip(self.blocks, jax.random.split(key, len(self.blocks))):
            p.update({f"{b.name}.{leaf}": v
                      for leaf, v in b.init_params(k, input_type, dtype).items()})
        p["norm_g"] = jnp.ones((input_type.size,), dtype)
        return p

    def leaves_of(self, params, block) -> dict:
        """A block's own leaves, under their own keys."""
        head = block.name + "."
        return {k[len(head):]: v for k, v in params.items() if k.startswith(head)}

    def forward(self, params, state, x, *, train, rng=None, mask=None, block=None):
        wrap = block or (lambda f: f)

        def step(b):
            def apply(p, u):
                with layer_scope(b, b.name):
                    return b.forward(p, {}, u, train=train, mask=mask)[0]
            return wrap(apply), self.leaves_of(params, b)
        steps = [step(b) for b in self.blocks]
        final = wrap(lambda p, u: rms_norm(u, p["norm_g"], self.eps))

        def one_pass(h, _):
            _count_traced(self.name, "bodies")
            for f, p in steps:
                h = f(p, h)
            h = final({"norm_g": params["norm_g"]}, h)
            return h, h
        _, states = lax.scan(one_pass, x, None, length=self.passes)
        _count_traced(self.name, "passes", self.passes)
        return states, state, mask


@register_layer
@dataclass
class LoopExitHead(TokenCrossEntropyHead):
    """The output head of a `LoopedStack` (Ouro's training objective): its
    input is every pass's state h_t (passes, batch, time, features). Each
    pass is scored by the same untied head, CE_t = -log softmax(h_t W)[label]
    (`TokenCrossEntropyHead`'s cross-entropy), and gated, lambda_t =
    sigmoid(h_t w_gate + b_gate) a token. The exit distribution is p_t =
    lambda_t prod_{j<t} (1 - lambda_j) for t < P, p_P = prod_{j<P} (1 -
    lambda_j) (the last pass's gate is not used), worked out in log space;
    the loss is the mean over positions of sum_t p_t CE_t - beta H(p), H(p) =
    -sum_t p_t log p_t, beta = `entropy_weight`. The passes are scored one
    at a time, each recomputed in the backward pass (`lax.map` of a
    checkpointed pass): one pass's float32 logits live at once. Everything
    here is float32. `forward` gives the last pass's logits."""
    entropy_weight: float = 0.05
    recomputes_its_score = True   # each pass is its own recomputed block

    def init_params(self, key, input_type, dtype=jnp.float32):
        k_head, k_gate = jax.random.split(key)
        p = super().init_params(k_head, input_type, dtype)
        p["w_gate"] = self._winit(k_gate, (self.n_in, 1), self.n_in, 1, dtype)
        p["b_gate"] = jnp.zeros((1,), dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return x[-1] @ params["W"], state, mask

    def _score(self, params, x, labels, mask):
        def one_pass(h):
            nll, _ = self._nll(params, h, labels, None)
            z = jnp.dot(h[:, :nll.shape[1]], params["w_gate"],
                        preferred_element_type=_F32)[..., 0]
            return nll, z + params["b_gate"].astype(_F32)[0]
        nll, z = lax.map(jax.checkpoint(one_pass), x)
        exit_p, log_p = exit_distribution(z)
        per_token = jnp.sum(exit_p * (nll + self.entropy_weight * log_p), axis=0)
        if self.shift and mask is not None:
            mask = mask[:, self.shift:]
        return _masked_mean(per_token, mask)


def exit_distribution(z):
    """Gate logits z (passes, ...) -> (p, log p) over the passes, p_t =
    sigmoid(z_t) prod_{j<t} sigmoid(-z_j) for t < P, p_P = prod_{j<P}
    sigmoid(-z_j), in log space (no 0 log 0)."""
    stay = jax.nn.log_sigmoid(-z)
    before = jnp.cumsum(stay, axis=0) - stay
    log_p = jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before[:-1],
                             before[-1:]], axis=0)
    return jnp.exp(log_p), log_p
