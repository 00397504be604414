"""Multi-head self-attention over recurrent streams.

Beyond-reference (the 2017 reference predates attention entirely — SURVEY §5
long-context: "no attention layer at all"); this is the long-context primitive
the TPU framework adds: a layer over the framework's recurrent activation
layout (batch, size, time) that composes with configs, masking, serialization,
and ShardedTrainer.

Long sequences never materialize the (B, H, T, T) score tensor: past
`block_size` timesteps the layer computes attention through the online-softmax
block recurrence (`blockwise_attention`, lax.scan over k/v blocks — peak
activation memory O(T * block), flash-attention's recurrence on one device).
Context parallelism comes in two forms:

- GSPMD: ShardedTrainer.Builder().sequence_axis("seq") shards the TIME
  dimension of recurrent inputs over a mesh axis; the attention einsums then
  partition across chips with XLA inserting the collectives (correct for
  causal + masked attention — softmax normalizers reduce over the sharded
  axis).
- hand-scheduled ring: ShardedTrainer.Builder().sequence_axis("seq")
  .ring_attention(True) routes this layer through
  parallel/sequence_parallel.py's ring_attention — k/v (+ key-mask) blocks
  rotate via ppermute with the same online-softmax accumulator, so per-chip
  memory is O((T/n_chips) * block) and communication is nearest-neighbor ICI.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    FeedForwardLayerConf, register_layer)
from deeplearning4j_tpu.parallel.sequence_parallel import (
    NEG_INF as _NEG_INF, blockwise_attention, current_attention_context,
    ring_attention)


@register_layer
@dataclass
class SelfAttentionLayer(FeedForwardLayerConf):
    """(batch, n_in, time) -> (batch, n_out, time); n_out % n_heads == 0.
    Pre-softmax masking drops padded timesteps (the framework's (batch, time)
    feature masks); `causal` gives autoregressive attention. `block_size`:
    sequences longer than this use the O(T * block) online-softmax path
    (0 disables blockwise and forces the dense score tensor)."""
    n_heads: int = 4
    causal: bool = False
    block_size: int = 128
    # sliding-window (local) attention: > 0 limits each query to the
    # trailing `attention_window` keys (causal) or the symmetric band
    # (non-causal) — flash_attention semantics; cost scales with T*window
    attention_window: int = 0
    # grouped-query attention: 0 -> n_heads (plain MHA); otherwise k/v are
    # projected to n_kv_heads heads and query head h reads kv head
    # h // (n_heads // n_kv_heads) — the same grouping as
    # ops/flash_attention._kv_row. Shrinks the k/v params and, above all,
    # the serving KV cache (serving/kv_cache.py) by the group factor; the
    # training forward broadcasts k/v back to n_heads, so every attention
    # path (dense/blockwise/ring/flash) and its backward stay unchanged
    n_kv_heads: int = 0

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out,
                                   getattr(input_type, "timeseries_length", -1))

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def init_params(self, key, input_type, dtype=jnp.float32):
        if self.n_out % self.n_heads != 0:
            raise ValueError(f"n_out {self.n_out} % n_heads {self.n_heads} != 0")
        if self.n_heads % self.kv_heads != 0:
            raise ValueError(f"n_heads {self.n_heads} % n_kv_heads "
                             f"{self.kv_heads} != 0")
        kq, kk, kv, ko = jax.random.split(key, 4)
        kv_out = self.kv_heads * (self.n_out // self.n_heads)
        w = lambda k, o: self._winit(k, (self.n_in, o), self.n_in, o, dtype)
        return {"w_q": w(kq, self.n_out), "w_k": w(kk, kv_out),
                "w_v": w(kv, kv_out),
                "w_o": self._winit(ko, (self.n_out, self.n_out), self.n_out,
                                   self.n_out, dtype),
                "b": jnp.full((self.n_out,), self.bias_init, dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        if x.ndim != 3:
            raise ValueError("SelfAttentionLayer expects (batch, size, time)")
        B, _, T = x.shape
        H = self.n_heads
        Dh = self.n_out // H
        xt = jnp.swapaxes(x, 1, 2)                       # (B, T, n_in)

        Hk = self.kv_heads

        def heads(w, h=H):
            return jnp.reshape(xt @ w, (B, T, h, Dh)).transpose(0, 2, 1, 3)

        q = heads(params["w_q"])
        k, v = heads(params["w_k"], Hk), heads(params["w_v"], Hk)
        if Hk != H:   # broadcast kv groups to full heads (see n_kv_heads doc)
            k = jnp.repeat(k, H // Hk, axis=1)
            v = jnp.repeat(v, H // Hk, axis=1)
        ctx = current_attention_context()
        seq_sharded = (ctx.mesh is not None and ctx.seq_axis is not None
                       and ctx.seq_axis in ctx.mesh.axis_names
                       and ctx.mesh.shape[ctx.seq_axis] > 1)
        ring = seq_sharded and ctx.use_ring
        if ring and T % ctx.mesh.shape[ctx.seq_axis] != 0:
            # fall through to a single-device path, but say so: the user asked
            # for ring CP and would otherwise discover the fallback as an OOM
            import warnings
            warnings.warn(
                f"ring attention disabled: T={T} not divisible by mesh axis "
                f"{ctx.seq_axis!r} ({ctx.mesh.shape[ctx.seq_axis]}); "
                f"falling back to the unsharded attention path")
            ring = False
        if ring:
            out = ring_attention(q, k, v, ctx.mesh, ctx.seq_axis,
                                 causal=self.causal, mask=mask,
                                 batch_axis=ctx.data_axis,
                                 window=self.attention_window)
        elif self.block_size and T > self.block_size and not seq_sharded:
            # single-device long-context path. Preferred impl: the fused
            # flash-attention Pallas kernel (ops/flash_attention.py,
            # default-on for TPU) — the whole online-softmax recurrence in
            # one kernel with an fp32-exact custom VJP that recomputes p
            # per tile. Fallback: the lax.scan blockwise recurrence (same
            # math, XLA-scheduled). Both skipped under GSPMD context
            # parallelism: there the DENSE einsums are what XLA partitions
            # over the seq axis — a lax.scan over reshaped k/v blocks
            # would force cross-shard gathers instead
            from deeplearning4j_tpu.ops.helpers import helper_for
            flash = helper_for("flash_attention", None)
            if flash is not None:
                # the kernel picks its own MXU-sized tiles; the layer's
                # block_size only governs the fallback scan granularity
                out = flash(q, k, v, mask, self.causal, None,
                            0, 0, self.attention_window)
            else:
                out = blockwise_attention(q, k, v, self.block_size,
                                          causal=self.causal, mask=mask,
                                          window=self.attention_window)
        else:
            # dense path: small T, or GSPMD CP (ctx.seq_axis sharding — the
            # einsums partition across chips with XLA inserting collectives)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(Dh)
            if self.causal:
                scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores,
                                   _NEG_INF)
            if self.attention_window:
                qi = jnp.arange(T)[:, None]
                kj = jnp.arange(T)[None, :]
                wm = qi - kj < self.attention_window
                if not self.causal:
                    wm = wm & (kj - qi < self.attention_window)
                scores = jnp.where(wm[None, None], scores, _NEG_INF)
            if mask is not None:  # (B, T) padding mask: padded keys drop
                scores = jnp.where(mask[:, None, None, :] > 0, scores,
                                   _NEG_INF)
            attn = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bhkv->bhqv", attn, v)  # (B, H, T, Dh)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, self.n_out)
        out = out @ params["w_o"] + params["b"]
        out = self._act(out)
        if mask is not None:  # zero padded query positions like RnnOutputLayer
            out = out * mask[:, :, None].astype(out.dtype)
        return jnp.swapaxes(out, 1, 2), state, mask
