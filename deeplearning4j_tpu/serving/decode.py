"""Incremental (KV-cached) decode for SelfAttentionLayer transformer stacks.

Beyond-reference: the attention stack recomputes all T x T scores per token
(O(T^2) per generated token); this module makes generation O(T) per token by
attending a SINGLE query position against the paged cache
(serving/kv_cache.py).

Two pieces:

- `decode_attention_paged`: the masked single-query dot-product against the
  paged cache, GQA-aware without materializing the head repeat (q is
  reshaped to (S, Hk, G, D) and contracted directly against the cache —
  query head h = hk*G + g reads kv head hk, the SAME grouping as
  ops/flash_attention._kv_row and the layer's jnp.repeat fallback). Scores
  and softmax run in fp32 (fp64 under x64), streams stay in the cache dtype
  (bf16 on TPU). Dispatches through the helper seam to the split-K
  flash-decode Pallas kernel (ops/decode_attention.py, default-on for TPU)
  which partitions the cache length axis by block and merges partials via
  logaddexp; the dense gather + einsum path is the fp64 oracle and
  universal fallback.

- `StackDecoder`: a stateful prefill-then-decode wrapper over an already
  initialized MultiLayerNetwork / ComputationGraph whose hidden layers are
  causal SelfAttentionLayers (plus position-wise layers). It re-derives each
  attention layer's q/k/v from the layer's OWN params with the exact math of
  SelfAttentionLayer.forward, so cached decode is position-for-position
  equal to the full-recompute forward oracle (tests/test_serving.py pins
  this in fp64). Both steps are pure functions of (params, cache state,
  activations) with FIXED shapes — the serving engine jits them ONCE and
  never retraces per token (prompt lengths are bucketed to powers of two;
  padded tail writes are harmless, see kv_cache.py's visibility invariant).

The cache is PAGED (ISSUE 7, serving/kv_cache.py): every slot resolves
logical positions through its device block-table row, so the decode step
attends via `decode_attention_paged` (block-table-aware split-K kernel,
ops/decode_attention.py) and prefill scatters whole blocks through the
table. Prompt buckets are rounded up to whole blocks so prefill writes
block-granular; padding past a slot's reservation trash-routes (see
kv_cache.py's trash invariant). Prefix sharing adds a third pure step,
`_prefill_shared_fn`: when admission mapped a request's leading prompt
blocks onto resident shared KV, only the SUFFIX is embedded and computed —
suffix queries attend the slot's full gathered prefix (shared blocks
included), skipping the shared positions' projection and score math
entirely. That is the prefill-FLOPs saving the bench measures.

CHUNKED prefill (ISSUE 9, Sarathi-style) is the same pure function under a
second stateful entry point, `prefill_chunk`: a prompt split into
fixed-budget chunks runs chunk i as a "suffix" whose already-resident
prefix is chunks 0..i-1 — `start` plays shared_len, `end` plays plen, the
chunk's k/v scatter through the block table and its queries attend the
slot's first gathered blocks (earlier chunks included), causal within the
chunk. One jit, one compile cache, for both features.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.enums import Activation
from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.layers.feedforward import (
    ActivationLayer, DropoutLayer, LossLayer)
from deeplearning4j_tpu.nn.conf.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.ops.decode_attention import (
    decode_attention_dense_paged, decode_attention_dense_spec_paged)
from deeplearning4j_tpu.ops.helpers import helper_for
from deeplearning4j_tpu.serving import kv_cache, quant

NEG_INF = -1e30

# Non-attention layers a decode step may apply one position at a time.
# Anything else (LSTM state, normalization statistics over time, pooling)
# is NOT position-wise and must fail loudly rather than decode garbage.
_POSITIONWISE = (RnnOutputLayer, ActivationLayer, DropoutLayer, LossLayer)


def decode_attention_paged(q, kp, vp, block_tables, visible, scale,
                           window: int = 0, k_scale=None, v_scale=None):
    """Single-query attention against the PAGED cache.

    q: (S, H, D) current-position queries; kp/vp: (num_blocks + 1,
    block_size, Hk, D) physical blocks (current position already appended)
    resolved per slot through its (blocks_per_seq,) block-table row;
    visible: (S,) number of visible positions per slot (= position index +
    1); `window` > 0 applies the layer's sliding-window semantics (query at
    position visible-1 sees keys j with (visible-1) - j < window). Returns
    (S, H, D) in q.dtype. Resolved through the helper seam:
    the block-table-aware split-K kernel
    (ops/decode_attention.flash_decode_attention_paged, default-on for
    TPU — the gather stays INSIDE the kernel via scalar prefetch) when
    enabled, else the dense paged oracle (gather + the dense einsum).
    k_scale/v_scale (num_blocks + 1, Hk): per-head-per-block scales of an
    int8 pool — both kernel and oracle dequantize per block, natively."""
    fn = helper_for("decode_attention_paged", decode_attention_dense_paged)
    return fn(q, kp, vp, block_tables, visible, scale, window,
              k_scale=k_scale, v_scale=v_scale)


def decode_attention_spec_paged(q, kp, vp, block_tables, visible, scale,
                                window: int = 0, k_scale=None,
                                v_scale=None):
    """Multi-query (speculative verification) attention against the PAGED
    cache: q (S, Q, H, D) — query i of slot s sits at logical position
    visible[s] - 1 + i and sees j < visible + i. Resolved through the
    helper seam: the multi-query split-K kernel
    (ops/decode_attention.flash_decode_attention_spec_paged, default-on for
    TPU) when enabled, else the dense spec paged oracle, whose per-position
    math is bit-identical to the single-query dense path. k_scale/v_scale:
    same int8-pool contract as `decode_attention_paged`."""
    fn = helper_for("decode_attention_spec_paged",
                    decode_attention_dense_spec_paged)
    return fn(q, kp, vp, block_tables, visible, scale, window,
              k_scale=k_scale, v_scale=v_scale)


def _attn_heads(layer: SelfAttentionLayer, params, xt):
    """(.., n_in) -> q (.., H, Dh), k/v (.., Hk, Dh) with the layer's exact
    projection math (SelfAttentionLayer.forward's `heads`). When the layer
    dict carries `w_*_scale` leaves (weight-only int8, ISSUE 15) the
    projection runs as (x @ w_int8) * scale — static key-presence
    dispatch, resolved at trace time."""
    H = layer.n_heads
    Hk = getattr(layer, "n_kv_heads", 0) or H
    Dh = layer.n_out // H

    def proj(name, h):
        w = params[name]
        sc = params.get(name + "_scale")
        y = xt @ w if sc is None else quant.int8_matmul(xt, w, sc)
        return jnp.reshape(y, xt.shape[:-1] + (h, Dh))

    return (proj("w_q", H), proj("w_k", Hk), proj("w_v", Hk))


def _out_proj(params, out):
    """The attention output projection out @ w_o + b, int8-aware the same
    way as `_attn_heads`."""
    sc = params.get("w_o_scale")
    y = out @ params["w_o"] if sc is None \
        else quant.int8_matmul(out, params["w_o"], sc)
    return y + params["b"]


def quantize_attention_weights(params, layers):
    """Weight-only int8 for every SelfAttentionLayer's q/k/v/o projections
    (per-output-channel scales, serving/quant.py): each weight leaf is
    replaced by its int8 payload plus a `<name>_scale` sibling. The output
    head (RnnOutputLayer W) deliberately stays float — logits are the
    accuracy-critical surface and its matmul is one row per token, not a
    bandwidth bottleneck. Returns a new params list; layer dicts are
    copied, never mutated (the net still owns the float originals)."""
    out = list(params)
    for i, layer in enumerate(layers):
        if not isinstance(layer, SelfAttentionLayer):
            continue
        p = dict(out[i])
        for name in ("w_q", "w_k", "w_v", "w_o"):
            wq, sc = quant.quantize_weight(p[name])
            p[name] = wq
            p[name + "_scale"] = sc
        out[i] = p
    return out


def _dense_causal_attention(layer, q, k, v):
    """Prefill attention: dense causal scores over the padded prompt block
    (B=1). q (T, H, Dh); k/v (T, Hk, Dh). Padded tail keys are masked by
    causality alone for the valid rows, so no key-padding mask is needed
    (see kv_cache.py's visibility invariant)."""
    T, H, Dh = q.shape
    Hk = k.shape[1]
    G = H // Hk
    if G > 1:   # same grouping as the layer's jnp.repeat fallback
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
    acc = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("qhd,khd->hqk", q.astype(acc), k.astype(acc)) \
        / np.sqrt(Dh)
    qi = jnp.arange(T)[:, None]
    kj = jnp.arange(T)[None, :]
    valid = qi >= kj
    if layer.attention_window:
        valid = valid & (qi - kj < layer.attention_window)
    s = jnp.where(valid[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p, v.astype(acc))
    return out.astype(q.dtype)


class StackDecoder:
    """Prefill-then-decode wrapper for a causal SelfAttentionLayer stack.

    Owns the KVCache geometry and the two jitted pure steps; the serving
    engine composes them with token embedding and sampling. `net` may be a
    MultiLayerNetwork or a linear-chain ComputationGraph."""

    def __init__(self, net, max_seqs: int, max_len: int,
                 dtype=None, block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_share: Optional[bool] = None,
                 prefix_registry=None, paged_attention=None,
                 paged_spec_attention=None, kv_quant: Optional[bool] = None,
                 quant_weights: Optional[bool] = None,
                 prefix_radix: Optional[bool] = None):
        layers, params = _extract_stack(net)
        self.layers = layers
        self.dtype = jnp.dtype(dtype) if dtype is not None else net.dtype
        from deeplearning4j_tpu.util.dtypes import cast_floats
        self.params = cast_floats(params, self.dtype) \
            if self.dtype != net.dtype else params
        self.quant_weights = quant.resolve_quant_weights(quant_weights)
        if self.quant_weights:
            self.params = quantize_attention_weights(self.params, layers)

        self.attn_idx = [i for i, l in enumerate(layers)
                         if isinstance(l, SelfAttentionLayer)]
        if not self.attn_idx:
            raise ValueError("StackDecoder needs at least one "
                             "SelfAttentionLayer in the stack")
        shapes = set()
        for i in self.attn_idx:
            l = layers[i]
            if not l.causal:
                raise ValueError(
                    f"layer {i} ({type(l).__name__}) is not causal — "
                    "autoregressive decode needs causal attention")
            Hk = getattr(l, "n_kv_heads", 0) or l.n_heads
            shapes.add((Hk, l.n_out // l.n_heads))
        if len(shapes) != 1:
            raise ValueError(f"attention layers disagree on (n_kv_heads, "
                             f"head_dim): {sorted(shapes)} — the stacked "
                             "cache needs a uniform shape")
        for i, l in enumerate(layers[:-1]):
            if not isinstance(l, (SelfAttentionLayer,) + _POSITIONWISE):
                raise NotImplementedError(
                    f"layer {i} ({type(l).__name__}) has no incremental "
                    "decode path (not position-wise)")
        (self.n_kv_heads, self.head_dim), = shapes
        self.n_in = layers[0].n_in if hasattr(layers[0], "n_in") else None
        self.cache = kv_cache.KVCache(len(self.attn_idx), max_seqs, max_len,
                                      self.n_kv_heads, self.head_dim,
                                      self.dtype, block_size=block_size,
                                      num_blocks=num_blocks,
                                      prefix_share=prefix_share,
                                      prefix_registry=prefix_registry,
                                      kv_quant=kv_quant,
                                      prefix_radix=prefix_radix)
        # Attention seam (ISSUE 10): the sharded engine swaps in a
        # shard_map-wrapped kernel with the same signature as
        # decode_attention_paged; the default is the single-mesh helper.
        self._paged_attention = (paged_attention if paged_attention
                                 is not None else decode_attention_paged)
        self._paged_spec_attention = (
            paged_spec_attention if paged_spec_attention is not None
            else decode_attention_spec_paged)
        self._prefill_jit = jax.jit(self._prefill_fn)
        # kv_blocks is static and POSITIONAL: jit rejects keyword arguments
        # once in_shardings are pinned (serving/sharding.py re-jits this)
        self._prefill_shared_jit = jax.jit(self._prefill_shared_fn,
                                           static_argnums=(6,))
        self._decode_jit = jax.jit(self._decode_fn)
        self._profiled_buckets: set = set()   # prefill cost-registry dedup
        self.metrics = None    # engine installs its child registry here so
        # prefill cost gauges land next to the engine's observe() gauges

    # ------------------------------------------------------------ pure fns
    def _positionwise(self, layer, params, x):
        """Apply a non-attention layer per position: x (..., n_feat) is fed
        as a 1-timestep recurrent activation (B, n_feat, 1)."""
        out, _, _ = layer.forward(params, {}, x[..., None], train=False,
                                  rng=None, mask=None)
        return out[..., 0]

    def _head_logprobs(self, h):
        """Log-probabilities from the final (output) layer given its input
        activations h (S, n_feat): preout -> log_softmax, the numerically
        exact log of the layer's softmax output."""
        out_layer = self.layers[-1]
        p = self.params[-1]
        if isinstance(out_layer, RnnOutputLayer):
            z = h @ p["W"]
            if out_layer.has_bias:
                z = z + p["b"]
        elif hasattr(out_layer, "preout"):
            z = out_layer.preout(p, h)
        else:
            z = self._positionwise(out_layer, p, h)
            if out_layer.activation == Activation.SOFTMAX:
                return jnp.log(jnp.clip(z, 1e-30, None))
            return jax.nn.log_softmax(z, axis=-1)
        if out_layer.activation != Activation.SOFTMAX:
            z = out_layer._act(z)
        return jax.nn.log_softmax(z, axis=-1)

    def _prefill_fn(self, params, cache_state, x, slot, plen):
        """Prompt pass: x (n_in, T_pad) features of ONE request; writes every
        attention layer's k/v block into `slot`, sets lengths[slot] = plen,
        returns (new_cache_state, (vocab,) logprobs at position plen-1).
        Positions >= plen are padding — their k/v writes are harmless and
        their outputs are discarded."""
        xt = jnp.swapaxes(x, 0, 1).astype(self.dtype)       # (T_pad, n_in)
        li = 0
        for i, layer in enumerate(self.layers[:-1]):
            p = params[i]
            if isinstance(layer, SelfAttentionLayer):
                q, k, v = _attn_heads(layer, p, xt)
                cache_state = kv_cache.write_prefill(cache_state, li, slot,
                                                     k, v)
                li += 1
                out = _dense_causal_attention(layer, q, k, v)
                out = out.reshape(xt.shape[0], layer.n_out)
                out = layer._act(_out_proj(p, out))
                xt = out
            else:
                xt = self._positionwise(layer, p, xt)
        cache_state = kv_cache.set_length(cache_state, slot, plen)
        h_last = jax.lax.dynamic_index_in_dim(xt, plen - 1, axis=0,
                                              keepdims=False)
        return cache_state, self._head_logprobs(h_last[None])[0]

    def _prefill_shared_fn(self, params, cache_state, x, slot, plen,
                           shared_len, kv_blocks):
        """Shared-prefix prompt pass: x (n_in, Ts_pad) features of the
        SUFFIX only (logical positions [shared_len, plen)) — the prefix KV
        is already resident in blocks admission mapped shared. Scatters the
        suffix k/v through the block table, then attends each suffix query
        against the slot's first `kv_blocks` blocks gathered back through
        the table (shared prefix included). Padding rows (position >= plen)
        trash-route their writes and their outputs are discarded.
        `kv_blocks` is static (engine-bucketed) so the gathered length is
        ~plen, not max_len — the compute skipped for the shared positions
        is the whole point.

        Chunked prefill (ISSUE 9) reuses this pass verbatim with the chunk
        START in the shared_len seat and the chunk END in the plen seat:
        chunk i's queries attend the slot's earlier chunks through the same
        block-table gather, causal within the chunk, and set_length(end)
        makes the chunk visible to subsequent decode/chunk iterations."""
        xt = jnp.swapaxes(x, 0, 1).astype(self.dtype)       # (Ts_pad, n_in)
        Ts = xt.shape[0]
        bs = self.cache.block_size
        qpos = jnp.asarray(shared_len, jnp.int32) + jnp.arange(Ts,
                                                               dtype=jnp.int32)
        valid = qpos < plen
        L = kv_blocks * bs
        j = jnp.arange(L, dtype=jnp.int32)[None, :]          # (1, L)
        li = 0
        for i, layer in enumerate(self.layers[:-1]):
            p = params[i]
            if isinstance(layer, SelfAttentionLayer):
                q, k, v = _attn_heads(layer, p, xt)
                cache_state = kv_cache.write_positions(
                    cache_state, li, slot, qpos, valid, k, v)
                row = cache_state["block_tables"][
                    jnp.asarray(slot, jnp.int32)][:kv_blocks]
                kb = cache_state["k"][li, row]       # (kvb, bs, Hk, D)
                vb = cache_state["v"][li, row]
                if kv_cache.is_quantized(cache_state):
                    # dequantize per GATHERED block (slot view, never the
                    # pool) — same reference math as the paged oracle
                    kb = quant.kv_dequantize(
                        kb, cache_state["k_scale"][li, row])
                    vb = quant.kv_dequantize(
                        vb, cache_state["v_scale"][li, row])
                kl = kb.reshape(L, self.n_kv_heads, self.head_dim)
                vl = vb.reshape(L, self.n_kv_heads, self.head_dim)
                li += 1
                H, Dh = layer.n_heads, self.head_dim
                G = H // self.n_kv_heads
                acc = jnp.promote_types(q.dtype, jnp.float32)
                q4 = q.reshape(Ts, self.n_kv_heads, G, Dh)
                s = jnp.einsum("thgd,lhd->thgl", q4.astype(acc),
                               kl.astype(acc)) / np.sqrt(Dh)
                causal = j <= qpos[:, None]                  # (Ts, L)
                if layer.attention_window:
                    causal = causal & (qpos[:, None] - j
                                       < layer.attention_window)
                s = jnp.where(causal[:, None, None, :], s, NEG_INF)
                pattn = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("thgl,lhd->thgd", pattn, vl.astype(acc))
                out = out.reshape(Ts, layer.n_out).astype(self.dtype)
                xt = layer._act(_out_proj(p, out))
            else:
                xt = self._positionwise(layer, p, xt)
        cache_state = kv_cache.set_length(cache_state, slot, plen)
        h_last = jax.lax.dynamic_index_in_dim(
            xt, plen - 1 - shared_len, axis=0, keepdims=False)
        return cache_state, self._head_logprobs(h_last[None])[0]

    def _decode_fn(self, params, cache_state, x, active):
        """One decode iteration for ALL slots: x (S, n_in) current-token
        features, active (S,) bool. Appends each attention layer's k/v at
        the slot's current position, attends the single query against the
        cache, advances lengths on active slots, returns
        (new_cache_state, (S, vocab) logprobs)."""
        h = x.astype(self.dtype)                            # (S, n_in)
        pos = cache_state["lengths"]                        # pre-advance
        li = 0
        for i, layer in enumerate(self.layers[:-1]):
            p = params[i]
            if isinstance(layer, SelfAttentionLayer):
                q, k_t, v_t = _attn_heads(layer, p, h)      # (S, H/Hk, Dh)
                cache_state = kv_cache.append_token(cache_state, li, k_t,
                                                    v_t, active)
                qkw = {} if not kv_cache.is_quantized(cache_state) else {
                    "k_scale": cache_state["k_scale"][li],
                    "v_scale": cache_state["v_scale"][li]}
                out = self._paged_attention(
                    q, cache_state["k"][li], cache_state["v"][li],
                    cache_state["block_tables"],
                    pos + 1, 1.0 / np.sqrt(self.head_dim),
                    layer.attention_window, **qkw)
                li += 1
                out = out.reshape(h.shape[0], layer.n_out)
                h = layer._act(_out_proj(p, out))
            else:
                h = self._positionwise(layer, p, h)
        cache_state = kv_cache.advance_lengths(cache_state, active)
        return cache_state, self._head_logprobs(h)

    def _spec_decode_fn(self, params, cache_state, x, active, draft_len):
        """One SPECULATIVE decode iteration (ISSUE 11) for all slots:
        x (S, Q, n_in) features of [last committed token, draft 0, ...,
        draft Q-2], active (S,) bool, draft_len (S,) int32 in [0, Q-1].
        Row i's k/v land at logical position lengths + i (trash-routed for
        inactive slots and rows past the slot's draft length — a short
        draft's padding can never dirty live blocks), and all Q queries are
        verified against the paged cache in ONE multi-query attention
        dispatch per layer. Returns (new_cache_state, (S, Q, vocab)
        logprobs); row i is the target distribution for the token AFTER
        position lengths + i - 1. Does NOT move `lengths` — the engine
        commits the accepted count afterwards (set-length semantics), which
        is the whole rollback story: rejected rows simply stay invisible.
        draft_len == 0 everywhere degenerates to `_decode_fn` semantics
        with Q - 1 dead verify lanes."""
        S, Q = x.shape[0], x.shape[1]
        h = x.astype(self.dtype)                            # (S, Q, n_in)
        pos = cache_state["lengths"]                        # pre-commit
        i = jnp.arange(Q, dtype=jnp.int32)[None, :]
        positions = pos[:, None] + i                        # (S, Q)
        valid = active[:, None] & (i <= draft_len[:, None])
        li = 0
        for idx, layer in enumerate(self.layers[:-1]):
            p = params[idx]
            if isinstance(layer, SelfAttentionLayer):
                q, k_t, v_t = _attn_heads(layer, p, h)      # (S, Q, ., Dh)
                cache_state = kv_cache.append_tokens(
                    cache_state, li, k_t, v_t, positions, valid)
                qkw = {} if not kv_cache.is_quantized(cache_state) else {
                    "k_scale": cache_state["k_scale"][li],
                    "v_scale": cache_state["v_scale"][li]}
                out = self._paged_spec_attention(
                    q, cache_state["k"][li], cache_state["v"][li],
                    cache_state["block_tables"],
                    pos + 1, 1.0 / np.sqrt(self.head_dim),
                    layer.attention_window, **qkw)
                li += 1
                out = out.reshape(S, Q, layer.n_out)
                h = layer._act(_out_proj(p, out))
            else:
                h = self._positionwise(
                    layer, p, h.reshape(S * Q, -1)).reshape(S, Q, -1)
        lp = self._head_logprobs(h.reshape(S * Q, -1))
        return cache_state, lp.reshape(S, Q, -1)

    # ------------------------------------------------------- stateful API
    def prefill(self, slot: int, x) -> jnp.ndarray:
        """Write a prompt into `slot`; returns the (vocab,) logprobs of the
        next-token distribution. x: (n_in, T) features. T is padded up to
        the next power of two so ragged prompts hit a bounded set of
        compiled shapes (ParallelInference._run's bucketing)."""
        x = jnp.asarray(x, self.dtype)
        T = x.shape[1]
        if T < 1 or T >= self.cache.max_len:
            raise ValueError(f"prompt length {T} outside [1, max_len)")
        Tp = self.prefill_bucket(T)
        if Tp != T:
            x = jnp.pad(x, ((0, 0), (0, Tp - T)))
        slot_a = jnp.asarray(slot, jnp.int32)
        plen_a = jnp.asarray(T, jnp.int32)
        # profiler cost registry (ISSUE 6): file this bucket's XLA
        # cost_analysis once per compiled shape when profiling is on — AOT
        # lower/compile, nothing executes, no buffer donated
        from deeplearning4j_tpu.telemetry import profiler
        if profiler.enabled() and Tp not in self._profiled_buckets:
            self._profiled_buckets.add(Tp)
            try:
                profiler.register(f"prefill_b{Tp}", self._prefill_jit,
                                  (self.params, self.cache.state, x,
                                   slot_a, plen_a),
                                  meta={"bucket": Tp},
                                  registry=self.metrics)
            except Exception:
                pass
        self.cache.state, logprobs = self._prefill_jit(
            self.params, self.cache.state, x, slot_a, plen_a)
        return logprobs

    def prefill_bucket(self, plen: int) -> int:
        """Padded prompt length for an unshared prefill: next power of two,
        rounded UP to KV-block granularity (paged prefill scatters WHOLE
        blocks; writes past the slot's reservation trash-route), capped at
        max_len. The engine uses this as the compile-miss key — it must
        match the shape `prefill` actually compiles."""
        Tp = min(self.cache.max_len, 1 << max(0, (plen - 1)).bit_length())
        bs = self.cache.block_size
        return min(self.cache.max_len, -(-Tp // bs) * bs)

    def shared_buckets(self, plen: int, shared_len: int):
        """(suffix bucket Ts_pad, static gathered-block count) for a
        shared-prefix prefill — the engine uses this pair as the compile
        key for jit-compile-miss attribution. Both dimensions bucket to
        powers of two (capped at max_len / blocks_per_seq) so ragged
        suffixes hit a bounded set of compiled shapes."""
        Ts = plen - shared_len
        Tsp = min(self.cache.max_len, 1 << max(0, (Ts - 1)).bit_length())
        nb = -(-plen // self.cache.block_size)       # blocks holding prompt
        kvb = min(self.cache.blocks_per_seq,
                  1 << max(0, (nb - 1)).bit_length())
        return Tsp, kvb

    def prefill_shared(self, slot: int, x, plen: int,
                       shared_len: int) -> jnp.ndarray:
        """Write a prompt whose first `shared_len` positions are already
        resident (admission mapped them shared); x: (n_in, Ts) features of
        the SUFFIX tokens only, Ts = plen - shared_len. Returns the
        (vocab,) next-token logprobs — identical to what prefill() would
        return for the full prompt, minus the shared positions' compute."""
        x = jnp.asarray(x, self.dtype)
        Ts = x.shape[1]
        if Ts != plen - shared_len or Ts < 1 or shared_len < 1:
            raise ValueError(f"bad shared prefill: plen={plen}, "
                             f"shared_len={shared_len}, suffix={Ts}")
        Tsp, kvb = self.shared_buckets(plen, shared_len)
        if Tsp != Ts:
            x = jnp.pad(x, ((0, 0), (0, Tsp - Ts)))
        slot_a = jnp.asarray(slot, jnp.int32)
        plen_a = jnp.asarray(plen, jnp.int32)
        shared_a = jnp.asarray(shared_len, jnp.int32)
        from deeplearning4j_tpu.telemetry import profiler
        key = ("shared", Tsp, kvb)
        if profiler.enabled() and key not in self._profiled_buckets:
            self._profiled_buckets.add(key)
            try:
                profiler.register(
                    f"prefill_shared_b{Tsp}k{kvb}", self._prefill_shared_jit,
                    (self.params, self.cache.state, x, slot_a, plen_a,
                     shared_a, kvb),
                    meta={"bucket": Tsp, "kv_blocks": kvb},
                    registry=self.metrics)
            except Exception:
                pass
        self.cache.state, logprobs = self._prefill_shared_jit(
            self.params, self.cache.state, x, slot_a, plen_a, shared_a, kvb)
        return logprobs

    def prefill_chunk(self, slot: int, x, start: int,
                      end: int) -> jnp.ndarray:
        """One chunk of an incremental prefill: x (n_in, Tc) features of
        prompt positions [start, end), Tc = end - start. Writes the chunk's
        k/v through the block table, attends each chunk query against the
        slot's earlier resident positions (prior chunks and any shared
        prefix) plus the causal part of the chunk itself, and advances
        lengths[slot] to `end`. Returns the (vocab,) logprobs at position
        end-1 — meaningful only on the final chunk (end == plen), where it
        equals what a monolithic prefill() would have returned.

        This is `_prefill_shared_fn` with (start, end) in the
        (shared_len, plen) seats — same jit, same compile cache as
        prefix-shared prefill."""
        x = jnp.asarray(x, self.dtype)
        Tc = x.shape[1]
        if Tc != end - start or Tc < 1 or start < 0 \
                or end > self.cache.max_len:
            raise ValueError(f"bad prefill chunk: start={start}, "
                             f"end={end}, chunk={Tc}")
        Tsp, kvb = self.shared_buckets(end, start)
        if Tsp != Tc:
            x = jnp.pad(x, ((0, 0), (0, Tsp - Tc)))
        slot_a = jnp.asarray(slot, jnp.int32)
        end_a = jnp.asarray(end, jnp.int32)
        start_a = jnp.asarray(start, jnp.int32)
        from deeplearning4j_tpu.telemetry import profiler
        key = ("shared", Tsp, kvb)                  # same compiled shape
        if profiler.enabled() and key not in self._profiled_buckets:
            self._profiled_buckets.add(key)
            try:
                profiler.register(
                    f"prefill_shared_b{Tsp}k{kvb}", self._prefill_shared_jit,
                    (self.params, self.cache.state, x, slot_a, end_a,
                     start_a, kvb),
                    meta={"bucket": Tsp, "kv_blocks": kvb},
                    registry=self.metrics)
            except Exception:
                pass
        self.cache.state, logprobs = self._prefill_shared_jit(
            self.params, self.cache.state, x, slot_a, end_a, start_a, kvb)
        return logprobs

    def decode_step(self, x, active) -> jnp.ndarray:
        """One cached decode iteration over all slots; returns (S, vocab)
        logprobs. Advances lengths on active slots."""
        self.cache.state, logprobs = self._decode_jit(
            self.params, self.cache.state, jnp.asarray(x, self.dtype),
            jnp.asarray(active, bool))
        return logprobs


def _extract_stack(net) -> Tuple[List, List]:
    """(layers, params_tree) for a MultiLayerNetwork or a linear-chain
    ComputationGraph. Anything with branching/merging or preprocessors has
    no incremental path yet — fail loudly."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    if isinstance(net, MultiLayerNetwork):
        if getattr(net.conf, "preprocessors", None):
            raise NotImplementedError(
                "StackDecoder does not support input preprocessors")
        if not net._initialized:
            raise RuntimeError("Call net.init() before building a decoder")
        return net.layers, net.params_tree
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
    if isinstance(net, ComputationGraph):
        if not net._initialized:
            raise RuntimeError("Call net.init() before building a decoder")
        conf = net.conf
        order = [n for n in conf.topo_order]
        for name in order:
            node = conf.nodes[name]
            if node.kind != "layer":
                raise NotImplementedError(
                    f"graph vertex {name!r} is not a layer — only linear "
                    "layer chains decode incrementally")
            if len(node.inputs) != 1 or node.preprocessor is not None:
                raise NotImplementedError(
                    f"graph node {name!r} is not a single-input chain link")
        # topo order == layer_names order for a chain; params align with it
        return net.layers, net.params_tree
    raise TypeError(f"unsupported model type {type(net).__name__}")


def one_hot_embedder(n_in: int, dtype=jnp.float32) -> Callable:
    """Default token->features map: one-hot into the stack's n_in (the
    framework's char-RNN convention). Jit-safe."""
    def embed(tokens):
        return jax.nn.one_hot(tokens, n_in, dtype=dtype)
    return embed
