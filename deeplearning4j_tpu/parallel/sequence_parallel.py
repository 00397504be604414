"""Sequence/context parallelism: ring attention over the device mesh.

The reference (0.9.1-era) has no attention ops — its long-context story is tBPTT
segmentation (implemented in nn/multilayer.py). This module is the framework's
forward-looking long-context primitive, required for parity-of-scale: attention
over sequences longer than one chip's HBM, sharded over a 'seq' mesh axis.

Design (the scaling-book / Ring Attention recipe, arXiv:2310.01889):
- q, k, v are sharded over the sequence axis: each device holds its q block
  permanently, and k/v blocks ROTATE around the ring via `lax.ppermute` (ICI
  neighbor exchange, bandwidth-optimal, overlapping compute with transfer).
- Each step computes blockwise attention against the resident k/v block and
  folds it into an online-softmax accumulator (running max + normalizer), so
  the full S x S score matrix never materializes — flash-attention's recurrence
  across devices.
- Causal masking is handled per block pair from the ring offset (a blk x blk
  mask built from global row/col ids each round); unmasked non-causal rounds
  skip elementwise masking (and the key-mask rotation) entirely.

`ring_attention` is the shard_map collective form; `attention_reference` is the
single-device oracle used by tests and small models.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from deeplearning4j_tpu.parallel.mesh import compat_shard_map
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def attention_reference(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Plain softmax attention oracle. q/k/v: (batch, heads, seq, dim)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = q.shape[2]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, NEG_INF)
    return jnp.einsum("bhqk,bhkv->bhqv", jax.nn.softmax(scores, axis=-1), v)


def _block_attn(q, k, v, scale, mask=None):
    """One q-block x k-block contribution: returns (unnormalized out, row max,
    row normalizer) for online-softmax accumulation. Score math runs fp32
    (flash-attention convention) with bf16 MXU inputs — matmuls accumulate one
    width up via preferred_element_type, exp/sum stay fp32 throughout."""
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=acc_dt) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                      # (b,h,q) fp32
    p = jnp.exp(scores - m[..., None])
    if mask is not None:  # rows with no visible keys: exp(NEG_INF - NEG_INF)=1 junk
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                           # (b,h,q) fp32
    o = jnp.einsum("bhqk,bhkv->bhqv", p.astype(q.dtype), v,
                   preferred_element_type=acc_dt)
    return o, m, l


def _merge(acc, o, m, l):
    """Fold a block contribution into the online-softmax accumulator."""
    acc_o, acc_m, acc_l = acc
    new_m = jnp.maximum(acc_m, m)
    a = jnp.exp(acc_m - new_m)[..., None]
    b = jnp.exp(m - new_m)[..., None]
    return (acc_o * a + o * b,
            new_m,
            acc_l * a[..., 0] + l * b[..., 0])


def blockwise_attention(q, k, v, block_size: int, causal: bool = False,
                        mask=None, scale: Optional[float] = None,
                        window: int = 0):
    """Single-device flash-attention recurrence: scan k/v in blocks of
    `block_size` with the online-softmax accumulator, so peak activation
    memory is O(T * block) instead of the dense O(T^2) score tensor
    (arXiv:2205.14135 recurrence; autodiff-friendly — jax.grad differentiates
    straight through the scan).

    q/k/v: (batch, heads, T, dim); mask: optional (batch, T) key-padding mask
    (padded keys drop from every softmax). T is padded internally up to a
    block multiple; padding keys are masked, queries stay unpadded.
    `window` > 0 = sliding-window attention (same semantics as
    ops/flash_attention.py: causal keeps the trailing window, non-causal
    the symmetric band)."""
    B, H, T, D = q.shape
    scale_ = scale if scale is not None else 1.0 / np.sqrt(D)
    scale_ = jnp.asarray(scale_, q.dtype)  # no accidental x64 promotion
    blk = max(1, min(int(block_size), T))
    nb = -(-T // blk)
    pad = nb * blk - T
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    km = jnp.ones((B, T), bool) if mask is None else (mask > 0)
    km = jnp.pad(km, ((0, 0), (0, pad)))                      # (B, Tp)
    kb = jnp.moveaxis(kp.reshape(B, H, nb, blk, D), 2, 0)     # (nb,B,H,blk,D)
    vb = jnp.moveaxis(vp.reshape(B, H, nb, blk, D), 2, 0)
    kmb = jnp.moveaxis(km.reshape(B, nb, blk), 1, 0)          # (nb,B,blk)
    ki = jnp.arange(nb * blk).reshape(nb, blk)
    qi = jnp.arange(T)

    # flash-attention convention: the online-softmax accumulators stay fp32
    # even for bf16 activations — repeated rescaling of a bf16 accumulator
    # across nb blocks degrades vs the dense softmax it replaces
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)

    @jax.checkpoint
    def step(acc, inp):
        # rematerialized: without checkpoint, jax.grad through the scan
        # saves each block's (B, H, T, blk) scores/mask residuals — O(T^2)
        # training memory, exactly what blockwise attention exists to avoid
        # (measured: T=8192 b4 d256 OOM'd at 24.6 GB on a 16 GB chip; with
        # remat it trains). Flash-attention recomputes per block; so do we.
        kb_, vb_, kmb_, ki_ = inp
        m = kmb_[:, None, None, :]  # (B,1,1,blk), broadcasts in _block_attn
        if causal:
            m = m & (qi[:, None] >= ki_[None, :])[None, None]
        if window:
            wm = (qi[:, None] - ki_[None, :] < window)
            if not causal:
                wm = wm & (ki_[None, :] - qi[:, None] < window)
            m = m & wm[None, None]
        o, mx, l = _block_attn(q, kb_, vb_, scale_, m)  # fp32 already
        return _merge(acc, o, mx, l), None

    acc0 = (jnp.zeros(q.shape, acc_dt),
            jnp.full((B, H, T), NEG_INF, acc_dt),
            jnp.zeros((B, H, T), acc_dt))
    (o, _, l), _ = lax.scan(step, acc0, (kb, vb, kmb, ki))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                   causal: bool = False, scale: Optional[float] = None,
                   mask=None, batch_axis: Optional[str] = None,
                   use_flash: Optional[bool] = None,
                   flash_bq: int = 512, flash_bk: int = 512,
                   window: int = 0):
    """Attention with q/k/v sequence-sharded over `axis`; k/v ride the ring.

    q/k/v: (batch, heads, seq, dim) GLOBAL arrays (sharded or to-be-sharded on
    the seq axis). `mask`: optional (batch, seq) key-padding mask; its blocks
    rotate with k/v. `batch_axis`: name of the mesh axis the batch dim is
    data-sharded over (so the shard_map composes with dp instead of gathering
    the batch). Returns output with q's sharding. Communication is N-1
    `ppermute` neighbor hops over ICI, compute overlaps transfers under XLA's
    async collectives.

    `use_flash` (None = the helper seam's policy, default-on for TPU): each
    ring round's local block runs through the fused flash-attention kernel
    (ops/flash_attention.py) returning (out, logsumexp), and rounds merge
    via logaddexp — the per-chip compute rides the MXU-fused kernel while
    ppermute still provides the ICI ring. Under causal masking the round
    where the visiting k/v block is the device's OWN block is flash-causal,
    earlier blocks are fully visible, future blocks contribute nothing.

    `window` > 0 = sliding-window attention (flash_attention semantics).
    Windowed rings use the classic masked round body — the kernel's window
    is a static (trace-time) parameter and cannot express the TRACED ring
    offset between a q block and its visiting k/v block — and SKIP rounds
    whose visiting block lies fully outside the window (for window <= blk
    that is all but 1-2 neighbors: the ring degrades gracefully into
    neighbor-exchange local attention).
    """
    d = q.shape[-1]
    scale_ = jnp.asarray(scale if scale is not None else 1.0 / np.sqrt(d),
                         q.dtype)
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)  # fp32 accumulators
    n_dev = mesh.shape[axis]
    seq = q.shape[2]
    assert seq % n_dev == 0, f"seq {seq} not divisible by mesh axis {n_dev}"
    blk = seq // n_dev
    has_mask = mask is not None
    if window:
        use_flash = False  # see docstring: the ring offset is traced
    elif use_flash is None:
        from deeplearning4j_tpu.ops.helpers import helper_for
        use_flash = helper_for("flash_attention", None) is not None

    def _rotate(kb, vb, mb):
        """One neighbor hop of the visiting k/v (+ key-mask) blocks —
        shared by both ring implementations."""
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        if mb is not None:
            mb = lax.ppermute(mb, axis, perm)
        return kb, vb, mb

    # Both ring bodies do round 0 on the RESIDENT block outside the scan and
    # rotate FIRST inside it, so exactly n_dev - 1 ppermute hops happen (a
    # rotate-after-last-round variant ships one dead full-block hop whose
    # result nothing reads — and its transpose in the backward).

    def local_flash(q_blk, k_blk, v_blk, m_blk):
        # per-round fused kernel + logaddexp merge across ring hops
        from deeplearning4j_tpu.ops.flash_attention import (
            NEG_INF as F_NEG_INF, flash_attention_lse)
        my = lax.axis_index(axis)
        b, h = q_blk.shape[0], q_blk.shape[1]
        # clamp tiles to the per-device block: the kernel pads T up to a
        # tile multiple, so an unclamped 512 tile would compute 512-wide
        # score tiles for e.g. 128-row blocks (16x wasted FLOPs)
        fbq = max(8, min(flash_bq, blk))
        fbk = max(8, min(flash_bk, blk))

        def round_fn(causal_flag):
            def f(args):
                kb, vb, mb = args
                o, L = flash_attention_lse(q_blk, kb, vb, mb, causal_flag,
                                           scale_f, fbq, fbk)
                return o.astype(acc_dt), L.astype(acc_dt)
            return f

        def skip_fn(args):
            return (jnp.zeros(q_blk.shape, acc_dt),
                    jnp.full((b, h, blk), F_NEG_INF, acc_dt))

        def merge(acc_o, acc_L, o_r, L_r):
            new_L = jnp.logaddexp(acc_L, L_r)
            w1 = jnp.exp(acc_L - new_L)[..., None]
            w2 = jnp.exp(L_r - new_L)[..., None]
            return acc_o * w1 + o_r * w2, new_L

        def step(carry, r):
            acc_o, acc_L, kb, vb, mb = carry
            kb, vb, mb = _rotate(kb, vb, mb)
            owner = (my - r) % n_dev
            args = (kb, vb, mb)
            if causal:  # rounds >= 1 never visit the own (diagonal) block
                o_r, L_r = lax.cond(owner < my, round_fn(False), skip_fn,
                                    args)
            else:
                o_r, L_r = round_fn(False)(args)
            acc_o, acc_L = merge(acc_o, acc_L, o_r, L_r)
            return (acc_o, acc_L, kb, vb, mb), None

        # round 0: the resident block — the causal diagonal when masking
        o0, L0 = round_fn(causal)((k_blk, v_blk, m_blk))
        acc0 = merge(jnp.zeros(q_blk.shape, acc_dt),
                     jnp.full((b, h, blk), F_NEG_INF, acc_dt), o0, L0)
        (out, _, _, _, _), _ = lax.scan(
            step, acc0 + (k_blk, v_blk, m_blk), jnp.arange(1, n_dev))
        return out.astype(q_blk.dtype)

    def local(q_blk, k_blk, v_blk, m_blk):
        # q_blk etc: (b, h, blk, d); m_blk: (b, blk) or None — this device's
        # shard. Unmasked non-causal rounds skip the elementwise mask (and
        # the third ppermute) entirely.
        my = lax.axis_index(axis)

        def band_mask(kv_owner):
            # global row ids of my q block vs col ids of the visiting k
            # block; combines the causal triangle and the sliding window
            qi = my * blk + jnp.arange(blk)
            ki = kv_owner * blk + jnp.arange(blk)
            m = None
            if causal:
                m = (qi[:, None] >= ki[None, :])
            if window:
                wm = (qi[:, None] - ki[None, :] < window)
                if not causal:
                    wm = wm & (ki[None, :] - qi[:, None] < window)
                m = wm if m is None else m & wm
            return None if m is None else m[None, None]  # (1,1,blk,blk)

        def round_(acc, kb, vb, mb, owner):
            m = None if mb is None else (mb > 0)[:, None, None, :]  # (b,1,1,blk)
            if causal or window:
                # blocks fully outside the visible band are masked out
                # entirely; since owner is traced, build the blk x blk mask
                # every step
                bm = band_mask(owner)
                m = bm if m is None else m & bm
            o, m_, l_ = _block_attn(q_blk, kb, vb, scale_, m)  # fp32 already
            return _merge(acc, o, m_, l_)

        def _round_visible(owner):
            # any valid (qi, ki) pair between my q rows and owner's keys?
            q_lo, q_hi = my * blk, my * blk + blk - 1
            k_lo, k_hi = owner * blk, owner * blk + blk - 1
            pred = None
            if causal:
                pred = k_lo <= q_hi
            if window:
                c = k_hi >= q_lo - (window - 1)
                pred = c if pred is None else pred & c
                if not causal:
                    c = k_lo <= q_hi + (window - 1)
                    pred = pred & c
            return pred

        @jax.checkpoint
        def step(carry, r):
            # rematerialized for the same reason as blockwise_attention's
            # step: per-round score residuals under jax.grad are O(T^2/n)
            acc, kb, vb, mb = carry
            kb, vb, mb = _rotate(kb, vb, mb)
            owner = (my - r) % n_dev
            if window:
                # skip rounds fully outside the window: zero compute for
                # the (majority of) rounds local attention never sees
                acc = lax.cond(_round_visible(owner),
                               lambda a: round_(a, kb, vb, mb, owner),
                               lambda a: a, acc)
            else:
                acc = round_(acc, kb, vb, mb, owner)
            return (acc, kb, vb, mb), None

        b, h = q_blk.shape[0], q_blk.shape[1]
        acc0 = (jnp.zeros(q_blk.shape, acc_dt),
                jnp.full((b, h, blk), NEG_INF, acc_dt),
                jnp.zeros((b, h, blk), acc_dt))
        # resident block — checkpointed like the scan rounds, else its
        # (b, h, blk, blk) score/softmax residuals alone are saved by
        # autodiff (O(T^2/n) memory, the exact thing this path avoids)
        acc0 = jax.checkpoint(
            lambda a, kb, vb, mb: round_(a, kb, vb, mb, my))(
            acc0, k_blk, v_blk, m_blk)
        (acc, _, _, _), _ = lax.scan(step, (acc0, k_blk, v_blk, m_blk),
                                     jnp.arange(1, n_dev))
        out, m_, l_ = acc
        return (out / jnp.maximum(l_, 1e-30)[..., None]).astype(q_blk.dtype)

    impl = local_flash if use_flash else local
    spec = P(batch_axis, None, axis, None)
    if has_mask:
        shmapped = compat_shard_map(
            impl, mesh=mesh,
            in_specs=(spec, spec, spec, P(batch_axis, axis)),
            out_specs=spec)
        return shmapped(q, k, v, mask)
    shmapped = compat_shard_map(
        lambda qb, kb, vb: impl(qb, kb, vb, None), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec)
    return shmapped(q, k, v)


class _AttentionContext:
    """Trace-time channel from a mesh-aware trainer to SelfAttentionLayer:
    which mesh/axes are active, and whether the layer should use the
    hand-scheduled ring instead of GSPMD partitioning. Set around step-fn
    tracing (jit caches the traced result, so the context only needs to be
    live while tracing)."""

    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.data_axis: Optional[str] = None
        self.seq_axis: Optional[str] = None
        self.use_ring: bool = False


_ATTN_CTX = _AttentionContext()


class attention_mesh_context:
    """with attention_mesh_context(mesh, data_axis, seq_axis, use_ring): ..."""

    def __init__(self, mesh, data_axis=None, seq_axis=None, use_ring=False):
        self._new = (mesh, data_axis, seq_axis, use_ring)

    def __enter__(self):
        c = _ATTN_CTX
        self._old = (c.mesh, c.data_axis, c.seq_axis, c.use_ring)
        c.mesh, c.data_axis, c.seq_axis, c.use_ring = self._new
        return c

    def __exit__(self, *exc):
        c = _ATTN_CTX
        c.mesh, c.data_axis, c.seq_axis, c.use_ring = self._old
        return False


def current_attention_context() -> _AttentionContext:
    return _ATTN_CTX


class SequenceParallelAttention:
    """User-facing wrapper: places inputs on the seq-sharded mesh and runs
    ring attention — the framework's long-context building block."""

    def __init__(self, mesh: Optional[Mesh] = None, axis: str = "seq",
                 causal: bool = False):
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()), (axis,))
        self.mesh = mesh
        self.axis = axis
        self.causal = causal
        self._jit = jax.jit(functools.partial(
            ring_attention, mesh=self.mesh, axis=self.axis, causal=self.causal))

    def __call__(self, q, k, v):
        sh = NamedSharding(self.mesh, P(None, None, self.axis, None))
        q, k, v = (jax.device_put(jnp.asarray(a), sh) for a in (q, k, v))
        return self._jit(q, k, v)
