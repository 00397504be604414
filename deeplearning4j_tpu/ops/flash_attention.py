"""Fused flash-attention Pallas kernels — the long-context hot path.

Beyond-reference (the 2017 reference has no attention at all; SURVEY §5
long-context). The framework's blockwise/ring attention
(parallel/sequence_parallel.py) implements the flash RECURRENCE as a
lax.scan — correct and O(T*block) memory, but each block step dispatches
thin XLA ops (scores matmul, exp/merge chain on the VPU, rescale) and
training rematerializes the whole scan body. These kernels fuse the
recurrence on-chip (arXiv:2205.14135 / flash-attention-2 schedule):

- forward: grid (B*H, T/bq, T/bk) with the k index FASTEST — the online
  softmax accumulator (acc, m, l) lives in VMEM scratch across each q
  block's k sweep (sequential, grid-order guarantee as in
  lstm_scan_fused), one (bq, bk) score tile at a time; emits o and the
  row logsumexp L = m + log(l) for the backward;
- backward, default "fused" single pass (grid = the dkv sweep, q index
  fastest): p is RECOMPUTED once per score tile from (q, k, L) — nothing
  but o/L is saved — and feeds dv/dk (VMEM scratch) AND dq in the same
  tile visit. dq accumulates across the SLOW grid axis, which TPU output
  revisiting cannot express, so each tile writes a (bq, D) partial to a
  per-k-block HBM buffer summed by one XLA reduction (nk*|dq| extra
  traffic — measured cheaper than paying the exp/softmax VPU chain twice;
  at these head dims the VPU, not the MXU, is the wall). Partials are
  stored in the fp32 accumulator dtype by default (full-precision dq
  accumulation, same as the two-pass scratch; measured +3.7% step cost vs
  bf16 partials — configure(dq_partials="io") buys it back if wanted).
  configure(bwd="two_pass") selects the flash-2 schedule (separate dq and
  dkv kernels, each recomputing p) for A/B.
  D_i = rowsum(dO * o) is one cheap XLA reduction outside.

Causal masking, sliding-window (local) attention, and the framework's
(B, T) key-padding masks are applied per score tile from global row/col
ids; tiles with no valid pair (fully future, fully outside the window)
skip the score math entirely, so windowed cost scales with T*window.
Score/softmax math is fp32 (flash convention); q/k/v stream in their
storage dtype (bf16 on TPU).

Registered as helper "flash_attention" (default-on for TPU);
SelfAttentionLayer's long-context path, the decoder's LatentAttention and
the ring's rounds dispatch here through `ops/helpers.helper_for`, with the
lax.scan blockwise recurrence (the dense product in the decoder) as fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.helpers import register_helper

NEG_INF = -1e30


def _interpret() -> bool:
    from deeplearning4j_tpu.ops.helpers import interpret_mode
    return interpret_mode()


# bq/bk = 0 means "auto": 1024 tiles at long T, 512 below (a plug-in-era
# chip A/B, not re-measured on today's installation — PERF.md "Tried, no
# win": 1024/1024 was +13% over 512/512 at the bench shape T=8192 Dh=64;
# 256 tiles were 15-28% WORSE, so 512 floors it).
DEFAULT_BQ = 0
DEFAULT_BK = 0

# Backward schedule: "fused" = one pass computing p once per tile (dk/dv in
# scratch, per-k-block dq partials to HBM + XLA reduce); "two_pass" =
# flash-2 style separate dq and dk/dv kernels (each recomputes p).
# dq_partials: dtype the fused schedule stores its per-k-block dq partials
# in before the XLA sum — "acc" (the fp32/fp64 accumulator dtype; default,
# matching the two-pass dq scratch's full-precision accumulation) or "io"
# (q.dtype — halves the partial-buffer HBM traffic at the cost of one bf16
# rounding per k block before the sum).
_CONFIG = {"bwd": "fused",
           "dq_partials": "acc"}

# HBM ceiling for the fused schedule's (BH, nk, Tp, D) dq-partials buffer —
# it grows O(T^2 * D / bk), so long contexts (T=32k is ~4.3 GB fp32 at the
# bench head count) must not pay it. Above the cap the backward silently
# takes the two_pass schedule (O(T * block) memory, same math). The bench
# shape T=8192 stays comfortably under the 2 GiB (a constant: tests set the
# module attribute).
DQ_PARTIALS_MAX_BYTES = 2 * 1024 ** 3


def configure(bwd: str | None = None, dq_partials: str | None = None):
    """Override the default backward schedule ('fused' | 'two_pass') and/or
    the fused-schedule dq-partials dtype ('acc' | 'io'); returns the
    previous (bwd, dq_partials) pair.

    The defaults are resolved when flash_attention() is CALLED (threaded
    through the custom VJP as explicit non-diff arguments), so configure()
    takes effect for every subsequent call — including the backward of a
    forward traced after the change. A jit-compiled CALLER that already
    baked a traced flash_attention keeps its schedule until that outer jit
    retraces (per-call schedule can also be forced explicitly:
    flash_attention(..., bwd='two_pass'))."""
    prev = (_CONFIG["bwd"], _CONFIG["dq_partials"])
    if bwd is not None:
        if bwd not in ("fused", "two_pass"):
            raise ValueError(f"unknown flash bwd mode {bwd!r}")
        _CONFIG["bwd"] = bwd
    if dq_partials is not None:
        if dq_partials not in ("acc", "io"):
            raise ValueError(f"unknown dq_partials mode {dq_partials!r}")
        _CONFIG["dq_partials"] = dq_partials
    return prev


def _resolve_blocks(bq: int, bk: int, T: int) -> tuple[int, int]:
    if not bq:
        bq = 1024 if T >= 4096 else 512
    if not bk:
        bk = 1024 if T >= 4096 else 512
    return bq, bk


def _blocks(T: int, b: int) -> int:
    return -(-T // b)


def _fwd_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, l_ref,
                acc_scr, m_scr, l_scr, *, causal, scale, bq, bk, T, Tp,
                has_mask, acc_dt, window=0):
    from jax.experimental import pallas as pl
    j = pl.program_id(2)
    i = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def update(masked):
        def body():
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt) * scale
            if masked:
                valid = _valid_tile(pl, i, j, bq, bk, T, Tp, causal,
                                    has_mask, km_ref, window)
                s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_scr[:], jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            if masked:
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_scr[:] - m_new)
            l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1)
            acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
            m_scr[:] = m_new
        return body

    _dispatch_tile(pl, update, i, j, nk, bq, bk, T, Tp, causal,
                   has_mask, window)

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)[:, None]).astype(
            o_ref.dtype)
        # L for the backward: rows with no visible key keep L = NEG_INF
        # (their recomputed p is masked to 0 anyway)
        l_ref[0, 0, pl.ds(i * bq, bq)] = jnp.where(
            l > 0, m_scr[:] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


def _valid_tile(pl, i, j, bq, bk, T, Tp, causal, has_mask, km_ref,
                window=0):
    """(bq, bk) validity of this score tile — built ONLY for tiles that
    need masking (the dispatcher routes interior tiles to the fast body
    with none of these VPU passes). `window` > 0 limits attention to
    qi - kj < window (causal: a trailing window ending at qi; non-causal:
    additionally kj - qi < window, a symmetric band)."""
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = None

    def _and(a, b):
        return b if a is None else a & b

    if Tp != T:
        valid = _and(valid, kj < T)      # tail-block padding keys drop
    if causal:
        valid = _and(valid, qi >= kj)
    if window:
        valid = _and(valid, qi - kj < window)
        if not causal:
            valid = _and(valid, kj - qi < window)
    if has_mask:
        valid = _and(valid, (km_ref[0, 0, pl.ds(j * bk, bk)] > 0)[None, :])
    if valid is None:                     # dispatcher never does this
        valid = jnp.ones((bq, bk), bool)
    return valid


def _dispatch_tile(pl, update, i, j, nk, bq, bk, T, Tp, causal, has_mask,
                   window=0, on_skip=None):
    """Route this tile to the fast (unmasked) body, the masked body, or
    skip it. Interior tiles — the majority at long T — take the fast body
    with zero mask passes; tiles with NO valid pair (fully future under
    causal, fully outside the sliding window) skip the math entirely (the
    DMA still streams: rectangular grid). `on_skip` runs INSTEAD of the
    body on skipped tiles — kernels whose per-tile output block must
    always be written (the fused backward's dq partials) zero-fill there."""
    q_lo, q_hi = i * bq, i * bq + bq - 1
    k_lo, k_hi = j * bk, j * bk + bk - 1

    # any-valid-pair conditions (tile runs at all)
    run_conds = []
    if causal:
        run_conds.append(k_lo <= q_hi)
    if window:
        run_conds.append(k_hi >= q_lo - (window - 1))
        if not causal:
            run_conds.append(k_lo <= q_hi + (window - 1))
    run = None
    for c in run_conds:
        run = c if run is None else run & c
    if run is not None and on_skip is not None:
        pl.when(jnp.logical_not(run))(on_skip)

    if has_mask:   # key-padding mask: every running tile takes the mask
        if run is None:
            update(True)()
        else:
            pl.when(run)(update(True))
        return

    # edge-crossing conditions (tile needs the masked body)
    mask_conds = []
    if causal:
        mask_conds.append(k_hi > q_lo)                    # crosses diagonal
    if window:
        mask_conds.append(q_hi - k_lo > window - 1)       # crosses back edge
        if not causal:
            mask_conds.append(k_hi - q_lo > window - 1)   # crosses front edge
    if Tp != T:
        mask_conds.append(j == nk - 1)                    # pad-key tail block
    masked = None
    for c in mask_conds:
        masked = c if masked is None else masked | c

    if masked is None:
        if run is None:
            update(False)()
        else:
            pl.when(run)(update(False))
        return
    if run is None:
        pl.when(masked)(update(True))
        pl.when(jnp.logical_not(masked))(update(False))
    else:
        pl.when(run & masked)(update(True))
        pl.when(run & jnp.logical_not(masked))(update(False))


def _dq_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, L_ref, Di_ref,
               dq_ref, dq_scr, *, causal, scale, bq, bk, T, Tp, has_mask,
               acc_dt, window=0):
    from jax.experimental import pallas as pl
    j = pl.program_id(2)
    i = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def update(masked):
        def body():
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt) * scale
            p = jnp.exp(s - L_ref[0, 0, pl.ds(i * bq, bq)][:, None])
            if masked:
                valid = _valid_tile(pl, i, j, bq, bk, T, Tp, causal,
                                    has_mask, km_ref, window)
                p = jnp.where(valid, p, 0.0)
            dp = jax.lax.dot_general(
                do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt)
            ds = p * (dp - Di_ref[0, 0, pl.ds(i * bq, bq)][:, None])
            dq_scr[:] += scale * jax.lax.dot_general(
                ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
        return body

    _dispatch_tile(pl, update, i, j, nk, bq, bk, T, Tp, causal,
                   has_mask, window)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, L_ref, Di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale, bq, bk,
                T, Tp, has_mask, acc_dt, window=0):
    from jax.experimental import pallas as pl
    i = pl.program_id(2)        # q block index — FASTEST (the k sweep)
    j = pl.program_id(1)        # k block index
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def update(masked):
        def body():
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt) * scale
            p = jnp.exp(s - L_ref[0, 0, pl.ds(i * bq, bq)][:, None])
            if masked:
                valid = _valid_tile(pl, i, j, bq, bk, T, Tp, causal,
                                    has_mask, km_ref, window)
                p = jnp.where(valid, p, 0.0)
            pl_ = p.astype(do_ref.dtype)
            dv_scr[:] += jax.lax.dot_general(
                pl_, do_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
            dp = jax.lax.dot_general(
                do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt)
            ds = (p * (dp - Di_ref[0, 0, pl.ds(i * bq, bq)][:, None])).astype(
                q_ref.dtype)
            dk_scr[:] += scale * jax.lax.dot_general(
                ds, q_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
        return body

    # note the swapped loop order: i is fastest here; the dispatcher's nk
    # (tail-k-block test) is this grid's dim 1, NOT nq
    _dispatch_tile(pl, update, i, j, pl.num_programs(1), bq, bk, T, Tp,
                   causal, has_mask, window)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, L_ref, Di_ref,
                      dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr, *, causal,
                      scale, bq, bk, T, Tp, has_mask, acc_dt, window=0):
    """One-pass backward: p is computed ONCE per score tile and feeds all
    three cotangents (the two-pass schedule pays the exp/softmax VPU chain
    twice — the measured wall at these head dims, not the MXU). dk/dv
    accumulate in VMEM scratch across the q sweep (i fastest, like
    _dkv_kernel); dq cannot share that residency (it accumulates across
    the SLOW axis j, and revisiting an output block on non-consecutive
    grid steps is not legal on TPU), so each tile writes its (bq, D)
    partial to a per-k-block HBM buffer that one XLA reduction sums —
    nk*|dq| extra traffic, far cheaper than a third tile pass."""
    from jax.experimental import pallas as pl
    i = pl.program_id(2)        # q block index — FASTEST (the k sweep)
    j = pl.program_id(1)        # k block index
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def update(masked):
        def body():
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt) * scale
            p = jnp.exp(s - L_ref[0, 0, pl.ds(i * bq, bq)][:, None])
            if masked:
                valid = _valid_tile(pl, i, j, bq, bk, T, Tp, causal,
                                    has_mask, km_ref, window)
                p = jnp.where(valid, p, 0.0)
            dv_scr[:] += jax.lax.dot_general(
                p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
            dp = jax.lax.dot_general(
                do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dt)
            ds = (p * (dp - Di_ref[0, 0, pl.ds(i * bq, bq)][:, None])).astype(
                q_ref.dtype)
            dk_scr[:] += scale * jax.lax.dot_general(
                ds, q_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt)
            dqp_ref[0, 0] = (scale * jax.lax.dot_general(
                ds, k_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt)).astype(dqp_ref.dtype)
        return body

    def skip():
        dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    # i fastest: the dispatcher's nk (tail-k-block test) is grid dim 1
    _dispatch_tile(pl, update, i, j, pl.num_programs(1), bq, bk, T, Tp,
                   causal, has_mask, window, on_skip=skip)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _prep(q, k, v, mask, bq, bk):
    """(B, H, T, D) q and (B, Hk, T, D) k/v -> (B*H, Tp, D) / (B*Hk, Tp, D)
    padded to block multiples + (B*H, 1, Tp) key mask (pad keys masked out;
    pad QUERY rows compute garbage that the caller slices off). Hk may
    divide H (grouped-query attention: each group of H/Hk query heads
    shares one k/v head — the kernels never materialize the repeat, their
    k/v BlockSpecs map the grid's q-head index to its kv row)."""
    B, H, T, D = q.shape
    Hk = k.shape[1]
    if k.shape != v.shape or k.shape[0] != B or k.shape[2] != T \
            or k.shape[3] != D or H % Hk != 0:
        raise ValueError(
            f"bad GQA shapes: q {q.shape}, k {k.shape}, v {v.shape} "
            f"(need k == v, same B/T/D, and n_heads % n_kv_heads == 0)")
    Tqp = _blocks(T, bq) * bq
    Tkp = _blocks(T, bk) * bk
    Tp = max(Tqp, Tkp)

    def r(a):
        a = a.reshape(-1, T, D)
        return jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0)))

    km = jnp.ones((B, T), jnp.int32) if mask is None \
        else (mask > 0).astype(jnp.int32)
    km = jnp.repeat(km, H, axis=0)                       # (BH, T)
    km = jnp.pad(km, ((0, 0), (0, Tp - T)))              # pad keys -> 0
    return r(q), r(k), r(v), km[:, None, :], Tp           # (BH, 1, Tp)


def _kv_row(H: int, Hk: int):
    """Grid q-head index b in [0, B*H) -> its kv row in [0, B*Hk): query
    head h = b % H belongs to kv head h // (H // Hk)."""
    if H == Hk:
        return lambda b: b
    g = H // Hk
    return lambda b: (b // H) * Hk + (b % H) // g


def _call_fwd(qp, kp, vp, km, causal, scale, bq, bk, T, has_mask,
              window=0, H=None, Hk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    BH, Tp, D = qp.shape
    kv = _kv_row(H, Hk) if H else (lambda b: b)
    nq, nk = Tp // bq, Tp // bk
    acc_dt = jnp.promote_types(qp.dtype, jnp.float32)
    kern = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                             bq=bq, bk=bk, T=T, Tp=Tp, has_mask=has_mask,
                             acc_dt=acc_dt, window=window)
    o, L = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, 1, Tp), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, Tp), lambda b, i, j: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tp, D), qp.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tp), acc_dt),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, D), acc_dt),
            pltpu.VMEM((bq,), acc_dt),
            pltpu.VMEM((bq,), acc_dt),
        ],
        interpret=_interpret(),
    )(qp, kp, vp, km)
    return o, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, mask, causal, scale, bq, bk, window, bwd,
                dq_partials):
    """custom_vjp core with the backward schedule as explicit non-diff
    arguments (resolved from _CONFIG by the public wrapper at CALL time, so
    configure() is never silently ignored by an already-traced vjp)."""
    out, _ = _fa_fwd(q, k, v, mask, causal, scale, bq, bk, window, bwd,
                     dq_partials)
    return out


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    scale: float | None = None, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, window: int = 0,
                    bwd: str | None = None, dq_partials: str | None = None):
    """q/k/v: (B, H, T, D); k/v may carry Hk | H heads (grouped-query
    attention: the repeat is never written, forward or backward; dk and dv
    are summed over each group's query heads). mask: optional (B, T)
    key-padding mask. Returns (B, H, T, D).
    Fused online-softmax attention; see module docstring. `window` > 0 =
    sliding-window (local) attention: causal keeps the trailing window
    qi-window < kj <= qi; non-causal keeps the symmetric band |qi-kj| <
    window. Tiles fully outside the window are SKIPPED (no score math), so
    cost scales with T*window, not T^2. bwd/dq_partials: per-call backward
    schedule override (None -> the configure() defaults, read NOW)."""
    if bwd is None:
        bwd = _CONFIG["bwd"]
    if dq_partials is None:
        dq_partials = _CONFIG["dq_partials"]
    return _flash_core(q, k, v, mask, causal, scale, bq, bk, window, bwd,
                       dq_partials)


def _fa_fwd(q, k, v, mask, causal, scale, bq, bk, window, bwd, dq_partials):
    (out, _), res = _fa_lse_fwd(q, k, v, mask, causal, scale, bq, bk,
                                window)
    return out, res


def _fa_bwd(causal, scale, bq, bk, window, bwd, dq_partials, saved, dout):
    return _fa_bwd_impl(causal, scale, bq, bk, saved, dout, None, window,
                        bwd, dq_partials)


def _fa_bwd_impl(causal, scale, bq, bk, saved, dout, dlse, window=0,
                 bwd=None, dq_partials=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if bwd is None:
        bwd = _CONFIG["bwd"]
    if dq_partials is None:
        dq_partials = _CONFIG["dq_partials"]
    q, k, v, mask, o, L = saved
    B, H, T, D = q.shape
    bq, bk = _resolve_blocks(bq, bk, T)
    scale_ = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    qp, kp, vp, km, Tp = _prep(q, k, v, mask, bq, bk)
    dop = jnp.pad(dout.reshape(B * H, T, D), ((0, 0), (0, Tp - T), (0, 0)))
    acc_dt = jnp.promote_types(qp.dtype, jnp.float32)
    # D_i = rowsum(dO * o) — one cheap XLA reduction, accumulated one width up
    Di = jnp.sum(dop.astype(acc_dt) * o.astype(acc_dt), axis=-1)[:, None, :]
    if dlse is not None:
        # L as an OUTPUT: dL_i/ds_ij = p_ij, so ds gains p * dL - absorbed
        # by shifting the D_i term (ds = p * (dp - (Di - dL)))
        dl = jnp.pad(dlse.reshape(B * H, T).astype(acc_dt),
                     ((0, 0), (0, Tp - T)))[:, None, :]
        Di = Di - dl
    BH = B * H
    nq, nk = Tp // bq, Tp // bk
    # grouped k/v heads: the kernels read a query head's k/v at its group's
    # row (the repeat is never written) and write dk/dv a QUERY head; the
    # group's heads are summed below
    Hk = k.shape[1]
    kv = _kv_row(H, Hk)

    def shp_kv(a):
        a = a[:, :T].reshape(B, Hk, H // Hk, T, D)
        return a[:, :, 0] if H == Hk else \
            jnp.sum(a.astype(acc_dt), axis=2).astype(a.dtype)
    if bwd == "fused":
        dqp_dt = acc_dt if dq_partials == "acc" else q.dtype
        # the dq-partials buffer is O(T^2 * D / bk) — above the HBM cap the
        # two_pass schedule (O(T * block) memory) takes over
        dqp_bytes = BH * nk * Tp * D * jnp.dtype(dqp_dt).itemsize
        if dqp_bytes > DQ_PARTIALS_MAX_BYTES:
            bwd = "two_pass"
    if bwd == "fused":
        qspec2 = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
        kspec2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
        kin2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (kv(b), j, 0))
        dk, dv, dqp = pl.pallas_call(
            functools.partial(_fused_bwd_kernel, causal=causal, scale=scale_,
                              bq=bq, bk=bk, T=T, Tp=Tp,
                              has_mask=mask is not None, acc_dt=acc_dt,
                              window=window),
            grid=(BH, nk, nq),
            in_specs=[qspec2, kin2, kin2,
                      pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0)),
                      qspec2,
                      pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0)),
                      pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0))],
            out_specs=(kspec2, kspec2,
                       pl.BlockSpec((1, 1, bq, D),
                                    lambda b, j, i: (b, j, i, 0))),
            out_shape=(jax.ShapeDtypeStruct((BH, Tp, D), k.dtype),
                       jax.ShapeDtypeStruct((BH, Tp, D), v.dtype),
                       jax.ShapeDtypeStruct((BH, nk, Tp, D), dqp_dt)),
            scratch_shapes=[pltpu.VMEM((bk, D), acc_dt),
                            pltpu.VMEM((bk, D), acc_dt)],
            interpret=_interpret(),
        )(qp, kp, vp, km, dop, L, Di)
        dq = jnp.sum(dqp.astype(acc_dt), axis=1).astype(q.dtype)
        shp = lambda a: a[:, :T].reshape(B, H, T, D)
        dmask = None if mask is None else jnp.zeros_like(mask)
        return shp(dq), shp_kv(dk), shp_kv(dv), dmask
    qspec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, D), lambda b, i, j: (kv(b), j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale_,
                          bq=bq, bk=bk, T=T, Tp=Tp,
                          has_mask=mask is not None, acc_dt=acc_dt,
                          window=window),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec,
                  pl.BlockSpec((1, 1, Tp), lambda b, i, j: (b, 0, 0)),
                  qspec,
                  pl.BlockSpec((1, 1, Tp), lambda b, i, j: (b, 0, 0)),
                  pl.BlockSpec((1, 1, Tp), lambda b, i, j: (b, 0, 0))],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((BH, Tp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), acc_dt)],
        interpret=_interpret(),
    )(qp, kp, vp, km, dop, L, Di)
    # dk/dv: q index fastest — grid (BH, nk, nq)
    qspec2 = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    kin2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (kv(b), j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale_,
                          bq=bq, bk=bk, T=T, Tp=Tp,
                          has_mask=mask is not None, acc_dt=acc_dt,
                          window=window),
        grid=(BH, nk, nq),
        in_specs=[qspec2, kin2, kin2,
                  pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0)),
                  qspec2,
                  pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0)),
                  pl.BlockSpec((1, 1, Tp), lambda b, j, i: (b, 0, 0))],
        out_specs=(kspec2, kspec2),
        out_shape=(jax.ShapeDtypeStruct((BH, Tp, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tp, D), v.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, D), acc_dt),
                        pltpu.VMEM((bk, D), acc_dt)],
        interpret=_interpret(),
    )(qp, kp, vp, km, dop, L, Di)
    shp = lambda a: a[:, :T].reshape(B, H, T, D)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return shp(dq), shp_kv(dk), shp_kv(dv), dmask


_flash_core.defvjp(_fa_fwd, _fa_bwd)
register_helper("flash_attention")(flash_attention)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_lse_core(q, k, v, mask, causal, scale, bq, bk, window, bwd,
                    dq_partials):
    (out, lse), _ = _fa_lse_fwd(q, k, v, mask, causal, scale, bq, bk,
                                window)
    return out, lse


def flash_attention_lse(q, k, v, mask=None, causal: bool = False,
                        scale: float | None = None, bq: int = DEFAULT_BQ,
                        bk: int = DEFAULT_BK, window: int = 0,
                        bwd: str | None = None,
                        dq_partials: str | None = None):
    '''Like flash_attention but ALSO returns the per-row logsumexp
    (B, H, T) fp32 - the quantity ring/context-parallel callers need to
    merge partial attention across k/v shards: (out_a, L_a) + (out_b, L_b)
    combine via logaddexp. Differentiable in BOTH outputs.'''
    if bwd is None:
        bwd = _CONFIG["bwd"]
    if dq_partials is None:
        dq_partials = _CONFIG["dq_partials"]
    return _flash_lse_core(q, k, v, mask, causal, scale, bq, bk, window,
                           bwd, dq_partials)


def _fa_lse_fwd_core(q, k, v, mask, causal, scale, bq, bk, window, bwd,
                     dq_partials):
    return _fa_lse_fwd(q, k, v, mask, causal, scale, bq, bk, window)


def _fa_lse_fwd(q, k, v, mask, causal, scale, bq, bk, window=0):
    B, H, T, D = q.shape
    bq, bk = _resolve_blocks(bq, bk, T)
    scale_ = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    qp, kp, vp, km, Tp = _prep(q, k, v, mask, bq, bk)
    o, L = _call_fwd(qp, kp, vp, km, causal, scale_, bq, bk, T,
                     mask is not None, window, H, k.shape[1])
    out = o[:, :T].reshape(B, H, T, D)
    lse = L[:, 0, :T].reshape(B, H, T)
    return (out, lse), (q, k, v, mask, o, L)


def _fa_lse_bwd(causal, scale, bq, bk, window, bwd, dq_partials, saved,
                cots):
    dout, dlse = cots
    return _fa_bwd_impl(causal, scale, bq, bk, saved, dout, dlse, window,
                        bwd, dq_partials)


_flash_lse_core.defvjp(_fa_lse_fwd_core, _fa_lse_bwd)


def flash_attention_reference(q, k, v, mask=None, causal=False, scale=None,
                              window=0):
    """Dense oracle with identical mask/window/GQA semantics (tests):
    grouped k/v heads (Hk | H) broadcast to full heads with _kv_row's
    grouping (query head h reads kv head h // (H // Hk))."""
    D = q.shape[-1]
    H, Hk = q.shape[1], k.shape[1]
    if Hk != H:
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
    scale_ = scale if scale is not None else 1.0 / np.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale_
    T = q.shape[2]
    valid = jnp.ones((1, 1, T, T), bool)
    if causal:
        valid = valid & jnp.tril(jnp.ones((T, T), bool))[None, None]
    if window:
        qi = jnp.arange(T)[:, None]
        kj = jnp.arange(T)[None, :]
        w = (qi - kj < window)
        if not causal:
            w = w & (kj - qi < window)
        valid = valid & w[None, None]
    if mask is not None:
        valid = valid & (mask > 0)[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)  # fully-masked rows -> zero output
    return jnp.einsum("bhqk,bhkv->bhqv", p, v)
