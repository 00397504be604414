"""Split-K flash-decode kernel: single-query paged attention for serving.

Beyond-reference (Flash-Decoding, Dao et al. 2023; SURVEY §5 serving). The
serving engine's decode step attends ONE query per slot against that slot's
KV-cache prefix (serving/kv_cache.py). The dense path
(`decode_attention_dense`, the fp64 oracle the paged oracles call) builds
the full (S, H, L) score tensor and softmaxes over the whole max_len axis no
matter how short the actual sequences are. At decode there is no query-axis
parallelism to tile over (q is a single position), so the flash trick that
matters is SPLIT-K: partition the cache LENGTH axis, compute each
partition's softmax-weighted partial sum and row logsumexp independently
(one grid cell per (slot, partition)), and merge the partials outside the
kernel with the SAME logaddexp algebra that ring attention and
`flash_attention_lse` use:

    out = sum_p exp(L_p - L_tot) * o_p,   L_tot = logsumexp_p L_p.

Partitions entirely beyond a slot's visible length — or entirely behind its
sliding window — are skipped inside the kernel (zero output block, L_p =
NEG_INF, which the merge weighs to zero), so per-slot score math follows
the slot's TRUE length, not max_len.

The cache is block-paged (ISSUE 7, serving/kv_cache.py): k/v live as
(num_blocks + 1, block_size, Hk, D) physical blocks and each slot maps
logical blocks through a (max_seqs, blocks_per_seq) int32 block table. The
split-K partition structure aligns with paging: one length partition = one
physical block, so `flash_decode_attention_paged` keeps the gather INSIDE
the kernel by feeding the block table through
`pltpu.PrefetchScalarGridSpec` (scalar-prefetch operand) and letting each
grid cell's k/v index_map resolve (slot, logical block j) ->
`bt_ref[s, j]` — no (S, L, Hk, D) contiguous copy of the cache is ever
materialized. `decode_attention_dense_paged` extends the fp64 oracle to
resolve block tables (gather + reshape, then the unchanged dense math) so
the parity harness covers the paged path end to end.

GQA-aware without materializing the head repeat: q arrives reshaped
(S, Hk, G, D), each grid cell holds one whole physical block (bs, Hk, D)
and contracts every kv head's (G, D) query group against that head's
(bs, D) k/v slice — the same grouping as ops/flash_attention._kv_row.
Score and softmax math run in fp32 (fp64 under x64); k/v stream in the
cache dtype (bf16 on TPU, int8 for a quantized pool).

Registered as helpers "decode_attention_paged" and
"decode_attention_spec_paged" (default-on for TPU); serving/decode.py
dispatches here through the helper seam with the dense paged paths as
oracle and fallback. Falls back to the dense-paged path when
block_size < 8. Inference-only: no custom VJP (the dense fallback is
differentiable if anyone ever needs gradients through decode).

Chunked prefill (ISSUE 9) adds no kernel variant: a prefill chunk
attends its predecessor blocks through the SAME block-table gather
semantics the shared-prefix suffix pass uses (serving/decode.py
`_prefill_shared_fn`), and decode iterations interleaved between chunks
hit this kernel unchanged — a partially-prefilled slot is invisible to
it because its `lengths` entry only covers completed chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.helpers import register_helper

NEG_INF = -1e30


def _interpret() -> bool:
    from deeplearning4j_tpu.ops.helpers import interpret_mode
    return interpret_mode()


def decode_attention_dense(q, kc, vc, visible, scale, window: int = 0):
    """Dense single-query attention against a contiguous per-slot cache —
    the fp64 oracle the paged oracles reduce to.

    q: (S, H, D) current-position queries; kc/vc: (S, L, Hk, D) cache
    (current position already appended); visible: (S,) number of visible
    positions per slot (= position index + 1); `window` > 0 applies sliding-
    window semantics (query at position visible-1 sees keys j with
    (visible-1) - j < window). Returns (S, H, D) in q.dtype."""
    S, H, D = q.shape
    L, Hk = kc.shape[1], kc.shape[2]
    if H % Hk != 0:
        raise ValueError(f"n_heads {H} % n_kv_heads {Hk} != 0")
    G = H // Hk
    acc = jnp.promote_types(q.dtype, jnp.float32)
    q4 = q.reshape(S, Hk, G, D)
    s = jnp.einsum("shgd,slhd->shgl", q4.astype(acc), kc.astype(acc)) * scale
    j = jnp.arange(L)[None, :]                       # (1, L)
    valid = j < visible[:, None]                     # (S, L)
    if window:
        valid = valid & (visible[:, None] - 1 - j < window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)   # fully-masked rows -> 0
    out = jnp.einsum("shgl,slhd->shgd", p, vc.astype(acc))
    return out.reshape(S, H, D).astype(q.dtype)


def _decode_kernel(bt_ref, vis_ref, q_ref, k_ref, v_ref, *rest, nq, bs,
                   window, scale, acc_dt, quantized):
    """One grid cell = (slot, logical block): for every kv head, the partial
    softmax-weighted sum o_p (R, D) and row logsumexp L_p (R, 1) of the
    head's R = nq * G query rows over this block's bs cache positions. Row
    r belongs to query position r // G, which sits at logical position
    vis - 1 + r // G and so sees cache positions below vis + r // G (nq = 1
    is plain decode; nq > 1 is speculative verification). Blocks no query
    can see (beyond the last query's horizon, or behind the first query's
    sliding window) skip the score math and emit (0, NEG_INF) — the merge
    weighs them to zero.

    The k/v tile is the whole physical block (bs, Hk, D): its last two
    dims are the pool's own, which is the only shape of a one-block tile
    the TPU lowering takes from a (.., bs, Hk, D) pool; heads are unrolled
    here. Validity is built from an iota against the scalar-prefetched
    visible length — no mask operand.

    Quantized pool (ISSUE 15): ks_ref/vs_ref are this block's per-head
    scales, a (1, 1, Hk) tile routed through the same block-table
    index_map as the k/v tiles, and the k/v streams are int8.
    Dequantization is ONE broadcast multiply per head tile right after the
    dtype widen — structurally the `payload * scale` the dense
    oracle applies per gathered block. The pool bytes crossing HBM stay
    int8; nothing dequantized outlives this cell."""
    from jax.experimental import pallas as pl
    if quantized:
        ks_ref, vs_ref, o_ref, l_ref = rest
    else:
        o_ref, l_ref = rest
    n_kv, R = q_ref.shape[1], q_ref.shape[2]
    G = R // nq
    vis = vis_ref[pl.program_id(0)]                  # query 0's visible length
    lo = pl.program_id(1) * bs
    run = lo < vis + (nq - 1)                        # any query sees any pos?
    if window:
        run = run & (lo + bs > vis - window)         # union over queries

    @pl.when(run)
    def _():
        pos = lo + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        horizon = vis
        if nq > 1:
            horizon = vis + jax.lax.broadcasted_iota(
                jnp.int32, (R, bs), 0) // G
        valid = pos < horizon
        if window:
            valid = valid & (horizon - 1 - pos < window)
        for h in range(n_kv):
            q = q_ref[0, h].astype(acc_dt)           # (R, D)
            k = k_ref[0, :, h, :].astype(acc_dt)     # (bs, D)
            v = v_ref[0, :, h, :].astype(acc_dt)
            if quantized:
                k = k * ks_ref[0, :, h:h + 1].astype(acc_dt)
                v = v * vs_ref[0, :, h:h + 1].astype(acc_dt)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=acc_dt) * scale
            s = jnp.where(valid, s, NEG_INF)
            m = jnp.max(s, axis=1, keepdims=True)    # (R, 1)
            p = jnp.where(valid, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, axis=1, keepdims=True)    # (R, 1)
            o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=acc_dt)
            o_ref[0, 0, h] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            l_ref[0, 0, h] = jnp.where(
                l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)

    @pl.when(jnp.logical_not(run))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        l_ref[...] = jnp.full_like(l_ref, NEG_INF)


def _paged_split_k(q4, kp, vp, block_tables, visible, scale, window,
                   k_scale, v_scale, nq):
    """The pallas_call + logaddexp merge shared by the single-query and the
    speculative entry points. q4: (S, Hk, R, D) with R = nq * G query rows
    per kv head (query-major); returns (S, Hk, R, D) in the accumulator
    dtype.

    `block_tables` and `visible` ride as `pltpu.PrefetchScalarGridSpec`
    scalar-prefetch operands: every index_map takes them as trailing refs,
    the k/v (and scale) maps do the paging gather — logical block j of slot
    s lives at physical block bt_ref[s, j] — and the kernel reads its
    slot's visible length from SMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, Hk, R, D = q4.shape
    bs = kp.shape[1]
    bps = block_tables.shape[1]
    quantized = k_scale is not None
    acc_dt = jnp.promote_types(q4.dtype, jnp.float32)
    page = pl.BlockSpec((1, bs, Hk, D),
                        lambda s, j, bt_ref, vis_ref: (bt_ref[s, j], 0, 0, 0))
    page_scale = pl.BlockSpec(
        (1, 1, Hk), lambda s, j, bt_ref, vis_ref: (bt_ref[s, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, bps),
        in_specs=[
            pl.BlockSpec((1, Hk, R, D),
                         lambda s, j, bt_ref, vis_ref: (s, 0, 0, 0)),
            page, page,
            *([page_scale, page_scale] if quantized else []),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, Hk, R, D),
                         lambda s, j, bt_ref, vis_ref: (s, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, Hk, R, 1),
                         lambda s, j, bt_ref, vis_ref: (s, j, 0, 0, 0)),
        ),
    )
    o_p, l_p = pl.pallas_call(
        functools.partial(_decode_kernel, nq=nq, bs=bs, window=window,
                          scale=float(scale), acc_dt=acc_dt,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((S, bps, Hk, R, D), acc_dt),
            jax.ShapeDtypeStruct((S, bps, Hk, R, 1), acc_dt),
        ),
        interpret=_interpret(),
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(visible, jnp.int32),
      q4, kp, vp,
      *((k_scale[:, None], v_scale[:, None]) if quantized else ()))

    # logaddexp merge across blocks (the flash_attention_lse algebra):
    # out = sum_p exp(L_p - L_tot) * o_p. Skipped blocks carry
    # L_p = NEG_INF -> weight 0; a fully-masked row (cannot happen for
    # visible >= 1, but kept safe) gets denom >= 1 and o_p = 0 -> output 0,
    # matching the dense path's zeroed fully-masked rows.
    m = jnp.max(l_p, axis=1, keepdims=True)          # (S, 1, Hk, R, 1)
    w = jnp.exp(l_p - jnp.maximum(m, NEG_INF))       # (S, bps, Hk, R, 1)
    denom = jnp.maximum(jnp.sum(w, axis=1), 1e-30)   # (S, Hk, R, 1)
    return jnp.sum(w * o_p, axis=1) / denom


# --------------------------------------------------------------- paged path
def decode_attention_dense_paged(q, kp, vp, block_tables, visible, scale,
                                 window: int = 0, k_scale=None,
                                 v_scale=None):
    """Dense paged oracle: gather each slot's cache through its block table
    into the (S, L, Hk, D) layout, then run the UNCHANGED dense math — so
    paged parity reduces to the already-trusted oracle. q: (S, H, D);
    kp/vp: (num_blocks + 1, block_size, Hk, D) physical blocks (last block
    is the trash block); block_tables: (S, blocks_per_seq) int32.

    Quantized pool: k_scale/v_scale (num_blocks + 1, Hk) dequantize each
    GATHERED block (`payload * scale[block, head]`) before the dense math
    — the quantize -> dequantize reference the int8 kernel is tested
    against. Only per-slot views are ever dequantized, never the pool."""
    S = q.shape[0]
    bs, Hk, D = kp.shape[1], kp.shape[2], kp.shape[3]
    bps = block_tables.shape[1]
    acc = jnp.promote_types(q.dtype, jnp.float32)
    if k_scale is not None:
        ks = k_scale[block_tables]                   # (S, bps, Hk)
        vs = v_scale[block_tables]
        kc = (kp[block_tables].astype(acc)
              * ks[:, :, None, :, None].astype(acc))
        vc = (vp[block_tables].astype(acc)
              * vs[:, :, None, :, None].astype(acc))
        kc = kc.reshape(S, bps * bs, Hk, D)
        vc = vc.reshape(S, bps * bs, Hk, D)
    else:
        kc = kp[block_tables].reshape(S, bps * bs, Hk, D)
        vc = vp[block_tables].reshape(S, bps * bs, Hk, D)
    return decode_attention_dense(q, kc, vc, visible, scale, window)


def flash_decode_attention_paged(q, kp, vp, block_tables, visible, scale,
                                 window: int = 0, k_scale=None,
                                 v_scale=None):
    """Block-table-aware split-K flash-decode: same contract as
    `decode_attention_dense_paged`, computed with one grid cell per
    (slot, LOGICAL block) and the logical -> physical lookup done by the
    k/v index_maps through the scalar-prefetched block table. A partition
    IS a physical block (physical blocks are not contiguous in HBM, so
    larger partitions cannot be one tile). Falls back to the dense paged
    path when block_size < 8 — fallback and kernel are value-identical
    either way.

    Quantized pool (ISSUE 15): pass k_scale/v_scale (num_blocks + 1, Hk)
    with int8 kp/vp; each cell receives its block's per-head scales and
    dequantizes its own int8 tile in-register (`_decode_kernel`). The pool
    streams at the int8 byte count and is never materialized dequantized
    anywhere."""
    S, H, D = q.shape
    bs, Hk = kp.shape[1], kp.shape[2]
    if H % Hk != 0:
        raise ValueError(f"n_heads {H} % n_kv_heads {Hk} != 0")
    if bs < 8:
        return decode_attention_dense_paged(q, kp, vp, block_tables,
                                            visible, scale, window,
                                            k_scale=k_scale,
                                            v_scale=v_scale)
    out = _paged_split_k(q.reshape(S, Hk, H // Hk, D), kp, vp, block_tables,
                         visible, scale, window, k_scale, v_scale, nq=1)
    return out.reshape(S, H, D).astype(q.dtype)


register_helper("decode_attention_paged")(
    flash_decode_attention_paged)


# ------------------------------------------------- speculative (multi-query)
def decode_attention_dense_spec_paged(q, kp, vp, block_tables, visible,
                                      scale, window: int = 0,
                                      k_scale=None, v_scale=None):
    """Dense paged oracle for SPECULATIVE verification (ISSUE 11): score Q
    consecutive query positions per slot in one call. q: (S, Q, H, D) where
    query i of slot s sits at logical position visible[s] - 1 + i (query 0
    is the ordinary next-token query; queries 1..Q-1 are draft tokens whose
    KV was provisionally appended). Query i therefore sees j < visible + i.

    Implemented as Q calls of the single-query dense paged oracle — the
    per-position math (shapes, einsum order, masking) is IDENTICAL to the
    plain decode path, so a spec step's row i is bit-identical to what the
    sequential decode step would have computed at that position given the
    same cache. That is what makes this both the fp64 oracle AND the
    bit-identical fallback for the multi-query kernel. A quantized pool
    threads k_scale/v_scale straight into the single-query oracle — the
    same quantize -> dequantize reference per gathered block."""
    S, Q = q.shape[0], q.shape[1]
    visible = jnp.asarray(visible, jnp.int32)
    outs = [decode_attention_dense_paged(q[:, i], kp, vp, block_tables,
                                         visible + i, scale, window,
                                         k_scale=k_scale, v_scale=v_scale)
            for i in range(Q)]
    return jnp.stack(outs, axis=1)                   # (S, Q, H, D)


def flash_decode_attention_spec_paged(q, kp, vp, block_tables, visible,
                                      scale, window: int = 0,
                                      k_scale=None, v_scale=None):
    """Block-table-aware split-K flash-decode over Q query positions per
    slot (speculative verification): same contract as
    `decode_attention_dense_spec_paged`, same grid and kernel as the
    single-query paged path with each kv head's query tile widened from
    (G, D) to (Q*G, D), so all draft positions are scored in ONE dispatch
    at unchanged k/v bytes moved (the whole point: decode is HBM-bound on
    the cache stream, so Q-for-1 amortizes the stream). Falls back to the
    dense spec oracle when block_size < 8 — value-identical either way.
    A quantized pool takes the same k_scale/v_scale as the single-query
    kernel: the int8 stream is the same bytes whether one or Q queries
    consume it."""
    S, Q, H, D = q.shape
    bs, Hk = kp.shape[1], kp.shape[2]
    if H % Hk != 0:
        raise ValueError(f"n_heads {H} % n_kv_heads {Hk} != 0")
    if bs < 8:
        return decode_attention_dense_spec_paged(q, kp, vp, block_tables,
                                                 visible, scale, window,
                                                 k_scale=k_scale,
                                                 v_scale=v_scale)
    G = H // Hk
    q4 = q.reshape(S, Q, Hk, G, D).transpose(0, 2, 1, 3, 4)  # (S,Hk,Q,G,D)
    out = _paged_split_k(q4.reshape(S, Hk, Q * G, D), kp, vp, block_tables,
                         visible, scale, window, k_scale, v_scale, nq=Q)
    out = out.reshape(S, Hk, Q, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(S, Q, H, D).astype(q.dtype)


register_helper("decode_attention_spec_paged")(
    flash_decode_attention_spec_paged)


def paged_spec_decode_specs(tensor_axis: str = "tensor",
                            quantized: bool = False):
    """shard_map partition specs for the SPECULATIVE paged attention call:
    `(in_specs, out_specs)` for `(q, kp, vp, block_tables, visible)` -> out
    with q/out shaped (S, Q, H, D). Identical head-locality argument to
    `paged_decode_specs` — the Q axis is per-slot and replicates with S, so
    the multi-query kernel stays collective-free under TP: every softmax
    reduction runs over L within one head shard. With `quantized`, two
    trailing (num_blocks + 1, Hk) scale operands shard with their heads."""
    from jax.sharding import PartitionSpec as P
    heads_q = P(None, None, tensor_axis, None)      # q/out: (S, Q, H, D)
    heads_kv = P(None, None, tensor_axis, None)     # kp/vp: (nb+1, bs, Hk, D)
    in_specs = (heads_q, heads_kv, heads_kv, P(None, None), P(None))
    if quantized:
        scales = P(None, tensor_axis)               # (nb+1, Hk)
        in_specs = in_specs + (scales, scales)
    return in_specs, heads_q


def paged_decode_specs(tensor_axis: str = "tensor",
                       quantized: bool = False):
    """shard_map partition specs for the paged decode attention call
    (ISSUE 10): `(in_specs, out_specs)` for the array operands
    `(q, kp, vp, block_tables, visible)` -> out, sharding the HEAD axes
    over `tensor_axis` — q/out over H (axis 1), the physical k/v pools
    over Hk (axis 2), block tables and visible lengths replicated.

    Head-local attention is what makes the kernel TP-viable unchanged:
    with whole (grouped) heads per shard, every softmax/score/value
    reduction runs over the L axis WITHIN one shard, so the shard_map body
    needs NO collective — the Pallas split-K kernel (or the dense paged
    fallback) executes per shard exactly as on one chip. The only
    cross-shard communication in a TP decode step is outside this call,
    in the row-parallel output projection (see PERF.md's cost model).
    Contiguous head splits preserve GQA grouping (head h reads kv head
    h // G) whenever the TP degree divides n_kv_heads.

    With `quantized`, two trailing scale operands (num_blocks + 1, Hk)
    shard over their HEAD axis (axis 1) — a scale lives and dies with the
    kv head it rescales, so TP sharding splits payload and scale along
    the same boundary and the kernel stays collective-free."""
    from jax.sharding import PartitionSpec as P
    heads_q = P(None, tensor_axis, None)            # q/out: (S, H, D)
    heads_kv = P(None, None, tensor_axis, None)     # kp/vp: (nb+1, bs, Hk, D)
    in_specs = (heads_q, heads_kv, heads_kv, P(None, None), P(None))
    if quantized:
        scales = P(None, tensor_axis)               # (nb+1, Hk)
        in_specs = in_specs + (scales, scales)
    return in_specs, heads_q
