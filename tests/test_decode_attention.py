"""Paged split-K flash-decode kernel vs the dense paged oracle.

conftest.py forces x64, so `decode_attention_dense` runs in fp64 and the
kernel (interpret mode off-TPU) must match it to ~1e-12 across the shapes
the serving engine actually produces: MHA / GQA / MQA head layouts, sliding
windows, and RAGGED visible lengths (continuous batching means every slot
sits at a different cache position). Also covers the automatic dense
fallback (block_size < 8) and the helper-seam wiring (an engine built with
helpers forced ON stays on the fp64 parity oracle).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.decode_attention import (
    decode_attention_dense, decode_attention_dense_paged,
    flash_decode_attention_paged)


def _rand(shape, key, dtype=jnp.float64):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


# ------------------------------------------------------------- paged kernel
def _paged_case(S, H, Hk, D, bs, bps, window, seed=0):
    """Physical blocks + a random NON-CONTIGUOUS, non-aliasing block table
    (the shapes serving/kv_cache.py produces; last physical block = trash)."""
    nb = S * bps + 1
    kp = _rand((nb, bs, Hk, D), seed + 1)
    vp = _rand((nb, bs, Hk, D), seed + 2)
    rng = np.random.RandomState(seed + 3)
    bt = jnp.asarray(rng.permutation(nb - 1)[:S * bps].reshape(S, bps),
                     jnp.int32)
    q = _rand((S, H, D), seed)
    L = bps * bs
    vis = jnp.asarray([(7 * (i + 1)) % L + 1 for i in range(S)], jnp.int32)
    vis = vis.at[0].set(1).at[S - 1].set(L)
    return q, kp, vp, bt, vis, 1.0 / np.sqrt(D), window


PAGED_SWEEP = [
    # (S, H, Hk, D, bs, bps, window)
    (3, 4, 4, 16, 16, 4, 0),    # MHA
    (3, 4, 2, 16, 16, 4, 0),    # GQA group 2
    (2, 4, 1, 8, 8, 4, 0),      # MQA, minimum kernel block
    (3, 4, 2, 16, 16, 4, 5),    # GQA + sliding window
    (2, 2, 2, 16, 32, 3, 3),    # MHA + window, odd block count
]


@pytest.mark.parametrize("S,H,Hk,D,bs,bps,window", PAGED_SWEEP)
def test_paged_kernel_matches_dense_paged_oracle(S, H, Hk, D, bs, bps,
                                                 window):
    q, kp, vp, bt, vis, scale, w = _paged_case(S, H, Hk, D, bs, bps, window)
    ref = decode_attention_dense_paged(q, kp, vp, bt, vis, scale, w)
    out = flash_decode_attention_paged(q, kp, vp, bt, vis, scale, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-12, rtol=1e-12)


def test_paged_oracle_equals_gathered_dense_oracle():
    """The paged oracle is DEFINED as gather-then-dense: resolving the
    block table by hand and calling the contiguous oracle must be
    bit-identical."""
    q, kp, vp, bt, vis, scale, w = _paged_case(3, 4, 2, 16, 16, 4, 5)
    S, bps, bs = 3, 4, 16
    kc = kp[bt].reshape(S, bps * bs, 2, 16)
    vc = vp[bt].reshape(S, bps * bs, 2, 16)
    ref = decode_attention_dense(q, kc, vc, vis, scale, w)
    out = decode_attention_dense_paged(q, kp, vp, bt, vis, scale, w)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_paged_small_block_falls_back_to_dense():
    """block_size < 8 cannot tile the kernel — the paged entry point must
    take the dense paged path, bit-identical."""
    q, kp, vp, bt, vis, scale, w = _paged_case(2, 4, 2, 8, 4, 4, 0, seed=7)
    ref = decode_attention_dense_paged(q, kp, vp, bt, vis, scale, w)
    out = flash_decode_attention_paged(q, kp, vp, bt, vis, scale, w)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_engaged_through_serving_engine():
    """helpers forced ON routes serving decode through the split-K kernel;
    the engine's captured logprobs must still sit on the full-recompute
    fp64 oracle (the end-to-end acceptance gate)."""
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
    from deeplearning4j_tpu.serving import Request, ServingEngine
    from tests.test_serving import _assert_parity, _build_net

    net = _build_net(n_kv=2)
    prompt = [1, 2, 3, 4, 5]
    with helpers_enabled_ctx(True):
        eng = ServingEngine(net, max_seqs=2, max_len=32, seed=0,
                            capture_logprobs=True)
        res = eng.generate([Request(prompt, max_new_tokens=6)])[0]
    assert len(res.tokens) == 6
    _assert_parity(net, res, prompt)


def test_paged_kernel_engaged_through_serving_engine_with_sharing():
    """helpers forced ON routes the paged decode through the block-table-
    aware kernel, WITH prefix sharing active — captured logprobs stay on
    the full-recompute fp64 oracle for both the donor and the sharer."""
    from deeplearning4j_tpu.ops.helpers import helpers_enabled_ctx
    from deeplearning4j_tpu.serving import Request, ServingEngine
    from tests.test_serving import _assert_parity, _build_net

    net = _build_net(n_kv=2)
    p1 = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    p2 = p1[:8] + [11, 12]
    with helpers_enabled_ctx(True):
        eng = ServingEngine(net, max_seqs=2, max_len=32, seed=0,
                            capture_logprobs=True, kv_block=8,
                            prefix_share=True)
        r1, r2 = eng.generate([Request(p1, max_new_tokens=6),
                               Request(p2, max_new_tokens=6)])
    assert eng.stats()["prefix_hits"] == 1
    _assert_parity(net, r1, p1)
    _assert_parity(net, r2, p2)
