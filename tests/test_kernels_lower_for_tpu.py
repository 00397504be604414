"""Every registered Pallas helper must lower — and compile — for the chip.

The parity tests run the kernels with `interpret=True`, which never meets
the TPU lowering rules: three decode kernels shipped in PR 3/7/11 whose
block shapes the Pallas->Mosaic lowering refuses, and twenty PRs of CPU
tests never saw it. Here each helper (and the custom-VJP backwards) is
traced at the shapes chip_smoke.py runs, with `interpret_mode` forced to
False and x64 off as on the chip, and

1. cross-lowered on the CPU backend with `lowering_platforms=("tpu",)`; the
   lowered text must hold a `tpu_custom_call`. This is the check that needs
   nothing but JAX and catches a block-shape refusal in about a second;
2. where the installed libtpu will describe a v5e without one being
   attached (`jax.experimental.topologies`), compiled ahead of time for it —
   the chip's own compiler, so a scoped-VMEM overflow or an op Mosaic cannot
   lay out fails here and not on the first chip run.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.decode_attention import (
    flash_decode_attention_paged, flash_decode_attention_spec_paged)
from deeplearning4j_tpu.ops.flash_attention import flash_attention
from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul_kernel
from deeplearning4j_tpu.ops import lstm_scan_fused
from deeplearning4j_tpu.ops.lstm_scan_fused import graves_lstm_scan_pallas

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(autouse=True)
def as_on_the_chip(monkeypatch):
    monkeypatch.setattr(helpers, "interpret_mode", lambda: False)
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def v5e_sharding():
    """A sharding on one described (not attached) v5e chip, or None where
    this installation cannot describe one."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    skip_mds = os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except (RuntimeError, ValueError, NotImplementedError):
        return None                     # no libtpu, or it is held elsewhere
    finally:
        if skip_mds == "1":
            os.environ.pop("TPU_SKIP_MDS_QUERY", None)
    return SingleDeviceSharding(topo.devices[0])


def _sum(outs):
    return sum(jnp.sum(o.astype(F32)) for o in jax.tree.leaves(outs))


def _grad(fn, n):
    """fn's custom-VJP backward: grads of a scalar of its outputs w.r.t.
    the first n arguments."""
    return jax.grad(lambda *a: _sum(fn(*a)), argnums=tuple(range(n)))


# ---- the smoke's shapes
# flash attention: B4 H4 T8192 D64 bf16 (bench_attention_longcontext)
QKV = [((4, 4, 8192, 64), BF16)] * 3


def _flash(window=0, bwd=None):
    return lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           window=window, bwd=bwd)


# fused Graves-LSTM scan: T100 B8192 H256 bf16 (zoo TextGenerationLSTM)
T, B, H = 100, 8192, 256
SCAN = [((T, B, 4 * H), BF16), ((4 * H,), BF16), ((H, 4 * H), BF16),
        ((H,), BF16), ((H,), BF16), ((H,), BF16), ((B, H), BF16),
        ((B, H), BF16)]


def _scan_ys_only(*a):
    """What the layers do: cs dropped, so its cotangent is a symbolic zero
    and the backward is built without the dcs stream."""
    return graves_lstm_scan_pallas(*a)[0]


# paged decode: 8 slots, 4 heads / 2 kv heads x 64, 1024 positions in
# blocks of 16 (bench_decode_serving through ServingEngine)
S, NH, NKV, D, BS, BPS = 8, 4, 2, 64, 16, 64
NB = S * BPS + 1


def _paged(q_shape, pool_dtype):
    args = [(q_shape, BF16), ((NB, BS, NKV, D), pool_dtype),
            ((NB, BS, NKV, D), pool_dtype), ((S, BPS), I32), ((S,), I32)]
    if pool_dtype == I8:
        args += [((NB, NKV), F32)] * 2
    return args


def _decode(kernel, window=0):
    def fn(q, kp, vp, bt, vis, ks=None, vs=None):
        return kernel(q, kp, vp, bt, vis, 0.125, window, k_scale=ks,
                      v_scale=vs)
    return fn


# latent attention of the Xing4.0-29B-A4B share: 1 sequence of 4096, 4 heads
# held, QK 192 wide against V 128 (padded to 192 inside the layer)
MLA_QKV = [((1, 4, 4096, 192), BF16)] * 2 + [((1, 4, 4096, 128), BF16)]


def _latent_attend(q, k, v):
    from deeplearning4j_tpu.nn.conf.layers.decoder import LatentAttention
    with helpers.helpers_enabled_ctx(True):
        return LatentAttention(n_in=3584, n_out=3584, n_heads=32,
                               heads_held=4)._attend(q, k, v)


# the held experts' products of the same share: every assignment of 4096
# tokens x top-4 as rows (most fall to absent experts: the groups' sum is
# what runs), 8 experts of 3584 x 1024 and back
ROWS = 4096 * 4
GMM_UP = [((ROWS, 3584), BF16), ((8, 3584, 1024), BF16), ((8,), I32)]
GMM_DOWN = [((ROWS, 1024), BF16), ((8, 1024, 3584), BF16), ((8,), I32)]


def _gmm_grad(x, w, sizes):
    return jax.grad(lambda x_, w_: _sum(grouped_matmul_kernel(x_, w_, sizes)),
                    argnums=(0, 1))(x, w)


# the expert layer around them at the same shapes, value and gradient: 8 of
# 64 experts held, so the routed part is traced twice, over the 4096 rows of
# its bound and over all 16384, under one `conditional`
EXPERT_LAYER = [((1, 4096, 3584), BF16), ((3584, 64), BF16)] \
    + [((8, 3584, 1024), BF16)] * 2 + [((8, 1024, 3584), BF16)] \
    + [((3584, 1024), BF16)] * 2 + [((1024, 3584), BF16), ((64,), F32)]


def _expert_layer_grad(x, *leaves):
    from deeplearning4j_tpu.nn.conf.layers.decoder import RoutedExperts
    layer = RoutedExperts(n_in=3584, n_out=3584, n_experts=64, experts_held=8,
                          top_k=4, width=1024, routed_scaling_factor=2.0)
    assert layer.row_bound(ROWS) == 4096
    names = ("w_r", "e_w_g", "e_w_u", "e_w_d", "s_w_g", "s_w_u", "s_w_d")

    def loss(x_, params):
        state = dict(layer.init_state(None), router_bias=leaves[-1])
        return _sum(layer.forward(params, state, x_, train=True)[0])
    with helpers.helpers_enabled_ctx(True):
        return jax.value_and_grad(loss, (0, 1))(x, dict(zip(names, leaves)))


# the hyper-connection round a sublayer at the decoder cell's shape: the
# state (1, 4, 4096, 3584), phi's three pieces side by side, the maps' scales
# and biases, the norm's gain; the sublayer costs nothing
HC_N, HC_T, HC_D = 4, 4096, 3584
HYPER = [((1, HC_N, HC_T, HC_D), BF16), ((HC_N * HC_D, 2 * HC_N + HC_N ** 2), BF16),
         ((3,), BF16), ((HC_N,), BF16), ((HC_N,), BF16), ((HC_N, HC_N), BF16),
         ((HC_D,), BF16)]


def _hyper(x, *leaves):
    from deeplearning4j_tpu.ops.hyper_connection import hyper_connection
    return hyper_connection(
        x, *leaves, lambda u: (u, None), sinkhorn_iters=20, hc_eps=1e-6,
        clamp_min=-30.0, clamp_max=30.0, eps=1e-6)[0]


def _hyper_grad_recomputed(*a):
    """As `ComputationGraph._forward_all` has it: the layer under
    `jax.checkpoint`, so its forward is traced again in the backward pass;
    the value is kept, as the next layer keeps it."""
    layer = jax.checkpoint(_hyper)
    return jax.value_and_grad(lambda *b: _sum(layer(*b)),
                              argnums=tuple(range(len(a))))(*a)


# the Qwen3-Next share's mixers at the cell's shapes: 2 sequences of 8192;
# gated attention, 16 query heads of 256 on 2 k/v heads (grouped: the k/v
# specs map a query head to its group's row, forward and backward); the
# gated delta rule, 32 value heads with a 128 x 128 state each on 16 key
# heads (the kernels map a value head to its key head and sum dq, dk over the
# group)
GQA_QKV = [((2, 16, 8192, 256), BF16)] + [((2, 2, 8192, 256), BF16)] * 2
RULE = [((2, 8192, 16, 128), BF16)] * 2 + [((2, 8192, 32, 128), BF16)] \
    + [((2, 8192, 32), F32)] * 2
# the Olmo-Hybrid share's: 15 heads, one value head a key head, 96-wide keys
# and 192-wide values (no whole lane tiles), 4 heads a grid step and the last
# block reaching past the fifteenth
RULE_96_192 = [((1, 8192, 15, 96), BF16)] * 2 + [((1, 8192, 15, 192), BF16)] \
    + [((1, 8192, 15), F32)] * 2


def _delta_rule(*a):
    from deeplearning4j_tpu.ops.gated_delta_rule import gated_delta_rule
    return gated_delta_rule(*a)


def _delta_rule_grad_recomputed(*a):
    """As a `PreNormResidual` block has it: the rule under `jax.checkpoint`,
    so the forward runs again in the backward pass."""
    rule = jax.checkpoint(_delta_rule)
    return jax.value_and_grad(lambda *b: _sum(rule(*b)),
                              argnums=tuple(range(len(a))))(*a)


# the ResNet50 cell's two max pools, 3x3 windows at stride 2: the stem's over
# the ReLU of its first convolution, 512 x 64 maps of 112 x 112 pooled to
# 55 x 55 (the batch on lanes), and the head's over 4 x 4 maps of 2048
# channels (the channels on lanes); and a `Same` pool, padded with -inf
STEM_POOL = [((512, 64, 112, 112), BF16), ((512, 64, 55, 55), BF16)]
HEAD_POOL = [((512, 2048, 4, 4), BF16), ((512, 2048, 1, 1), BF16)]
SAME_POOL = [((128, 32, 27, 27), BF16), ((128, 32, 14, 14), BF16)]


def _max_pool_grad(padding=((0, 0), (0, 0))):
    from deeplearning4j_tpu.ops.max_pool import max_pool

    def fn(x, dy):
        return jax.vjp(lambda a: max_pool(a, (3, 3), (2, 2), padding), x)[1](dy)
    return fn


CASES = {
    "hyper_connection": (_hyper, HYPER),
    "hyper_connection bwd recomputed": (_hyper_grad_recomputed, HYPER),
    "flash_attention": (_flash(), QKV),
    "flash_attention window=1024": (_flash(1024), QKV),
    "flash_attention bwd fused": (_grad(_flash(), 3), QKV),
    "flash_attention bwd fused window=1024": (_grad(_flash(1024), 3), QKV),
    "flash_attention bwd two_pass": (_grad(_flash(bwd="two_pass"), 3), QKV),
    "flash_attention bwd two_pass window=1024":
        (_grad(_flash(1024, "two_pass"), 3), QKV),
    "flash_attention qk192 v128 (latent attention)": (_latent_attend, MLA_QKV),
    "flash_attention qk192 v128 bwd": (_grad(_latent_attend, 3), MLA_QKV),
    "flash_attention 16 heads on 2 k/v heads, width 256": (_flash(), GQA_QKV),
    "flash_attention 16 heads on 2 k/v heads, width 256 bwd":
        (_grad(_flash(), 3), GQA_QKV),
    "gated_delta_rule": (_delta_rule, RULE),
    "gated_delta_rule bwd": (_grad(_delta_rule, 5), RULE),
    "gated_delta_rule bwd recomputed": (_delta_rule_grad_recomputed, RULE),
    "gated_delta_rule 15 heads of 96 x 192": (_delta_rule, RULE_96_192),
    "gated_delta_rule 15 heads of 96 x 192 bwd recomputed":
        (_delta_rule_grad_recomputed, RULE_96_192),
    "grouped_matmul up": (grouped_matmul_kernel, GMM_UP),
    "grouped_matmul down": (grouped_matmul_kernel, GMM_DOWN),
    "grouped_matmul bwd": (_gmm_grad, GMM_UP),
    "grouped_matmul down bwd": (_gmm_grad, GMM_DOWN),
    "grouped_matmul in the expert layer, bounded and whole":
        (_expert_layer_grad, EXPERT_LAYER),
    "graves_lstm_scan": (graves_lstm_scan_pallas, SCAN),
    "graves_lstm_scan bwd": (_grad(graves_lstm_scan_pallas, 8), SCAN),
    "graves_lstm_scan bwd cs unused": (_grad(_scan_ys_only, 8), SCAN),
    "decode_attention_paged":
        (_decode(flash_decode_attention_paged), _paged((S, NH, D), BF16)),
    "decode_attention_paged window=256":
        (_decode(flash_decode_attention_paged, 256),
         _paged((S, NH, D), BF16)),
    "decode_attention_paged int8":
        (_decode(flash_decode_attention_paged), _paged((S, NH, D), I8)),
    "decode_attention_spec_paged Q=4":
        (_decode(flash_decode_attention_spec_paged),
         _paged((S, 4, NH, D), BF16)),
    "decode_attention_spec_paged Q=4 int8 window=256":
        (_decode(flash_decode_attention_spec_paged, 256),
         _paged((S, 4, NH, D), I8)),
    "max_pool_grad stem 3x3/2": (_max_pool_grad(), STEM_POOL),
    "max_pool_grad head 3x3/2": (_max_pool_grad(), HEAD_POOL),
    "max_pool_grad Same 3x3/2": (_max_pool_grad(((1, 1), (1, 1))), SAME_POOL),
}


# what else the lowered text has to hold: both branches of the routed part
LOWERED = {"grouped_matmul in the expert layer, bounded and whole":
           ("blocks/while",)}

# the names a device trace shows the scan's two Mosaic calls under
# (`kernel_name` of the custom call, the compiled instruction's name)
KERNEL_NAMES = {
    "graves_lstm_scan": ("dl4j_lstm_scan_fwd",),
    "graves_lstm_scan bwd": ("dl4j_lstm_scan_fwd", "dl4j_lstm_scan_bwd"),
    "graves_lstm_scan bwd cs unused":
        ("dl4j_lstm_scan_fwd", "dl4j_lstm_scan_bwd"),
    "hyper_connection": ("dl4j_hc_pre", "dl4j_hc_post"),
    "hyper_connection bwd recomputed":
        ("dl4j_hc_pre", "dl4j_hc_post", "dl4j_hc_post_bwd", "dl4j_hc_pre_bwd"),
    "gated_delta_rule": ("dl4j_gdr_fwd",),
    "gated_delta_rule bwd": ("dl4j_gdr_fwd", "dl4j_gdr_bwd"),
    "gated_delta_rule bwd recomputed": ("dl4j_gdr_fwd", "dl4j_gdr_bwd"),
    "gated_delta_rule 15 heads of 96 x 192": ("dl4j_gdr_fwd",),
    "gated_delta_rule 15 heads of 96 x 192 bwd recomputed":
        ("dl4j_gdr_fwd", "dl4j_gdr_bwd"),
    "max_pool_grad stem 3x3/2": ("dl4j_max_pool_bwd",),
    "max_pool_grad head 3x3/2": ("dl4j_max_pool_bwd",),
    "max_pool_grad Same 3x3/2": ("dl4j_max_pool_bwd",),
}

# how often a kernel stays in the compiled program: the recomputed forward's
# `post` writes an `out` nothing reads, and XLA drops the call; the rule's
# forward runs for the value and again, for the states its backward starts
# from, in the recomputation
COMPILED_CALLS = {
    "hyper_connection bwd recomputed":
        {"dl4j_hc_pre": 2, "dl4j_hc_post": 1, "dl4j_hc_post_bwd": 1,
         "dl4j_hc_pre_bwd": 1},
    "gated_delta_rule bwd recomputed": {"dl4j_gdr_fwd": 2, "dl4j_gdr_bwd": 1},
    "gated_delta_rule 15 heads of 96 x 192 bwd recomputed":
        {"dl4j_gdr_fwd": 2, "dl4j_gdr_bwd": 1},
}


def test_every_registered_helper_has_a_case():
    covered = {name.split()[0] for name in CASES}
    assert covered == set(helpers.registered_helpers())


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_lowers_and_compiles_for_tpu(name, v5e_sharding):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=name in LOWERED)
    assert "tpu_custom_call" in text, \
        f"{name}: no Mosaic call in the lowered text — the kernel gave way " \
        "to its reference"
    for kernel in KERNEL_NAMES.get(name, ()):
        assert f'kernel_name = "{kernel}"' in text
    for piece in LOWERED.get(name, ()):
        assert piece in text
    if v5e_sharding is None:
        return
    on_chip = [jax.ShapeDtypeStruct(s, d, sharding=v5e_sharding)
               for s, d in shapes]
    compiled = jax.jit(fn).lower(*on_chip).compile().as_text()
    calls = [line.split(" = ")[0] for line in compiled.splitlines()
             if " custom-call(" in line]
    for kernel in KERNEL_NAMES.get(name, ()):
        # `dl4j_lstm_scan_fwd.1` inside a net, `jvp_dl4j_lstm_scan_fwd_.1` here
        assert any(kernel in call for call in calls), calls
    for kernel, times in COMPILED_CALLS.get(name, {}).items():
        # `dl4j_hc_pre.1` the forward's, `jvp_dl4j_hc_pre_.1` the recomputed
        named = [c for c in calls if re.fullmatch(
            rf"%(jvp_)?{kernel}_?(\.\d+)?", c.strip())]
        assert len(named) == times, (kernel, calls)


@pytest.mark.parametrize("shape,heads", [
    # what the rule's kernels take on the chip: (n_k, n_v, d_k, d_v, itemsize)
    ((16, 32, 128, 128, 2), 4),      # the Qwen3-Next share's: two key heads
    ((15, 15, 96, 192, 2), 4),       # the Olmo-Hybrid share's: 4 heads of 96
                                     # and of 192 are 384 and 768 lanes, the
                                     # last block reaching past the fifteenth
    ((15, 15, 96, 192, 4), 4),
    ((15, 15, 128, 256, 2), 3),      # whole tiles: the most that divide 15
    ((2, 16, 128, 128, 2), 8),       # a group larger than a step's heads
    # and what they still refuse
    ((3, 3, 96, 192, 2), None),      # 4 heads make whole tiles, 3 are held
    ((15, 15, 100, 192, 2), None),   # no count under five makes 100 whole
    ((3, 3, 12, 24, 4), None),       # the tests' widths: interpreted only
    ((4, 6, 128, 128, 2), None),     # value heads in no whole groups
    ((15, 15, 96, 192, 8), None),    # wider than the float32 they reckon in
])
def test_the_rules_heads_a_step_on_the_chip(shape, heads):
    from deeplearning4j_tpu.ops.gated_delta_rule import heads_a_step
    assert heads_a_step(*shape) == heads


@pytest.mark.parametrize("stream_dcs", [True, False])
def test_scan_layout_at_the_smoke_shape(stream_dcs):
    """The estimate that picks the tiles the compiler is shown above: forward
    1024 and backward 512, batch-major, with the dcs stream and without it
    (the 0.5 MB it frees does not reach the next tile, which estimates
    several MB over the budget)."""
    assert lstm_scan_fused._pick_layout(T, B, H, 2, stream_dcs) == \
        (False, 1, 1024, 512)
    fits, next_up = (
        lstm_scan_fused._vmem_cost(H, 2, bt, True, bt, 1, stream_dcs)
        for bt in (512, 1024))
    assert fits <= lstm_scan_fused.VMEM_BUDGET < next_up - 4 * 1024 * 1024
