"""Pallas TPU kernels behind the helper seam.

Two hot ops where hand-tiling pays (everything else is left to XLA fusion):

- `lstm_gates`: the per-timestep gate nonlinearity + cell update of the LSTM scan
  body (ref nn/layers/recurrent/LSTMHelpers.java:200 — the reference's cudnn
  fast path). One VMEM-resident kernel computes sigmoid/tanh gates and the new
  (c, h) for a batch tile, replacing four separate slice+activation HLOs between
  the two MXU matmuls.
- `threshold_encode`: the gradient-compression quantizer of the SHARED_GRADIENTS
  path (ref EncodingHandler / threshold encoding) — elementwise ternarize with
  residual carry, the "quantization kernels" pattern from the Pallas guide.

Both run with `interpret=True` off-TPU so the CPU test mesh exercises the same
code path, and both have pure-jnp fallbacks wired through the seam.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.helpers import register_helper


def _interpret() -> bool:
    from deeplearning4j_tpu.ops.helpers import interpret_mode
    return interpret_mode()


# ------------------------------------------------------------------ lstm gates


def _lstm_gates_kernel(gates_ref, c_ref, c_out_ref, h_out_ref):
    import jax.nn as jnn
    acc = jnp.promote_types(gates_ref.dtype, jnp.float32)
    g = gates_ref[:].astype(acc)           # (TB, 4H)
    c = c_ref[:].astype(acc)               # (TB, H)
    H = c.shape[-1]
    zi = jnn.sigmoid(g[:, :H])
    zf = jnn.sigmoid(g[:, H:2 * H])
    zo = jnn.sigmoid(g[:, 2 * H:3 * H])
    zg = jnp.tanh(g[:, 3 * H:])
    c_new = zf * c + zi * zg
    c_out_ref[:] = c_new.astype(c_out_ref.dtype)
    h_out_ref[:] = (zo * jnp.tanh(c_new)).astype(h_out_ref.dtype)


def _lstm_gates_bwd_kernel(gates_ref, c_ref, dc_ref, dh_ref,
                           dgates_ref, dcprev_ref):
    """Backward: recompute activations from the saved inputs (remat-style — no
    forward activations are kept in HBM), then the closed-form gate gradients."""
    import jax.nn as jnn
    acc = jnp.promote_types(gates_ref.dtype, jnp.float32)
    g = gates_ref[:].astype(acc)
    c = c_ref[:].astype(acc)
    dc_new = dc_ref[:].astype(acc)
    dh = dh_ref[:].astype(acc)
    H = c.shape[-1]
    one = jnp.ones((), g.dtype)
    i = jnn.sigmoid(g[:, :H])
    f = jnn.sigmoid(g[:, H:2 * H])
    o = jnn.sigmoid(g[:, 2 * H:3 * H])
    gg = jnp.tanh(g[:, 3 * H:])
    c_new = f * c + i * gg
    t = jnp.tanh(c_new)
    do = dh * t
    dct = dc_new + dh * o * (one - t * t)
    dzi = dct * gg * i * (one - i)
    dzf = dct * c * f * (one - f)
    dzo = do * o * (one - o)
    dzg = dct * i * (one - gg * gg)
    dgates_ref[:] = jnp.concatenate([dzi, dzf, dzo, dzg],
                                    axis=-1).astype(dgates_ref.dtype)
    dcprev_ref[:] = (dct * f).astype(dcprev_ref.dtype)


def _batch_grid(B: int, tile: int = 512):
    """(grid, tile, padded_B) for tiling a batch dim into VMEM-sized rows."""
    tb = min(B, tile)
    Bp = (B + tb - 1) // tb * tb
    return (Bp // tb,), tb, Bp


def _pad_rows(a, Bp):
    return a if a.shape[0] == Bp else jnp.pad(
        a, ((0, Bp - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


@jax.custom_vjp
def lstm_gates_pallas(gates: jnp.ndarray, c: jnp.ndarray):
    """gates (B, 4H) pre-activations [i|f|o|g], c (B, H) -> (c_new, h_new).

    Gate order matches nn/conf/layers/recurrent.py:67-70 (zi, zf, zo, zg).
    Tiled over the batch (VMEM-sized row blocks); internally computed in
    fp32 for sub-fp32 activations (transcendentals in one pass, cast once at
    the boundary). Differentiable via a custom VJP whose backward is itself
    a Pallas kernel (the guide's Custom VJP pattern)."""
    from jax.experimental import pallas as pl
    B, H = c.shape
    grid, tb, Bp = _batch_grid(B)
    gates_p, c_p = _pad_rows(gates, Bp), _pad_rows(c, Bp)
    c_new, h_new = pl.pallas_call(
        _lstm_gates_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0))],
        out_specs=(pl.BlockSpec((tb, H), lambda b: (b, 0)),
                   pl.BlockSpec((tb, H), lambda b: (b, 0))),
        out_shape=(jax.ShapeDtypeStruct((Bp, H), c.dtype),
                   jax.ShapeDtypeStruct((Bp, H), c.dtype)),
        interpret=_interpret(),
    )(gates_p, c_p)
    return c_new[:B], h_new[:B]


def _lstm_gates_fwd(gates, c):
    return lstm_gates_pallas(gates, c), (gates, c)


def _lstm_gates_bwd(saved, cotangents):
    from jax.experimental import pallas as pl
    gates, c = saved
    dc_new, dh = cotangents
    B, H = c.shape
    grid, tb, Bp = _batch_grid(B)
    args = [_pad_rows(a, Bp) for a in (gates, c, dc_new, dh)]
    dgates, dc_prev = pl.pallas_call(
        _lstm_gates_bwd_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0))],
        out_specs=(pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                   pl.BlockSpec((tb, H), lambda b: (b, 0))),
        out_shape=(jax.ShapeDtypeStruct((Bp, 4 * H), gates.dtype),
                   jax.ShapeDtypeStruct((Bp, H), c.dtype)),
        interpret=_interpret(),
    )(*args)
    return dgates[:B], dc_prev[:B]


lstm_gates_pallas.defvjp(_lstm_gates_fwd, _lstm_gates_bwd)
register_helper("lstm_gates")(lstm_gates_pallas)


def lstm_gates_xla(gates: jnp.ndarray, c: jnp.ndarray):
    """Fallback: plain jnp (what the layer inlines today)."""
    H = c.shape[-1]
    zi = jax.nn.sigmoid(gates[:, :H])
    zf = jax.nn.sigmoid(gates[:, H:2 * H])
    zo = jax.nn.sigmoid(gates[:, 2 * H:3 * H])
    zg = jnp.tanh(gates[:, 3 * H:])
    c_new = zf * c + zi * zg
    return c_new, zo * jnp.tanh(c_new)


# --------------------------------------------------- graves (peephole) gates


def _graves_gates_kernel(gates_ref, c_ref, pi_ref, pf_ref, po_ref,
                         c_out_ref, h_out_ref):
    """Graves-2013 peephole cell update (ref CudnnLSTMHelper.java:175 — the
    reference's GravesLSTM fast path; math mirrors
    nn/conf/layers/recurrent.py:_step peephole branch)."""
    import jax.nn as jnn
    acc = jnp.promote_types(gates_ref.dtype, jnp.float32)
    g = gates_ref[:].astype(acc)           # (TB, 4H)
    c = c_ref[:].astype(acc)               # (TB, H)
    H = c.shape[-1]
    pi, pf, po = (r[:].astype(acc) for r in (pi_ref, pf_ref, po_ref))
    i = jnn.sigmoid(g[:, :H] + c * pi)
    f = jnn.sigmoid(g[:, H:2 * H] + c * pf)
    gg = jnp.tanh(g[:, 3 * H:])
    c_new = f * c + i * gg
    o = jnn.sigmoid(g[:, 2 * H:3 * H] + c_new * po)
    c_out_ref[:] = c_new.astype(c_out_ref.dtype)
    h_out_ref[:] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)


def _graves_gates_bwd_kernel(gates_ref, c_ref, pi_ref, pf_ref, po_ref,
                             dc_ref, dh_ref,
                             dgates_ref, dcprev_ref, dpi_ref, dpf_ref,
                             dpo_ref):
    """Backward with remat-style recompute (no forward activations kept)."""
    import jax.nn as jnn
    acc = jnp.promote_types(gates_ref.dtype, jnp.float32)
    g = gates_ref[:].astype(acc)
    c = c_ref[:].astype(acc)
    H = c.shape[-1]
    pi, pf, po = (r[:].astype(acc) for r in (pi_ref, pf_ref, po_ref))
    dc_new_in = dc_ref[:].astype(acc)
    dh = dh_ref[:].astype(acc)
    one = jnp.ones((), g.dtype)
    i = jnn.sigmoid(g[:, :H] + c * pi)
    f = jnn.sigmoid(g[:, H:2 * H] + c * pf)
    gg = jnp.tanh(g[:, 3 * H:])
    c_new = f * c + i * gg
    o = jnn.sigmoid(g[:, 2 * H:3 * H] + c_new * po)
    t = jnp.tanh(c_new)
    dzo = dh * t * o * (one - o)           # grad wrt zo + c_new*po
    dct = dc_new_in + dh * o * (one - t * t) + dzo * po
    dzi = dct * gg * i * (one - i)         # grad wrt zi + c*pi
    dzf = dct * c * f * (one - f)          # grad wrt zf + c*pf
    dzg = dct * i * (one - gg * gg)
    from jax.experimental import pallas as pl
    dgates_ref[:] = jnp.concatenate([dzi, dzf, dzo, dzg],
                                    axis=-1).astype(dgates_ref.dtype)
    dcprev_ref[:] = (dct * f + dzi * pi + dzf * pf).astype(dcprev_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dpi_ref[:] = jnp.zeros_like(dpi_ref)
        dpf_ref[:] = jnp.zeros_like(dpf_ref)
        dpo_ref[:] = jnp.zeros_like(dpo_ref)

    dpi_ref[:] += jnp.sum(dzi * c, axis=0, keepdims=True)
    dpf_ref[:] += jnp.sum(dzf * c, axis=0, keepdims=True)
    dpo_ref[:] += jnp.sum(dzo * c_new, axis=0, keepdims=True)


@jax.custom_vjp
def graves_gates_pallas(gates, c, pi, pf, po):
    """gates (B, 4H) pre-activations [i|f|o|g] (NO peephole terms added),
    c (B, H), pi/pf/po (H,) peephole weights -> (c_new, h_new).

    One VMEM-resident kernel for the whole Graves cell update — the
    elementwise chain between the scan's two MXU matmuls (ref
    LSTMHelpers.java:200 fwd; cuDNN fuses exactly this span)."""
    from jax.experimental import pallas as pl
    B, H = c.shape
    p2 = lambda v: v.reshape(1, H)
    grid, tb, Bp = _batch_grid(B)
    c_new, h_new = pl.pallas_call(
        _graves_gates_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0))],
        out_specs=(pl.BlockSpec((tb, H), lambda b: (b, 0)),
                   pl.BlockSpec((tb, H), lambda b: (b, 0))),
        out_shape=(jax.ShapeDtypeStruct((Bp, H), c.dtype),
                   jax.ShapeDtypeStruct((Bp, H), c.dtype)),
        interpret=_interpret(),
    )(_pad_rows(gates, Bp), _pad_rows(c, Bp), p2(pi), p2(pf), p2(po))
    return c_new[:B], h_new[:B]


def _graves_gates_fwd(gates, c, pi, pf, po):
    return graves_gates_pallas(gates, c, pi, pf, po), (gates, c, pi, pf, po)


def _graves_gates_bwd(saved, cotangents):
    from jax.experimental import pallas as pl
    gates, c, pi, pf, po = saved
    dc_new, dh = cotangents
    B, H = c.shape
    p2 = lambda v: v.reshape(1, H)
    grid, tb, Bp = _batch_grid(B)
    acc = jnp.promote_types(c.dtype, jnp.float32)
    # padded cotangent rows are zero, so they contribute nothing to the
    # accumulated peephole gradients
    dgates, dc_prev, dpi, dpf, dpo = pl.pallas_call(
        _graves_gates_bwd_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0)),
                  pl.BlockSpec((1, H), lambda b: (0, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0)),
                  pl.BlockSpec((tb, H), lambda b: (b, 0))],
        out_specs=(pl.BlockSpec((tb, 4 * H), lambda b: (b, 0)),
                   pl.BlockSpec((tb, H), lambda b: (b, 0)),
                   pl.BlockSpec((1, H), lambda b: (0, 0)),
                   pl.BlockSpec((1, H), lambda b: (0, 0)),
                   pl.BlockSpec((1, H), lambda b: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((Bp, 4 * H), gates.dtype),
                   jax.ShapeDtypeStruct((Bp, H), c.dtype),
                   jax.ShapeDtypeStruct((1, H), acc),
                   jax.ShapeDtypeStruct((1, H), acc),
                   jax.ShapeDtypeStruct((1, H), acc)),
        interpret=_interpret(),
    )(_pad_rows(gates, Bp), _pad_rows(c, Bp), p2(pi), p2(pf), p2(po),
      _pad_rows(dc_new, Bp), _pad_rows(dh, Bp))
    return (dgates[:B], dc_prev[:B], dpi.reshape(H).astype(pi.dtype),
            dpf.reshape(H).astype(pf.dtype), dpo.reshape(H).astype(po.dtype))


graves_gates_pallas.defvjp(_graves_gates_fwd, _graves_gates_bwd)
register_helper("graves_lstm_gates")(graves_gates_pallas)


def graves_gates_xla(gates, c, pi, pf, po):
    """Fallback: plain jnp peephole cell update (same math as the layer)."""
    H = c.shape[-1]
    i = jax.nn.sigmoid(gates[:, :H] + c * pi)
    f = jax.nn.sigmoid(gates[:, H:2 * H] + c * pf)
    gg = jnp.tanh(gates[:, 3 * H:])
    c_new = f * c + i * gg
    o = jax.nn.sigmoid(gates[:, 2 * H:3 * H] + c_new * po)
    return c_new, o * jnp.tanh(c_new)


# ------------------------------------------------------------ threshold encode


def _make_threshold_kernel(thr: float):
    def kernel(acc_ref, msg_ref, res_ref):
        acc = acc_ref[:]
        mask = jnp.abs(acc) >= thr
        msg = jnp.where(mask, jnp.sign(acc) * thr, 0.0).astype(acc.dtype)
        msg_ref[:] = msg
        res_ref[:] = acc - msg
    return kernel


@register_helper("threshold_encode")
@functools.partial(jax.jit, static_argnames=("threshold",))
def threshold_encode_pallas(update: jnp.ndarray, residual: jnp.ndarray,
                            threshold: float):
    """Ternarize update+residual to {-t, 0, +t} with residual carry — same
    contract as parallel/accumulation.threshold_encode. The threshold is a
    compile-time constant (one compiled kernel per threshold value, exactly like
    the reference's fixed EncodingHandler threshold)."""
    from jax.experimental import pallas as pl
    n = update.shape[0]
    lanes = 128
    rows = -(-n // lanes)
    # row tiles of at most 1024 x 128: one input and two output blocks,
    # double-buffered, stay far under the 16 MiB scoped-VMEM limit at any n
    # (ungridded, a 1M-element update asked for 20 MB and did not compile)
    tile = min(1024, -(-rows // 8) * 8)
    rows = -(-rows // tile) * tile
    acc = update + residual
    acc2d = jnp.zeros((rows * lanes,), update.dtype).at[:n].set(acc) \
        .reshape(rows, lanes)
    block = pl.BlockSpec((tile, lanes), lambda i: (i, 0))
    msg2d, res2d = pl.pallas_call(
        _make_threshold_kernel(float(threshold)),
        grid=(rows // tile,),
        in_specs=[block],
        out_specs=(block, block),
        out_shape=(jax.ShapeDtypeStruct((rows, lanes), update.dtype),
                   jax.ShapeDtypeStruct((rows, lanes), update.dtype)),
        interpret=_interpret(),
    )(acc2d)
    return msg2d.reshape(-1)[:n], res2d.reshape(-1)[:n]
