"""Whole-sequence fused Graves-LSTM scan kernel — the cuDNN-LSTM analog.

Parity target: ref deeplearning4j-cuda/.../CudnnLSTMHelper.java:175 — cuDNN
replaces the reference's per-timestep Java loop (LSTMHelpers.java:200/:403)
with ONE fused sequence kernel. This kernel runs the ENTIRE recurrence as
one `pallas_call` forward and one backward.

Operands, `graves_lstm_scan_pallas(xw, b, rw, pi, pf, po, h0, c0)`:
xw (T, B, 4H) the input projection x @ W WITHOUT the bias, b (4H,) the
gates' bias, rw (H, 4H), pi/pf/po (H,) peepholes (zeros for a plain LSTM),
h0/c0 (B, H); it returns ys, cs (T, B, H). The projection stays OUTSIDE the
kernel (one big MXU matmul over all timesteps that XLA schedules itself),
and so does the bias add, as plain XLA inside this function (`_biased`): it
fuses into that matmul's epilogue and the kernels stream one array. The
bias is an argument of its own because its GRADIENT belongs to the backward
kernel: the column sum of the gate gradients is accumulated where those
rows already are, and XLA does not read the (T, B, 4H) gate gradient a
third time only to add its rows. (Adding the bias in VMEM instead was
refused by the compiler: the forward at its tile of 1024 stands 0.07 MB
under the 16 MB scoped limit, and one more operand and add take 0.4 MB;
PERF.md section 6, PR 26.)

- per step: the xw_t block (bias included) streams in (double-buffered DMA
  under the grid pipeline), gates = xw_t + h @ RW on the MXU, peephole cell
  update on the VPU, h_t/c_t blocks stream out;
- backward: a second Pallas kernel scans in reverse, RECOMPUTING the gates
  from (xw_t, h_{t-1}, c_{t-1}) — nothing but the (already-emitted) h/c
  sequences is saved — writing dxw and accumulating dRW, db (rows of ones
  under h_prev^T in dRW's product) and the peephole grads in float32 VMEM
  scratch, zeroed on the
  first grid step and flushed on the last. It streams xw, h_prev, c_prev and
  dys in and dxw out, and the cotangent of cs ONLY where the caller consumed
  cs: the VJP is registered with `symbolic_zeros=True`, and a symbolic-zero
  dcs (the layers drop cs) builds the kernel without that operand
  (`_make_bwd_kernel(stream_dcs=False)`). Which variant a trace got is
  counted in `ops.lstm_scan.bwd.dcs_streamed` / `.dcs_elided`.
- h_prev/c_prev are read DIRECTLY from the forward's ys/cs outputs via a
  one-step-shifted clamped index map (initial state substituted in-kernel
  at the t=0 boundary): no (T, B, H) concat copies.

Two grid layouts share one kernel body (`_make_fwd_kernel`/
`_make_bwd_kernel`), plus a K-timestep tile factor:

- BATCH-major grid (B/bt, T/K) — THE DEFAULT: each batch tile runs its
  whole time sweep before the next tile starts, so only a (bt, H) h/c
  scratch is resident and the streamed tiles can be as large as VMEM
  allows; works at ANY batch size.
- TIME-major grid (T/K, B/bt): the FULL (B, H) h/c state resident in VMEM
  scratch, batch tiles iterating fastest. Selectable via
  configure(grid="tm").
- K > 1 processes K consecutive timesteps per grid step (streaming a
  (K, bt, 4H) xw block); VMEM caps K*bt, so K > 1 shrinks bt.

Why batch-major at K=1 with the biggest tiles is the default: an A/B of the
plug-in era (before PR 21; NOT measured on today's code or by today's
benchmark) found time-major and every K > 1 slower at each VMEM-feasible
tile. What today's kernels measure is in PERF.md sections 5 and 6.

Composition: under GSPMD (ShardedTrainer dp x tp) the kernel is an opaque
custom call — XLA reshards its operands around it, so correctness holds at
any sharding (parity-tested on the 8-device mesh). NOTE: default-on applies
to tp runs too; there the custom call implies per-step gathers of the
gate-dim-sharded RW — once real multi-chip hardware is available, measure
that cost and add a sharding-aware guard here if it loses to GSPMD's
partitioned lax.scan.

Gate order [i|f|o|g] matches nn/conf/layers/recurrent.py. Internal gate math
is fp32 by default (accumulated one width above bf16 activations); h/c
carries round-trip through the activation dtype between steps exactly like
the unfused scan, so helpers-on training matches helpers-off within bf16
rounding (exact in fp32/fp64 tests). `configure(gate_math="native")` keeps
gate math in the activation dtype. The layouts are module constants that
only `configure()` changes (no environment variable reads them): what is
left to select is ROADMAP.md, Design 11.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.helpers import register_helper


def _interpret() -> bool:
    from deeplearning4j_tpu.ops.helpers import interpret_mode
    return interpret_mode()


# Headroom under Mosaic's 16 MB scoped VMEM limit, calibrated against
# compiles: at the bench shape (H=256, bf16) the estimate for the largest
# config that compiles (bwd bt=512) is 14.69 MB (14.17 without the dcs
# stream) and the smallest that fails (bwd bt=1024, fwd bt=2048, tm
# 1024/512) estimates >= 19 MB — 15 MB splits them. The picked layout (fwd
# 1024 / bwd 512) compiles under libtpu 0.0.34 for a v5e
# (tests/test_kernels_lower_for_tpu.py). The compiler's own count there
# (AOT compile, PR 26): fwd 15.93 MB of its 16.00, so the forward kernel has
# room for nothing more; bwd 11.74 MB with the dcs stream, 11.26 without.
VMEM_BUDGET = 15 * 1024 * 1024

_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)

# Dispatch knobs — production defaults; configure() overrides for A/Bs.
#   grid: "auto" = batch-major (module docstring); "tm" / "bm" force one
#         layout.
#   k_steps: 0 = auto (largest of _K_CANDIDATES dividing T that fits VMEM),
#            n >= 1 forces K=n (requires K | T).
#   gate_math: "fp32" promotes gate math one width up; "native" keeps the
#              activation dtype (bf16 in, bf16 math).
_CONFIG = {
    "grid": "auto",
    "k_steps": 0,
    "gate_math": "fp32",
}

_K_CANDIDATES = (8, 5, 4, 2, 1)


def configure(**kw):
    """Override dispatch knobs (grid / k_steps / gate_math); returns the
    previous values so experiments can restore them.

    NOTE: the knobs are read at TRACE time — a function jitted before the
    configure() call keeps its compiled layout (JAX returns the cached
    executable). An A/B harness must build a fresh jit per configuration."""
    prev = dict(_CONFIG)
    for k, v in kw.items():
        if k not in _CONFIG:
            raise KeyError(f"unknown lstm_scan_fused config key {k!r}")
        _CONFIG[k] = v
    return prev


def _vmem_cost(H: int, db: int, bt: int, bwd: bool, state_rows: int,
               K: int = 1, stream_dcs: bool = True) -> int:
    """Estimated resident VMEM. `state_rows` is the h/c (fwd) or dh/dc (bwd)
    scratch height: bt for batch-major, padded B for time-major. Streamed
    blocks are double-buffered; per K-step row bytes count the streams that
    exist: fwd = xw in (2x4H) + ys/cs out (2x2xH) = 12*H*db; bwd = xw in
    (2x4H) + h_prev, c_prev, dys in (2x3xH) + dxw out (2x4H) = 22*H*db, and
    24*H*db with the dcs stream (`stream_dcs`; see _scan_bwd). The fp32
    gate intermediates (bt, 4H) and, for bwd, the dRW, bias and peephole
    accumulators + their constant-index-map output blocks are counted
    explicitly."""
    rw = 4 * H * H * db                      # streamed (H, 4H) weight block
    acc = 2 * (4 * H * H * 4) + 2 * (7 * H * 4) if bwd else 0
    interm = bt * 4 * H * 4 * (2 if bwd else 1)
    state = 2 * state_rows * H * db
    per_k = ((24 if stream_dcs else 22) if bwd else 12) * H * db
    fixed = 4 * H * db                       # h0/c0 or dh0/dc0 blocks (2x)
    return rw + acc + interm + state + bt * (K * per_k + fixed)


def _pick_bt(B: int, H: int, db: int, bwd: bool, time_major: bool,
             K: int = 1, stream_dcs: bool = True):
    """Largest VMEM-fitting batch tile (None if nothing fits in time-major
    mode — the caller then falls back to batch-major). B is PADDED up to a
    tile multiple by the callers (zero rows compute garbage that is sliced
    off; their zero cotangents contribute nothing to parameter grads)."""
    for bt in _TILES:
        if bt > B:
            continue
        sr = (-(-B // bt) * bt) if time_major else bt
        if _vmem_cost(H, db, bt, bwd, sr, K, stream_dcs) <= VMEM_BUDGET:
            return bt
    return None if time_major else min(B, 8)


def _pick_layout(T: int, B: int, H: int, db: int, stream_dcs: bool = True):
    """Resolve (time_major, K, bt_fwd, bt_bwd) from the config + shape;
    `stream_dcs` says whether the backward being laid out has the dcs
    stream (the forward's call leaves it at the larger estimate)."""
    mode = _CONFIG["grid"]
    if _CONFIG["k_steps"]:
        ks = (_CONFIG["k_steps"],)
        if T % ks[0]:
            # a FORCED K that does not divide T must fail loudly — silently
            # degrading to the min-tile config would make any A/B forcing K
            # report garbage with no error
            raise ValueError(
                f"forced k_steps={ks[0]} does not divide T={T}")
    else:
        ks = _K_CANDIDATES
    # auto grid = batch-major: tm's full-state scratch shrinks the streamed
    # tiles, adding grid steps (slower in the plug-in era's A/B; not
    # measured on today's code). tm stays selectable via configure(grid="tm").
    modes = (mode == "tm",) if mode in ("tm", "bm") else (False,)
    best = None
    for tm in modes:
        for K in ks:
            if T % K:
                continue
            bt_f = _pick_bt(B, H, db, False, tm, K)
            bt_b = _pick_bt(B, H, db, True, tm, K, stream_dcs)
            if bt_f is None or bt_b is None:
                continue
            # the biggest tiles win (plug-in era A/B, not measured on
            # today's code: bm K=1 1024/512 beat every K>1 config even when
            # K*bt said fewer grid steps; per-step DMA/MXU efficiency of
            # large tiles dominates). Prefer max tile bytes, then smaller K.
            score = (bt_f + bt_b, -K)
            if best is None or score > best[0]:
                best = (score, (tm, K, bt_f, bt_b))
    if best is not None:
        return best[1]
    if mode != "auto" or _CONFIG["k_steps"]:
        raise ValueError(
            f"forced layout grid={mode!r} k_steps={_CONFIG['k_steps']} "
            f"cannot fit VMEM at T={T} B={B} H={H}")
    # nothing fits even batch-major at K=1 with the smallest tile: callers
    # should have gated on fits_vmem; degrade to the smallest config
    return False, 1, min(B, 8), min(B, 8)


def _pad_batch(a, Bp):
    """Zero-pad dim 1 (batch) of a (T/1, B, ...) array up to Bp rows."""
    if a.shape[1] == Bp:
        return a
    pad = [(0, 0)] * a.ndim
    pad[1] = (0, Bp - a.shape[1])
    return jnp.pad(a, pad)


def fits_vmem(B: int, H: int, dtype_bytes: int = 2) -> bool:
    """Callers fall back to lax.scan when even the smallest batch-major tile
    cannot fit — the kernel is default-on, so oversize shapes must degrade
    gracefully, not fail to compile."""
    return _vmem_cost(H, dtype_bytes, min(B, 8), True, min(B, 8)) \
        <= VMEM_BUDGET


def _gate_acc(dtype):
    if _CONFIG["gate_math"] == "native":
        return dtype
    return jnp.promote_types(dtype, jnp.float32)


def _make_fwd_kernel(time_major: bool, K: int):
    """One grid step of the forward recurrence, covering K timesteps of one
    batch tile. Batch-major: tile b finishes its whole time sweep before
    tile b+1 (the (bt, H) scratch is private to the running tile).
    Time-major: the scratch holds the FULL padded-(B, H) state and tiles
    iterate fastest; each tile reads/writes only its own row slice."""
    from jax.experimental import pallas as pl

    def kernel(xw_ref, rw_ref, pi_ref, pf_ref, po_ref, h0_ref, c0_ref,
               ys_ref, cs_ref, h_scr, c_scr):
        bt = xw_ref.shape[1]
        if time_major:
            t, b = pl.program_id(0), pl.program_id(1)
            rows = pl.ds(b * bt, bt)
        else:
            t = pl.program_id(1)
            rows = slice(None)
        acc = _gate_acc(xw_ref.dtype)
        H = c0_ref.shape[-1]

        @pl.when(t == 0)
        def _():  # adopt the initial state for this batch tile
            h_scr[rows] = h0_ref[0]
            c_scr[rows] = c0_ref[0]

        h_t = h_scr[rows]                           # (bt, H) storage dtype
        c_t = c_scr[rows]
        pi = pi_ref[:].astype(acc)
        pf = pf_ref[:].astype(acc)
        po = po_ref[:].astype(acc)
        for k in range(K):
            c = c_t.astype(acc)
            gates = xw_ref[k].astype(acc) + jnp.dot(
                h_t, rw_ref[:], preferred_element_type=acc)
            i = jax.nn.sigmoid(gates[:, :H] + c * pi)
            f = jax.nn.sigmoid(gates[:, H:2 * H] + c * pf)
            g = jnp.tanh(gates[:, 3 * H:])
            c_new = f * c + i * g
            o = jax.nn.sigmoid(gates[:, 2 * H:3 * H] + c_new * po)
            h_new = o * jnp.tanh(c_new)
            # round-trip through the storage dtype between sub-steps so K>1
            # matches K=1 (and the lax.scan fallback) bit-for-bit
            h_t = h_new.astype(ys_ref.dtype)
            c_t = c_new.astype(cs_ref.dtype)
            ys_ref[k] = h_t
            cs_ref[k] = c_t
        h_scr[rows] = h_t
        c_scr[rows] = c_t

    return kernel


def _make_bwd_kernel(time_major: bool, K: int, direct_prev: bool = False,
                     stream_dcs: bool = True):
    """Reverse-sweep grid step covering K timesteps, recomputing the gates
    from streamed (xw, h_prev, c_prev) and folding the cs-cotangents into
    the carried dc. dRW / bias / peephole grads accumulate in VMEM scratch
    across the whole grid (zeroed on the first step, flushed on the last).

    stream_dcs=False: the cotangent of cs is a symbolic zero (the caller
    dropped cs, as LSTM.forward does), so the kernel has no dcs operand and
    the carried dc is used as it is.

    direct_prev (K=1 only): h_prev/c_prev are read DIRECTLY from the fwd's
    ys/cs outputs with a one-step-shifted (clamped) index map, selecting the
    streamed h0/c0 block at the time-0 step in-kernel — this deletes the
    hprev/cprev concat materialization (two (T, B, H) HBM copies per
    backward) the non-direct path pays."""
    from jax.experimental import pallas as pl

    def kernel(xw_ref, rw_ref, pi_ref, pf_ref, po_ref,
               hprev_ref, cprev_ref, h0_ref, c0_ref, dys_ref, *rest):
        if stream_dcs:
            dcs_ref, *rest = rest
        (dxw_ref, db_ref, drw_ref, dpi_ref, dpf_ref, dpo_ref, dh0_ref,
         dc0_ref, dh_scr, dc_scr, db_scr, drw_scr, dp_scr) = rest
        bt = xw_ref.shape[1]
        if time_major:
            t, b = pl.program_id(0), pl.program_id(1)
            nb = pl.num_programs(1)
            nt = pl.num_programs(0)
            rows = pl.ds(b * bt, bt)
        else:
            b, t = pl.program_id(0), pl.program_id(1)
            nb = pl.num_programs(0)
            nt = pl.num_programs(1)
            rows = slice(None)
        acc = _gate_acc(xw_ref.dtype)
        H = pi_ref.shape[-1]

        @pl.when(t == 0)
        def _():  # start of this tile's reversed sweep
            dh_scr[rows] = jnp.zeros((bt, H), dh_scr.dtype)
            dc_scr[rows] = jnp.zeros((bt, H), dc_scr.dtype)

        @pl.when((t == 0) & (b == 0))
        def _():
            db_scr[:] = jnp.zeros_like(db_scr)
            drw_scr[:] = jnp.zeros_like(drw_scr)
            dp_scr[:] = jnp.zeros_like(dp_scr)

        pi = pi_ref[:].astype(acc)
        pf = pf_ref[:].astype(acc)
        po = po_ref[:].astype(acc)
        dh_c = dh_scr[rows].astype(acc)
        dc_c = dc_scr[rows].astype(acc)
        one = jnp.ones((), acc)
        # one sublane tile of ones: under h_prev^T in dRW's product they make
        # the bias gradient's column sum in the pass of dgl through the MXU
        # that gives dRW (row H). A product of its own, or a bt-row VPU
        # reduction, cost 0.15-0.25 ms a call more at the benchmark's shape
        # (PERF.md section 6, PR 26)
        ones = jnp.ones((16, bt), hprev_ref.dtype)
        # the block holds K timesteps in ascending time order; the reversed
        # sweep processes them k = K-1 .. 0
        for k in reversed(range(K)):
            if direct_prev:
                # grid step t (reversed) handles time nt-1-t; its h_prev is
                # ys[time-1], streamed via the clamped shifted index map —
                # at time 0 (t == nt-1) substitute the initial state
                is_first = (t == nt - 1)
                h_prev = jnp.where(is_first, h0_ref[0], hprev_ref[k])
                c_prev = jnp.where(is_first, c0_ref[0],
                                   cprev_ref[k]).astype(acc)
            else:
                h_prev = hprev_ref[k]
                c_prev = cprev_ref[k].astype(acc)
            gates = xw_ref[k].astype(acc) + jnp.dot(
                h_prev, rw_ref[:], preferred_element_type=acc)
            i = jax.nn.sigmoid(gates[:, :H] + c_prev * pi)
            f = jax.nn.sigmoid(gates[:, H:2 * H] + c_prev * pf)
            g = jnp.tanh(gates[:, 3 * H:])
            c_new = f * c_prev + i * g
            o = jax.nn.sigmoid(gates[:, 2 * H:3 * H] + c_new * po)
            t_new = jnp.tanh(c_new)
            dh = dys_ref[k].astype(acc) + dh_c
            dc_in = dc_c + dcs_ref[k].astype(acc) if stream_dcs else dc_c
            dzo = dh * t_new * o * (one - o)
            dct = dc_in + dh * o * (one - t_new * t_new) + dzo * po
            dzi = dct * g * i * (one - i)
            dzf = dct * c_prev * f * (one - f)
            dzg = dct * i * (one - g * g)
            dgates = jnp.concatenate([dzi, dzf, dzo, dzg], axis=-1)
            dxw_ref[k] = dgates.astype(dxw_ref.dtype)
            dgl = dgates.astype(h_prev.dtype)
            dh_c = jnp.dot(dgl, rw_ref[:].T, preferred_element_type=acc)
            dc_c = dct * f + dzi * pi + dzf * pf
            prod = jnp.dot(jnp.concatenate([h_prev.T, ones], axis=0), dgl,
                           preferred_element_type=drw_scr.dtype)
            drw_scr[:] += prod[:H]
            db_scr[:] += prod[H:H + 1]
            dp_scr[0:1] += jnp.sum(dzi * c_prev, axis=0,
                                   keepdims=True).astype(dp_scr.dtype)
            dp_scr[1:2] += jnp.sum(dzf * c_prev, axis=0,
                                   keepdims=True).astype(dp_scr.dtype)
            dp_scr[2:3] += jnp.sum(dzo * c_new, axis=0,
                                   keepdims=True).astype(dp_scr.dtype)
        dh_scr[rows] = dh_c.astype(dh_scr.dtype)
        dc_scr[rows] = dc_c.astype(dc_scr.dtype)

        @pl.when((t == nt - 1) & (b == nb - 1))
        def _():
            db_ref[:] = db_scr[:]
            drw_ref[:] = drw_scr[:]
            dpi_ref[:] = dp_scr[0:1]
            dpf_ref[:] = dp_scr[1:2]
            dpo_ref[:] = dp_scr[2:3]

        @pl.when(t == nt - 1)
        def _():  # after processing t=0 (reversed), the carries are dh0/dc0
            dh0_ref[0] = dh_scr[rows].astype(dh0_ref.dtype)
            dc0_ref[0] = dc_scr[rows].astype(dc0_ref.dtype)

    return kernel


@jax.custom_vjp
def graves_lstm_scan_pallas(xw, b, rw, pi, pf, po, h0, c0):
    """xw (T, B, 4H) input projection x @ W (no bias), b (4H,) the gates'
    bias, rw (H, 4H), pi/pf/po (H,), h0/c0 (B, H) -> (ys (T, B, H),
    cs (T, B, H)).

    The whole recurrence as one Pallas call; see module docstring."""
    return _scan_fwd_impl(_biased(xw, b), rw, pi, pf, po, h0, c0)


def _biased(xw, b):
    """The gates' input term xw + b, in xw's type. Plain XLA on purpose: it
    fuses into the epilogue of the projection matmul that produced xw, so
    the sum costs no pass of its own and the kernels stream one array."""
    return xw + b.astype(xw.dtype)


def _scan_fwd_impl(xw, rw, pi, pf, po, h0, c0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, B, H4 = xw.shape
    H = H4 // 4
    db = jnp.dtype(xw.dtype).itemsize
    tm, K, bt, _ = _pick_layout(T, B, H, db)
    Bp = -(-B // bt) * bt
    nb = Bp // bt
    nt = T // K
    xw = _pad_batch(xw, Bp)
    h0p = _pad_batch(h0[None], Bp)
    c0p = _pad_batch(c0[None], Bp)
    p2 = lambda v: v.reshape(1, H)
    grid = (nt, nb) if tm else (nb, nt)
    if tm:
        xmap = lambda t, b: (t, b, 0)
        cmap = lambda t, b: (0, 0)
        pmap_ = lambda t, b: (0, b, 0)
    else:
        xmap = lambda b, t: (t, b, 0)
        cmap = lambda b, t: (0, 0)
        pmap_ = lambda b, t: (0, b, 0)
    ys, cs = pl.pallas_call(
        _make_fwd_kernel(tm, K),
        name="dl4j_lstm_scan_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bt, 4 * H), xmap),
            pl.BlockSpec((H, 4 * H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, bt, H), pmap_),
            pl.BlockSpec((1, bt, H), pmap_),
        ],
        out_specs=(
            pl.BlockSpec((K, bt, H), xmap),
            pl.BlockSpec((K, bt, H), xmap),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((T, Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((T, Bp, H), xw.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((Bp if tm else bt, H), xw.dtype),
            pltpu.VMEM((Bp if tm else bt, H), xw.dtype),
        ],
        interpret=_interpret(),
    )(xw, rw, p2(pi), p2(pf), p2(po), h0p, c0p)
    return ys[:, :B], cs[:, :B]


def _scan_fwd(*primals):
    # symbolic_zeros=True hands the primals over as CustomVJPPrimal
    xw, b, *rest = (p.value for p in primals)
    xw = _biased(xw, b)
    ys, cs = _scan_fwd_impl(xw, *rest)
    return (ys, cs), (xw, b, *rest, ys, cs)


def _scan_bwd(saved, cots):
    from jax.custom_derivatives import SymbolicZero
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from deeplearning4j_tpu import telemetry
    xw, b, rw, pi, pf, po, h0, c0, ys, cs = saved     # xw with the bias
    dys, dcs = cots
    # cs is exposed mainly for this backward; a caller that drops it (the
    # layers do: only ys reaches the loss) leaves a symbolic-zero cotangent,
    # and the kernel is then built without the dcs stream. Where cs is
    # consumed, dcs_t is folded into the carried dc BEFORE the gate
    # backward of step t, inside the kernel.
    stream_dcs = not isinstance(dcs, SymbolicZero)
    telemetry.registry().counter(
        f"ops.lstm_scan.bwd.dcs_{'streamed' if stream_dcs else 'elided'}",
        "fused LSTM scan backwards traced, by whether the cotangent of cs "
        "is streamed or was a symbolic zero").inc()
    if isinstance(dys, SymbolicZero):
        dys = jnp.zeros(dys.shape, dys.dtype)
    T, B, H4 = xw.shape
    H = H4 // 4
    db = jnp.dtype(xw.dtype).itemsize
    tm, K, _, bt = _pick_layout(T, B, H, db, stream_dcs)
    Bp = -(-B // bt) * bt
    nb = Bp // bt
    nt = T // K
    p2 = lambda v: v.reshape(1, H)
    acc = jnp.promote_types(xw.dtype, jnp.float32)
    grid = (nt, nb) if tm else (nb, nt)
    if tm:
        rev = lambda t, b: (nt - 1 - t, b, 0)
        cmap = lambda t, b: (0, 0)
        pmap_ = lambda t, b: (0, b, 0)
    else:
        rev = lambda b, t: (nt - 1 - t, b, 0)
        cmap = lambda b, t: (0, 0)
        pmap_ = lambda b, t: (0, b, 0)
    direct = K == 1
    if direct:
        # read h_prev/c_prev straight from ys/cs via the one-step-shifted
        # clamped map (the t==0 boundary substitutes h0/c0 in-kernel) —
        # no (T, B, H) concat copies
        hsrc = _pad_batch(ys, Bp)
        csrc = _pad_batch(cs, Bp)
        if tm:
            prev_map = lambda t, b: (jnp.maximum(nt - 2 - t, 0), b, 0)
        else:
            prev_map = lambda b, t: (jnp.maximum(nt - 2 - t, 0), b, 0)
    else:
        hsrc = _pad_batch(jnp.concatenate([h0[None], ys[:-1]], axis=0), Bp)
        csrc = _pad_batch(jnp.concatenate([c0[None], cs[:-1]], axis=0), Bp)
        prev_map = rev
    h0p = _pad_batch(h0[None], Bp)
    c0p = _pad_batch(c0[None], Bp)
    xw = _pad_batch(xw, Bp)
    streams = [_pad_batch(dys, Bp)]
    if stream_dcs:
        streams.append(_pad_batch(dcs, Bp))
    dxw, dbias, drw, dpi, dpf, dpo, dh0, dc0 = pl.pallas_call(
        _make_bwd_kernel(tm, K, direct_prev=direct, stream_dcs=stream_dcs),
        name="dl4j_lstm_scan_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bt, 4 * H), rev),
            pl.BlockSpec((H, 4 * H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((K, bt, H), prev_map),
            pl.BlockSpec((K, bt, H), prev_map),
            pl.BlockSpec((1, bt, H), pmap_),
            pl.BlockSpec((1, bt, H), pmap_),
        ] + [pl.BlockSpec((K, bt, H), rev)] * len(streams),
        out_specs=(
            pl.BlockSpec((K, bt, 4 * H), rev),
            pl.BlockSpec((1, 4 * H), cmap),
            pl.BlockSpec((H, 4 * H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, H), cmap),
            pl.BlockSpec((1, bt, H), pmap_),
            pl.BlockSpec((1, bt, H), pmap_),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((T, Bp, 4 * H), xw.dtype),
            jax.ShapeDtypeStruct((1, 4 * H), acc),
            jax.ShapeDtypeStruct((H, 4 * H), acc),
            jax.ShapeDtypeStruct((1, H), acc),
            jax.ShapeDtypeStruct((1, H), acc),
            jax.ShapeDtypeStruct((1, H), acc),
            jax.ShapeDtypeStruct((1, Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((1, Bp, H), xw.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((Bp if tm else bt, H), xw.dtype),
            pltpu.VMEM((Bp if tm else bt, H), xw.dtype),
            pltpu.VMEM((1, 4 * H), acc),
            pltpu.VMEM((H, 4 * H), acc),
            pltpu.VMEM((3, H), acc),
        ],
        interpret=_interpret(),
    )(xw, rw, p2(pi), p2(pf), p2(po), hsrc, csrc, h0p, c0p, *streams)
    return (dxw[:, :B], dbias.reshape(4 * H).astype(b.dtype),
            drw.astype(rw.dtype), dpi.reshape(H).astype(pi.dtype),
            dpf.reshape(H).astype(pf.dtype), dpo.reshape(H).astype(po.dtype),
            dh0[0, :B], dc0[0, :B])


graves_lstm_scan_pallas.defvjp(_scan_fwd, _scan_bwd, symbolic_zeros=True)
# default-on for TPU (batch-major fwd-1024/bwd-512 K=1 with the direct-prev
# backward); exact fp64 parity + bf16 net-level equivalence tests gate every
# layout. What it measures on today's code: PERF.md sections 5 and 6.
register_helper("graves_lstm_scan")(graves_lstm_scan_pallas)


def graves_lstm_scan_xla(xw, b, rw, pi, pf, po, h0, c0):
    """Reference lax.scan composition, the kernel's twin: same signature,
    the bias added to the gates at every step."""
    def body(carry, xw_t):
        h, c = carry
        H = c.shape[-1]
        gates = xw_t + b + h @ rw
        i = jax.nn.sigmoid(gates[:, :H] + c * pi)
        f = jax.nn.sigmoid(gates[:, H:2 * H] + c * pf)
        g = jnp.tanh(gates[:, 3 * H:])
        c_new = f * c + i * g
        o = jax.nn.sigmoid(gates[:, 2 * H:3 * H] + c_new * po)
        h_new = o * jnp.tanh(c_new)
        return (h_new, c_new), (h_new, c_new)

    (_, _), (ys, cs) = jax.lax.scan(body, (h0, c0), xw)
    return ys, cs
