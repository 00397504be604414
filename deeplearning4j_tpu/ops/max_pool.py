"""The gradient of MAX pooling over NCHW maps as one Mosaic kernel.

`max_pool(x, window, strides, padding)` is the maximum over windows of the
last two axes of x (N, C, H, W): forward, XLA's `reduce_window` as the
plain pool has it; backward, under a `custom_vjp`, one Pallas call,
`dl4j_max_pool_bwd`, in place of XLA's `select-and-scatter`.

Semantics are the select-and-scatter's that `jax.grad` of the plain pool
runs (select `ge`). A window's cotangent goes to one of its cells: the walk
goes through the window in row-major order, keeping the chosen cell while
it is `>=` the next and taking the next one otherwise. That is the first
maximum, and for a NaN the walk's own rule. Padding cells hold -inf, the
pool's own padding, and take part in the walk as the select-and-scatter's
do; what a window leaves on one is dropped. What a cell receives from the
windows that overlap it is summed in float32 and rounded once to x's type.
The forward saves x, as the select-and-scatter keeps it, and nothing else.

Layout. The kernel views the maps with H and W major and the batch and the
channels minor, one of them on the lanes: the channels where they fill
whole lane tiles, else the batch (`_view`). That is the physical layout XLA
gives such NCHW maps on a TPU, so the view is a bitcast: the ResNet stem's
64 channels are `{0,1,3,2}` (the batch on lanes, the channels on
sublanes), its head's 2048 channels `{1,0,3,2}` (the channels on lanes, the
batch on sublanes). Over H and W, major axes, a stride is address
arithmetic; the kernel treats the two minor axes alike.

Tiling. The grid's last, sequential axis walks blocks of `sh` rows of x
over tiles of the two minor axes. Where a window reaches `kh - sh` rows
into the next block, step t finishes window row t - 1: it reads block t - 1
from the VMEM copy made at the step before and the first rows of block t
from the pipeline, and writes block t - 1 of the gradient; what the window
row adds to block t waits in a float32 carry in VMEM (where kh <= sh, step
t finishes row t and nothing is carried). Inside a step a loop walks the
row's windows, one (16, 128) tile of each at a time, carrying in registers
what a window adds to the columns it shares with the next. x and the
cotangent are read once, the gradient written once: at the ResNet50 stem's
shape on a v5e, 2.81 ms against the select-and-scatter's 4.45 (PERF.md,
section 6).

Behind the helper seam (`register_helper("max_pool_grad")`): the kernel on
a TPU for what `max_pool_grad_tiles` takes, interpreted off a TPU under the
override, XLA's select-and-scatter elsewhere.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops.helpers import register_helper

_F32 = jnp.float32
_LANES = 128
# What a call may ask of VMEM (v5e has 128 MiB), and what it asks beyond
# its blocks and scratch for the values the loop spills.
_VMEM_MOST = 64 * 1024 * 1024
_HEADROOM = 4 * 1024 * 1024


def _round_up(n, m):
    return -(-n // m) * m


def _geometry(shape, window, strides, padding):
    """The padded height and width, and the pooled ones."""
    (kh, kw), (sh, sw), ((top, bottom), (left, right)) = window, strides, padding
    hp, wp = shape[2] + top + bottom, shape[3] + left + right
    return hp, wp, (hp - kh) // sh + 1, (wp - kw) // sw + 1


def _vmem_bytes(window, strides, wp, ow, sb, lb, itemsize):
    """The blocks of x, of the gradient and of the cotangent, each twice
    (the pipeline's two buffers), the copy of the block before, the float32
    carry, and `_HEADROOM`."""
    (kh, _), (sh, _) = window, strides
    tile = _round_up(sb, 32 // itemsize) * _round_up(lb, _LANES)
    rows = sh * wp * tile * itemsize
    carry = max(kh - sh, 0) * wp * _round_up(sb, 8) * _round_up(lb, _LANES) * 4
    lag = int(kh > sh)
    return 4 * rows + 2 * ow * tile * itemsize + lag * rows + carry + _HEADROOM


def _view(shape):
    """The axes of x (N, C, H, W) in the kernel's order: H, W, then what
    goes on sublanes and what on lanes, the channels where they are whole
    lane tiles (XLA lays such maps out channels minor), else the batch."""
    return (2, 3, 0, 1) if shape[1] % _LANES == 0 else (2, 3, 1, 0)


def max_pool_grad_tiles(shape, dtype, window, strides, padding
                        ) -> Optional[Tuple[int, int]]:
    """The (sublanes, lanes) block of the minor axes a grid step takes, or
    None where the kernel refuses: x 4-D and floating, no wider than
    float32; a window that reaches into the next window's rows and columns
    and no further (kh <= 2 sh, kw <= 2 sw); at least one window; and
    compiled for the chip, the step's blocks within VMEM. The lanes go in
    tiles of 128 where they have whole ones, else whole; the sublanes
    whole, else halved while the halves are whole sublane tiles and the
    blocks too large."""
    if len(shape) != 4 or len(window) != 2 or len(strides) != 2:
        return None
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize > 4:
        return None
    (kh, kw), (sh, sw) = window, strides
    if not (0 < kh <= 2 * sh and 0 < kw <= 2 * sw):
        return None
    if min(p for pair in padding for p in pair) < 0:
        return None
    _, wp, oh, ow = _geometry(shape, window, strides, padding)
    if oh < 1 or ow < 1:
        return None
    sb, lanes = (shape[a] for a in _view(shape)[2:])
    lb = _LANES if lanes % _LANES == 0 else lanes
    if helpers.interpret_mode():
        return sb, lb
    while _vmem_bytes(window, strides, wp, ow, sb, lb, dtype.itemsize) > _VMEM_MOST:
        if sb % 2 or (sb // 2) % (32 // dtype.itemsize):
            return None
        sb //= 2
    return sb, lb


def _sub_tile(sb):
    """Sublanes a value of the kernel's loops holds: 16, a (16, 128) tile of
    bfloat16 (one register) or two of float32, where they divide the
    block's; else the block's."""
    return 16 if sb % 16 == 0 else sb


def _kernel(geo, x_ref, g_ref, out_ref, *scratch):
    from jax.experimental import pallas as pl
    (kh, kw), (sh, sw), oh, ow = geo
    lag = int(kh > sh)
    prev_ref, carry_ref = scratch if lag else (None, None)
    rows, wp, (sb, lb) = max(kh, sh), out_ref.shape[1], out_ref.shape[2:]
    sub = _sub_tile(sb)
    zero = jnp.zeros((sub, lb), _F32)
    i = pl.program_id(2) - lag          # the window row this step finishes

    def x_at(a, col, part):
        """Row a of window row i, at column col, sublanes part."""
        if lag and a < sh:
            return prev_ref[a, col, part]
        return x_ref[a - lag * sh, col, part]

    def put(a, col, part, v):
        """v: the last of what row a of window row i receives at column
        col (the carry's rows: what they receive first)."""
        if a >= sh:
            carry_ref[a - sh, col, part] = v
            return
        if a < kh - sh:
            v = v + carry_ref[a, col, part]
        out_ref[a, col, part] = v.astype(out_ref.dtype)

    def walk(part):
        """The row's windows over sublanes part, one after the other."""
        def window(j, shared):
            """Window j: the walk, then what each cell receives; the columns
            it shares with window j + 1 are handed on in `shared`."""
            g = g_ref[0, j, part].astype(_F32)
            base = j * sw
            for p in range(kh * kw):
                a, b = divmod(p, kw)
                v = x_at(a, base + b, part).astype(_F32)
                if p == 0:
                    sel, idx = v, jnp.zeros((sub, lb), jnp.int32)
                    continue
                keep = sel >= v
                sel, idx = jnp.where(keep, sel, v), jnp.where(keep, idx, p)
            got = {(a, b): jnp.where(idx == a * kw + b, g, 0.0)
                   for a in range(kh) for b in range(kw)}
            for b in range(sw):
                for a in range(rows):
                    v = got.get((a, b), zero)
                    if b < kw - sw:
                        v = v + shared[a][b]
                    put(a, base + b, part, v)
            return tuple(tuple(got[a, b + sw] for b in range(kw - sw))
                         for a in range(kh))

        shared = tuple(tuple(zero for _ in range(kw - sw)) for _ in range(kh))
        shared = lax.fori_loop(0, ow, window, shared)
        for col in range(ow * sw, wp):      # past the last window's start
            b = col - ow * sw
            for a in range(rows):
                put(a, col, part, shared[a][b] if a < kh and b < kw - sw
                    else zero)

    def over_parts(body):
        """body(part) for each sub-tile of the block's sublanes, in a loop."""
        lax.fori_loop(0, sb // sub, lambda s, c: (body(
            pl.ds(pl.multiple_of(s * sub, sub), sub)), c)[1], 0)

    def each_column(body):
        """body(col, part) for every column and sub-tile: as a decorator."""
        over_parts(lambda part: lax.fori_loop(
            0, wp, lambda col, c: (body(col, part), c)[1], 0))

    if lag:
        @pl.when(i < 0)                     # before the first window row
        def _():
            @each_column
            def _(col, part):
                carry_ref[:, col, part] = jnp.zeros((kh - sh, sub, lb), _F32)

    @pl.when((i >= 0) & (i < oh))
    def _():
        over_parts(walk)

    @pl.when(i >= oh)                       # rows under no window's start
    def _():
        @each_column
        def _(col, part):
            for a in range(rows):
                put(a, col, part, zero)

    if lag:                                 # block t, for the next step
        @each_column
        def _(col, part):
            prev_ref[:, col, part] = x_ref[:, col, part]


# A jit of its own: a net's pools of one shape share one trace of the kernel
# and one lowering to Mosaic. Whether it is interpreted
# (ops/helpers.interpret_mode) is asked outside and is part of the key.
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _bwd_call(window, strides, padding, tiles, interpret, x, dy):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    h, w = x.shape[2:]
    (kh, _), (sh, _), ((top, bottom), (left, right)) = window, strides, padding
    hp, wp, oh, ow = _geometry(x.shape, window, strides, padding)
    view = _view(x.shape)
    sb, lb = tiles
    lag = int(kh > sh)
    blocks = -(-hp // sh)
    xt = jnp.pad(jnp.transpose(x, view),
                 ((top, bottom), (left, right), (0, 0), (0, 0)),
                 constant_values=-jnp.inf)
    rows = lambda at: pl.BlockSpec((sh, wp, sb, lb),
                                   lambda m, k, t: (at(t), 0, k, m))
    scratch = [pltpu.VMEM((sh, wp, sb, lb), x.dtype),
               pltpu.VMEM((kh - sh, wp, sb, lb), _F32)] if lag else []
    call = pl.pallas_call(
        functools.partial(_kernel, (window, strides, oh, ow)),
        name="dl4j_max_pool_bwd",
        grid=(xt.shape[3] // lb, xt.shape[2] // sb, blocks + lag),
        in_specs=[rows(lambda t: jnp.minimum(t, blocks - 1)),
                  pl.BlockSpec((1, ow, sb, lb), lambda m, k, t: (
                      jnp.clip(t - lag, 0, oh - 1), 0, k, m))],
        out_specs=rows(lambda t: jnp.maximum(t - lag, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        scratch_shapes=scratch, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(window, strides, wp, ow, sb, lb,
                                         x.dtype.itemsize)))
    # interpreted off the chip: traced with x64 off, as the chip has it
    with jax.enable_x64(False) if interpret else contextlib.nullcontext():
        dxt = call(xt, jnp.transpose(dy, view))
    back = tuple(view.index(a) for a in range(4))
    return jnp.transpose(dxt[top:top + h, left:left + w], back)


def _forward(x, window, strides, padding):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1) + window,
                             (1, 1) + strides, ((0, 0), (0, 0)) + padding)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _pool(x, window, strides, padding):
    return _forward(x, window, strides, padding)


def _pool_fwd(x, window, strides, padding):
    return _forward(x, window, strides, padding), x


def _pool_bwd(window, strides, padding, x, dy):
    tiles = max_pool_grad_tiles(x.shape, x.dtype, window, strides, padding)
    return (_bwd_call(window, strides, padding, tiles, helpers.interpret_mode(),
                      x, dy),)


_pool.defvjp(_pool_fwd, _pool_bwd)


@register_helper("max_pool_grad")
def max_pool(x, window, strides, padding):
    """MAX pooling of x (N, C, H, W) over `window` (kh, kw) at `strides`
    (sh, sw) with `padding` ((top, bottom), (left, right)), for a shape
    `max_pool_grad_tiles` takes: XLA's forward, the kernel's gradient."""
    return _pool(x, tuple(window), tuple(strides),
                 tuple(tuple(p) for p in padding))
