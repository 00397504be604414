"""Convolution / pooling / padding / global-pooling layers.

Parity: ref nn/conf/layers/{ConvolutionLayer,Convolution1DLayer,SubsamplingLayer,
Subsampling1DLayer,ZeroPaddingLayer,GlobalPoolingLayer}.java, impls under
nn/layers/convolution/ and nn/layers/pooling/. The reference lowers conv to
im2col+gemm or delegates to cuDNN (ConvolutionLayer.java:166-169); here a single
`lax.conv_general_dilated` maps directly onto the MXU and XLA fuses bias+activation.
Shape math mirrors ConvolutionUtils/InputTypeUtil (Strict/Truncate/Same modes).

Layouts: NCHW activations, OIHW weights (reference layout); XLA relayouts for TPU.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.common.enums import Activation, ConvolutionMode, PoolingType
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    BaseLayerConf, FeedForwardLayerConf, register_layer)
from deeplearning4j_tpu.ops.helpers import helper_for
from deeplearning4j_tpu.ops.max_pool import max_pool_grad_tiles


def conv_output_size(in_size: int, k: int, s: int, p: int, mode: ConvolutionMode) -> int:
    if mode == ConvolutionMode.Same:
        return -(-in_size // s)  # ceil
    out = (in_size + 2 * p - k) // s + 1
    if mode == ConvolutionMode.Strict and (in_size + 2 * p - k) % s != 0:
        raise ValueError(
            f"Strict convolution mode: (in={in_size} + 2*pad={p} - k={k}) not divisible "
            f"by stride {s} (ref ConvolutionUtils strict check)")
    return out


def _same_pad(in_size: int, k: int, s: int) -> Tuple[int, int]:
    out = -(-in_size // s)
    total = max(0, (out - 1) * s + k - in_size)
    return total // 2, total - total // 2


def _pad_config(h, w, kernel, stride, padding, mode, dilation=(1, 1)):
    if mode == ConvolutionMode.Same:
        kh = kernel[0] + (kernel[0] - 1) * (dilation[0] - 1)
        kw = kernel[1] + (kernel[1] - 1) * (dilation[1] - 1)
        return _same_pad(h, kh, stride[0]), _same_pad(w, kw, stride[1])
    return (padding[0], padding[0]), (padding[1], padding[1])


def _stride_time_mask(mask, out_t: int, stride: int):
    """Mask for a strided 1D conv/pool output: output step i covers the window starting
    at i*stride, so it is valid iff that window-start step is valid (right-padded
    sequences). Plain truncation would misalign for stride>1."""
    if mask is None:
        return None
    idx = jnp.clip(jnp.arange(out_t) * stride, 0, mask.shape[-1] - 1)
    return jnp.take(mask, idx, axis=-1)


@register_layer
@dataclass
class ConvolutionLayer(FeedForwardLayerConf):
    """2D convolution (ref nn/layers/convolution/ConvolutionLayer.java)."""
    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: ConvolutionMode = ConvolutionMode.Truncate
    dilation: Tuple[int, int] = (1, 1)
    has_bias: bool = True

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.channels

    def get_output_type(self, input_type):
        if input_type.kind != "cnn":
            raise ValueError(f"ConvolutionLayer expects CNN input, got {input_type}")
        kh = self.kernel_size[0] + (self.kernel_size[0] - 1) * (self.dilation[0] - 1)
        kw = self.kernel_size[1] + (self.kernel_size[1] - 1) * (self.dilation[1] - 1)
        oh = conv_output_size(input_type.height, kh, self.stride[0], self.padding[0],
                              self.convolution_mode)
        ow = conv_output_size(input_type.width, kw, self.stride[1], self.padding[1],
                              self.convolution_mode)
        return InputType.convolutional(oh, ow, self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = self.kernel_size
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        p = {"W": self._winit(key, (self.n_out, self.n_in, kh, kw), fan_in, fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        ph, pw = _pad_config(x.shape[2], x.shape[3], self.kernel_size, self.stride,
                             self.padding, self.convolution_mode, self.dilation)
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=self.stride, padding=(ph, pw),
            rhs_dilation=self.dilation,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if self.has_bias:
            z = z + params["b"][None, :, None, None]
        return self._act(z), state, mask


@register_layer
@dataclass
class Convolution1DLayer(ConvolutionLayer):
    """1D conv over (batch, channels, length) RNN-format input
    (ref nn/conf/layers/Convolution1DLayer.java)."""
    kernel_size: Tuple[int, int] = (3, 1)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        if input_type.kind != "rnn":
            raise ValueError("Convolution1DLayer expects RNN input")
        t = input_type.timeseries_length
        if t > 0:
            t = conv_output_size(t, self.kernel_size[0], self.stride[0], self.padding[0],
                                 self.convolution_mode)
        return InputType.recurrent(self.n_out, t)

    def init_params(self, key, input_type, dtype=jnp.float32):
        k = self.kernel_size[0]
        fan_in, fan_out = self.n_in * k, self.n_out * k
        p = {"W": self._winit(key, (self.n_out, self.n_in, k, 1), fan_in, fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        # (batch, channels, time) → NCHW with W=1
        x4 = x[:, :, :, None]
        if self.convolution_mode == ConvolutionMode.Same:
            pt = _same_pad(x.shape[2], self.kernel_size[0], self.stride[0])
        else:
            pt = (self.padding[0], self.padding[0])
        z = lax.conv_general_dilated(
            x4, params["W"], window_strides=(self.stride[0], 1),
            padding=(pt, (0, 0)), dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if self.has_bias:
            z = z + params["b"][None, :, None, None]
        out = self._act(z[:, :, :, 0])
        out_mask = mask
        if mask is not None and out.shape[2] != mask.shape[-1]:
            out_mask = _stride_time_mask(mask, out.shape[2], self.stride[0])
        return out, state, out_mask


def _pool(x, pooling_type: PoolingType, window, strides, padding, pnorm: int = 2):
    init, op = {
        PoolingType.MAX: (-jnp.inf, lax.max),
        PoolingType.SUM: (0.0, lax.add),
        PoolingType.AVG: (0.0, lax.add),
        PoolingType.PNORM: (0.0, lax.add),
    }[pooling_type]
    xin = x
    if pooling_type == PoolingType.PNORM:
        xin = jnp.abs(x) ** pnorm
    r = lax.reduce_window(xin, init, op, window, strides, padding)
    if pooling_type == PoolingType.AVG:
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        r = r / counts
    elif pooling_type == PoolingType.PNORM:
        r = r ** (1.0 / pnorm)
    return r


@register_layer
@dataclass
class SubsamplingLayer(BaseLayerConf):
    """Spatial pooling (ref nn/layers/convolution/subsampling/SubsamplingLayer.java)."""
    pooling_type: PoolingType = PoolingType.MAX
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: ConvolutionMode = ConvolutionMode.Truncate
    pnorm: int = 2

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        oh = conv_output_size(input_type.height, self.kernel_size[0], self.stride[0],
                              self.padding[0], self.convolution_mode)
        ow = conv_output_size(input_type.width, self.kernel_size[1], self.stride[1],
                              self.padding[1], self.convolution_mode)
        return InputType.convolutional(oh, ow, input_type.channels)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        ph, pw = _pad_config(x.shape[2], x.shape[3], self.kernel_size, self.stride,
                             self.padding, self.convolution_mode)
        if self.pooling_type == PoolingType.MAX and max_pool_grad_tiles(
                x.shape, x.dtype, self.kernel_size, self.stride, (ph, pw)) is not None:
            pool = helper_for("max_pool_grad", None)
            if pool is not None:
                return pool(x, self.kernel_size, self.stride, (ph, pw)), state, mask
        out = _pool(x, self.pooling_type, (1, 1) + tuple(self.kernel_size),
                    (1, 1) + tuple(self.stride), ((0, 0), (0, 0), ph, pw), self.pnorm)
        return out, state, mask


@register_layer
@dataclass
class Subsampling1DLayer(SubsamplingLayer):
    """1D pooling over (batch, channels, time) (ref Subsampling1DLayer.java)."""

    def get_output_type(self, input_type):
        t = input_type.timeseries_length
        if t > 0:
            t = conv_output_size(t, self.kernel_size[0], self.stride[0], self.padding[0],
                                 self.convolution_mode)
        return InputType.recurrent(input_type.size, t)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        if self.convolution_mode == ConvolutionMode.Same:
            pt = _same_pad(x.shape[2], self.kernel_size[0], self.stride[0])
        else:
            pt = (self.padding[0], self.padding[0])
        out = _pool(x, self.pooling_type, (1, 1, self.kernel_size[0]),
                    (1, 1, self.stride[0]), ((0, 0), (0, 0), pt), self.pnorm)
        out_mask = mask
        if mask is not None and out.shape[2] != mask.shape[-1]:
            out_mask = _stride_time_mask(mask, out.shape[2], self.stride[0])
        return out, state, out_mask


@register_layer
@dataclass
class ZeroPaddingLayer(BaseLayerConf):
    """Spatial zero padding (ref nn/conf/layers/ZeroPaddingLayer.java)."""
    pad: Tuple[int, int, int, int] = (0, 0, 0, 0)  # top, bottom, left, right

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        t, b, l, r = self.pad
        return InputType.convolutional(input_type.height + t + b,
                                       input_type.width + l + r, input_type.channels)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        t, b, l, r = self.pad
        return jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r))), state, mask


@register_layer
@dataclass
class GlobalPoolingLayer(BaseLayerConf):
    """Global pooling over time (RNN) or space (CNN), mask-aware
    (ref nn/layers/pooling/GlobalPoolingLayer.java + util/MaskedReductionUtil.java)."""
    pooling_type: PoolingType = PoolingType.MAX
    pnorm: int = 2
    collapse_dimensions: bool = True

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        if input_type.kind == "rnn":
            return InputType.feed_forward(input_type.size)
        if input_type.kind == "cnn":
            return InputType.feed_forward(input_type.channels)
        return input_type

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        if x.ndim == 3:  # (batch, size, time)
            axes = (2,)
        elif x.ndim == 4:  # NCHW
            axes = (2, 3)
        else:
            raise ValueError("GlobalPoolingLayer expects rank-3/4 input")
        pt = self.pooling_type
        if mask is not None and x.ndim == 3:
            m = mask[:, None, :].astype(x.dtype)  # (batch, 1, time)
            if pt == PoolingType.MAX:
                out = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=axes)
            elif pt == PoolingType.SUM:
                out = jnp.sum(x * m, axis=axes)
            elif pt == PoolingType.AVG:
                out = jnp.sum(x * m, axis=axes) / jnp.clip(jnp.sum(m, axis=axes), 1.0)
            else:
                out = (jnp.sum((jnp.abs(x) ** self.pnorm) * m, axis=axes)) ** (1.0 / self.pnorm)
        else:
            if pt == PoolingType.MAX:
                out = jnp.max(x, axis=axes)
            elif pt == PoolingType.SUM:
                out = jnp.sum(x, axis=axes)
            elif pt == PoolingType.AVG:
                out = jnp.mean(x, axis=axes)
            else:
                out = (jnp.sum(jnp.abs(x) ** self.pnorm, axis=axes)) ** (1.0 / self.pnorm)
        return out, state, None


@register_layer
@dataclass
class Upsampling2D(BaseLayerConf):
    """Nearest-neighbor spatial upsampling (ref nn/conf/layers/Upsampling2D.java).
    On TPU this is a pair of jnp.repeat ops — pure data movement, fused by XLA."""
    size: Tuple[int, int] = (2, 2)

    def __post_init__(self):
        if isinstance(self.size, int):
            self.size = (self.size, self.size)
        self.size = tuple(self.size)

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        return InputType.convolutional(input_type.height * self.size[0],
                                       input_type.width * self.size[1],
                                       input_type.channels)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        z = jnp.repeat(jnp.repeat(x, self.size[0], axis=2), self.size[1], axis=3)
        return z, state, mask  # pure data movement — no activation


@register_layer
@dataclass
class SpaceToDepthLayer(BaseLayerConf):
    """Rearrange spatial blocks into channels (ref nn/conf/layers/
    SpaceToDepthLayer.java; blocks=NCHW DCR order)."""
    block_size: int = 2

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        b = self.block_size
        return InputType.convolutional(input_type.height // b,
                                       input_type.width // b,
                                       input_type.channels * b * b)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        n, c, h, w = x.shape
        b = self.block_size
        z = x.reshape(n, c, h // b, b, w // b, b)
        z = z.transpose(0, 3, 5, 1, 2, 4).reshape(n, c * b * b, h // b, w // b)
        return z, state, mask  # pure data movement — no activation


@register_layer
@dataclass
class Cropping2D(BaseLayerConf):
    """Crop spatial borders (ref nn/conf/layers/convolutional/Cropping2D.java);
    crop = (top, bottom, left, right)."""
    crop: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def __post_init__(self):
        if isinstance(self.crop, int):
            self.crop = (self.crop,) * 4
        elif len(self.crop) == 2:
            self.crop = (self.crop[0], self.crop[0], self.crop[1], self.crop[1])
        self.crop = tuple(self.crop)

    def has_params(self):
        return False

    def get_output_type(self, input_type):
        t, b, l, r = self.crop
        return InputType.convolutional(input_type.height - t - b,
                                       input_type.width - l - r,
                                       input_type.channels)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        t, b, l, r = self.crop
        h, w = x.shape[2], x.shape[3]
        return x[:, :, t:h - b or None, l:w - r or None], state, mask


@register_layer
@dataclass
class Deconvolution2D(ConvolutionLayer):
    """Transposed convolution (ref nn/conf/layers/Deconvolution2D.java) via
    lax.conv_transpose."""

    def get_output_type(self, input_type):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.convolution_mode == ConvolutionMode.Same:
            oh, ow = input_type.height * sh, input_type.width * sw
        else:
            oh = sh * (input_type.height - 1) + kh - 2 * self.padding[0]
            ow = sw * (input_type.width - 1) + kw - 2 * self.padding[1]
        return InputType.convolutional(oh, ow, self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = self.kernel_size
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        p = {"W": self._winit(key, (self.n_in, self.n_out, kh, kw), fan_in,
                              fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        if self.convolution_mode == ConvolutionMode.Same:
            pad = "SAME"
        else:
            kh, kw = self.kernel_size
            ph, pw = self.padding
            pad = ((kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw))
        z = lax.conv_transpose(
            x, params["W"], strides=self.stride, padding=pad,
            dimension_numbers=("NCHW", "IOHW", "NCHW"))
        if self.has_bias:
            z = z + params["b"][None, :, None, None]
        return self._act(z), state, mask


@register_layer
@dataclass
class DepthwiseConvolutionLayer(ConvolutionLayer):
    """Depthwise conv (ref nn/conf/layers/DepthwiseConvolution2D.java):
    feature_group_count=n_in on the MXU conv op, depth_multiplier channels out
    per input channel."""
    depth_multiplier: int = 1

    def get_output_type(self, input_type):
        base = super().get_output_type(input_type)
        return InputType.convolutional(base.height, base.width,
                                       self.n_in * self.depth_multiplier)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = self.kernel_size
        dm = self.depth_multiplier
        fan_in = kh * kw
        fan_out = dm * kh * kw
        p = {"W": self._winit(key, (self.n_in * dm, 1, kh, kw), fan_in, fan_out,
                              dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_in * dm,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        ph, pw = _pad_config(x.shape[2], x.shape[3], self.kernel_size,
                             self.stride, self.padding, self.convolution_mode,
                             self.dilation)
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=self.stride, padding=(ph, pw),
            rhs_dilation=self.dilation, feature_group_count=self.n_in,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if self.has_bias:
            z = z + params["b"][None, :, None, None]
        return self._act(z), state, mask


@register_layer
@dataclass
class SeparableConvolution2D(ConvolutionLayer):
    """Depthwise-separable conv (ref nn/conf/layers/SeparableConvolution2D.java):
    depthwise spatial conv + 1x1 pointwise mix."""
    depth_multiplier: int = 1

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = self.kernel_size
        dm = self.depth_multiplier
        kd, kp = jax.random.split(key)
        p = {
            "W": self._winit(kd, (self.n_in * dm, 1, kh, kw), kh * kw,
                             dm * kh * kw, dtype),  # depthwise
            "w_point": self._winit(kp, (self.n_out, self.n_in * dm, 1, 1),
                                   self.n_in * dm, self.n_out, dtype),
        }
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        ph, pw = _pad_config(x.shape[2], x.shape[3], self.kernel_size,
                             self.stride, self.padding, self.convolution_mode,
                             self.dilation)
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=self.stride, padding=(ph, pw),
            rhs_dilation=self.dilation, feature_group_count=self.n_in,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        z = lax.conv_general_dilated(
            z, params["w_point"], window_strides=(1, 1), padding=((0, 0), (0, 0)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if self.has_bias:
            z = z + params["b"][None, :, None, None]
        return self._act(z), state, mask
