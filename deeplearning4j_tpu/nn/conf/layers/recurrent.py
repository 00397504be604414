"""Recurrent layers: LSTM, GravesLSTM (peepholes), GravesBidirectionalLSTM, RnnOutputLayer.

Parity: ref nn/layers/recurrent/{LSTM,GravesLSTM,GravesBidirectionalLSTM,RnnOutputLayer}.java
with the shared time-loop in LSTMHelpers.java:200-340 (fwd) / :403-700 (bwd). The reference
iterates per-timestep issuing an mmul each step — its #1 hot loop, replaced by cuDNN when
available. Here the whole sequence is a single `lax.scan`: XLA compiles one fused loop with
the input projection batched over all timesteps up front (one big MXU matmul), and autodiff
differentiates through the scan — no hand-written BPTT.

Layout: DL4J RNN activations are (batch, size, time); internally we scan over (time, batch,
size). Gate order within the fused weight matrices: [input, forget, output, cell(g)].

Masking: per-(example, timestep) mask (batch, time). Masked steps produce zero output and
hold the recurrent state (so variable-length sequences behave as if right-padded).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.common.enums import Activation, LossFunction
from deeplearning4j_tpu.nn.activations import apply_activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers.base import (
    BaseLayerConf, FeedForwardLayerConf, register_layer)
from deeplearning4j_tpu.nn.losses import compute_loss


@register_layer
@dataclass
class LSTM(FeedForwardLayerConf):
    """LSTM without peepholes (ref nn/layers/recurrent/LSTM.java — the cuDNN-compatible
    formulation)."""
    activation: Activation = Activation.TANH
    gate_activation: Activation = Activation.SIGMOID
    forget_gate_bias_init: float = 1.0
    peephole: bool = False

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size

    def init_params(self, key, input_type, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        n_in, n_out = self.n_in, self.n_out
        p = {
            "W": self._winit(k1, (n_in, 4 * n_out), n_in, n_out, dtype),
            "RW": self._winit(k2, (n_out, 4 * n_out), n_out, n_out, dtype),
            "b": jnp.zeros((4 * n_out,), dtype).at[n_out:2 * n_out].set(
                self.forget_gate_bias_init),
        }
        if self.peephole:
            p["pi"] = jnp.zeros((n_out,), dtype)
            p["pf"] = jnp.zeros((n_out,), dtype)
            p["po"] = jnp.zeros((n_out,), dtype)
        return p

    # single timestep; xw = x_t @ W + b precomputed
    def _step(self, params, xw_t, h, c):
        n = self.n_out
        gates = xw_t + h @ params["RW"]
        gact = lambda v: apply_activation(self.gate_activation, v)
        act = lambda v: apply_activation(self.activation, v)
        # peephole: the gate also sees the cell state through a diagonal
        peep = (lambda z, cell, k: z + cell * params[k]) if self.peephole \
            else (lambda z, cell, k: z)
        i = gact(peep(gates[:, :n], c, "pi"))
        f = gact(peep(gates[:, n:2 * n], c, "pf"))
        g = act(gates[:, 3 * n:])
        c_new = f * c + i * g
        o = gact(peep(gates[:, 2 * n:3 * n], c_new, "po"))
        return o * act(c_new), c_new

    def _scan(self, params, x, mask, h0=None, c0=None, reverse=False):
        """x: (batch, size, time) → outputs (batch, n_out, time), final (h, c)."""
        b = x.shape[0]
        n = self.n_out
        dtype = x.dtype
        h = jnp.zeros((b, n), dtype) if h0 is None else h0
        c = jnp.zeros((b, n), dtype) if c0 is None else c0
        xt = jnp.moveaxis(x, 2, 0)  # (time, batch, size)
        # one big batched input projection — single MXU matmul over all timesteps
        xw = xt @ params["W"]
        # helper seam, whole-sequence form (the cuDNN-LSTM analog, ref
        # CudnnLSTMHelper.java:175): the ENTIRE recurrence as one Pallas
        # kernel with h/c resident in VMEM (ops/lstm_scan_fused.py). It adds
        # the bias itself, so that its backward sums the bias gradient where
        # the gate gradients already are. Zero peepholes reduce exactly to
        # the plain-LSTM math. Masked sequences keep the lax.scan path (the
        # kernel has no state-hold select); what the site can see itself is
        # checked before the seam is asked.
        from deeplearning4j_tpu.ops.helpers import helper_for
        from deeplearning4j_tpu.ops.lstm_scan_fused import fits_vmem
        if mask is None and self.gate_activation == Activation.SIGMOID \
                and self.activation == Activation.TANH \
                and fits_vmem(b, n, jnp.dtype(dtype).itemsize):
            fused = helper_for("graves_lstm_scan", None)
            if fused is not None:
                zero = jnp.zeros((n,), dtype)
                pi = params.get("pi", zero)
                pf = params.get("pf", zero)
                po = params.get("po", zero)
                xw_k = xw[::-1] if reverse else xw
                ys, cs = fused(xw_k, params["b"], params["RW"], pi, pf, po,
                               h, c)
                h_f, c_f = ys[-1], cs[-1]
                if reverse:
                    ys = ys[::-1]
                return jnp.moveaxis(ys, 0, 2), (h_f, c_f)
        xw = xw + params["b"]
        mt = None if mask is None else jnp.moveaxis(mask, 1, 0)[..., None].astype(dtype)

        def body(carry, inp):
            h, c = carry
            if mask is None:
                xw_t = inp
                h_new, c_new = self._step(params, xw_t, h, c)
                return (h_new, c_new), h_new
            xw_t, m = inp
            h_new, c_new = self._step(params, xw_t, h, c)
            h_keep = m * h_new + (1 - m) * h
            c_keep = m * c_new + (1 - m) * c
            return (h_keep, c_keep), m * h_new

        xs = xw if mask is None else (xw, mt)
        (h, c), ys = lax.scan(body, (h, c), xs, reverse=reverse)
        return jnp.moveaxis(ys, 0, 2), (h, c)  # (batch, n_out, time)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        out, _ = self._scan(params, x, mask)
        return out, state, mask

    def step_forward(self, params, x_t, h, c):
        """Single streaming step for rnnTimeStep (ref BaseRecurrentLayer stateMap)."""
        xw = x_t @ params["W"] + params["b"]
        return self._step(params, xw, h, c)


@register_layer
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (ref nn/layers/recurrent/GravesLSTM.java,
    Graves 2013 formulation)."""
    peephole: bool = True


@register_layer
@dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional Graves LSTM; forward and backward passes are *summed*
    (ref GravesBidirectionalLSTM.java:227-228)."""
    peephole: bool = True

    def init_params(self, key, input_type, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        fwd = super().init_params(kf, input_type, dtype)
        bwd = super().init_params(kb, input_type, dtype)
        p = {f"{k}_f": v for k, v in fwd.items()}
        p.update({f"{k}_b": v for k, v in bwd.items()})
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        pf = {k[:-2]: v for k, v in params.items() if k.endswith("_f")}
        pb = {k[:-2]: v for k, v in params.items() if k.endswith("_b")}
        out_f, _ = self._scan(pf, x, mask)
        out_b, _ = self._scan(pb, x, mask, reverse=True)
        return out_f + out_b, state, mask


@register_layer
@dataclass
class RnnOutputLayer(FeedForwardLayerConf):
    """Per-timestep dense + loss head over (batch, size, time)
    (ref nn/layers/recurrent/RnnOutputLayer.java)."""
    loss_fn: LossFunction = LossFunction.MCXENT
    activation: Activation = Activation.SOFTMAX
    has_bias: bool = True

    def is_output_layer(self):
        return True

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def init_params(self, key, input_type, dtype=jnp.float32):
        p = {"W": self._winit(key, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def preout(self, params, x):
        # (batch, size, time) → (batch, time, size) @ W → back
        z = jnp.einsum("bst,so->bot", x, params["W"])
        if self.has_bias:
            z = z + params["b"][None, :, None]
        return z

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        z = self.preout(params, x)
        # softmax over the feature axis (axis=1 in NCT layout)
        if self.activation == Activation.SOFTMAX:
            out = jax.nn.softmax(z, axis=1)
        else:
            out = self._act(z)
        if mask is not None:
            out = out * mask[:, None, :].astype(out.dtype)
        return out, state, mask

    def compute_score(self, params, x, labels, mask=None):
        z = self.preout(params, x)  # (batch, n_out, time)
        # move feature axis last for the loss ((batch, time, n_out))
        z2 = jnp.moveaxis(z, 1, 2).reshape(-1, self.n_out)
        l2 = jnp.moveaxis(labels, 1, 2).reshape(-1, self.n_out)
        m2 = None if mask is None else mask.reshape(-1)
        return compute_loss(self.loss_fn, l2, z2, self.activation, m2)

    def compute_score_per_example(self, params, x, labels, mask=None):
        """(batch,) scores: each example's loss summed over its (unmasked)
        timesteps (ref scoreExamples time-series semantics; the scalar score
        normalizes by batch*time, so mean(per_example)/T == score)."""
        from deeplearning4j_tpu.nn.losses import compute_loss_per_example
        B = x.shape[0]
        z = self.preout(params, x)
        z2 = jnp.moveaxis(z, 1, 2).reshape(-1, self.n_out)
        l2 = jnp.moveaxis(labels, 1, 2).reshape(-1, self.n_out)
        m2 = None if mask is None else mask.reshape(-1)
        per_bt = compute_loss_per_example(self.loss_fn, l2, z2,
                                          self.activation, m2)
        return per_bt.reshape(B, -1).sum(axis=1)


@register_layer
@dataclass
class SimpleRnn(FeedForwardLayerConf):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} RW + b)
    (ref nn/conf/layers/recurrent/SimpleRnn.java). Input projection batched over
    all timesteps up front, recurrence as one lax.scan — same TPU shape as LSTM."""
    activation: Activation = Activation.TANH

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out,
                                   getattr(input_type, "timeseries_length", -1))

    def set_n_in(self, input_type, override=False):
        if self.n_in == 0 or override:
            self.n_in = input_type.size

    def init_params(self, key, input_type, dtype=jnp.float32):
        kw, kr = jax.random.split(key)
        return {
            "W": self._winit(kw, (self.n_in, self.n_out), self.n_in, self.n_out,
                             dtype),
            "RW": self._winit(kr, (self.n_out, self.n_out), self.n_out,
                              self.n_out, dtype),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def _scan(self, params, x, mask, h0=None, reverse=False):
        b = x.shape[0]
        dtype = x.dtype
        h = jnp.zeros((b, self.n_out), dtype) if h0 is None else h0
        xt = jnp.moveaxis(x, 2, 0)
        xw = xt @ params["W"] + params["b"]
        mt = None if mask is None else \
            jnp.moveaxis(mask, 1, 0)[..., None].astype(dtype)

        def body(h, inp):
            if mask is None:
                h_new = apply_activation(self.activation,
                                         inp + h @ params["RW"])
                return h_new, h_new
            xw_t, m = inp
            h_new = apply_activation(self.activation, xw_t + h @ params["RW"])
            h_keep = m * h_new + (1 - m) * h
            return h_keep, m * h_new

        xs = xw if mask is None else (xw, mt)
        h, ys = lax.scan(body, h, xs, reverse=reverse)
        return jnp.moveaxis(ys, 0, 2), h

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        out, _ = self._scan(params, x, mask)
        return out, state, mask


@register_layer
@dataclass
class Bidirectional(BaseLayerConf):
    """Bidirectional wrapper around any recurrent layer
    (ref nn/conf/layers/recurrent/Bidirectional.java). Modes: CONCAT (default),
    ADD, MUL, AVERAGE — applied to the forward and time-reversed passes."""
    fwd: Optional[FeedForwardLayerConf] = None  # the wrapped RNN layer conf
    mode: str = "concat"

    def __post_init__(self):
        from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf as _B
        if isinstance(self.fwd, dict):
            self.fwd = _B.from_dict(self.fwd)

    @property
    def n_out(self):
        base = self.fwd.n_out
        return 2 * base if self.mode == "concat" else base

    def set_n_in(self, input_type, override=False):
        self.fwd.set_n_in(input_type, override)

    def get_output_type(self, input_type):
        base = self.fwd.get_output_type(input_type)
        if self.mode == "concat":
            return InputType.recurrent(
                base.size * 2, getattr(base, "timeseries_length", -1))
        return base

    def init_params(self, key, input_type, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        f = self.fwd.init_params(kf, input_type, dtype)
        b = self.fwd.init_params(kb, input_type, dtype)
        p = {f"{k}_f": v for k, v in f.items()}
        p.update({f"{k}_b": v for k, v in b.items()})
        return p

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        pf = {k[:-2]: v for k, v in params.items() if k.endswith("_f")}
        pb = {k[:-2]: v for k, v in params.items() if k.endswith("_b")}
        out_f, _ = self.fwd._scan(pf, x, mask)
        out_b, _ = self.fwd._scan(pb, x, mask, reverse=True)
        if self.mode == "concat":
            out = jnp.concatenate([out_f, out_b], axis=1)
        elif self.mode == "add":
            out = out_f + out_b
        elif self.mode == "mul":
            out = out_f * out_b
        elif self.mode == "average":
            out = 0.5 * (out_f + out_b)
        else:
            raise ValueError(f"unknown Bidirectional mode {self.mode!r}")
        return out, state, mask


@register_layer
@dataclass
class LastTimeStep(BaseLayerConf):
    """Wrapper returning only the last (unmasked) timestep of the wrapped RNN
    layer's output as feed-forward activations
    (ref nn/conf/layers/recurrent/LastTimeStep.java)."""
    underlying: Optional[FeedForwardLayerConf] = None

    def __post_init__(self):
        from deeplearning4j_tpu.nn.conf.layers.base import BaseLayerConf as _B
        if isinstance(self.underlying, dict):
            self.underlying = _B.from_dict(self.underlying)

    @property
    def n_out(self):
        return self.underlying.n_out

    def set_n_in(self, input_type, override=False):
        self.underlying.set_n_in(input_type, override)

    def get_output_type(self, input_type):
        base = self.underlying.get_output_type(input_type)
        return InputType.feed_forward(base.size)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return self.underlying.init_params(key, input_type, dtype)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        out, ns, out_mask = self.underlying.forward(
            params, state, x, train=train, rng=rng, mask=mask)
        if out_mask is None:
            last = out[:, :, -1]
        else:
            idx = jnp.maximum(
                jnp.sum(out_mask.astype(jnp.int32), axis=1) - 1, 0)  # (batch,)
            last = jnp.take_along_axis(
                out, idx[:, None, None], axis=2)[:, :, 0]
        return last, ns, None  # pure selection — underlying already activated
