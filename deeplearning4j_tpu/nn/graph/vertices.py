"""Graph vertices: the non-layer nodes of a ComputationGraph.

Parity: ref nn/graph/vertex/impl/ — MergeVertex, ElementWiseVertex, SubsetVertex,
StackVertex, UnstackVertex, ScaleVertex, ShiftVertex, ReshapeVertex, L2Vertex,
L2NormalizeVertex, PoolHelperVertex, rnn/{LastTimeStepVertex,DuplicateToTimeSeriesVertex}
(+ mirror conf classes in nn/conf/graph/). In the reference each vertex implements
doForward/doBackward imperatively; here a vertex is a pure function of its input arrays —
the graph traces to one XLA computation and autodiff handles the backward pass
(the topological-order interpreter of ComputationGraph.java:1414-1491 disappears at
trace time, SURVEY §3.2).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.input_type import InputType

VERTEX_REGISTRY: dict[str, type] = {}


def register_vertex(cls):
    VERTEX_REGISTRY[cls.__name__] = cls
    return cls


@dataclass
class GraphVertex:
    """Base: pure function of input arrays. Vertices are parameterless (layers carry the
    params)."""

    def forward(self, inputs: List[jnp.ndarray], masks: List[Optional[jnp.ndarray]]
                ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        raise NotImplementedError

    def get_output_type(self, input_types: List[InputType]) -> InputType:
        raise NotImplementedError

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["@class"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "GraphVertex":
        d = dict(d)
        cls = VERTEX_REGISTRY[d.pop("@class")]
        for k, v in list(d.items()):
            if isinstance(v, list):
                d[k] = tuple(v)
        return cls(**d)


def _first_mask(masks):
    for m in masks:
        if m is not None:
            return m
    return None


@register_vertex
@dataclass
class MergeVertex(GraphVertex):
    """Concatenate along the feature/channel axis (axis 1 in NC*/NCHW/NCT layouts)
    (ref nn/graph/vertex/impl/MergeVertex.java); `axis` -1 for the decoder
    layers' (batch, time, features)."""
    axis: int = 1

    def forward(self, inputs, masks):
        return jnp.concatenate(inputs, axis=self.axis), _first_mask(masks)

    def get_output_type(self, input_types):
        t0 = input_types[0]
        total = sum(t.size for t in input_types)
        if t0.kind == "cnn":
            return InputType.convolutional(t0.height, t0.width, total)
        if t0.kind == "rnn":
            return InputType.recurrent(total, t0.timeseries_length)
        return InputType.feed_forward(total)


@register_vertex
@dataclass
class ElementWiseVertex(GraphVertex):
    """Elementwise Add/Subtract/Product/Average/Max of same-shaped inputs
    (ref ElementWiseVertex.java)."""
    op: str = "Add"

    def forward(self, inputs, masks):
        op = self.op.lower()
        out = inputs[0]
        if op == "add":
            for x in inputs[1:]:
                out = out + x
        elif op == "subtract":
            out = inputs[0] - inputs[1]
        elif op == "product":
            for x in inputs[1:]:
                out = out * x
        elif op == "average":
            out = sum(inputs) / len(inputs)
        elif op == "max":
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(f"Unknown ElementWise op {self.op}")
        return out, _first_mask(masks)

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class SubsetVertex(GraphVertex):
    """Feature-axis slice [from, to] inclusive (ref SubsetVertex.java)."""
    from_idx: int = 0
    to_idx: int = 0

    def forward(self, inputs, masks):
        return inputs[0][:, self.from_idx:self.to_idx + 1], _first_mask(masks)

    def get_output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        t = input_types[0]
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width, n)
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timeseries_length)
        return InputType.feed_forward(n)


@register_vertex
@dataclass
class StackVertex(GraphVertex):
    """Stack along the batch axis (ref StackVertex.java)."""

    def forward(self, inputs, masks):
        ms = [m for m in masks if m is not None]
        mask = jnp.concatenate(ms, axis=0) if len(ms) == len(inputs) else None
        return jnp.concatenate(inputs, axis=0), mask

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class UnstackVertex(GraphVertex):
    """Take batch-slice `from_idx` of `stack_size` equal chunks (ref UnstackVertex.java)."""
    from_idx: int = 0
    stack_size: int = 1

    def forward(self, inputs, masks):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        sl = slice(self.from_idx * step, (self.from_idx + 1) * step)
        m = _first_mask(masks)
        return x[sl], None if m is None else m[sl]

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class ScaleVertex(GraphVertex):
    scale_factor: float = 1.0

    def forward(self, inputs, masks):
        return inputs[0] * self.scale_factor, _first_mask(masks)

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class ShiftVertex(GraphVertex):
    shift_factor: float = 0.0

    def forward(self, inputs, masks):
        return inputs[0] + self.shift_factor, _first_mask(masks)

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class ReshapeVertex(GraphVertex):
    """Reshape to (batch, *new_shape[1:]) (ref ReshapeVertex.java)."""
    new_shape: tuple = ()

    def forward(self, inputs, masks):
        return inputs[0].reshape(self.new_shape), _first_mask(masks)

    def get_output_type(self, input_types):
        if len(self.new_shape) == 2:
            return InputType.feed_forward(self.new_shape[1])
        if len(self.new_shape) == 3:
            return InputType.recurrent(self.new_shape[1])
        if len(self.new_shape) == 4:
            return InputType.convolutional(self.new_shape[2], self.new_shape[3],
                                           self.new_shape[1])
        raise ValueError(self.new_shape)


@register_vertex
@dataclass
class L2Vertex(GraphVertex):
    """Pairwise L2 distance between two inputs, per example (ref L2Vertex.java)."""
    eps: float = 1e-8

    def forward(self, inputs, masks):
        a = inputs[0].reshape(inputs[0].shape[0], -1)
        b = inputs[1].reshape(inputs[1].shape[0], -1)
        d = jnp.sqrt(jnp.sum(jnp.square(a - b), axis=1) + self.eps)
        return d[:, None], None

    def get_output_type(self, input_types):
        return InputType.feed_forward(1)


@register_vertex
@dataclass
class L2NormalizeVertex(GraphVertex):
    """Normalize each example to unit L2 norm (ref L2NormalizeVertex.java)."""
    eps: float = 1e-8

    def forward(self, inputs, masks):
        x = inputs[0]
        flat = x.reshape(x.shape[0], -1)
        norm = jnp.sqrt(jnp.sum(jnp.square(flat), axis=1) + self.eps)
        norm = norm.reshape((-1,) + (1,) * (x.ndim - 1))
        return x / norm, _first_mask(masks)

    def get_output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclass
class PoolHelperVertex(GraphVertex):
    """Strips the first row/column of a CNN activation (GoogLeNet import compat,
    ref PoolHelperVertex.java)."""

    def forward(self, inputs, masks):
        return inputs[0][:, :, 1:, 1:], _first_mask(masks)

    def get_output_type(self, input_types):
        t = input_types[0]
        return InputType.convolutional(t.height - 1, t.width - 1, t.channels)


@register_vertex
@dataclass
class LastTimeStepVertex(GraphVertex):
    """(batch, size, time) → (batch, size) at the last *unmasked* step
    (ref rnn/LastTimeStepVertex.java)."""

    def forward(self, inputs, masks):
        x = inputs[0]
        m = _first_mask(masks)
        if m is None:
            return x[:, :, -1], None
        idx = jnp.sum(m > 0, axis=1).astype(jnp.int32) - 1  # (batch,)
        idx = jnp.clip(idx, 0, x.shape[2] - 1)
        out = jnp.take_along_axis(x, idx[:, None, None], axis=2)[:, :, 0]
        return out, None

    def get_output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)


@register_vertex
@dataclass
class DuplicateToTimeSeriesVertex(GraphVertex):
    """(batch, size) → (batch, size, time), copying across time; the time dimension is
    taken from a reference input at forward time (ref rnn/DuplicateToTimeSeriesVertex.java).
    Here the second input supplies the time axis."""

    def forward(self, inputs, masks):
        x, ref = inputs[0], inputs[1]
        t = ref.shape[2]
        return jnp.broadcast_to(x[:, :, None], x.shape + (t,)), masks[1]

    def get_output_type(self, input_types):
        t = input_types[1].timeseries_length if len(input_types) > 1 else -1
        return InputType.recurrent(input_types[0].size, t)


@register_vertex
@dataclass
class HyperStreamsVertex(GraphVertex):
    """The ends of a hyper-connected stack (nn/conf/layers/decoder.py):
    `expand` copies (batch, time, d) into `n_streams` residual streams
    (batch, n, time, d); `sum` adds the streams up again."""
    n_streams: int = 4
    mode: str = "expand"

    def forward(self, inputs, masks):
        x = inputs[0]
        if self.mode == "expand":
            return jnp.broadcast_to(x[:, None], (x.shape[0], self.n_streams)
                                    + x.shape[1:]), masks[0]
        return jnp.sum(x, axis=1), masks[0]

    def get_output_type(self, input_types):
        t = input_types[0]
        size = t.size * self.n_streams if self.mode == "expand" \
            else t.size // self.n_streams
        return InputType.recurrent(size, t.timeseries_length)
