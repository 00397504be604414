"""Gradient-sharing accumulators + threshold compression.

Parity: ref optimize/solvers/accumulation/ — GradientsAccumulator API,
EncodedGradientsAccumulator.java:33 (threshold quantization with residuals,
`thresholdDecode` :257-374) and EncodingHandler.java:30-114. The reference's native
"THRESHOLD" NDArrayCompressor quantizes each update to a sparse ±threshold message,
keeping the un-sent remainder as a residual that accumulates locally (Strom-style 1-bit
SGD). Here the encode/decode pair is pure jnp (XLA fuses it into the step); the
cross-replica transport that Aeron/parameter-server provided becomes an ICI psum inside
ParallelWrapper (SURVEY §2.6 mapping). The async staleness model of the reference is
deliberately implemented as *synchronous* application with identical message semantics —
see SURVEY §7 "hard parts" (documented behavioral delta).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np


def threshold_encode(update: jnp.ndarray, residual: jnp.ndarray, threshold: float):
    """Quantize update+residual to {-t, 0, +t}; remainder stays in the residual
    (ref EncodingHandler threshold logic). Returns (message, new_residual)."""
    acc = update + residual
    mask = jnp.abs(acc) >= threshold
    message = jnp.where(mask, jnp.sign(acc) * threshold, 0.0).astype(update.dtype)
    return message, acc - message


class GradientsAccumulator:
    """Base API (ref accumulation/GradientsAccumulator.java): store updates, hand back
    the aggregated update to apply."""

    def store_update(self, flat_grads: jnp.ndarray, party: int = 0) -> None:
        """Store one worker's update. `party` identifies the worker so stateful
        encoders keep per-worker residuals (ref: one EncodingHandler per trainer)."""
        raise NotImplementedError

    def get_update(self) -> jnp.ndarray:
        raise NotImplementedError

    def reset(self) -> None:
        pass


class BasicGradientsAccumulator(GradientsAccumulator):
    """Identity accumulator: aggregates whatever replicas stored since last get
    (ref BasicGradientsAccumulator). Single-process form: averages stored updates."""

    def __init__(self, parties: int = 1):
        self.parties = parties
        self._stored = []

    def store_update(self, flat_grads, party: int = 0):
        self._stored.append(flat_grads)

    def get_update(self):
        if not self._stored:
            raise ValueError("No updates stored")
        out = self._stored[0]
        for u in self._stored[1:]:
            out = out + u
        agg = out / len(self._stored)
        self._stored = []
        return agg

    def reset(self):
        self._stored = []


class EncodedGradientsAccumulator(GradientsAccumulator):
    """Threshold-compressed accumulator (ref EncodedGradientsAccumulator.java:33):
    each stored update is quantized to ±threshold with a persistent residual; the
    aggregated message is what a worker would have broadcast through the parameter
    server. Adaptive threshold decay mirrors EncodingHandler's decay parameters."""

    def __init__(self, parties: int = 1, threshold: float = 1e-3,
                 threshold_decay: float = 1.0, min_threshold: float = 1e-5):
        self.parties = parties
        self.threshold = float(threshold)
        self.threshold_decay = float(threshold_decay)
        self.min_threshold = float(min_threshold)
        # one residual per party: each worker owns its own encoder state
        # (ref: one EncodingHandler instance per trainer thread)
        self._residuals: dict = {}
        self._stored = []

    def store_update(self, flat_grads, party: int = 0):
        residual = self._residuals.get(party)
        if residual is None:
            residual = jnp.zeros_like(flat_grads)
        message, self._residuals[party] = threshold_encode(flat_grads, residual,
                                                           self.threshold)
        self._stored.append(message)

    def get_update(self):
        if not self._stored:
            raise ValueError("No updates stored")
        out = self._stored[0]
        for u in self._stored[1:]:
            out = out + u
        self._stored = []
        # decay once per aggregation round, not once per party's store
        # (ref EncodingHandler: one decay step per iteration)
        self.threshold = max(self.min_threshold,
                             self.threshold * self.threshold_decay)
        return out

    def reset(self):
        self._stored = []
        self._residuals = {}
