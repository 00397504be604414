"""Acceleration-helper seam (L2): the one place that chooses an implementation.

Parity: ref nn/layers/LayerHelper + ConvolutionHelper/LSTMHelper/
BatchNormalizationHelper — the reference's pluggable cudnn fast-path interfaces
(e.g. nn/layers/recurrent/LSTMHelper.java). TPU rendering: a kernel module
registers its Pallas implementation under an op name, and every call site asks
`helper_for(name, fallback)` and nothing else. The policy is one sentence: a
registered kernel runs on a TPU unless the override says otherwise, and off a
TPU only when the override forces it (interpreted). What a site can observe
itself (a mask, a shape the kernel refuses) it checks BEFORE it asks, so that
`ops.helper.<name>.kernel|fallback` count decisions that were really open.
XLA's default codegen is already excellent — a kernel stays registered only
where hand-tiling beats the compiler, and everything keeps working with the
override off.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional

_REGISTRY: Dict[str, Callable] = {}
_OVERRIDE: Optional[bool] = None


def interpret_mode() -> bool:
    """Pallas kernels run interpreted off-TPU so the CPU test mesh exercises
    the same code path (single policy point for every kernel module)."""
    import jax
    return jax.default_backend() != "tpu"


def register_helper(op_name: str):
    """Decorator: register the accelerated implementation of `op_name` — the
    reference's 'cuDNN used when supported' (ConvolutionLayer.java:72
    reflection-load)."""
    def deco(fn):
        _REGISTRY[op_name] = fn
        return fn
    return deco


def enable_helpers(flag: Optional[bool] = True) -> None:
    """The override: True forces every registered kernel (interpreted off a
    TPU), False none, None resets to the default policy."""
    global _OVERRIDE
    _OVERRIDE = None if flag is None else bool(flag)


@contextlib.contextmanager
def helpers_enabled_ctx(flag: Optional[bool]):
    """Scoped enable_helpers: restores the previous override on exit, so a
    temporary flip can never pin it for the rest of the process."""
    prev = _OVERRIDE
    enable_helpers(flag)
    try:
        yield
    finally:
        enable_helpers(prev)


def helpers_enabled_for(op_name: str) -> bool:
    """The policy `helper_for` asks: the override, else 'on a TPU'."""
    if op_name not in _REGISTRY:
        return False
    if _OVERRIDE is not None:
        return _OVERRIDE
    import jax
    return jax.default_backend() == "tpu"


def helper_for(op_name: str, fallback: Optional[Callable]) -> Optional[Callable]:
    """The seam: the registered kernel where the policy engages it, else the
    fallback (ref LayerHelper selection in BaseLayer.initializeHelper). A
    site whose fallback has another signature than the kernel passes None
    and branches on the answer."""
    engaged = helpers_enabled_for(op_name)
    # seam attribution (ISSUE 6): count which path resolved, at resolve
    # time — under jit that is trace time, never per step. Sanitized: op
    # names are free-form.
    try:
        from deeplearning4j_tpu import telemetry
        telemetry.registry().counter(
            f"ops.helper.{telemetry.sanitize_component(op_name)}."
            f"{'kernel' if engaged else 'fallback'}",
            "helper-seam resolutions by path (counted at trace time)").inc()
    except Exception:
        pass
    return _REGISTRY[op_name] if engaged else fallback


def registered_helpers():
    return dict(_REGISTRY)
