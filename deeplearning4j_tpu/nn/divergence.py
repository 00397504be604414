"""Divergence-sentinel bookkeeping shared by MultiLayerNetwork and
ComputationGraph (SURVEY §5 failure detection).

The fit_on_device scan carries a first-bad-step index (`div`, -1 = clean)
computed entirely on device. `sync=True` resolves it at the end of the call
(one host readback, immediate warning — the reference's
InvalidScoreIterationTerminationCondition semantics). `sync=False` defers:
the index is STASHED as a device scalar and materialized on the first
`_diverged_at` access, so benchmark loops never pay a host readback per
call.

Back-to-back deferred calls merge STICKILY on device (`jnp.where(prev >= 0,
prev, new)`): a later clean call must not clobber an unobserved divergence —
the first bad step survives until somebody looks, then the warning fires
exactly once and subsequent stashes can clear the state again."""
from __future__ import annotations


class DivergenceSentinelMixin:
    _pending_div = None       # device scalar: first bad step, -1 = clean
    _diverged_at_v = None     # resolved host value (int step or None)

    def _stash_pending_div(self, div):
        """Record a new device-side sentinel, preserving any unobserved one."""
        if self._pending_div is not None:
            import jax.numpy as jnp
            prev = self._pending_div
            div = jnp.where(prev >= 0, prev, div)
        self._pending_div = div

    def _resolve_divergence(self, div: int):
        self._pending_div = None
        self._diverged_at_v = div if div >= 0 else None
        if self._diverged_at_v is not None:
            import warnings
            warnings.warn(
                f"Training diverged: non-finite loss at step "
                f"{self._diverged_at_v}; parameters frozen at the last "
                f"finite step (ref InvalidScoreIterationTerminationCondition "
                f"semantics)")

    @property
    def _diverged_at(self):
        if self._pending_div is not None:
            self._resolve_divergence(int(self._pending_div))
        return self._diverged_at_v

    @_diverged_at.setter
    def _diverged_at(self, v):
        self._pending_div = None
        self._diverged_at_v = v
