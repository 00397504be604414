"""XLA cost-analysis helpers (MFU accounting for bench.py) plus the
process-wide compiled-function cost registry (ISSUE 6).

The registry half is deliberately dumb storage: `record_costs` files a
{'flops', 'bytes_accessed'} entry under a function name, `analyze_and_record`
derives one from a jitted callable via `lowered_costs`, and
telemetry/profiler.py is the consumer that turns entries into MFU /
roofline-fraction gauges. Keeping the store here (jax-free apart from the
AOT lower call) lets bench.py and the telemetry package share one table
without import cycles.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

_COSTS: Dict[str, dict] = {}
_COSTS_LOCK = threading.Lock()   # registration only — reads are lock-free


def lowered_costs(jitted, *args, **kwargs) -> dict:
    """{'flops', 'bytes_accessed'} of `jitted(*args, **kwargs)` per XLA's cost
    model (AOT lower/compile, nothing executes). bytes_accessed is the
    per-HLO-instruction sum — an upper-ish estimate of HBM traffic that
    ignores fusion reuse; PERF.md's roofline uses it as the optimistic-roof
    side of the bracket."""
    return costs_of(jitted.lower(*args, **kwargs))


def costs_of(lowered) -> dict:
    """`lowered_costs` for an already-lowered computation (a
    `jax.stages.Lowered`, e.g. a net's `lower_train_step`)."""
    try:
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:
        import warnings
        warnings.warn(f"XLA cost analysis unavailable ({type(e).__name__}: {e})")
        return {"flops": 0.0, "bytes_accessed": 0.0}


# --------------------------------------------------- named cost registry
def record_costs(name: str, flops: float = 0.0, bytes_accessed: float = 0.0,
                 meta: Optional[dict] = None) -> dict:
    """File XLA cost-model numbers for a named compiled function. Idempotent
    by name (last writer wins — recompiles of the same entry point refresh
    the entry). Returns the stored record."""
    rec = {"flops": float(flops), "bytes_accessed": float(bytes_accessed),
           "meta": dict(meta) if meta else {}}
    with _COSTS_LOCK:
        _COSTS[name] = rec
    return rec


def analyze_and_record(name: str, jitted, *args,
                       meta: Optional[dict] = None, **kwargs) -> dict:
    """`lowered_costs` + `record_costs` in one step. AOT lower/compile —
    nothing executes and no buffer is donated, so it is safe to call
    immediately BEFORE dispatching a jit whose donated args are still alive
    (register first, then dispatch)."""
    costs = lowered_costs(jitted, *args, **kwargs)
    return record_costs(name, costs["flops"], costs["bytes_accessed"],
                        meta=meta)


def get_costs(name: str) -> Optional[dict]:
    """The registered record for `name`, or None. Lock-free read (dict get
    is atomic under the GIL) — safe from hot paths."""
    return _COSTS.get(name)


def all_costs() -> Dict[str, dict]:
    """Snapshot of every registered entry (shallow copy)."""
    with _COSTS_LOCK:
        return dict(_COSTS)


def clear_costs() -> None:
    """Drop every registered entry (tests / bench warm-up exclusion)."""
    with _COSTS_LOCK:
        _COSTS.clear()
