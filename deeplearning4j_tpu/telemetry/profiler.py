"""Device-time profiler: compiled-function costs -> live roofline gauges.

ISSUE 6 tentpole, part 1+3. Three jobs:

1. **Compiled-function cost registry.** The serving engine's jit entry
   points (prefill buckets, decode_chunk per K) call
   `register(name, jitted, args...)` at compile time — an
   AOT `lower().compile().cost_analysis()` via util/costs, nothing
   executes, no buffer is donated — filing FLOPs/bytes under the function
   name and publishing `profiler.fn.<name>.{flops,bytes,mxu_floor_ms}`
   gauges.

2. **Live roofline attribution.** Call sites feed `observe(name, ms)` with
   wall times they ALREADY measure on the host (the same perf_counter
   deltas the tracer's spans record) — combining a host float with a
   registered cost is pure host arithmetic, so the PR 4 zero-added-syncs
   invariant holds with profiling on (regression-tested in
   tests/test_profiler.py). Published per function: an `ms` histogram plus
   `measured_ms` / `mfu` / `roofline_frac` / `x_floor` gauges.

3. **From a device trace's operation to the program's own name.** The
   nets put `jax.named_scope("dl4j.<LayerClass>/<name>")` (and `dl4j.loss`,
   `dl4j.regularization`, `dl4j.updater`) around what they trace. A device
   trace's events carry the optimised instruction's text and no metadata,
   but the compiled program's text has an `op_name` for every fusion, dot
   and custom call: `op_scopes(compiled)` is the table from instruction
   name to `op_name`, and `scope_phase(op_name)` reduces one to its
   innermost `dl4j.` scope and forward / backward / update. The table is
   computed by whoever reads a trace (`net.lower_train_step(...)` /
   `net.lower_fit_batch(...)` lower the program that ran from shapes
   alone); nothing is kept at dispatch.

Honesty notes:
- `mxu_floor_ms` is flops / peak FLOP/s, with the peak taken from
  `DEVICE_PEAKS` by the chip's `device_kind`. A TPU kind that is not in the
  table raises; off a TPU there is no peak, so rows and gauges carry the
  platform, the measured ms and the cost-model counts, and no floor, no
  MFU and no roofline fraction.
- `bytes_accessed` is XLA's per-HLO sum (ignores fusion reuse) — the
  optimistic-roof side of the bracket, same caveat as PERF.md.

Env toggle: DL4J_TPU_PROFILE (any value but 0/false/off) enables cost
registration at the instrumented call sites. Unset/0 keeps every site inert
(one dict/flag check on the compile-miss path, nothing per token/step).
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.telemetry.registry import (DEFAULT_MS_BUCKETS,
                                                   MetricsRegistry,
                                                   sanitize_component)
from deeplearning4j_tpu.util import costs as _costs

# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`. The one
# table every floor, MFU and roofline share in the repo divides by (bench.py
# imports it). A device that is not listed is an error, not a default.
DEVICE_PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 819 GB/s HBM per chip",
    },
}

_FALSEY = ("", "0", "false", "off")

_ENABLED = os.environ.get("DL4J_TPU_PROFILE", "").lower() not in _FALSEY
_PLATFORM: Optional[str] = None          # lazy jax.default_backend()
_DEVICE_KIND: Optional[str] = None       # lazy jax.devices()[0].device_kind

# host-side per-function aggregates: name -> {count, total_ms, last_ms}
_OBSERVED: Dict[str, dict] = {}


def enabled() -> bool:
    """Whether instrumented call sites should register costs / feed
    observations (DL4J_TPU_PROFILE, default off)."""
    return _ENABLED


def configure(enabled: Optional[bool] = None,
              platform: Optional[str] = None,
              device_kind: Optional[str] = None) -> None:
    """Override env defaults at runtime (tests, bench, embedding apps).
    `platform`/`device_kind` pin what would otherwise be detected from
    JAX."""
    global _ENABLED, _PLATFORM, _DEVICE_KIND
    if enabled is not None:
        _ENABLED = bool(enabled)
    if platform is not None:
        _PLATFORM = str(platform)
    if device_kind is not None:
        _DEVICE_KIND = str(device_kind)


def clear_observations() -> None:
    """Drop the host wall-time aggregates, keeping registered costs and
    config — callers use this between a compile warmup and the timed runs
    so `roofline_table()` means are compile-free (bench_serving_profile)."""
    _OBSERVED.clear()


def reset() -> None:
    """Forget observations and restore env-derived config (tests)."""
    global _ENABLED, _PLATFORM, _DEVICE_KIND
    _OBSERVED.clear()
    _ENABLED = os.environ.get("DL4J_TPU_PROFILE", "").lower() not in _FALSEY
    _PLATFORM = None
    _DEVICE_KIND = None


def _detect_platform() -> str:
    global _PLATFORM
    if _PLATFORM is None:
        try:
            import jax
            _PLATFORM = jax.default_backend()
        except Exception:
            _PLATFORM = "unknown"
    return _PLATFORM


def platform() -> str:
    """The accelerator platform name ("tpu"/"cpu"/...), detected lazily."""
    return _detect_platform()


def device_peaks(device_kind: Optional[str] = None) -> dict:
    """The `DEVICE_PEAKS` entry (`bf16_flops`, `hbm_bytes_per_s`, `source`)
    for `device_kind`, by default the kind of `jax.devices()[0]`. Raises
    KeyError for a kind the table does not hold — a floor computed from
    another chip's peak is a wrong number, not an estimate."""
    global _DEVICE_KIND
    if device_kind is None:
        if _DEVICE_KIND is None:
            import jax
            _DEVICE_KIND = jax.devices()[0].device_kind
        device_kind = _DEVICE_KIND
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to telemetry.profiler."
            "DEVICE_PEAKS with its source") from None


def mxu_floor_ms(flops: float, plat: Optional[str] = None) -> Optional[float]:
    """Compute-roofline floor in ms for `flops`: flops over the chip's bf16
    peak on a TPU (an unknown kind raises), None on any other platform."""
    if (plat or _detect_platform()) != "tpu":
        return None
    return flops / device_peaks()["bf16_flops"] * 1e3


def _default_registry() -> MetricsRegistry:
    from deeplearning4j_tpu import telemetry
    return telemetry.registry()


# ------------------------------------------------------------- register
def register(name: str, jitted=None, args=(), *,
             flops: Optional[float] = None,
             bytes_accessed: Optional[float] = None,
             meta: Optional[dict] = None,
             registry: Optional[MetricsRegistry] = None) -> dict:
    """Register a compiled function's cost-model numbers under `name`.

    Either pass `jitted` (+ the call args about to be dispatched) for an
    AOT `cost_analysis()`, or pass `flops`/`bytes_accessed` directly (bench
    replays already-measured numbers). Registration is explicit — the
    instrumented call sites gate on `enabled()` so default runs never pay
    the extra lower/compile. Publishes `profiler.fn.<name>.flops/.bytes`
    gauges (and `.mxu_floor_ms` on a TPU) and returns the cost record.

    Safe to call immediately before dispatching a donated-arg jit (AOT
    lowering does not consume buffers) — and that ordering is REQUIRED
    where the real call donates its arguments."""
    plat = _detect_platform()
    meta = dict(meta or {})
    meta.setdefault("platform", plat)
    if jitted is not None:
        rec = _costs.analyze_and_record(name, jitted, *args, meta=meta)
    else:
        rec = _costs.record_costs(name, flops or 0.0, bytes_accessed or 0.0,
                                  meta=meta)
    reg = registry or _default_registry()
    n = sanitize_component(name)
    reg.gauge(f"profiler.fn.{n}.flops",
              "XLA cost-model FLOPs per call").set(rec["flops"])
    reg.gauge(f"profiler.fn.{n}.bytes",
              "XLA cost-model bytes accessed per call (per-HLO sum)"
              ).set(rec["bytes_accessed"])
    floor = mxu_floor_ms(rec["flops"], plat)
    if floor is not None:
        reg.gauge(f"profiler.fn.{n}.mxu_floor_ms",
                  "compute-roofline floor ms (flops / chip bf16 peak)"
                  ).set(floor)
    return rec


# -------------------------------------------------------------- observe
def observe(name: str, ms: float,
            registry: Optional[MetricsRegistry] = None) -> None:
    """Feed one measured wall-time (milliseconds, a HOST value the caller
    already holds — never a device read) for a registered function.
    Publishes the ms histogram + measured_ms gauge, and when costs are on
    file for a TPU run, the mfu / roofline_frac / x_floor gauges. Pure host
    arithmetic: zero added syncs."""
    ms = float(ms)  # sync-ok: caller passes a host wall-clock delta
    agg = _OBSERVED.get(name)
    if agg is None:
        agg = _OBSERVED.setdefault(name, {"count": 0, "total_ms": 0.0,
                                          "last_ms": 0.0})
    agg["count"] += 1
    agg["total_ms"] += ms
    agg["last_ms"] = ms
    reg = registry or _default_registry()
    n = sanitize_component(name)
    reg.histogram(f"profiler.fn.{n}.ms",
                  "measured wall time per call (host clock)",
                  buckets=DEFAULT_MS_BUCKETS).observe(ms)
    reg.gauge(f"profiler.fn.{n}.measured_ms",
              "last measured wall time per call").set(ms)
    rec = _costs.get_costs(name)
    if rec is None or ms <= 0.0:
        return
    floor = mxu_floor_ms(rec["flops"], rec.get("meta", {}).get("platform"))
    if not floor:
        return
    reg.gauge(f"profiler.fn.{n}.roofline_frac",
              "MXU-floor ms / measured ms (1.0 = at the roofline)"
              ).set(floor / ms)
    reg.gauge(f"profiler.fn.{n}.x_floor",
              "measured ms / MXU-floor ms").set(ms / floor)
    reg.gauge(f"profiler.fn.{n}.mfu",
              "model FLOPs utilization vs the chip's bf16 peak"
              ).set(floor / ms)


def observed(name: str) -> Optional[dict]:
    """Host aggregate for `name`: {count, total_ms, last_ms} or None."""
    agg = _OBSERVED.get(name)
    return dict(agg) if agg else None


# ------------------------------------------------------- roofline table
def roofline_table(registry: Optional[MetricsRegistry] = None) -> List[dict]:
    """Join registered costs with host aggregates: one dict per function
    with measured vs floor, MFU, bytes. Functions registered but never
    observed get measured_ms None (compile happened, no timed call yet);
    rows from a platform other than a TPU carry no floor and no MFU."""
    rows: List[dict] = []
    for name, rec in sorted(_costs.all_costs().items()):
        plat = rec.get("meta", {}).get("platform") or _detect_platform()
        agg = _OBSERVED.get(name)
        mean_ms = (agg["total_ms"] / agg["count"]
                   if agg and agg["count"] else None)
        floor = mxu_floor_ms(rec["flops"], plat)
        row = {
            "function": name,
            "platform": plat,
            "flops": rec["flops"],
            "bytes_accessed": rec["bytes_accessed"],
            "mxu_floor_ms": None if floor is None else round(floor, 4),
            "measured_ms": None if mean_ms is None else round(mean_ms, 4),
            "calls": agg["count"] if agg else 0,
            "mfu": None,
            "x_floor": None,
        }
        if floor and mean_ms and mean_ms > 0.0:
            mfu = floor / mean_ms
            # keep tiny utilizations exact — rounding to 0.0 would read as
            # "no flops ran" (and fail the schema's (0,1))
            row["mfu"] = round(mfu, 4) if mfu >= 1e-4 else mfu
            row["x_floor"] = round(mean_ms / floor, 2)
        rows.append(row)
    return rows


# ------------------------------------- device operation -> program's name
# `  %fusion.7 = bf16[8]{0} fusion(%p.1, %copy-done.3), kind=kLoop, ...,
#  metadata={op_name="jit(f)/..." ...}`
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
# the innermost of these in an op_name is the operation's scope
_SCOPE = re.compile(r"dl4j\.(?:loss|regularization|updater)\b"
                    r"|dl4j\.\w+/[^/()]+")


def op_scopes(compiled) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled program (`jitted.lower(
    ...).compile()`, or its `as_text()`): every instruction of the entry
    computation and of the computations it calls (a scan's `while` body),
    the fused computations' insides left out — a device trace has one event
    per fusion, named by the fusion instruction. XLA keeps one instruction's
    metadata for a fusion, so a fusion goes whole to that instruction's
    scope. What the compiler put in to move an operand (`copy`,
    `copy-done`, `slice-done`, ...) has no metadata: it goes to the
    `op_name` of the first instruction that consumes its result."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    rows = []                   # (name, own op_name or None, operands)
    inside_fusion = False
    for line in text.splitlines():
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            if head is not None:
                inside_fusion = "fused_computation" in head.group(1)
            continue
        found = None if inside_fusion else _INSTRUCTION.match(line)
        if found is None:
            continue
        name, rest = found.groups()
        own = _OP_NAME.search(rest)
        rows.append((name, own.group(1) if own else None,
                     _OPERAND.findall(rest.split(", metadata=")[0])))
    table = {name: own for name, own, _ in rows if own is not None}
    inherited: Dict[str, str] = {}
    # consumers before producers: the first consumer's name is written last
    for name, own, operands in reversed(rows):
        op_name = own or inherited.get(name)
        if op_name is not None:
            inherited.update((o, op_name) for o in operands if o not in table)
    return {**inherited, **table}


def scope_phase(op_name: str) -> Tuple[Optional[str], str]:
    """(scope, phase) of an `op_name`: the innermost `dl4j.` scope in it
    (`dl4j.ConvolutionLayer/res2a`, `dl4j.updater`; None where the program
    named nothing) and `update` under `dl4j.updater`, `backward` under a
    `transpose(`, else `forward` (under `jvp(` only, or plain)."""
    scopes = _SCOPE.findall(op_name)
    scope = scopes[-1] if scopes else None
    if "dl4j.updater" in scopes:
        return scope, "update"
    return scope, "backward" if "transpose(" in op_name else "forward"
