"""The device this run got: the chip check, the table of peaks, the compile
counter, the compile-cache rule and the memory reading. Copies of the sound
pieces of `chip_smoke.py` and `util/compile_cache.py`, kept here so that a
later PR cannot change the yardstick."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
EXIT_NO_ACCELERATOR = 4
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and no
    directory is named in code; otherwise one fixed, git-ignored directory in
    the checkout. Every program is cached, however short its compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chip(chips: int) -> dict:
    """Name the device; leave with no result unless it is a TPU with at least
    as many chips as the cell asks for."""
    import jax
    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] < chips:
        print(f"benchmark: this cell needs {chips} TPU chip(s); JAX found "
              f"platform {found['platform']!r} ({found['kind']}, "
              f"{found['count']} device(s)). No number is taken off the chip.",
              file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_ACCELERATOR)
    return found


def load_peaks(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error and never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {path}: add its "
                       "published peaks with their source before measuring on it")
    return table[kind]


class Compiles:
    """Counts what this process compiled, from JAX's own monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of the devices used: the buffers
    (`peak_bytes_in_use`) and, where the runtime counts it apart, what it
    reserved for the loaded programs' temporaries (`peak_bytes_reserved`; on a
    TPU v5 lite the two are disjoint: `bytes_limit` less both is the largest
    free block, PERF.md section 4)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
