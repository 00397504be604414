"""Where the persistent XLA compilation cache lives.

One rule, shared by every entry point that compiles for the chip
(`chip_smoke.py`'s phase processes, `bench.py`): where the environment sets
`JAX_COMPILATION_CACHE_DIR`, JAX reads it itself and the program names no
directory in code; where it does not, the cache goes to one fixed,
git-ignored directory inside the checkout. The path is part of the cache
key, so a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for this process. Returns
    the directory this call set, or None when `JAX_COMPILATION_CACHE_DIR`
    placed the cache from outside. Touches only `jax.config`, so it
    initialises no backend."""
    import jax
    # cache every executable, not only those that took over a second to
    # build: a second run against the same directory then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
