"""Seconds the process spent in the backend for its programs, by the
program's own counter `dl4j.compile.backend_s` at the same moment as
`setup_trace_lower_s`: XLA's compile on a cache miss, the load from the
persistent cache on a hit. JAX's event spans the cache's retrieval
(`interpreters/pxla.py`: `backend_compile_duration` is taken around
`compile_or_get_cached`), so `dl4j.compile.cache_load_s` is a part of it
and is not added again."""
from harness import program_trace


def read(run):
    c = program_trace.counters(run)
    return None if c is None else c["backend_s"]
