"""Compile: programs the persistent compilation cache did not have
(``/jax/compilation_cache/cache_misses`` events in the worker).  0 in
every run of a cell after its first in a checkout."""


def read(trace, spans, run):
    return run["final"]["cache"]["misses"]
