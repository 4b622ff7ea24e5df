"""Compile: seconds of the step's ``xla:backend_compile`` spans in the
gang worker before the window: a backend compile, or the persistent
cache's answer (``compile_cache_misses`` says which)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.compile_phase_s(ps.timeline(), "backend_compile",
                              run["step_module"],
                              run["final"]["window"]["t_start"])
