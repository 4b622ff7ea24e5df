"""Compile: seconds jax spent tracing the step's function to a jaxpr in
the gang worker before the window (its own ``jaxpr_trace_duration``
event, left in the timeline as ``xla:trace``)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.compile_phase_s(ps.timeline(), "trace", run["step_module"],
                              run["final"]["window"]["t_start"])
