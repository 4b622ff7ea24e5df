"""Compile: seconds jax spent lowering the step's jaxpr to an MLIR
module in the gang worker before the window (``xla:lower``)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.compile_phase_s(ps.timeline(), "lower", run["step_module"],
                              run["final"]["window"]["t_start"])
