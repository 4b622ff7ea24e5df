"""Train step: device milliseconds a step in the FORWARD pass: ``jvp(``
in the op's name, and neither ``transpose(`` nor
``rematted_computation``.  All parts together, each op's self time on
device 0, told by jax's own markers (``scopes.phase``).  ``None``
without a ``model:step.scopes`` span or without names in the profiler's
file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.phase_ms(trace, run, "forward")
