"""Train step: device milliseconds a step in the BACKWARD pass proper:
``transpose(`` in the op's name, the recomputed forward left out.  All
parts together, each op's self time on device 0, told by jax's own
markers (``scopes.phase``).  ``None`` without a ``model:step.scopes``
span or without names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.phase_ms(trace, run, "backward")
