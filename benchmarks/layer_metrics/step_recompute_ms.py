"""Train step: device milliseconds a step in the forward that a
``jax.checkpoint`` runs AGAIN inside the backward pass: the component
``rematted_computation`` in the op's name.  All parts together, each
op's self time on device 0, told by jax's own markers
(``scopes.phase``).  ``None`` without a ``model:step.scopes`` span or
without names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.phase_ms(trace, run, "recompute")
