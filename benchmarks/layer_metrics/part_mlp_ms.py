"""Train step: device milliseconds a step under the part ``mlp``: a
layer's dense MLP or shared expert, with its norms and residual add (the
routed experts are the ``part_moe_*``).  All phases together, each op's
self time on device 0; the part is the OUTERMOST component of the op's
name that is on the program's list (``scopes.part``, the list from the
run's ``model:step.scopes`` span).  ``None`` without that span or
without names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.part_ms(trace, run, "mlp")
