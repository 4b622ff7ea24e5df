"""Train step: device milliseconds a step under the part ``ssm.in_proj``
and ``ssm.out_proj``: a mixer's norm, its two projections, the split and
the residual add.  All phases together, each op's self time on device 0;
the part is the OUTERMOST component of the op's name that is on the
program's list (``scopes.part``, the list from the run's
``model:step.scopes`` span).  ``None`` without that span or without
names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.part_ms(trace, run, "ssm.in_proj", "ssm.out_proj")
