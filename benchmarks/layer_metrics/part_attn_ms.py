"""Train step: device milliseconds a step under the part ``attn``: a
layer's attention half, with its norms, projections, rotation, gate and
residual add, and its kernel calls (``flash_ms`` and its kin are the
kernels alone).  All phases together, each op's self time on device 0;
the part is the OUTERMOST component of the op's name that is on the
program's list (``scopes.part``, the list from the run's
``model:step.scopes`` span).  ``None`` without that span or without
names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.part_ms(trace, run, "attn")
