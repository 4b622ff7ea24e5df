"""Train step: device milliseconds a step under the part ``ssm.scan``: a
mixer's step sizes and decays and the chunked scan's kernel calls
(``ssd_ms`` is the kernels alone).  All phases together, each op's self
time on device 0; the part is the OUTERMOST component of the op's name
that is on the program's list (``scopes.part``, the list from the run's
``model:step.scopes`` span).  ``None`` without that span or without
names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.part_ms(trace, run, "ssm.scan")
