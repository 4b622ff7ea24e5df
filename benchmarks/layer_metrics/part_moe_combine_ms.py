"""Train step: device milliseconds a step under the part
``moe.combine``: ``tokens <- rows``, weighted by the router's scores.
All phases together, each op's self time on device 0; the part is the
OUTERMOST component of the op's name that is on the program's list
(``scopes.part``, the list from the run's ``model:step.scopes`` span).
``None`` without that span or without names in the profiler's file."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.part_ms(trace, run, "moe.combine")
