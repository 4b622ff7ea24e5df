"""Device: the instrument's own health: percent of device 0's op time in
the traced window (each op's self time, collectives left out) that lies
under NO part of the program's list (``scopes.part`` gives ``None``:
what XLA hoists out of any scope, and what the program has not named).
``None`` where there is no ``model:step.scopes`` span or the profiler's
file names no op."""

from benchmarks.reduce import scopes


def read(trace, spans, run):
    return scopes.unnamed_share(trace, run)
