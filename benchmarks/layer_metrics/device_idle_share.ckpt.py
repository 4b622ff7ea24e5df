"""Device, in a cell that saves: share of one traced cycle (16 steps and
their save) in which no operation ran on the chip.  The same reduction
as ``device_idle_share``; apart because here it is the save that moves
it."""


def read(trace, spans, run):
    return 100.0 * trace["idle_share"] if trace else None
