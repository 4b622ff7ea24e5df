"""Device: share of the traced window in which no operation ran on the
chip: 1 - union of op intervals / window, averaged over the chips."""


def read(trace, spans, run):
    return 100.0 * trace["idle_share"] if trace else None
