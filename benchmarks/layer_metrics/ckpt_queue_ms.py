"""Checkpoint and object plane: median milliseconds a reported
checkpoint waits in the worker's session queue: end of ``train:report``
until the ``train:next_results`` call that carries it starts (the driver
polls again only after it registered the save before)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.median_leg_ms(ps.timeline(), run, "queue")
