"""Checkpoint and object plane: median milliseconds from the end of the
worker's ``worker:reply`` to the end of the driver's ``train:poll`` that
delivered the checkpoint (the pull and ``worker:get.deserialize``)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.median_leg_ms(ps.timeline(), run, "fetch")
