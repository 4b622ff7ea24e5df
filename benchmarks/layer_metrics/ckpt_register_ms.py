"""Checkpoint and object plane: median milliseconds of
``train:ckpt.register`` in the driver (``to_directory``, the metrics
file, retention)."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.median_leg_ms(ps.timeline(), run, "register")
