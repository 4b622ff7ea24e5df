"""Trainer and gang: seconds of the program's ``train:gang.place`` span
(``WorkerGroup.start``: ``placement_group(...)`` until ``pg.wait``
returns), from the ended run's timeline."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.last_seconds(ps.timeline(), "train", "gang.place")
