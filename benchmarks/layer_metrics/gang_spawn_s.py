"""Lease and chip: seconds of the program's ``train:gang.spawn`` span
(``WorkerGroup.start``: the first ``.remote()`` until every worker
answered ``__ray_ready__``).  Its children in the timeline are the
raylet's ``lease:spawn`` and the worker's ``worker:boot``."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.last_seconds(ps.timeline(), "train", "gang.spawn")
