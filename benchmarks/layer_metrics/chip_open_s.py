"""Lease and chip: seconds of the program's ``train:chip_open`` span
(``TrainWorker.setup_jax``: ``jax.distributed.initialize`` and the
first touch of the backend), the longest over the gang's workers.  A
gang that asked for no chip leaves the span too, at about zero."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    found = ps.select(ps.timeline(), "train", "chip_open")
    return max(map(ps.seconds, found)) if found else None
