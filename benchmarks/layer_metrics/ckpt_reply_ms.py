"""Checkpoint and object plane: median milliseconds from the start of
the ``train:next_results`` that carries a checkpoint to the end of its
``worker:reply`` (serialising the reply and storing it in the node's
object store), in the worker that also runs the train loop."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return ps.median_leg_ms(ps.timeline(), run, "reply")
