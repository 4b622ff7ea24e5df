"""Checkpoint and object plane: median milliseconds of the encode leg of
``Checkpoint.from_pytree`` (its ``train:ckpt.encode`` child: the msgpack
framing worked out, one buffer allocated, each leaf copied into it
once), as the ``encode_ms`` argument of ``train:ckpt.from_pytree`` gives
it."""

from benchmarks.layer_metrics.ckpt_d2h_ms import median_arg
from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    return median_arg(ps.timeline(), run, "encode_ms")
