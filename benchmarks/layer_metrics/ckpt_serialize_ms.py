"""Checkpoint and object plane: median host milliseconds of
``Checkpoint.from_pytree(params)`` per save: device-to-host, then flax
msgpack."""

import statistics


def read(trace, spans, run):
    xs = [s["serialize_s"] for s in run["final"]["window"]["saves"]]
    return 1e3 * statistics.median(xs) if xs else None
