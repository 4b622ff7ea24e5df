"""Kernels: device milliseconds per step in the grouped matrix products
of the routed experts (per expert layer gate, up and down: forward,
recomputed forward, d lhs, d rhs), told from the other Mosaic kernels by
their result shapes (``benchmarks/reduce/kernels.py``), device 0."""

from benchmarks.reduce import kernels


def read(trace, spans, run):
    split = kernels.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "gmm" not in split:
        return None
    return split["gmm"]["ns"] / steps / 1e6
