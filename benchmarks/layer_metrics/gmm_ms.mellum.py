"""Kernels: device milliseconds per step in the grouped matrix products
of the routed experts in the Mellum cell, on ONE CHIP (device 0: its 16
experts a layer, gate, up and down: forward, recomputed forward, d lhs,
d rhs, a call a piece of a sequence).  ``gmm_ms`` tells the products by
their result shapes, which are the one-chip cells' (here a norm over
4,096 rows would read as one); this one takes the calls by the PROGRAM'S
names (``reduce/kernels_named.py``: under ``moe.experts``, built by
``grouped_matmul*``), the calls ``gmm_roofline.mellum`` divides by."""

from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "moe.experts", "grouped_matmul")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    return got["ns"] / steps / 1e6
