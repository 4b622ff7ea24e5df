"""Kernels: device milliseconds per step in the flash-attention calls of
a step that has other Mosaic kernels beside them (windowed and full
layers, grouped K/V heads: forward, recomputed forward, dK/dV, dQ),
told from the grouped products and the fused norms by their result
shapes (``benchmarks/reduce/kernels.py``), device 0."""

from benchmarks.reduce import kernels


def read(trace, spans, run):
    split = kernels.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "flash" not in split:
        return None
    return split["flash"]["ns"] / steps / 1e6
