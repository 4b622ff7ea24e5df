"""Kernels: device milliseconds per step in the latent-attention flash
calls (per layer forward, recomputed forward, dK/dV, dQ; the key in two
parts, v narrower than q), told from the grouped products and the fused
norms by their 4-d head-major result shapes
(``benchmarks/reduce/kernels_mla.py``), device 0."""

from benchmarks.reduce import kernels_mla


def read(trace, spans, run):
    split = kernels_mla.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "mla_flash" not in split:
        return None
    return split["mla_flash"]["ns"] / steps / 1e6
