"""Kernels: device milliseconds per step in the chunked state-space
scan's calls (per mixer and sequence: forward, recomputed forward,
backward), told from flash, the grouped products and the fused norms by
their chunk-laid 4-d result shapes (``benchmarks/reduce/
kernels_ssd.py``), device 0."""

from benchmarks.reduce import kernels_ssd


def read(trace, spans, run):
    split = kernels_ssd.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    kinds = [k for k in ("ssd_fwd", "ssd_bwd") if split and k in split]
    if not steps or not kinds:
        return None
    return sum(split[k]["ns"] for k in kinds) / steps / 1e6
