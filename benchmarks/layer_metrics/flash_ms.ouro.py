"""Kernels: device milliseconds per step in the flash-attention calls of
the looped stack (a call a layer-call a sequence: forward, recomputed
forward, dK/dV, dQ), found by the program's names: the Mosaic calls
under the part ``attn`` whose ``tf_op`` holds ``_flash_``
(``benchmarks/reduce/kernels_named.py``), device 0.  ``None`` without
the program's ``model:step.scopes`` span or without names in the file."""

from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    found = kernels_named.of_run(trace, run, "attn", "_flash_")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not found or not steps or not found["calls"]:
        return None
    return found["ns"] / steps / 1e6
