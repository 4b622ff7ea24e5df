"""Kernels: device milliseconds per step under the gated delta rule's
scan (per linear-attention layer and sequence: forward, recomputed
forward, backward), every op the program put under the name
``gated_delta`` (``ray_tpu/ops/gated_delta.py`` ``SCOPE``), kernel calls
or XLA's own ops alike, each op's self time on device 0
(``benchmarks/reduce/named_ops.py``).  ``part_ssm_scan_ms`` holds the l2
norms and the gates beside it.  ``None`` where no op carries the name
(a program without the scan)."""

from benchmarks.reduce import named_ops


def read(trace, spans, run):
    found = named_ops.of_run(trace, run, "gated_delta")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not found or not steps or not found["ops"]:
        return None
    return found["ns"] / steps / 1e6
