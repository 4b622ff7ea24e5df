"""Kernels: the windowed and full grouped-head flash calls' share of
their roofline in the Mellum cell: the least time the chip could take
for the operations and bytes the calls of one step on ONE CHIP need
(``benchmarks/costs_mellum.py``: a window of 1,024 of 8,192 in three
layers of four, only (query, key) pairs inside the causal window count,
K and V read once a K/V head) over the device time they took.  The
calls are told by the program's names (``reduce/kernels_named.py``:
under ``attn``, built by ``_flash_*``).  Left out, with the count on
stderr, when the trace holds another number of calls a step."""

import sys

from benchmarks import costs, costs_mellum, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "attn", "_flash_")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_mellum.flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] swa_flash_roofline.mellum left out: {got['calls']} "
              f"flash calls in {steps} steps, {need['calls']} a step "
              f"expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] mellum flash roofline bound: {least['bound']}, "
          f"{got['ns'] / steps / 1e6:.2f} ms a step", file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
