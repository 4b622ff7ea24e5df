"""Kernels: the windowed, grouped-head flash calls' share of their
roofline: the least time the chip could take for the operations and
bytes the calls of one step need (``benchmarks/costs_afmoe.py``: only
(query, key) pairs inside the causal window count, K and V are read once
a K/V head) over the device time they took.  Says on stderr which bound
holds.  Left out, with the count on stderr, when the trace holds another
number of flash calls per step than the configuration implies."""

import sys

from benchmarks import costs, costs_afmoe, peaks
from benchmarks.reduce import kernels


def read(trace, spans, run):
    split = kernels.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "flash" not in split:
        return None
    final = run["final"]
    need = costs_afmoe.flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = split["flash"]
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] swa_flash_roofline left out: {got['calls']} flash "
              f"calls in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] swa flash roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
