"""Kernels: the grouped products' share of their roofline: the least
time the chip could take for the operations and bytes one step's calls
need (``benchmarks/costs_afmoe.py``) over the device time they took.
The live rows are TAKEN AS THE EXPECTED ``tokens x top_k x held /
published`` a layer (16,384 in the cell, in two calls of 8,192): the step's own count cannot
reach a reader (PERF.md section 7), and at seeded weights it is within
1% of that.  Row tiles padded to the tile and dead tiles' grid steps
are the kernel's cost, not needed work.  Left out, with the count on
stderr, when the trace holds another number of calls per step."""

import sys

from benchmarks import costs, costs_afmoe, peaks
from benchmarks.reduce import kernels


def read(trace, spans, run):
    split = kernels.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "gmm" not in split:
        return None
    final = run["final"]
    need = costs_afmoe.gmm_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = split["gmm"]
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] gmm_roofline left out: {got['calls']} grouped "
              f"products in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] gmm roofline bound: {least['bound']}", file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
