"""Kernels: the grouped products' share of their roofline in the
Nemotron-H cell: the least time the chip could take for the operations
and bytes one step's calls need (``benchmarks/costs_nemotron_h.py``: TWO
products an expert, the width 1856 as published, each call through
``costs_afmoe.gmm_call_cost``) over the device time they took.  The live
rows are TAKEN AS THE EXPECTED ``tokens x top_k x held / published`` a
layer (768 x 8 = 6,144 a step in the cell, 3,072 a sequence's call): the step's own count
cannot reach a reader (PERF.md section 7).  Left out, with the count on
stderr, when the trace holds another number of calls per step."""

import sys

from benchmarks import costs, costs_nemotron_h, peaks
from benchmarks.reduce import kernels_ssd


def read(trace, spans, run):
    split = kernels_ssd.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "gmm" not in split:
        return None
    final = run["final"]
    need = costs_nemotron_h.gmm_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = split["gmm"]
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] gmm_roofline.nemotron_h left out: {got['calls']} "
              f"grouped products in {steps} steps, {need['calls']} a step "
              f"expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] gmm (nemotron_h) roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
