"""Kernels: the grouped products' share of their roofline in the
DeepSeek-V3 cell: the least time the chip could take for the operations
and bytes one step's calls need (``benchmarks/costs_deepseek_v3.py``,
each call through ``costs_afmoe.gmm_call_cost``) over the device time
they took.  The live rows are TAKEN AS THE EXPECTED ``tokens x top_k x
held / published`` a layer (12,288 in the cell): the step's own count
cannot reach a reader (PERF.md section 7).  Left out, with the count on
stderr, when the trace holds another number of calls per step."""

import sys

from benchmarks import costs, costs_deepseek_v3, peaks
from benchmarks.reduce import kernels_mla


def read(trace, spans, run):
    split = kernels_mla.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "gmm" not in split:
        return None
    final = run["final"]
    need = costs_deepseek_v3.gmm_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = split["gmm"]
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] gmm_roofline.dsv3 left out: {got['calls']} grouped "
              f"products in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] gmm (dsv3) roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
