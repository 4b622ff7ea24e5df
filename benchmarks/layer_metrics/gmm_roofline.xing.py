"""Kernels: the grouped products' share of their roofline in the Xing4.0
cell: the least time the chip could take for the operations and bytes
one step's calls need (``benchmarks/costs_xing.py``, each call through
``costs_afmoe.gmm_call_cost`` at 3584 x 1024) over the device time they
took.  The calls are found by the PROGRAM'S names
(``reduce/kernels_named.py``: under ``moe.experts``, ``tf_op`` holding
``grouped_matmul``).  The live rows are TAKEN AS THE EXPECTED ``tokens x
top_k x held / published`` a call (2,048 at 4,096 tokens): the step's
own count cannot reach a reader (PERF.md section 7).  Left out, with
the count on stderr, when the trace holds another number of calls per
step."""

import sys

from benchmarks import costs, costs_xing, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "moe.experts", "grouped_matmul")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_xing.gmm_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] gmm_roofline.xing left out: {got['calls']} grouped "
              f"products in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] gmm (xing) roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
