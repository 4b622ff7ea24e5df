"""Kernels: the looped stack's flash calls' share of their roofline: the
least time the chip could take for the operations and bytes the calls of
one step need (``benchmarks/costs_ouro.py``: layers x passes x sequences
calls, only the causal half of the (query, key) pairs, q, k, v and o
once a head) over the device time they took.  Says on stderr which
bound holds.  Left out, with the count on stderr, when the trace holds
another number of flash calls a step than the configuration implies."""

import sys

from benchmarks import costs, costs_ouro, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "attn", "_flash_")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_ouro.flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] flash_roofline.ouro left out: {got['calls']} flash "
              f"calls in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] ouro flash roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
