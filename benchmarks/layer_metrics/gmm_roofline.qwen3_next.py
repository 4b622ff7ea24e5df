"""Kernels: the grouped products' share of their roofline in the
Qwen3-Next cell: the least time the chip could take for the operations
and bytes one step's calls need (``benchmarks/costs_qwen3_next.py``:
three products an expert at width 512 over the chip's 32 experts, each
call through ``costs_afmoe.gmm_call_cost``) over the device time they
took.  The live rows are TAKEN AS THE EXPECTED ``tokens x top_k x held /
published`` a layer-call (2,560 of a call's 40,960 pairs, 80 an
expert under row tiles of 256): the step's own count cannot reach a
reader (PERF.md section 7).  The calls are told by the program's names
(``reduce/kernels_named.py``: under ``moe.experts``, built by
``grouped_matmul*``).  Left out, with the count on stderr, when the
trace holds another number of calls a step."""

import sys

from benchmarks import costs, costs_qwen3_next, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "moe.experts", "grouped_matmul")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_qwen3_next.gmm_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] gmm_roofline.qwen3_next left out: {got['calls']} "
              f"grouped products in {steps} steps, {need['calls']} a step "
              f"expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] gmm (qwen3_next) roofline bound: {least['bound']}, "
          f"{got['ns'] / steps / 1e6:.2f} ms a step", file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
