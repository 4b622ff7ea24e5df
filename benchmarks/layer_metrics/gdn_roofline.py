"""Kernels: the gated delta rule's scan's share of its roofline: the
least time the chip could take for the operations and bytes the scan
calls of one step need (``benchmarks/costs_qwen3_next.py``: the chunked
algebra's necessary products at chunk 64, the triangular halves of a
chunk's ``C x C``, ``q``, ``k``, ``v``, ``g``, ``beta`` read once and
``o`` written once; forward and backward apart) over the device time
under the name ``gated_delta`` (``gdn_ms``).  Says on stderr which bound
holds.  Left out, with the count on stderr, when the trace holds another
number of the scan's loops than the configuration implies (linear
layers x sequences x (2 forward, 1 backward): a call's carry is one
``while`` instruction of the step's program, which the profiler's file
keeps)."""

import sys

from benchmarks import costs, costs_qwen3_next, peaks
from benchmarks.reduce import named_ops


def read(trace, spans, run):
    found = named_ops.of_run(trace, run, "gated_delta")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not found or not steps or not found["ops"]:
        return None
    final = run["final"]
    need = costs_qwen3_next.gdn_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if found["program_loops"] != need["calls"]:
        print(f"[bench] gdn_roofline left out: {found['program_loops']} "
              f"scan loops in the step's program, {need['calls']} a step "
              f"expected; the ops under the name in {steps} steps: "
              f"{found['stems']}", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    by_phase = {k: round(v / steps / 1e6, 3)
                for k, v in sorted(found["by_phase"].items())}
    print(f"[bench] gdn roofline bound: {least['bound']}, "
          f"{found['ns'] / steps / 1e6:.2f} ms a step {by_phase}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (found["ns"] / steps / 1e9)
