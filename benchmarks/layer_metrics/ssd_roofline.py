"""Kernels: the chunked scan's share of its roofline: the least time
the chip could take for the operations and bytes the scan calls of one
step need (``benchmarks/costs_nemotron_h.py``: the causal half of a
chunk's ``Q x Q``, ``C B^T`` once a group, ``xs``, ``B``, ``C``, ``dt``
read once and ``y`` written once; forward and backward apart) over the
device time they took.  Says on stderr which bound holds.  Left out,
with the count on stderr, when the trace holds another number of scan
calls per step than the configuration implies (mixers x sequences x (2
forward, 1 backward))."""

import sys

from benchmarks import costs, costs_nemotron_h, peaks
from benchmarks.reduce import kernels_ssd


def read(trace, spans, run):
    split = kernels_ssd.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "ssd_fwd" not in split \
            or "ssd_bwd" not in split:
        return None
    final = run["final"]
    need = costs_nemotron_h.ssd_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = {k: split["ssd_" + k]["calls"] for k in ("fwd", "bwd")}
    if any(got[k] != need[k] * steps for k in got):
        print(f"[bench] ssd_roofline left out: {got} scan calls in "
              f"{steps} steps, forward {need['fwd']} and backward "
              f"{need['bwd']} a step expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] ssd roofline bound: {least['bound']}", file=sys.stderr)
    ns = split["ssd_fwd"]["ns"] + split["ssd_bwd"]["ns"]
    return 100.0 * least["seconds"] / (ns / steps / 1e9)
