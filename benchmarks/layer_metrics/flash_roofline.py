"""Kernels: the flash calls' share of their roofline: the least time the
chip could take for the operations and bytes the calls of one step need
(benchmarks/costs.py, from shapes; the larger of FLOPs over peak and
bytes over peak) over the device time they took.  Says on stderr which
bound holds.  Left out when the trace holds another number of kernel
calls per step than the configuration implies: then the count, not the
share, is the finding."""

import sys

from benchmarks import costs, peaks


def read(trace, spans, run):
    if not trace:
        return None
    dev = trace["devices"][0]
    final = run["final"]
    sizes = final["sizes"]
    if not dev["steps"] or not dev["kernel_ns"]:
        return None
    need = costs.flash_step_cost(
        sizes["n_layer"], final["batch"] // run["chips"], final["seq"],
        sizes["n_head"], sizes["n_embd"] // sizes["n_head"],
        remat=final["remat"])
    if dev["kernel_calls"] != need["calls"] * dev["steps"]:
        print(f"[bench] flash_roofline left out: {dev['kernel_calls']} "
              f"kernel calls in {dev['steps']} steps, {need['calls']} a "
              f"step expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] flash roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (dev["kernel_ns"] / dev["steps"] / 1e9)
