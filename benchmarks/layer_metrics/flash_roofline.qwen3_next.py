"""Kernels: the gated full-attention layer's flash calls' share of their
roofline in the Qwen3-Next cell: the least time the chip could take for
the operations and bytes the calls of one step need
(``benchmarks/costs_qwen3_next.py``: full layers x sequences x (2
forward, dK/dV, dQ) calls, only the causal half of the (query, key)
pairs, 16 query heads on 2 K/V heads of 256, K and V read once a K/V
head) over the device time they took.  The calls are told by the
program's names (``reduce/kernels_named.py``: under ``attn``, built by
``_flash_*``); the transposes around the head-major family's calls are
``attn_layout_ms``'s, not here.  Says on stderr which bound holds.  Left
out, with the count on stderr, when the trace holds another number of
flash calls a step than the configuration implies."""

import sys

from benchmarks import costs, costs_qwen3_next, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "attn", "_flash_")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_qwen3_next.flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] flash_roofline.qwen3_next left out: {got['calls']} "
              f"flash calls in {steps} steps, {need['calls']} a step "
              f"expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] qwen3_next flash roofline bound: {least['bound']}, "
          f"{got['ns'] / steps / 1e6:.2f} ms a step", file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
