"""Kernels: the latent-attention flash calls' share of their roofline in
the Xing4.0 cell: the least time the chip could take for the operations
and bytes the calls of one step need (``benchmarks/costs_xing.py``:
``costs_deepseek_v3.mla_flash_call_cost`` at 32 heads, q/k 192, v 128:
visible pairs only; q, ``k_nope``, v and o once a head, ``k_rope``
once) over the device time they took.  The calls are found by the
PROGRAM'S names (``reduce/kernels_named.py``: under ``attn``, ``tf_op``
holding ``_flash_``).  Says on stderr which bound holds.  Left out, with
the count on stderr, when the trace holds another number of flash calls
a step than the configuration implies (layers x sequences x (2 forward
+ dK/dV + dQ))."""

import sys

from benchmarks import costs, costs_xing, peaks
from benchmarks.reduce import kernels_named


def read(trace, spans, run):
    got = kernels_named.of_run(trace, run, "attn", "_flash_")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["calls"]:
        return None
    final = run["final"]
    need = costs_xing.mla_flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] mla_flash_roofline.xing left out: {got['calls']} "
              f"flash calls in {steps} steps, {need['calls']} a step "
              f"expected", file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] xing mla flash roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
