"""Kernels: the latent-attention flash calls' share of their roofline:
the least time the chip could take for the operations and bytes the
calls of one step need (``benchmarks/costs_deepseek_v3.py``: visible
(query, key) pairs only; a pair a head costs ``nope + rope`` for the
score and ``dv`` for the weighted sum, and so on for each backward
product; q, ``k_nope``, v and o once a head, ``k_rope`` once) over the
device time they took.  Says on stderr which bound holds.  Left out,
with the count on stderr, when the trace holds another number of flash
calls per step than the configuration implies (layers x (2 forward +
dK/dV + dQ))."""

import sys

from benchmarks import costs, costs_deepseek_v3, peaks
from benchmarks.reduce import kernels_mla


def read(trace, spans, run):
    split = kernels_mla.of_run(trace, run)
    steps = trace["devices"][0]["steps"] if trace else 0
    if not split or not steps or "mla_flash" not in split:
        return None
    final = run["final"]
    need = costs_deepseek_v3.mla_flash_step_cost(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    got = split["mla_flash"]
    if got["calls"] != need["calls"] * steps:
        print(f"[bench] mla_flash_roofline left out: {got['calls']} flash "
              f"calls in {steps} steps, {need['calls']} a step expected",
              file=sys.stderr)
        return None
    least = costs.roofline_seconds(
        need["flops"], need["bytes"], peaks.peaks(run["device"]["kind"]))
    print(f"[bench] mla flash roofline bound: {least['bound']}",
          file=sys.stderr)
    return 100.0 * least["seconds"] / (got["ns"] / steps / 1e9)
