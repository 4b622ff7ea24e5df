"""Sharding: what crosses one chip's links for the routed layers'
exchange in a step (``benchmarks/costs_mellum.py``
``exchange_step_bytes``: the gathers' bytes received, the scatters' sent,
forward, recomputed forward and the backward's transposes) over the
device time the exchange's collectives were in flight
(``reduce/exchange.py``, the part ``moe.exchange``), in GB/s; says on
stderr what share that is of the chip's published interconnect figure
(``peaks.py``), and how much of the flight no compute covered."""

import sys

from benchmarks import costs_mellum, peaks
from benchmarks.reduce import exchange


def read(trace, spans, run):
    got = exchange.of_run(trace, run, "moe.exchange")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["ns"]:
        return None
    final = run["final"]
    need = costs_mellum.exchange_step_bytes(
        run["config"], final["batch"] // run["chips"], final["seq"],
        remat=final["remat"])
    rate = need["bytes"] / (got["ns"] / steps / 1e9)
    ici = peaks.peaks(run["device"]["kind"])["ici_bytes_per_s"]
    print(f"[bench] moe exchange: {need['bytes'] / 1e9:.3f} GB a step, "
          f"{got['ops'] / steps:.0f} collective events a step, "
          f"{100 * rate / ici:.1f}% of {ici / 1e9:.0f} GB/s, exposed "
          f"{got['exposed_ns'] / steps / 1e6:.2f} ms a step",
          file=sys.stderr)
    return rate / 1e9
