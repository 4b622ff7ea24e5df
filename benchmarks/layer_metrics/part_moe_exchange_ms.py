"""Train step: device milliseconds a step under the part
``moe.exchange``: the routed layers' all-gathers of the group's rows
(with their choices and weights), the reduce-scatters of the chips'
parts, and the transposes of both in the backward pass, each from start
to done, overlapping ones once (``reduce/exchange.py``: the other
``part_*`` leave every collective to ``collective_ms``), plus what else
the part holds (a layout copy for a neighbour).  Device 0.  ``None``
where the program has no such part, or the trace holds none of it."""

from benchmarks.reduce import exchange, scopes


def read(trace, spans, run):
    got = exchange.of_run(trace, run, "moe.exchange")
    steps = trace["devices"][0]["steps"] if trace else 0
    if not got or not steps or not got["ops"]:
        return None
    return got["ns"] / steps / 1e6 + (
        scopes.part_ms(trace, run, "moe.exchange") or 0.0)
