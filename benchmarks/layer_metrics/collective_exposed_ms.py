"""Sharding: the part of ``collective_ms`` during which no compute op
runs on that device — what the step really pays for the exchange."""


def read(trace, spans, run):
    if not trace:
        return None
    dev = trace["devices"][0]
    if not dev["steps"]:
        return None
    return dev["collective_exposed_ns"] / dev["steps"] / 1e6
