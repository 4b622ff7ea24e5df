"""Sharding: device-0 milliseconds per step inside collective ops
(all-gather, all-reduce, all-to-all, reduce-scatter, permute), from
start to done, overlapping ones counted once."""


def read(trace, spans, run):
    if not trace:
        return None
    dev = trace["devices"][0]
    return dev["collective_ns"] / dev["steps"] / 1e6 if dev["steps"] else None
