"""Train step: host milliseconds for the jitted call to return (the
loop's ``dispatch`` spans, mean per step): the part of a step the host
cannot hide when it exceeds the device's step."""


def read(trace, spans, run):
    calls = [t1 - t0 for name, t0, t1 in spans if name == "dispatch"]
    return 1e3 * sum(calls) / len(calls) if calls else None
