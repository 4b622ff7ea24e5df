"""Input: milliseconds a step of the window waits for its next batch
(the loop's ``data`` spans, mean per step)."""


def read(trace, spans, run):
    steps = run["final"]["window"]["steps"]
    waits = [t1 - t0 for name, t0, t1 in spans if name == "data"]
    return 1e3 * sum(waits) / steps if steps else None
