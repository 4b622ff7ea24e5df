"""Train step: median of the step completion intervals in the window
(host clock, stamped one step behind; intervals that hold a save are
left out).  The steadier companion of the end-to-end rate."""

import statistics


def read(trace, spans, run):
    xs = run["step_intervals_s"]
    return 1e3 * statistics.median(xs) if xs else None
