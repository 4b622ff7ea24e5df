"""Checkpoint and object plane: median host milliseconds of the FIRST
dispatch after a save.  ``save_stall_ms`` ends when the loop is free to
dispatch; that dispatch then returns late while the same process
pickles the reply that carries the checkpoint, and the device stays
idle until it does.  (The window's last save has no dispatch after it.)"""

import statistics


def read(trace, spans, run):
    xs = run["redispatch_s"]
    return 1e3 * statistics.median(xs) if xs else None
